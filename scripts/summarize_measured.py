"""Print a one-screen summary of every measured artifact in the repo
root (the *_measured.json files the measuring scripts write); safe to
run any time."""
import glob
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def show(path):
    try:
        d = json.load(open(path))
    except Exception as e:
        print(f"{os.path.basename(path)}: UNREADABLE ({e})")
        return
    name = os.path.basename(path)
    if "results" in d and isinstance(d["results"], list):
        print(f"{name} (backend={d.get('backend', '?')}):")
        for r in d["results"]:
            key = r.get("case") or r.get("workload", "?")
            spi = (r.get("seconds_per_iter")
                   or r.get("seconds_per_iter_no_eval"))
            extra = ""
            if "max_bin" in r:
                extra += f" @{r['max_bin']}bins"
            if "final_test_ndcg" in r:
                extra += f" ndcg={r['final_test_ndcg']}"
            print(f"  {key}{extra}: {spi} s/iter")
        return
    spi = d.get("seconds_per_iter") or d.get("value")
    bits = [f"{name}: {spi} s/iter" if spi else name]
    for k in ("backend", "max_bin", "histogram_dtype", "test_auc",
              "auc_delta_vs_ref", "speedup_vs_ref_same_host",
              "vs_baseline", "note", "measured_at_commit",
              "train_sample_auc", "full_update_ms"):
        if d.get(k) is not None:
            bits.append(f"{k}={d[k]}")
    print("  ".join(bits))
    if "kernels" in d:
        for k, v in d["kernels"].items():
            print(f"    {k}: {v}")


def main():
    for p in sorted(glob.glob(os.path.join(ROOT, "*_measured.json"))):
        show(p)


if __name__ == "__main__":
    main()
