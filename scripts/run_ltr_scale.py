"""LTR at the reference's tracked ranking scales on the live chip
(round-2 verdict weak #8; round-4 verdict missing LTR artifact).

Two synthetic workloads shaped like the reference's ranking benchmarks
(docs/GPU-Performance.md:77-84):
  MS-LTR  2,270,296 x 137, ~30.7k queries (74 rows/query avg)
  Yahoo     473,134 x 700, ~20.6k queries (23 rows/query avg)
graded 0-4 relevance, lambdarank objective, NDCG@{1,3,5} tracked on a
held-out query set.  Measures s/iter with NO eval vs eval EVERY
iteration — the device ndcg_at_k kernel (ops/eval.py) keeps scores
resident, so the delta is the claim under test.

Writes ltr_scale_measured.json at the repo root.
Env: LTR_ROWS / LTR_ITERS to shrink for smoke runs (MS-LTR only when
LTR_ROWS is set).
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS = int(os.environ.get("LTR_ROWS", 2_270_296))
TEST_ROWS = int(os.environ.get("LTR_TEST_ROWS", 340_000))
ITERS = int(os.environ.get("LTR_ITERS", 30))
WARMUP = 3


def synth_ltr(n, f, seed, avg_q):
    rng = np.random.RandomState(seed)
    sizes = []
    tot = 0
    while tot < n:
        s = int(rng.randint(avg_q // 2, avg_q * 2))
        sizes.append(min(s, n - tot))
        tot += sizes[-1]
    sizes = np.asarray(sizes, np.int64)
    X = rng.randn(n, f).astype(np.float32)
    beta = np.random.RandomState(99).randn(f) / np.sqrt(f)
    rel = X @ beta + 0.8 * rng.randn(n)
    y = np.clip(np.digitize(rel, [-1.0, 0.0, 1.0, 1.8]), 0, 4).astype(
        np.float64)
    return X.astype(np.float64), y, sizes


def run_workload(name, rows, test_rows, f, avg_q):
    import jax
    import lightgbm_tpu as lgb

    params = {"objective": "lambdarank", "metric": "ndcg",
              "ndcg_eval_at": [1, 3, 5], "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 100.0, "verbose": -1,
              "histogram_dtype": "bfloat16"}
    X, y, q = synth_ltr(rows, f=f, seed=0, avg_q=avg_q)
    Xt, yt, qt = synth_ltr(test_rows, f=f, seed=5, avg_q=avg_q)
    t0 = time.perf_counter()
    from bench import binned_dataset
    train = binned_dataset(f"ltr-{name}", X, y, params, group=q)
    valid = lgb.Dataset(Xt, yt, group=qt, reference=train).construct(params)
    t_bin = time.perf_counter() - t0

    def run(with_eval):
        bst = lgb.Booster(params, train)
        if with_eval:
            bst._gbdt.add_valid(valid._inner, "test")
        ndcg = None
        for _ in range(WARMUP):
            bst.update()
            if with_eval:
                ndcg = bst._gbdt.eval_valid()
        float(bst._gbdt.train_score.score.sum())  # value fetch: a real sync
        t0 = time.perf_counter()
        for _ in range(ITERS):
            bst.update()
            if with_eval:
                ndcg = bst._gbdt.eval_valid()
        float(bst._gbdt.train_score.score.sum())  # value fetch: a real sync
        return (time.perf_counter() - t0) / ITERS, ndcg

    s_noeval, _ = run(False)
    s_eval, ndcg = run(True)
    out = {
        "workload": f"synthetic {name}-shaped lambdarank {rows}x{f}, "
                    f"{len(q)} train queries, 255 leaves, 255 bins",
        "backend": jax.default_backend(),
        "iters": ITERS,
        "bin_seconds": round(t_bin, 1),
        "seconds_per_iter_no_eval": round(s_noeval, 4),
        "seconds_per_iter_with_ndcg_eval_every_iter": round(s_eval, 4),
        "eval_overhead_ratio": round(s_eval / s_noeval, 3),
        "final_test_ndcg": {nm: round(float(v), 6)
                            for _, nm, v, _ in (ndcg or [])},
    }
    print(json.dumps(out), flush=True)
    return out


def main():
    from lightgbm_tpu.jaxutil import require_accelerator
    require_accelerator()
    results = [run_workload("MS-LTR", ROWS, TEST_ROWS, f=137, avg_q=74)]
    if "LTR_ROWS" not in os.environ:
        # Yahoo set1 shape: 473k x 700, ~20.6k queries (23 rows/query)
        results.append(run_workload("Yahoo-LTR", 473_134, 71_083, f=700,
                                    avg_q=23))
    with open(os.path.join(ROOT, "ltr_scale_measured.json"), "w") as f:
        json.dump({"iters": ITERS, "results": results}, f, indent=1)
    print("wrote ltr_scale_measured.json")


if __name__ == "__main__":
    main()
