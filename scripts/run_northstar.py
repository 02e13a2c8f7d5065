"""The north-star measurement: lightgbm_tpu at HIGGS scale on the real TPU.

Trains 10.5M x 28 synthetic HIGGS (the same data and config measured for
the reference binary in baseline_measured.json): gbdt, 255 leaves, 255
bins, lr 0.1, 500 iterations, AUC tracked on the 500k-row test set every
EVAL_FREQ iterations via the device AUC kernel.

Writes northstar_measured.json at the repo root (tracked).
Run:  python scripts/run_northstar.py            (on the TPU chip)
Env:  NS_ROWS / NS_ITERS / NS_EVAL_FREQ to shrink for smoke runs.
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import synth_higgs  # noqa: E402

ROWS = int(os.environ.get("NS_ROWS", 10_500_000))
TEST_ROWS = int(os.environ.get("NS_TEST_ROWS", 500_000))
ITERS = int(os.environ.get("NS_ITERS", 500))
EVAL_FREQ = int(os.environ.get("NS_EVAL_FREQ", 25))
# int8 is the validated bench default (northstar_int8_accuracy.json:
# 500-iter AUC 0.889807 vs the reference binary's 0.889423)
HIST_DTYPE = os.environ.get("NS_HIST_DTYPE", "int8")
# 255 = tracked config; 63 = the reference accelerator sweet spot
# (docs/GPU-Performance.md:153-156), written to its own artifact
BINS = int(os.environ.get("NS_BINS", 255))


def main():
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.jaxutil import require_accelerator

    require_accelerator()
    backend = jax.default_backend()
    t0 = time.perf_counter()
    X, y = synth_higgs(ROWS, seed=42)
    Xt, yt = synth_higgs(TEST_ROWS, seed=7)
    t_gen = time.perf_counter() - t0

    params = {
        "objective": "binary", "metric": "auc", "verbose": -1,
        "num_leaves": 255, "learning_rate": 0.1, "max_bin": BINS,
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
        "histogram_dtype": HIST_DTYPE,
    }
    # binning happens here, OUTSIDE the training wall-clock — the same
    # accounting as the reference log, whose 89s data load is separate
    t0 = time.perf_counter()
    from bench import binned_dataset
    train = binned_dataset("higgs", X, y, params)
    valid = lgb.Dataset(Xt, yt, reference=train).construct(params)
    t_bin = time.perf_counter() - t0

    # the training wall-clock includes the first-iteration compile, the
    # same accounting as the reference log (its first iteration carries
    # tree-learner init and runs 4x its steady state).  Eval runs every
    # EVAL_FREQ iterations — the reference run used metric_freq=25, so
    # the timed windows pay comparable eval costs.
    bst = lgb.Booster(params, train)
    bst._gbdt.add_valid(valid._inner, "test")
    aucs = {}
    t0 = time.perf_counter()
    for it in range(1, ITERS + 1):
        bst.update()
        if it % EVAL_FREQ == 0 or it == ITERS:
            auc = bst._gbdt.eval_valid()[0][2]
            aucs[it] = round(float(auc), 6)
            el = time.perf_counter() - t0
            print(f"iter {it}: test auc {auc:.6f}  ({el:.1f}s, "
                  f"{el / it:.3f} s/iter)", flush=True)
    t_train = time.perf_counter() - t0

    base_f = os.path.join(ROOT, "baseline_measured.json")
    base = json.load(open(base_f)) if os.path.exists(base_f) else {}
    ref = base.get("measured", {})
    # comparisons against the reference are only meaningful at the FULL
    # north-star shape; smoke runs must not emit full-scale claims
    at_full_shape = (ROWS == 10_500_000 and ITERS == 500 and BINS == 255)
    import subprocess
    try:
        # --dirty: an artifact stamped from a modified tree must say so
        head = subprocess.run(["git", "describe", "--always", "--dirty"],
                              cwd=ROOT, capture_output=True,
                              text=True).stdout.strip() or "unknown"
    except OSError:
        head = "unknown"
    out = {
        "workload": ((base.get("workload", "")
                      + f" [histogram_dtype={HIST_DTYPE}]")
                     if at_full_shape else
                     f"SMOKE RUN {ROWS}x28 synthetic higgs, {ITERS} iters "
                     "- not comparable to the reference baseline"),
        "measured_at_commit": head,
        "histogram_dtype": HIST_DTYPE,
        "max_bin": BINS,
        "backend": backend,
        "rows": ROWS, "iters": ITERS,
        "data_gen_seconds": round(t_gen, 1),
        "bin_seconds": round(t_bin, 1),
        "train_seconds": round(t_train, 1),
        "seconds_per_iter": round(t_train / ITERS, 4),
        "test_auc": aucs.get(ITERS),
        "auc_trajectory": aucs,
        "ref_total_train_seconds": ref.get(
            "ref_total_train_seconds_500_iters"),
        "ref_test_auc": ref.get("ref_test_auc_at_500_iters"),
        "speedup_vs_ref_same_host": (
            round(ref["ref_total_train_seconds_500_iters"] / t_train, 3)
            if ref.get("ref_total_train_seconds_500_iters")
            and at_full_shape else None),
        "auc_delta_vs_ref": (
            round(aucs[ITERS] - ref["ref_test_auc_at_500_iters"], 6)
            if ref.get("ref_test_auc_at_500_iters") and at_full_shape
            and ITERS in aucs else None),
    }
    dest = os.path.join(ROOT, "northstar_measured.json" if BINS == 255
                        else f"northstar{BINS}bin_measured.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
