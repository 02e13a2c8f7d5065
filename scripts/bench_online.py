"""Online-learning bench — leaf-refit vs full-retrain wall-clock and
AUC-after-drift at the (reduced) north-star shape.

Prints ONE JSON line (bench.py shape) and writes it, pretty-printed, to
``BENCH_ONLINE_OUT`` when set (the committed artifact is
``bench_online_measured.json``; the chip-queue stage refreshes it).

Scenario — the ROADMAP item 5 production story, measured:

1. Train a base model (ITERS trees) on the base distribution.
2. The world drifts: the label-generating weights rotate, and labeled
   traffic from the drifted distribution accumulates in a streaming
   window (frozen bin mappers — the online ingestion path).
3. Refresh the model two ways and compare:
   - **refit**: `LeafRefitter` reweights the existing tree structures'
     leaves on the window — one binned ensemble traversal + one jitted
     scan, no tree growth.  First call (compile) timed separately;
     REPS steady-state refresh cycles (refit → reset window → refill)
     timed as the loop the `task=online` daemon runs.
   - **retrain**: an equivalent offline refresh — ITERS trees from
     scratch on the SAME window rows (2 untimed warmup iterations
     first, so both sides exclude their one-time compiles).
4. AUC on a held-out drifted slice: base (degraded), refit, retrain.

Acceptance: steady-state refit >= 10x faster than the equivalent full
retrain (asserted AFTER the JSON prints, so a violation still leaves
the evidence; disable with BENCH_ONLINE_REQUIRE_SPEEDUP=0).

BENCH_SANITIZE=1 runs the steady-state refresh cycles under
`HotPathSanitizer` and asserts the PR 5 contract — ZERO retraces and
ZERO implicit transfers per refresh — after the JSON prints.

Env knobs: BENCH_ONLINE_ROWS (100000 base rows), BENCH_ONLINE_WINDOW
(25000 traffic rows), BENCH_ONLINE_EVAL (16000 held-out drifted rows),
BENCH_ONLINE_ITERS (60 trees), BENCH_ONLINE_LEAVES (255),
BENCH_ONLINE_BINS (255), BENCH_ONLINE_REPS (5 steady refits),
BENCH_ONLINE_OUT.  Runs on the platform it is given; the JSON names it.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ROWS = int(os.environ.get("BENCH_ONLINE_ROWS", 100_000))
WINDOW = int(os.environ.get("BENCH_ONLINE_WINDOW", 25_000))
EVAL = int(os.environ.get("BENCH_ONLINE_EVAL", 16_000))
ITERS = int(os.environ.get("BENCH_ONLINE_ITERS", 60))
LEAVES = int(os.environ.get("BENCH_ONLINE_LEAVES", 255))
BINS = int(os.environ.get("BENCH_ONLINE_BINS", 255))
REPS = int(os.environ.get("BENCH_ONLINE_REPS", 5))
REQUIRE_SPEEDUP = os.environ.get("BENCH_ONLINE_REQUIRE_SPEEDUP", "1") != "0"
FEATURES = 28


def synth(n: int, weights: np.ndarray, seed: int):
    """HIGGS-shaped rows labeled by `weights` (bench.py synth_higgs
    family) — drift = a different weight vector over the same X
    distribution, so tree STRUCTURES stay informative but the leaf
    values trained on the base weights go stale."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, FEATURES))
    y = (X @ weights + rng.logistic(size=n) * 0.5 > 0).astype(np.float64)
    return X, y


def auc(y, p):
    """Rank-based AUC (exact Mann-Whitney, average ranks on ties)."""
    y = np.asarray(y) > 0.5
    order = np.argsort(p, kind="mergesort")
    ranks = np.empty(len(p), np.float64)
    ranks[order] = np.arange(1, len(p) + 1)
    ps = np.asarray(p)[order]
    # average ranks over tied prediction runs
    start = 0
    for i in range(1, len(ps) + 1):
        if i == len(ps) or ps[i] != ps[start]:
            ranks[order[start:i]] = 0.5 * (start + 1 + i)
            start = i
    npos = int(y.sum())
    nneg = len(y) - npos
    if not npos or not nneg:
        return float("nan")
    return float((ranks[y].sum() - npos * (npos + 1) / 2) / (npos * nneg))


def main():
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    from lightgbm_tpu.online import LeafRefitter

    params = {
        "objective": "binary", "metric": "auc", "verbose": -1,
        "num_leaves": LEAVES, "max_bin": BINS, "learning_rate": 0.1,
        "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 100.0,
        "refit_decay_rate": 0.0, "refit_min_rows": 1,
    }
    rng = np.random.default_rng(7)
    w_base = rng.standard_normal(FEATURES)
    # concept drift: half the weights flip sign — feature relevance
    # (the structures) survives, the leaf values do not
    w_drift = w_base.copy()
    w_drift[: FEATURES // 2] *= -1.0

    Xb, yb = synth(ROWS, w_base, seed=1)
    Xw, yw = synth(WINDOW, w_drift, seed=2)
    Xe, ye = synth(EVAL, w_drift, seed=3)

    t0 = time.perf_counter()
    bst = lgb.train(params, lgb.Dataset(Xb, yb), num_boost_round=ITERS)
    base_train_s = time.perf_counter() - t0
    auc_base = auc(ye, bst.predict(Xe))

    # --- online refit path: streaming window + LeafRefitter ------------
    cfg = config_from_params(params)
    base_ds = RawDataset(Xb, yb.astype(np.float32), cfg)
    window = RawDataset.streaming_from(base_ds, cfg, capacity=WINDOW)
    window.append_rows(Xw, yw)

    t0 = time.perf_counter()
    refitter = LeafRefitter(bst._gbdt, window)
    refitter.refit()
    refit_first_s = time.perf_counter() - t0
    auc_refit = auc(ye, bst.predict(Xe))

    # steady state: the daemon's refresh cycle (refit -> reset ->
    # refill), compiled programs reused across windows
    def refill(seed):
        window.reset_rows()
        Xr, yr = synth(WINDOW, w_drift, seed=100 + seed)
        window.append_rows(Xr, yr)

    steady = []
    san = HotPathSanitizer(warmup=0, label="bench-online-refit")
    sanitize = sanitize_enabled()
    if sanitize:
        san.__enter__()
    for i in range(REPS):
        refill(i)
        t0 = time.perf_counter()
        if sanitize:
            with san.step():
                refitter.refit()
        else:
            refitter.refit()
        steady.append(time.perf_counter() - t0)
    if sanitize:
        san.__exit__(None, None, None)
    refit_steady_s = float(np.median(steady))

    # --- equivalent full retrain on the same window rows ----------------
    lgb.train(params, lgb.Dataset(Xw, yw), num_boost_round=2)  # compiles
    t0 = time.perf_counter()
    re_bst = lgb.train(params, lgb.Dataset(Xw, yw), num_boost_round=ITERS)
    retrain_s = time.perf_counter() - t0
    auc_retrain = auc(ye, re_bst.predict(Xe))

    speedup = retrain_s / refit_steady_s if refit_steady_s else float("inf")
    out = {
        "what": ("online refit vs equivalent full retrain after concept "
                 "drift; see scripts/bench_online.py"),
        "backend": jax.default_backend(),
        "shape": {"base_rows": ROWS, "window_rows": WINDOW,
                  "eval_rows": EVAL, "features": FEATURES,
                  "num_trees": ITERS, "num_leaves": LEAVES,
                  "max_bin": BINS},
        "command": (f"BENCH_ONLINE_ROWS={ROWS} BENCH_ONLINE_WINDOW={WINDOW} "
                    f"BENCH_ONLINE_EVAL={EVAL} BENCH_ONLINE_ITERS={ITERS} "
                    f"BENCH_ONLINE_LEAVES={LEAVES} BENCH_ONLINE_BINS={BINS} "
                    "python scripts/bench_online.py"),
        "base_train_seconds": round(base_train_s, 4),
        "refit_first_seconds": round(refit_first_s, 4),
        "refit_steady_seconds_median": round(refit_steady_s, 4),
        "refit_steady_seconds_min": round(float(np.min(steady)), 4),
        "refit_steady_reps": REPS,
        "retrain_seconds": round(retrain_s, 4),
        "refit_speedup_vs_retrain": round(speedup, 2),
        "auc_drifted_base": round(auc_base, 6),
        "auc_drifted_refit": round(auc_refit, 6),
        "auc_drifted_retrain": round(auc_retrain, 6),
        "auc_recovered": round(auc_refit - auc_base, 6),
    }
    if sanitize:
        out["sanitize"] = san.report()
    print(json.dumps(out))
    dest = os.environ.get("BENCH_ONLINE_OUT")
    if dest:
        with open(dest, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {dest}", file=sys.stderr)
    # gates AFTER the evidence prints
    if sanitize:
        assert san.retraces == 0, f"refit loop retraced: {san.compile_names}"
        assert san.implicit_transfers == 0, "refit loop moved data implicitly"
    assert auc_refit > auc_base + 0.02, (
        f"refit did not recover drifted AUC: {auc_base} -> {auc_refit}")
    if REQUIRE_SPEEDUP:
        assert speedup >= 10.0, (
            f"refit speedup {speedup:.1f}x < 10x vs equivalent retrain")


if __name__ == "__main__":
    main()
