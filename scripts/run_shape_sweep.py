"""The sparse/wide decision measurement (SURVEY.md §7: "decide by
measurement, start dense"; round-2 verdict Missing #9).

Runs Epsilon- and Bosch-shaped synthetic workloads through the dense
uint8 learner at 63 and 255 bins on the real chip, and measures what a
CSR-style path would have to beat: for sparse data the dense formulation
histograms EVERY cell (zeros included), so its cost is independent of
sparsity — the numbers below quantify that overhead directly (dense
s/iter scales with N*F, not nnz).

Shapes (docs/GPU-Performance.md:77-84):
  Epsilon 400k x 2000 dense      — the wide-dense stress case
  Bosch    1M x 968, ~80% sparse — the sparse stress case
  (row counts scaled by SWEEP_SCALE when set; full size by default)

Writes shape_sweep_measured.json at the repo root.
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SCALE = float(os.environ.get("SWEEP_SCALE", 1.0))
ITERS = int(os.environ.get("SWEEP_ITERS", 15))
# int8 matches the bench default (validated at AUC parity on the
# north-star workload); SWEEP_HIST_DTYPE=bfloat16 reproduces the
# round-3 sweep conditions
HIST_DTYPE = os.environ.get("SWEEP_HIST_DTYPE", "int8")
WARMUP = 2


def make_epsilon(n, f=2000, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    y = (X @ w + 0.3 * rng.logistic(size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def make_bosch(n, f=968, sparsity=0.8, seed=6):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    X[rng.rand(n, f) < sparsity] = 0.0
    w = rng.randn(f) / np.sqrt(f * (1 - sparsity))
    y = (X @ w + 0.5 * rng.logistic(size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def run_case(name, X, y, max_bin):
    import jax
    import lightgbm_tpu as lgb

    params = {"objective": "binary", "verbose": -1, "num_leaves": 255,
              "learning_rate": 0.1, "max_bin": max_bin,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
              "histogram_dtype": HIST_DTYPE}
    t0 = time.perf_counter()
    from bench import binned_dataset
    train = binned_dataset(name, X, y, params)
    t_bin = time.perf_counter() - t0
    bst = lgb.Booster(params, train)
    for _ in range(WARMUP):
        bst.update()
    float(bst._gbdt.train_score.score.sum())  # drain warmup in-flight work
    t0 = time.perf_counter()
    for _ in range(ITERS):
        bst.update()
    float(bst._gbdt.train_score.score.sum())  # value fetch: a real sync
    dt = (time.perf_counter() - t0) / ITERS
    learner = bst._gbdt.learner
    out = {
        "case": name, "rows": len(y), "features": X.shape[1],
        "max_bin": max_bin, "seconds_per_iter": round(dt, 4),
        "bin_seconds": round(t_bin, 1),
        "binned_mb": round(train._inner.bins.nbytes / 1e6, 1),
        "bounded_hist_mode": not getattr(learner, "cache_parent_hist",
                                         True),
    }
    print(json.dumps(out), flush=True)
    return out


def main():
    from lightgbm_tpu.jaxutil import require_accelerator
    require_accelerator()
    results = []
    n_eps = int(400_000 * SCALE)
    n_bos = int(1_000_000 * SCALE)
    Xe, ye = make_epsilon(n_eps)
    for mb in (63, 255):
        results.append(run_case("epsilon-shaped", Xe, ye, mb))
    del Xe
    Xb, yb = make_bosch(n_bos)
    nnz = float((Xb != 0).mean())
    for mb in (63, 255):
        r = run_case("bosch-shaped", Xb, yb, mb)
        r["density"] = round(nnz, 3)
        results.append(r)
    import jax
    with open(os.path.join(ROOT, "shape_sweep_measured.json"), "w") as f:
        json.dump({"scale": SCALE, "iters": ITERS,
                   "backend": jax.default_backend(),
                   "results": results}, f, indent=1)
    print("wrote shape_sweep_measured.json")


if __name__ == "__main__":
    main()
