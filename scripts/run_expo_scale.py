"""Categorical training at scale (Expo-style workload, BASELINE.md's
"multiclass softmax + raw categorical (Expo)" tracked config).

Synthetic Expo-shaped binary workload: EXPO_ROWS x 100 raw CATEGORICAL
features (64 categories each, skewed frequencies) — exercises the
categorical BinMapper (top-98% frequency bins), the one-hot-equality
split path (decision_type=1), and categorical model text round-trip at
scale.  Writes expo_scale_measured.json.

Env: EXPO_ROWS (default 2,000,000) / EXPO_ITERS (default 30).
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS = int(os.environ.get("EXPO_ROWS", 11_000_000))
ITERS = int(os.environ.get("EXPO_ITERS", 30))
WARMUP = 3
F = int(os.environ.get("EXPO_FEATURES", 700))
NCAT = 64


def synth_expo(n, f=F, seed=11):
    """Full Expo shape (docs/GPU-Performance.md:77-84: 11M x 700 raw
    categorical).  Column-blocked generation: a [n, f] float64 matrix
    plus int64 indexing transients would need ~130 GB; float32 storage
    + per-column accumulation stays ~31 GB (category ids <= 64 are
    exact in f32)."""
    rng = np.random.RandomState(seed)
    # skewed category frequencies (zipf-ish), like carrier/airport codes
    p = 1.0 / np.arange(1, NCAT + 1)
    p /= p.sum()
    X = np.empty((n, f), np.float32)
    logits = np.zeros(n, np.float64)
    beta = np.random.RandomState(50).randn(f, NCAT) * 0.3
    for j in range(f):
        col = rng.choice(NCAT, size=n, p=p)
        X[:, j] = col
        logits += beta[j, col]
    y = (logits + rng.logistic(size=n) > 0).astype(np.float64)
    return X, y


def _load_or_synth():
    """Single-core generation of the 11M x 700 matrix takes ~30 min —
    cache it on disk (EXPO_CACHE=0 disables) so the chip window is spent
    training, not synthesizing."""
    cache = os.path.join(ROOT, ".bench", f"expo_cache_{ROWS}x{F}.npz")
    if os.environ.get("EXPO_CACHE", "1") == "0":
        return synth_expo(ROWS)
    if os.path.exists(cache):
        d = np.load(cache)
        return d["X"], d["y"]
    X, y = synth_expo(ROWS)
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    # atomic write: a concurrent reader (e.g. the chip queue starting
    # while a pre-generation run is finishing) must never see a partial
    # npz; unique tmp per writer, removed on failure (a dead writer must
    # not leak a ~31 GB orphan)
    tmp = f"{cache}.tmp.{os.getpid()}.npz"
    try:
        np.savez(tmp, X=X, y=y)
        os.replace(tmp, cache)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise
    return X, y


def main():
    from lightgbm_tpu.jaxutil import require_accelerator
    device = require_accelerator()
    import lightgbm_tpu as lgb

    X, y = _load_or_synth()
    params = {"objective": "binary", "metric": "auc", "verbose": -1,
              "num_leaves": 255, "max_bin": 255, "learning_rate": 0.1,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
              "histogram_dtype": "bfloat16",
              "categorical_feature": list(range(F))}
    # host binning of 11M x 700 costs ~25 min — the shared binned-store
    # cache (bench.binned_dataset: load ~80 s, label-checked, bad caches
    # self-heal by rebinning) keeps the chip window for training
    from bench import binned_dataset
    t0 = time.perf_counter()
    train = binned_dataset("expo", X, y, params,
                           categorical_feature=list(range(F)))
    t_bin = time.perf_counter() - t0
    bst = lgb.Booster(params, train)
    for _ in range(WARMUP):
        bst.update()
    float(bst._gbdt.train_score.score.sum())  # value fetch: a real sync
    t0 = time.perf_counter()
    for _ in range(ITERS):
        bst.update()
    float(bst._gbdt.train_score.score.sum())  # value fetch: a real sync
    s_iter = (time.perf_counter() - t0) / ITERS

    # categorical split sanity: the model uses equality decisions and
    # survives a text round-trip
    s = bst.model_to_string()
    bst2 = lgb.Booster(model_str=s)
    idx = np.random.RandomState(1).choice(ROWS, min(ROWS, 10_000),
                                          replace=False)
    p1, p2 = bst.predict(X[idx]), bst2.predict(X[idx])
    roundtrip_max_delta = float(np.abs(p1 - p2).max())
    assert roundtrip_max_delta < 1e-6, roundtrip_max_delta
    n_cat_splits = s.count("decision_type=1")

    auc = None
    try:
        from sklearn.metrics import roc_auc_score
        auc = round(float(roc_auc_score(y[idx], p1)), 4)
    except Exception:
        pass
    out = {
        "workload": f"synthetic Expo-shaped binary {ROWS}x{F} raw "
                    f"categorical ({NCAT} cats, zipf), 255 leaves",
        "device": device,
        "iters": ITERS,
        "bin_seconds": round(t_bin, 1),
        "seconds_per_iter": round(s_iter, 4),
        "trees_with_categorical_splits": n_cat_splits > 0,
        "train_sample_auc": auc,
        "model_roundtrip_max_abs_delta": roundtrip_max_delta,
    }
    with open(os.path.join(ROOT, "expo_scale_measured.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
