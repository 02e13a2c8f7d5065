"""CTR workload A/B — sparse vs dense binned store + adaptive bin
budgets (ISSUE 14 acceptance evidence; docs/Sparse.md runbook).

Four measured runs on the same synthetic wide-sparse lambdarank data
(bench.synth_ctr):

1. dense store (sparse_store=dense) — baseline s/iter + histogram
   cells touched (rows x store columns, counter-derived);
2. csr store (sparse_store=csr) — same trees wanted, nnz-scaled cells
   (tree/sparse_nnz_touched); the artifact records the cells ratio
   (acceptance gate: >= 5x) and whether the grown trees are identical;
3. a dyadic-gradient tree-parity check (+/-1 grads, 0.5 hessians: every
   f32 partial sum is exact in any order, so sparse and dense trees
   must match BITWISE — the exact-arithmetic identity claim; the real
   lambdarank run is also compared and agreement recorded honestly,
   f32 zero-bin reconstruction reorders sums like EFB's default-bin
   reconstruction already does);
4. adaptive bin budgets: uniform max_bin=B0 vs bin_budget set to the
   uniform run's ACTUAL total bins (same budget, adaptively allocated,
   cap 255) — held-out AUC + ndcg recorded (acceptance: adaptive >=
   uniform at the same total);
5. int8 vs f32 sparse histograms (ISSUE 19): cells/s ratio (>= 1.3x
   gate, enforced on the TPU backend where the int8 MXU contraction
   exists; the XLA emulation measures parity) and held-out AUC within
   the dense-int8 tolerance (|delta| <= 0.01);
6. replay-densify probe: a csr train + csr valid loop must keep
   tree/sparse_fallbacks at EXACTLY 0 (sparse binned score replay).

Writes bench_ctr_measured.json (BENCH_CTR_OUT overrides).  Shape via
BENCH_ROWS / BENCH_CTR_* envs.  The run fails when JAX finds no
accelerator: its times are device metrics.
Acceptance gates are asserted AFTER the JSON prints/writes, so a
failed gate still leaves the measurements on disk.
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import synth_ctr  # noqa: E402

OUT = os.environ.get("BENCH_CTR_OUT",
                     os.path.join(ROOT, "bench_ctr_measured.json"))
ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
FEATURES = int(os.environ.get("BENCH_CTR_FEATURES", 50_000))
DENSITY = float(os.environ.get("BENCH_CTR_DENSITY", 0.01))
QUERY = int(os.environ.get("BENCH_CTR_QUERY", 20))
ITERS = int(os.environ.get("BENCH_ITERS", 10))
WARMUP = int(os.environ.get("BENCH_WARMUP", 2))
LEAVES = int(os.environ.get("BENCH_LEAVES", 31))
UNIFORM_BIN = int(os.environ.get("BENCH_CTR_UNIFORM_BIN", 16))


def _auc(y: np.ndarray, s: np.ndarray) -> float:
    """Rank-based AUC (average over tied ranks), no sklearn."""
    order = np.argsort(s, kind="mergesort")
    ranks = np.empty(len(s), np.float64)
    sv = s[order]
    i = 0
    r = np.arange(1, len(s) + 1, dtype=np.float64)
    while i < len(s):
        j = i
        while j + 1 < len(s) and sv[j + 1] == sv[i]:
            j += 1
        ranks[order[i:j + 1]] = r[i:j + 1].mean()
        i = j + 1
    pos = y > 0
    n1, n0 = int(pos.sum()), int((~pos).sum())
    if n1 == 0 or n0 == 0:
        return 0.5
    return (ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0)


def _train(X, y, group, params, iters, warmup, fobj=None):
    """One measured run: returns (booster, steady s/iter, counter
    deltas over the timed window)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import profiling
    ds = lgb.Dataset(X, y, group=group).construct(params)
    bst = lgb.Booster(params, ds)
    for _ in range(warmup):
        bst.update(fobj=fobj)
    float(bst._gbdt.train_score.score.sum())
    keys = (profiling.HIST_ROWS_TOUCHED, profiling.SPARSE_NNZ_TOUCHED,
            profiling.SPARSE_FALLBACKS)
    t0v = {k: profiling.counter_value(k) for k in keys}
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update(fobj=fobj)
    float(bst._gbdt.train_score.score.sum())
    dt = (time.perf_counter() - t0) / iters
    deltas = {k: (profiling.counter_value(k) - t0v[k]) / iters
              for k in keys}
    return bst, ds, dt, deltas


def main():
    global ROWS, FEATURES
    import jax
    from lightgbm_tpu.jaxutil import require_accelerator
    device = require_accelerator()
    # the DENSE leg bounds the A/B shape: an int32/int8 [F, N] store
    # plus [K, F, 3, B] histograms at 50k columns would blow past one
    # chip's HBM — the csr_full_shape probe below carries the
    # >= 50k-feature evidence instead
    FEATURES = min(FEATURES, 8_192)
    ROWS = min(ROWS, 1_000_000)
    import lightgbm_tpu as lgb  # noqa: F401
    from lightgbm_tpu import profiling

    X, y, group = synth_ctr(ROWS, FEATURES, DENSITY, query=QUERY)
    Xv, yv, _ = synth_ctr(max(len(y) // 4, QUERY), FEATURES, DENSITY,
                          seed=43, query=QUERY)
    base = {"objective": "lambdarank", "metric": "ndcg", "verbose": -1,
            "num_leaves": LEAVES, "learning_rate": 0.1, "max_bin": 255,
            "min_data_in_leaf": 20, "histogram_dtype": "float32",
            # FindBin densifies its row sample — cap it so wide shapes
            # don't stage an N_sample x F float64 matrix
            "bin_construct_sample_cnt": 20_000,
            # both sides must run the SAME learner — sparse auto-routes
            # to rounds, so pin the dense side there too
            "tree_growth": "rounds"}
    out = {"metric": f"synthetic-ctr {len(y)}x{FEATURES} lambdarank "
                     f"{LEAVES} leaves: sparse-store + adaptive-bin A/B",
           "rows": len(y), "features": FEATURES, "density": DENSITY,
           "iters": ITERS, "device": device}

    # ---- 1+2: dense vs csr store ------------------------------------
    runs = {}
    for store in ("dense", "csr"):
        p = dict(base, sparse_store=store)
        bst, ds, spi, deltas = _train(X, y, group, p, ITERS, WARMUP)
        cols = int(ds._inner.num_store_columns)
        dense_cells = deltas[profiling.HIST_ROWS_TOUCHED] * cols
        runs[store] = {
            "seconds_per_iter": round(spi, 4),
            "store_columns": cols,
            "cells_touched_per_iter": round(
                deltas[profiling.SPARSE_NNZ_TOUCHED] if store == "csr"
                else dense_cells, 1),
            "sparse_fallbacks_per_iter": deltas[
                profiling.SPARSE_FALLBACKS],
            "model": bst.model_to_string(),
        }
        if store == "csr":
            assert ds._inner.sparse is not None, "csr store did not build"
            runs[store]["nnz"] = int(ds._inner.sparse.nnz)
    ratio = (runs["dense"]["cells_touched_per_iter"]
             / max(runs["csr"]["cells_touched_per_iter"], 1.0))
    ident = runs["dense"]["model"] == runs["csr"]["model"]
    out["store_ab"] = {
        "dense": {k: v for k, v in runs["dense"].items() if k != "model"},
        "csr": {k: v for k, v in runs["csr"].items() if k != "model"},
        "cells_ratio_dense_over_csr": round(ratio, 2),
        "cells_ratio_gate_5x": ratio >= 5.0,
        "speedup_csr_over_dense": round(
            runs["dense"]["seconds_per_iter"]
            / max(runs["csr"]["seconds_per_iter"], 1e-9), 3),
        "trees_identical": ident,
    }

    # ---- 3: dyadic-gradient bitwise tree parity ----------------------
    # +/-1 grads, 0.5 hessians: every f32 partial sum is exact in any
    # accumulation order, so the zero-bin reconstruction is exact and
    # sparse trees must equal dense trees BITWISE
    gd = np.where(y > 0, -1.0, 1.0).astype(np.float32)

    def dyadic(_preds, _ds):
        return gd.copy(), np.full(len(y), 0.5, np.float32)

    dy = {}
    pd_ = dict(base, objective="binary", metric="auc")
    for store in ("dense", "csr"):
        p = dict(pd_, sparse_store=store)
        bst, _, _, _ = _train(X, y, None, p, 3, 1, fobj=dyadic)
        dy[store] = bst.model_to_string()
    out["store_ab"]["trees_identical_dyadic"] = dy["dense"] == dy["csr"]

    # ---- 4: adaptive bin budgets at the same total -------------------
    p_u = dict(base, sparse_store="csr", max_bin=UNIFORM_BIN)
    bst_u, ds_u, _, _ = _train(X, y, group, p_u, ITERS, 1)
    total_bins = int(np.sum(ds_u._inner.num_bins))
    p_a = dict(base, sparse_store="csr", max_bin=255,
               bin_budget=total_bins)
    bst_a, ds_a, _, _ = _train(X, y, group, p_a, ITERS, 1)
    def predict_sparse(bst, Xs, chunk=16_384):
        # densify bounded row slabs (the whole valid matrix is
        # rows x F float64 — ~100 GB at the acceptance shape)
        outs = [np.asarray(bst.predict(
            np.asarray(Xs[i:i + chunk].todense()))).ravel()
            for i in range(0, Xs.shape[0], chunk)]
        return np.concatenate(outs)

    scores = {}
    for name, bst, ds in (("uniform", bst_u, ds_u),
                          ("adaptive", bst_a, ds_a)):
        sv = predict_sparse(bst, Xv)
        scores[name] = {
            "valid_auc": round(_auc(yv, sv), 5),
            "total_bins": int(np.sum(ds._inner.num_bins)),
            "num_bins_min": int(ds._inner.num_bins.min()),
            "num_bins_max": int(ds._inner.num_bins.max()),
        }
    out["adaptive_ab"] = {
        "uniform_max_bin": UNIFORM_BIN,
        "budget": total_bins,
        "uniform": scores["uniform"],
        "adaptive": scores["adaptive"],
        "auc_delta_adaptive_minus_uniform": round(
            scores["adaptive"]["valid_auc"]
            - scores["uniform"]["valid_auc"], 5),
    }

    # ---- 5: int8 vs f32 sparse histograms ----------------------------
    # Both legs run the csr store; int8 keeps the whole accumulation in
    # integer lanes (int8 MXU contraction on chip, int32 scatter on the
    # XLA path).  cells/s is the throughput metric (same nnz cells per
    # iteration on both sides).  The >= 1.3x gate is an MXU property —
    # on a non-TPU backend the XLA emulation measures parity, so the
    # ratio is recorded honestly but only enforced on chip.
    i8 = {}
    for hd in ("float32", "int8"):
        p = dict(base, sparse_store="csr", histogram_dtype=hd)
        bst, ds, spi, deltas = _train(X, y, group, p, ITERS, WARMUP)
        cells = deltas[profiling.SPARSE_NNZ_TOUCHED]
        i8[hd] = {
            "seconds_per_iter": round(spi, 4),
            "cells_touched_per_iter": round(cells, 1),
            "cells_per_second": round(cells / max(spi, 1e-9), 1),
            "valid_auc": round(_auc(yv, predict_sparse(bst, Xv)), 5),
        }
    r_cells = (i8["int8"]["cells_per_second"]
               / max(i8["float32"]["cells_per_second"], 1e-9))
    d_auc = i8["int8"]["valid_auc"] - i8["float32"]["valid_auc"]
    on_tpu = jax.default_backend() == "tpu"
    out["int8_ab"] = {
        "float32": i8["float32"], "int8": i8["int8"],
        "cells_per_s_ratio_int8_over_f32": round(r_cells, 3),
        "gate_cells_per_s_1_3x": bool(r_cells >= 1.3),
        "gate_enforced_on_this_backend": on_tpu,
        # quantization may cost at most what the validated dense int8
        # path accepts (|delta AUC| <= 0.01 on held-out)
        "auc_delta_int8_minus_f32": float(round(d_auc, 5)),
        "gate_auc_within_dense_int8_tolerance": bool(abs(d_auc) <= 0.01),
    }

    # ---- 6: replay-densify probe -------------------------------------
    # A csr train + csr valid loop (training, score replay, metric
    # eval) must densify exactly NEVER: tree/sparse_fallbacks delta 0
    # over the whole run.
    p = dict(base, sparse_store="csr", objective="binary", metric="auc")
    f0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
    ds_t = lgb.Dataset(X, y).construct(p)
    ds_v = lgb.Dataset(Xv, yv, reference=ds_t).construct(p)
    bst = lgb.Booster(p, ds_t)
    bst.add_valid(ds_v, "valid")
    for _ in range(3):
        bst.update()
    bst._gbdt._flush_pending()
    ev = bst.eval_valid()
    d_fall = profiling.counter_value(profiling.SPARSE_FALLBACKS) - f0
    out["replay_probe"] = {
        "iters": 3,
        "valid_metric": [(nm, m, float(round(v, 5))) for nm, m, v, _ in ev],
        "sparse_fallbacks": int(d_fall),
        "gate_zero_fallbacks": bool(d_fall == 0),
    }

    # ---- full acceptance-shape probe (csr only) ----------------------
    # When the A/B degraded below the >= 50k-feature acceptance shape,
    # still prove the sparse path RUNS there: csr store, EFB off (the
    # conflict-graph planner's [F, S] sample matrix is a host-memory
    # hazard at 50k sparse features), reduced leaves/bins so the
    # [K, F, 3, B] reduced histogram stays CPU-feasible.
    if FEATURES < 50_000 and os.environ.get("BENCH_CTR_FULL", "1") != "0":
        nf = min(len(y), 4_096)
        Xf, yf, gf = synth_ctr(nf, 50_000, DENSITY, query=QUERY)
        p = dict(base, sparse_store="csr", enable_bundle=False,
                 num_leaves=15, max_bin=63)
        bst, ds, spi, deltas = _train(Xf, yf, gf, p, 2, 1)
        cols = int(ds._inner.num_store_columns)
        out["csr_full_shape"] = {
            "rows": len(yf), "features": 50_000,
            "store_columns": cols,
            "nnz": int(ds._inner.sparse.nnz),
            "seconds_per_iter": round(spi, 4),
            "nnz_touched_per_iter": round(
                deltas[profiling.SPARSE_NNZ_TOUCHED], 1),
            "dense_cells_equiv_per_iter": round(
                deltas[profiling.HIST_ROWS_TOUCHED] * cols, 1),
        }

    print(json.dumps(out))
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")

    # ---- acceptance gates: asserted AFTER the artifact prints/writes,
    # so a failed gate still leaves the measurements on disk for triage
    gates = [
        ("cells_ratio_gate_5x", out["store_ab"]["cells_ratio_gate_5x"]),
        ("trees_identical_dyadic",
         out["store_ab"]["trees_identical_dyadic"]),
        ("replay_zero_fallbacks",
         out["replay_probe"]["gate_zero_fallbacks"]),
        ("int8_auc_within_tolerance",
         out["int8_ab"]["gate_auc_within_dense_int8_tolerance"]),
    ]
    if out["int8_ab"]["gate_enforced_on_this_backend"]:
        gates.append(("int8_cells_per_s_1_3x",
                      out["int8_ab"]["gate_cells_per_s_1_3x"]))
    failed = [name for name, ok in gates if not ok]
    assert not failed, f"acceptance gates failed: {failed}"


if __name__ == "__main__":
    main()
