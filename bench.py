"""Benchmark harness — prints ONE JSON line.

Workload: synthetic HIGGS-shaped binary classification (N×28 dense
numerical features, the shape of the reference's headline benchmark,
docs/GPU-Performance.md:77-84) trained with the north-star config
(num_leaves=255, max_bin=255, lr=0.1, min_data_in_leaf=1,
min_sum_hessian_in_leaf=100 — BASELINE.md).

Metric: training seconds per boosting iteration on the accelerator JAX
reports, at the FULL north-star shape (10.5M rows) by default.  The run
fails when JAX finds only the CPU (jaxutil.require_accelerator): a CPU
timing is never written under this metric's name.  `device` in the JSON
line is the platform / device_kind / count it ran on.  `vs_baseline` is
baseline_seconds_per_iter / our_seconds_per_iter (higher is better, >1
means faster than baseline) against the COMMITTED measurement of the
compiled reference binary at the same shape (baseline_measured.json).
The JSON line also carries the 500-iteration accuracy evidence from
northstar_measured.json when present.
"""
import json
import os
import sys
import time

import numpy as np

ROWS = int(os.environ.get("BENCH_ROWS", 10_500_000))
ITERS = int(os.environ.get("BENCH_ITERS", 60))
WARMUP = int(os.environ.get("BENCH_WARMUP", 3))
LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
# histogram MXU precision.  int8 (per-pass symmetric gradient
# quantization, exact int32 accumulation) is the validated default:
# 500-iteration full-shape AUC 0.889807 vs the reference binary's
# 0.889423 on identical data (northstar_int8_accuracy.json), ~20%
# faster than bfloat16 (k_sweep_measured.json).  bfloat16 remains the
# validated fallback (tests/test_bf16.py).
HIST_DTYPE = os.environ.get("BENCH_HIST_DTYPE", "int8")
# 255 is the tracked north-star config; 63 is the reference accelerator
# sweet spot (docs/GPU-Performance.md:153-156) measured as a variant
BINS = int(os.environ.get("BENCH_BINS", 255))
# "higgs" (tracked), "onehot" (EFB acceptance shape: 240 one-hot
# columns, 100% exclusive; A/B with BENCH_ENABLE_BUNDLE=0/1), or "ctr"
# (wide-sparse hashed-count ranking shape, lambdarank over query
# groups — the sparse-store acceptance workload, docs/Sparse.md;
# A/B with BENCH_SPARSE_STORE=dense|csr and BENCH_BIN_BUDGET)
WORKLOAD = os.environ.get("BENCH_WORKLOAD", "higgs")
ENABLE_BUNDLE = os.environ.get("BENCH_ENABLE_BUNDLE", "1") != "0"
# CTR shape knobs: feature count, nnz density, query size; the sparse
# store (auto|csr|dense) and adaptive bin budget ride the same A/B envs
CTR_FEATURES = int(os.environ.get("BENCH_CTR_FEATURES", 50_000))
CTR_DENSITY = float(os.environ.get("BENCH_CTR_DENSITY", 0.01))
CTR_QUERY = int(os.environ.get("BENCH_CTR_QUERY", 20))
SPARSE_STORE = os.environ.get("BENCH_SPARSE_STORE", "")
BIN_BUDGET = int(os.environ.get("BENCH_BIN_BUDGET", "0") or 0)
# growth schedule override ("" keeps the config default: rounds on TPU)
TREE_GROWTH = os.environ.get("BENCH_TREE_GROWTH", "")
# data-parallel histogram exchange override: "" keeps the config default
# (auto = psum_scatter at large payloads); set psum|psum_scatter for the
# comms A/B on multi-device runs (docs/Readme.md "Histogram exchange")
HIST_EXCHANGE = os.environ.get("BENCH_HIST_EXCHANGE", "")
# BENCH_SANITIZE=1 runs the timed window under the hot-path sanitizer
# (diagnostics/sanitize.py): jax.transfer_guard("disallow") + compile
# capture, asserting ZERO retraces and ZERO implicit device→host
# transfers per iteration after one warmup step — and, on multi-device
# meshes, arms the learners' DivergenceSanitizer hooks, so the JSON
# "sanitize" block also reports divergence_checks/divergences (the
# cross-shard replication audit) and san.check() fails on any
# divergence.  Counters land in the JSON line under "sanitize".
# Meaningful for the TPU learners
# (BENCH_TREE_GROWTH=rounds, or exact→fused on chip).  The truthiness
# rule mirrors diagnostics.sanitize.sanitize_enabled.
SANITIZE = os.environ.get("BENCH_SANITIZE", "0") not in ("0", "", "false")
# BENCH_TRACE=<logdir>: wrap the timed window in profiling.device_trace
# (jax.profiler → xprof/TensorBoard artifacts in <logdir>) and record
# the artifact dir in the JSON line; with telemetry enabled the same
# window also emits a `profiling.device_trace` host span carrying the
# logdir, which is how scripts/trace_view.py lines the two up.
TRACE_DIR = os.environ.get("BENCH_TRACE", "")


def _feature_fingerprint(X) -> str:
    """Cheap content hash of a fixed row/column sample of X, folded into
    the binned-store cache key: a generator change that alters features
    but not labels must MISS the cache instead of silently reusing
    stale binned data (the label check alone cannot see it)."""
    import hashlib
    import numpy as np
    n, f = X.shape
    ri = np.linspace(0, n - 1, min(n, 64)).astype(np.int64)
    ci = np.linspace(0, f - 1, min(f, 64)).astype(np.int64)
    # index BEFORE any dtype conversion: a full float64 copy of X would
    # be ~62 GB at the Expo shape, on every call incl. cache hits
    sample = np.ascontiguousarray(
        np.asarray(X)[np.ix_(ri, ci)].astype(np.float64))
    return hashlib.sha1(sample.tobytes()).hexdigest()[:10]


def binned_dataset(tag, X, y, params, categorical_feature="auto",
                   group=None):
    """lgb.Dataset for (X, y) backed by a binned-store cache keyed by
    tag/shape/max_bin/feature-fingerprint
    (.bench/<tag>_binned_<N>x<F>_b<bins>_<fp>.bin).

    Host binning at benchmark shapes costs minutes (Epsilon 400k x 2000:
    ~113 s; Expo 11M x 700: ~25 min) — cached, a rerun spends that time
    training.  ANY bad cache (unreadable, old format, stale labels)
    falls through to the self-healing rebin-and-overwrite path; writes
    are atomic per-writer and cleaned up on failure."""
    import numpy as np
    import lightgbm_tpu as lgb

    root = os.path.dirname(os.path.abspath(__file__))
    mb = int(params.get("max_bin", 255))
    fp = _feature_fingerprint(X)
    cache = os.path.join(
        root, ".bench",
        f"{tag}_binned_{len(y)}x{X.shape[1]}_b{mb}_{fp}.bin")
    if os.path.exists(cache):
        from lightgbm_tpu.capi import _wrap_inner
        from lightgbm_tpu.config import config_from_params
        from lightgbm_tpu.dataset import Dataset as RawDataset
        try:
            inner = RawDataset.from_binary(cache,
                                           config_from_params(params))
            # compare in float32 — the store's label dtype — so labels
            # that aren't f32-exact don't make the cache permanently miss
            labels_ok = np.array_equal(
                np.asarray(inner.metadata.label, np.float32),
                np.asarray(y, np.float32))
            qb = inner.metadata.query_boundaries
            if group is None:
                groups_ok = qb is None or len(qb) <= 1
            else:
                want = np.concatenate([[0], np.cumsum(group)])
                groups_ok = qb is not None and np.array_equal(
                    np.asarray(qb, np.int64), want.astype(np.int64))
            if labels_ok and groups_ok:
                return _wrap_inner(inner, params)
            reason = ("labels differ" if not labels_ok
                      else "query groups differ")
        except Exception as e:
            reason = f"unreadable: {e}"
        print(f"stale bin cache {cache} ({reason}); rebinning",
              file=sys.stderr)
    ds = lgb.Dataset(X, y, group=group,
                     categorical_feature=categorical_feature
                     ).construct(params)
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    tmp = f"{cache}.tmp.{os.getpid()}"
    try:
        ds._inner.save_binary(tmp)
        os.replace(tmp, cache)
    except BaseException:
        if os.path.exists(tmp):
            os.remove(tmp)
        raise
    return ds


def synth_higgs(n, f=28, seed=42):
    # the labeling function is FIXED (seed 0) so train/valid sets drawn
    # with different seeds share it; only X and the label noise vary
    w = np.random.RandomState(0).randn(f) / np.sqrt(f)
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    logits = X @ w + 0.5 * np.sin(X[:, 0] * 2.0) * X[:, 1] - 0.3 * X[:, 2] * X[:, 3]
    y = (logits + rng.logistic(size=n) * 0.5 > 0).astype(np.float64)
    return X.astype(np.float64), y


def synth_ctr(n, features=50_000, density=0.01, seed=42, query=20):
    """Wide-sparse CTR/ranking shape (BENCH_WORKLOAD=ctr): hashed COUNT
    features — popularity-skewed column draw (power-law, so a few hot
    columns carry most mass and many distinct values, the regime
    adaptive bin budgets target) with lognormal values, graded 0/1
    relevance in `query`-row queries for lambdarank (ROADMAP item 4's
    recommender/ads class).  Returns (scipy CSR X, y, group sizes)."""
    import scipy.sparse as spm
    rng = np.random.RandomState(seed)
    n = max(query, (n // query) * query)
    nnz = max(1, int(round(features * density)))
    cols = (features * rng.rand(n * nnz) ** 3.0).astype(np.int64)
    np.clip(cols, 0, features - 1, out=cols)
    rows = np.repeat(np.arange(n), nnz)
    vals = np.exp(rng.randn(n * nnz))
    X = spm.csr_matrix((vals, (rows, cols)), shape=(n, features))
    X.sum_duplicates()
    # the labeling function is FIXED (seed 0), like synth_higgs
    w = np.random.RandomState(0).randn(features) / np.sqrt(nnz)
    lin = np.asarray(X @ w).ravel()
    logits = lin + 0.5 * np.sin(3.0 * lin)
    y = (logits + rng.logistic(size=n) * 0.3 > 0).astype(np.float64)
    group = np.full(n // query, query, np.int64)
    return X, y, group


def synth_onehot(n, groups=40, card=6, seed=42):
    """One-hot-heavy EFB acceptance shape (BENCH_WORKLOAD=onehot):
    groups*card columns, exactly one non-zero per group per row — 100%
    exclusive, so bundling shrinks the histogrammed width to ~groups."""
    w = np.random.RandomState(0).randn(groups * card)
    rng = np.random.RandomState(seed)
    codes = rng.randint(0, card, size=(n, groups))
    X = np.zeros((n, groups * card), np.float64)
    for g in range(groups):
        X[np.arange(n), g * card + codes[:, g]] = 1.0
    y = (X @ w + rng.logistic(size=n) * 0.5 > 0).astype(np.float64)
    return X, y


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    global ROWS
    from lightgbm_tpu.jaxutil import enable_compile_cache, \
        require_accelerator
    enable_compile_cache()
    device = require_accelerator()
    import lightgbm_tpu as lgb

    group = None
    if WORKLOAD == "onehot":
        X, y = synth_onehot(ROWS)
    elif WORKLOAD == "ctr":
        if "BENCH_ROWS" not in os.environ:
            # the north-star 10.5M default is a HIGGS-shape number: at
            # 50k features x 1% density its COO staging alone is
            # >100 GB of host RAM — cap the ctr default (explicit
            # BENCH_ROWS is honored as given)
            ROWS = min(ROWS, 1_000_000)
        X, y, group = synth_ctr(ROWS, CTR_FEATURES, CTR_DENSITY,
                                query=CTR_QUERY)
        ROWS = len(y)
    else:
        X, y = synth_higgs(ROWS)
    params = {
        "objective": "binary", "metric": "auc", "verbose": -1,
        "num_leaves": LEAVES, "learning_rate": 0.1, "max_bin": BINS,
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
        "enable_bundle": ENABLE_BUNDLE,
        # bf16 histogram operands: validated at AUC parity with f32 on
        # this workload (the reference GPU path makes the same
        # single-precision trade, docs/GPU-Performance.md:130-134)
        "histogram_dtype": HIST_DTYPE,
    }
    if WORKLOAD == "onehot":
        # the EFB A/B must isolate bundling: the nobundle side's 240
        # one-hot columns would otherwise auto-resolve the csr store on
        # TPU and compare two different code paths
        params["sparse_store"] = "dense"
    if WORKLOAD == "ctr":
        # wide-sparse ranking: lambdarank over the query groups; the
        # tracked ctr metric stays f32 for series continuity — pin
        # BENCH_HIST_DTYPE=int8 for the integer-accumulating sparse
        # kernel pair
        params.update(objective="lambdarank", metric="ndcg")
        if "BENCH_HIST_DTYPE" not in os.environ:
            params["histogram_dtype"] = "float32"
        # FindBin densifies its row sample: the default 200k-row sample
        # at 50k features is an 80 GB float64 matrix — cap it (hashed
        # one-hot/count columns saturate their distinct values long
        # before 20k rows)
        params.setdefault("bin_construct_sample_cnt",
                          int(os.environ.get("BENCH_CTR_SAMPLE", 20_000)))
    if SPARSE_STORE:
        params["sparse_store"] = SPARSE_STORE
    if BIN_BUDGET:
        params["bin_budget"] = BIN_BUDGET
    if TREE_GROWTH:
        params["tree_growth"] = TREE_GROWTH
    if HIST_EXCHANGE:
        params["hist_exchange"] = HIST_EXCHANGE
    cache_tag = WORKLOAD if ENABLE_BUNDLE else f"{WORKLOAD}_nobundle"
    if WORKLOAD == "ctr":
        # no binned-store cache: the fingerprint samples dense rows and
        # the scipy matrix constructs via from_csc directly
        train = lgb.Dataset(X, y, group=group).construct(params)
    else:
        train = binned_dataset(cache_tag, X, y, params)
    bst = lgb.Booster(params, train)
    for _ in range(WARMUP):          # first update = compile
        bst.update()
    float(bst._gbdt.train_score.score.sum())   # drain warmup in-flight work
    from lightgbm_tpu import profiling
    rows_t0 = profiling.counter_value(profiling.HIST_ROWS_TOUCHED)
    hx_t0 = profiling.counter_value(profiling.HIST_EXCHANGE_BYTES)
    sr_t0 = profiling.counter_value(profiling.SPLIT_RECORDS_BYTES)
    nz_t0 = profiling.counter_value(profiling.SPARSE_NNZ_TOUCHED)
    san = None
    import contextlib
    trace_ctx = (profiling.device_trace(TRACE_DIR) if TRACE_DIR
                 else contextlib.nullcontext())
    t0 = time.perf_counter()
    with trace_ctx:
        if SANITIZE:
            from lightgbm_tpu.diagnostics.sanitize import HotPathSanitizer
            san = HotPathSanitizer(warmup=1, label=f"train/{WORKLOAD}")
            with san:
                for _ in range(ITERS):
                    with san.step():
                        bst.update()
        else:
            for _ in range(ITERS):
                bst.update()
    # value fetch: bounds the in-flight pipelined iteration (update()
    # syncs only the PREVIOUS tree)
    float(bst._gbdt.train_score.score.sum())
    dt = time.perf_counter() - t0
    s_per_iter = dt / ITERS
    # histogram-kernel row traffic over the same window (0 for
    # non-rounds learners)
    rows_per_iter = (profiling.counter_value(profiling.HIST_ROWS_TOUCHED)
                     - rows_t0) / ITERS
    # data-parallel comms traffic per iteration (per-device payload of
    # the histogram exchange + the psum_scatter record allgather; 0 on
    # single-device runs) — the hist_exchange=psum|psum_scatter A/B
    hx_bytes_per_iter = (profiling.counter_value(
        profiling.HIST_EXCHANGE_BYTES) - hx_t0) / ITERS
    sr_bytes_per_iter = (profiling.counter_value(
        profiling.SPLIT_RECORDS_BYTES) - sr_t0) / ITERS
    nnz_per_iter = (profiling.counter_value(
        profiling.SPARSE_NNZ_TOUCHED) - nz_t0) / ITERS

    root = os.path.dirname(os.path.abspath(__file__))
    vs = 0.0
    # tracked baseline (baseline_measured.json): the reference binary
    # measured on this machine at the north-star shape — see the file for
    # provenance.  Steady-state s/iter is the fair comparison: this bench
    # window is also post-compile steady state.
    tracked = os.path.join(root, "baseline_measured.json")
    if (WORKLOAD == "higgs" and ROWS == 10_500_000 and LEAVES == 255
            and BINS == 255 and os.path.exists(tracked)):
        ref = json.load(open(tracked)).get("measured", {})
        if ref.get("ref_seconds_per_iter_steady_state"):
            vs = ref["ref_seconds_per_iter_steady_state"] / s_per_iter
    if vs == 0.0 and BINS == 255 and WORKLOAD == "higgs":
        # the ad-hoc baseline is a 255-bin run (make_baseline.py); a
        # 63-bin variant must not claim a speedup against it
        base_file = os.path.join(root, ".bench", "baseline.json")
        if os.path.exists(base_file):
            with open(base_file) as f:
                base = json.load(f)
            if base.get("rows") == ROWS and base.get("num_leaves") == LEAVES:
                vs = base["seconds_per_iter"] / s_per_iter

    # bundling stats: what the histogram kernel actually saw (effective
    # column count + realized conflict rate) — the perf trajectory must
    # distinguish an EFB-compacted run from a full-width one
    inner = train._inner
    plan = inner.bundle_plan
    bundling = {
        "enable_bundle": bool(getattr(inner.config, "enable_bundle", False)),
        "features": int(inner.num_features),
        "effective_features": int(inner.num_store_columns),
        "bundles": 0 if plan is None else plan.num_bundles,
        "realized_conflict_rate": round(inner.realized_conflict_rate(), 6),
    }
    # ingestion accounting (sharded/ingest.py): this process's peak RSS
    # high-water mark, plus — when BENCH_STREAM_CHUNK_ROWS is set — a
    # timed `Dataset.from_stream` construction of the same data at that
    # chunk size (the A/B across env values; scripts/bench_ingest.py
    # measures the controlled matrix in fresh processes so each
    # configuration owns its ru_maxrss)
    import resource
    ingest = {"peak_rss_mb": round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)}
    scr = os.environ.get("BENCH_STREAM_CHUNK_ROWS", "")
    if scr:
        from lightgbm_tpu.config import config_from_params
        from lightgbm_tpu.dataset import Dataset as RawDataset
        icfg = config_from_params({"verbose": -1,
                                   "stream_chunk_rows": int(scr)})
        t_ing = time.perf_counter()
        sds = RawDataset.from_stream((X, y), icfg)
        ingest.update({
            "stream_chunk_rows": int(scr),
            "ingest_seconds": round(time.perf_counter() - t_ing, 3),
            "streamed_rows": int(sds.num_data),
            "sketch_exact": bool(getattr(sds, "_sketch_exact", False)),
        })
        del sds

    out = {
        "metric": f"synthetic-{WORKLOAD} {ROWS}x{X.shape[1]} gbdt "
                  f"{LEAVES} leaves, {BINS} bins: train seconds/iter",
        "value": round(s_per_iter, 4),
        "unit": "s/iter",
        "vs_baseline": round(vs, 4),
        # measured histogram row traffic
        "rows_touched_per_iter": round(rows_per_iter, 1),
        # the histogram exchange that ran (auto resolves per payload/
        # topology) and its measured per-device comms traffic
        "hist_exchange": getattr(bst._gbdt.learner, "hist_exchange", "n/a"),
        "hist_exchange_bytes_per_iter": round(hx_bytes_per_iter, 1),
        "split_records_bytes_per_iter": round(sr_bytes_per_iter, 1),
        "ingest": ingest,
        "hist_dtype": params["histogram_dtype"],
        "learner": type(bst._gbdt.learner).__name__,
        "device": device,
        "bundling": bundling,
    }
    if WORKLOAD == "ctr" or inner.sparse is not None:
        # sparse-store evidence: cells touched per iteration — stored
        # entries on the nonzero-iterating path vs rows x store columns
        # on the dense path; the ratio is the acceptance gate
        # (docs/Sparse.md, scripts/run_ctr_ab.py)
        out["sparse"] = {
            "sparse_store": "csr" if inner.sparse is not None else "dense",
            "nnz": 0 if inner.sparse is None else int(inner.sparse.nnz),
            "nnz_touched_per_iter": round(nnz_per_iter, 1),
            "dense_cells_per_iter": round(
                rows_per_iter * inner.num_store_columns, 1),
            "sparse_fallbacks": profiling.counter_value(
                profiling.SPARSE_FALLBACKS),
            "bin_budget": int(params.get("bin_budget", 0)),
        }
    if san is not None:
        out["sanitize"] = san.report()
    if TRACE_DIR:
        out["device_trace_dir"] = TRACE_DIR
    # full 500-iteration accuracy evidence (scripts/run_northstar.py)
    ns_file = os.path.join(root, "northstar_measured.json")
    if os.path.exists(ns_file):
        ns = json.load(open(ns_file))
        if ns.get("rows") == 10_500_000 and ns.get("iters") == 500:
            out["northstar_500iter_auc"] = ns.get("test_auc")
            out["northstar_auc_delta_vs_ref"] = ns.get("auc_delta_vs_ref")
            out["northstar_speedup_vs_ref"] = ns.get(
                "speedup_vs_ref_same_host")
    print(json.dumps(out))
    if san is not None:
        # fail AFTER the JSON so the counters are always recorded
        san.check()


if __name__ == "__main__":
    main()
