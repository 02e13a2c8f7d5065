"""Sparse binned store + adaptive bin budgets (docs/Sparse.md).

Parity convention: the nonzero-iterating kernels reconstruct each
column's zero bin as `leaf totals - sum(stored bins)` — the same
total-minus-sum EFB's default-bin reconstruction already runs — so
bitwise tree identity is asserted with DYADIC gradients (±1 grads,
power-of-two hessians: every f32 partial sum is exact in any
accumulation order), exactly like tests/test_exchange.py.  Real
objectives (binary, lambdarank) assert split-structure identity and
leaf values to f32 reassociation tolerance.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu import profiling
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import (Dataset as RawDataset, SparseStore,
                                  nnz_capacity_tier, resolve_sparse_store,
                                  store_zero_bins)
from lightgbm_tpu.learner.rounds import RoundsTreeLearner

pytestmark = pytest.mark.quick


def _sparse_X(n=2048, f=160, density=0.05, seed=3, values="int"):
    """Dense ndarray with mostly-zero hashed-indicator columns plus one
    dense numeric column (so numeric binning is exercised too)."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, f))
    nz = rng.rand(n, f) < density
    if values == "int":
        X[nz] = rng.randint(1, 4, int(nz.sum()))
    else:
        X[nz] = np.exp(rng.randn(int(nz.sum())))
    X[:, 0] = rng.randn(n)
    # DISTINCT weights: near-symmetric influence would leave two
    # features' split gains within reconstruction ulps of each other,
    # making argmax tie-breaks seed-dependent
    y = (X[:, 0] + 0.8 * X[:, 3] - 0.6 * X[:, 7] + 0.4 * X[:, 11] > 0
         ).astype(np.float64)
    return X, y


def _dyadic_gh(y):
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(len(y), 0.5, np.float32))
    return g, h


def _splits(t):
    return list(zip(t.split_feature_inner[: t.num_leaves - 1],
                    t.threshold_in_bin[: t.num_leaves - 1]))


def _cfg(**kw):
    base = dict(objective="binary", num_leaves=15, min_data_in_leaf=10,
                verbose=-1, enable_bundle=False, tree_growth="rounds")
    base.update(kw)
    return config_from_params(base)


# ---------------------------------------------------------------------------
# store construction
# ---------------------------------------------------------------------------

def test_sparsified_store_densifies_bitwise():
    X, y = _sparse_X()
    dsd = RawDataset(X, y, config=_cfg(sparse_store="dense"))
    dss = RawDataset(X, y, config=_cfg(sparse_store="csr"))
    assert dsd.sparse is None and dss.sparse is not None
    assert np.array_equal(dss.sparse.densify(np.uint8), dsd.bins)
    # the zero bin of every stored entry differs from the column's
    zb = dss.sparse.zero_bin
    cols, bins = dss.sparse.cols, dss.sparse.bins
    C = dss.sparse.num_columns
    live = cols < C
    assert np.all(bins[live] != zb[cols[live]])


def test_from_csc_builds_csr_store_directly_and_matches_dense():
    scipy_sparse = pytest.importorskip("scipy.sparse")
    X, y = _sparse_X(values="float")
    sp = scipy_sparse.csr_matrix(X)
    dss = RawDataset.from_csc(sp, y, _cfg(sparse_store="csr"))
    dsd = RawDataset.from_csc(sp, y, _cfg(sparse_store="dense"))
    assert dss.sparse is not None and dsd.sparse is None
    assert np.array_equal(dss.sparse.densify(np.uint8), dsd.bins)
    # EFB-composed store: packed columns' entries match the dense pack
    ce = _cfg(sparse_store="csr", enable_bundle=True)
    cde = _cfg(sparse_store="dense", enable_bundle=True)
    dse = RawDataset.from_csc(sp, y, ce)
    dsde = RawDataset.from_csc(sp, y, cde)
    assert dse.bundle_plan is not None
    assert np.array_equal(dse.sparse.densify(np.uint8), dsde.bins)


def test_auto_rule_and_master_switch():
    X, y = _sparse_X()
    ds = RawDataset(X, y, config=_cfg())
    used, mp, plan = ds.used_features, ds.mappers, None
    assert resolve_sparse_store(_cfg(sparse_store="auto"), mp, used, plan)
    assert not resolve_sparse_store(
        _cfg(sparse_store="auto", is_enable_sparse=False), mp, used, plan)
    assert not resolve_sparse_store(
        _cfg(sparse_store="auto", sparse_threshold=0.9999), mp, used,
        plan)
    assert not resolve_sparse_store(_cfg(sparse_store="dense"), mp, used,
                                    plan)
    # narrow stores stay dense under auto
    assert not resolve_sparse_store(_cfg(), mp[:50], used[:50], plan)


def test_dense_fallback_counts_and_matches():
    X, y = _sparse_X()
    dss = RawDataset(X, y, config=_cfg(sparse_store="csr"))
    dsd = RawDataset(X, y, config=_cfg(sparse_store="dense"))
    c0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
    dense = dss.bins                      # materializes, counted
    assert profiling.counter_value(profiling.SPARSE_FALLBACKS) == c0 + 1
    assert np.array_equal(dense, dsd.bins)
    _ = dss.bins                          # cached: no second count
    assert profiling.counter_value(profiling.SPARSE_FALLBACKS) == c0 + 1


def test_implicit_vs_explicit_zero_equivalence():
    """Rows whose raw value is an EXPLICIT 0.0 bin to the column's zero
    bin and are never stored — a dataset whose zeros are explicit in a
    dense ndarray and one built from a scipy matrix that drops them
    produce the same entries."""
    scipy_sparse = pytest.importorskip("scipy.sparse")
    X, y = _sparse_X()
    cfg = _cfg(sparse_store="csr")
    ds_dense_input = RawDataset(X, y, config=cfg)
    ds_sparse_input = RawDataset.from_csc(scipy_sparse.csr_matrix(X), y,
                                          cfg)
    a, b = ds_dense_input.sparse, ds_sparse_input.sparse
    assert np.array_equal(a.cols, b.cols)
    assert np.array_equal(a.bins, b.bins)
    assert np.array_equal(a.zero_bin, b.zero_bin)
    assert a.nnz == b.nnz


def test_nnz_capacity_tiers():
    assert nnz_capacity_tier(1) == 4
    assert nnz_capacity_tier(4) == 4
    assert nnz_capacity_tier(5) == 8
    assert nnz_capacity_tier(500) == 512


def test_zero_bin_table_with_and_without_plan():
    X, y = _sparse_X()
    ds = RawDataset(X, y, config=_cfg())
    zb = store_zero_bins(ds.mappers, ds.used_features, None)
    want = [ds.mappers[i].default_bin for i in ds.used_features]
    assert list(zb) == want


# ---------------------------------------------------------------------------
# tree parity
# ---------------------------------------------------------------------------

def test_sparse_trees_bitwise_identical_dyadic():
    """±1 grads / 0.5 hessians: every f32 partial sum is exact in any
    order, so the zero-bin reconstruction is exact and sparse trees
    must equal dense trees BITWISE (thresholds, gains, leaf values)."""
    X, y = _sparse_X()
    g, h = _dyadic_gh(y)
    trees = {}
    for store in ("dense", "csr"):
        cfg = _cfg(sparse_store=store)
        ds = RawDataset(X, y, config=cfg)
        t, lid = RoundsTreeLearner(ds, cfg).train(g, h)
        trees[store] = (t, np.asarray(lid))
    td, ts = trees["dense"][0], trees["csr"][0]
    assert td.num_leaves == ts.num_leaves > 1
    assert _splits(td) == _splits(ts)
    np.testing.assert_array_equal(
        td.leaf_value[: td.num_leaves], ts.leaf_value[: ts.num_leaves])
    np.testing.assert_array_equal(trees["dense"][1], trees["csr"][1])


def test_sparse_trees_bitwise_identical_dyadic_efb():
    """EFB-composed store: bundled columns + packed-slot predicates
    still grow bitwise-identical trees on the sparse path."""
    X, y = _sparse_X()
    g, h = _dyadic_gh(y)
    trees = {}
    for store in ("dense", "csr"):
        cfg = _cfg(sparse_store=store, enable_bundle=True)
        ds = RawDataset(X, y, config=cfg)
        assert ds.bundle_plan is not None
        t, _ = RoundsTreeLearner(ds, cfg).train(g, h)
        trees[store] = t
    assert _splits(trees["dense"]) == _splits(trees["csr"])
    np.testing.assert_array_equal(
        trees["dense"].leaf_value[: trees["dense"].num_leaves],
        trees["csr"].leaf_value[: trees["csr"].num_leaves])


def test_sparse_stream_grows_the_dense_tree_under_bagging_goss():
    """Rows a bag drops carry a zero row mask, which has to clear both
    their stored entries and their share of the per-leaf totals the zero
    bin is rebuilt from; GOSS-style amplified gradients (a power of two,
    sums stay exact) ride the same value rows.  The nonzero-iterating
    stream must grow the dense stream's tree, leaf id for leaf id."""
    X, y = _sparse_X()
    g, h = _dyadic_gh(y)
    rng = np.random.RandomState(11)
    amp = np.where(rng.rand(len(y)) < 0.5, 2.0, 1.0).astype(np.float32)
    g, h = jnp.asarray(amp * np.asarray(g)), jnp.asarray(amp * np.asarray(h))
    bag = np.sort(rng.choice(len(y), size=int(len(y) * 0.6),
                             replace=False)).astype(np.int32)
    trees = {}
    for store in ("dense", "csr"):
        cfg = _cfg(sparse_store=store)
        ds = RawDataset(X, y, config=cfg)
        lrn = RoundsTreeLearner(ds, cfg)
        assert lrn.sparse == (store == "csr")
        t, lid = lrn.train(g, h, jnp.asarray(bag), len(bag))
        trees[store] = (t, np.asarray(lid))
    td, ts = trees["dense"][0], trees["csr"][0]
    assert td.num_leaves == ts.num_leaves > 1
    assert td.leaf_count[: td.num_leaves].sum() == len(bag)
    assert _splits(td) == _splits(ts)
    np.testing.assert_array_equal(
        td.leaf_value[: td.num_leaves], ts.leaf_value[: ts.num_leaves])
    np.testing.assert_array_equal(trees["dense"][1], trees["csr"][1])


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
def test_sparse_booster_structural_parity(objective):
    """Real objectives through the full Booster: identical split
    structure; leaf values agree to f32 reassociation tolerance."""
    import lightgbm_tpu as lgb
    X, y = _sparse_X(n=1024, f=140)
    kw = {}
    params = {"objective": objective, "verbose": -1, "num_leaves": 15,
              "num_iterations": 3, "min_data_in_leaf": 10,
              "min_gain_to_split": 1e-3, "tree_growth": "rounds",
              "enable_bundle": False}
    if objective == "lambdarank":
        kw["group"] = np.full(len(y) // 16, 16, np.int64)
        params["metric"] = "ndcg"
    models = {}
    for store in ("dense", "csr"):
        p = dict(params, sparse_store=store)
        ds = lgb.Dataset(X, y, params=p, **kw).construct()
        assert (ds._inner.sparse is not None) == (store == "csr")
        bst = lgb.Booster(p, ds)
        for _ in range(3):
            bst.update()
        bst._gbdt._flush_pending()     # the pipelined last tree
        models[store] = bst._gbdt.models
        scores = np.asarray(bst._gbdt.train_score.get()).ravel()
        models[store + "_score"] = scores
    for td, ts in zip(models["dense"], models["csr"]):
        if objective == "binary":
            # bin-exact structural identity holds for the smooth
            # sigmoid gradients
            assert _splits(td) == _splits(ts)
        else:
            # lambdarank's pairwise gradients leave adjacent threshold
            # bins gain-tied within reconstruction ulps — assert the
            # split FEATURE sequence and leaf count instead
            assert td.num_leaves == ts.num_leaves
            assert list(td.split_feature_inner[: td.num_leaves - 1]) \
                == list(ts.split_feature_inner[: ts.num_leaves - 1])
        # zero-bin reconstruction reorders f32 sums (like EFB's
        # default-bin reconstruction); drift compounds over iterations
        np.testing.assert_allclose(
            td.leaf_value[: td.num_leaves],
            ts.leaf_value[: ts.num_leaves], rtol=0, atol=1e-3)
    np.testing.assert_allclose(models["dense_score"],
                               models["csr_score"], rtol=0, atol=2e-3)


# ---------------------------------------------------------------------------
# counters + sanitized steady state
# ---------------------------------------------------------------------------

def test_sparse_counters_scale_with_nnz():
    X, y = _sparse_X()
    g, h = _dyadic_gh(y)
    cfg = _cfg(sparse_store="csr")
    ds = RawDataset(X, y, config=cfg)
    lrn = RoundsTreeLearner(ds, cfg)
    n0 = profiling.counter_value(profiling.SPARSE_NNZ_TOUCHED)
    r0 = profiling.counter_value(profiling.HIST_ROWS_TOUCHED)
    lrn.train(g, h)
    nnz_t = profiling.counter_value(profiling.SPARSE_NNZ_TOUCHED) - n0
    rows_t = profiling.counter_value(profiling.HIST_ROWS_TOUCHED) - r0
    assert nnz_t > 0 and rows_t > 0
    # cells touched collapse from rows x columns to ~nnz per pass
    dense_cells = rows_t * ds.num_store_columns
    assert nnz_t < dense_cells / 4


def test_sparse_steady_state_sanitized_zero_retrace():
    """Sanitize-marked 0/0 loop: steady-state sparse training neither
    retraces nor implicitly transfers after warmup, and a SECOND
    dataset in the same nnz capacity tier reuses every compiled
    program (tier growth without retrace)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.diagnostics.sanitize import HotPathSanitizer
    X1, y1 = _sparse_X(seed=3)
    X2, y2 = _sparse_X(seed=4)    # same shape/density -> same tier
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "min_data_in_leaf": 10, "tree_growth": "rounds",
         "enable_bundle": False, "sparse_store": "csr"}
    ds1 = lgb.Dataset(X1, y1, params=p).construct()
    ds2 = lgb.Dataset(X2, y2, params=p).construct()
    t1 = ds1._inner.sparse.nnz_capacity
    assert t1 == ds2._inner.sparse.nnz_capacity
    bst1 = lgb.Booster(p, ds1)
    bst2 = lgb.Booster(p, ds2)
    # warm outside the guard (bench.py's WARMUP convention: the first
    # iterations legitimately compile the pipelined/eval programs)
    for _ in range(3):
        bst1.update()
    bst2.update()
    with HotPathSanitizer(warmup=1, label="sparse/steady") as san:
        for _ in range(3):
            with san.step():
                bst1.update()
        # tier-sharing dataset: every program is already compiled
        for _ in range(2):
            with san.step():
                bst2.update()
    assert san.retraces == 0, san.report()
    assert san.implicit_transfers == 0, san.report()


# ---------------------------------------------------------------------------
# adaptive bin budgets
# ---------------------------------------------------------------------------

def test_allocate_bin_budgets_invariants():
    from lightgbm_tpu.binning import allocate_bin_budgets
    d = np.array([2, 2, 500, 50, 1], np.int64)
    m = np.array([100, 100, 5000, 500, 1], np.int64)
    b = allocate_bin_budgets(d, m, 300)
    assert b.sum() <= 300 + len(d)          # waterfill never overshoots far
    assert np.all(b <= np.minimum(d, 255))  # never more bins than values
    assert np.all(b >= np.minimum(d, 2))    # floor
    assert b[2] > b[0]                      # resolution follows mass
    # deterministic
    assert np.array_equal(b, allocate_bin_budgets(d, m, 300))


def test_adaptive_budget_mappers_roundtrip_binary_cache(tmp_path):
    X, y = _sparse_X(values="float")
    cfg = _cfg(sparse_store="dense", bin_budget=800)
    ds = RawDataset(X, y, config=cfg)
    nb = ds.num_bins
    assert nb.min() != nb.max()            # budgets actually differ
    path = str(tmp_path / "adaptive.bin")
    ds.save_binary(path)
    ds2 = RawDataset.from_binary(path, cfg)
    assert np.array_equal(ds2.num_bins, nb)
    for a, b in zip(ds.mappers, ds2.mappers):
        assert a.num_bin == b.num_bin
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)
    assert np.array_equal(ds2.bins, ds.bins)


def test_adaptive_budget_sketch_path_agrees_on_distincts():
    """The sketch-side budget allocation uses the same rule: with eps
    tight enough that summaries hold every distinct value, sketch and
    exact-sample mappers get identical per-feature bin counts."""
    X, y = _sparse_X(n=512, f=130, values="float")
    c_ex = _cfg(sparse_store="dense", bin_budget=600)
    c_sk = _cfg(sparse_store="dense", bin_budget=600, bin_find="sketch",
                sketch_eps=0.0005)
    ds_ex = RawDataset(X, y, config=c_ex)
    ds_sk = RawDataset(X, y, config=c_sk)
    assert np.array_equal(ds_ex.num_bins, ds_sk.num_bins)


# ---------------------------------------------------------------------------
# sparse ops directly
# ---------------------------------------------------------------------------

def test_sparse_partition_matches_dense():
    from lightgbm_tpu.ops.partition import (partition_rows,
                                            partition_rows_sparse)
    X, y = _sparse_X()
    cfg = _cfg(sparse_store="csr")
    ds = RawDataset(X, y, config=cfg)
    sp = ds.sparse
    dense = jnp.asarray(sp.densify(np.uint8).astype(np.int32))
    N = ds.num_data
    rng = np.random.RandomState(0)
    lid = jnp.asarray(rng.randint(0, 3, N).astype(np.int32))
    tbl = np.zeros((7, 16), np.float32)
    tbl[:, 1] = [2.0, 1.0, 0.0, 5.0, 0.0, float(1 << 30), 0.0]
    tbl[:, 2] = [0.0, 3.0, 0.0, 6.0, 0.0, float(1 << 30), 0.0]
    tblj = jnp.asarray(tbl)
    a = partition_rows(dense, lid, tblj, num_slots=16)
    b = partition_rows_sparse(jnp.asarray(sp.cols), jnp.asarray(
        sp.bins.astype(np.int32)), jnp.asarray(sp.zero_bin), lid, tblj,
        num_slots=16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sparse_hist_kernels_bitwise_vs_dense_integer_gh():
    from lightgbm_tpu.ops.histogram import (hist_multileaf_masked,
                                            hist_sparse_pallas,
                                            hist_sparse_xla,
                                            sparse_window_streams)
    rng = np.random.RandomState(5)
    N, C, B = 384, 24, 128
    zb = rng.randint(0, 3, C).astype(np.int32)
    dense = np.tile(zb[:, None], (1, N)).astype(np.int32)
    for _ in range(600):
        dense[rng.randint(C), rng.randint(N)] = rng.randint(0, 8)
    nz = dense != zb[:, None]
    nzr, nzc = np.nonzero(nz.T)
    cnt = np.bincount(nzr, minlength=N)
    R = nnz_capacity_tier(int(cnt.max(initial=1)))
    cols = np.full((N, R), C, np.int32)
    binsv = np.zeros((N, R), np.int32)
    offs = np.concatenate([[0], np.cumsum(cnt)])
    pos = np.arange(nzr.size) - offs[nzr]
    cols[nzr, pos] = nzc
    binsv[nzr, pos] = dense[nzc, nzr]
    lid = rng.randint(0, 6, N).astype(np.int32)
    gh8 = np.zeros((8, N), np.float32)
    gh8[0] = rng.randint(-8, 8, N)
    gh8[1] = rng.randint(0, 4, N)
    gh8[2] = (rng.rand(N) > 0.1).astype(np.float32)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    sl = np.array([0, 2, 5, -1], np.int32)
    hd = np.asarray(hist_multileaf_masked(
        jnp.asarray(dense), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype="float32"))
    hs = np.asarray(hist_sparse_xla(
        jnp.asarray(cols), jnp.asarray(binsv), jnp.asarray(zb),
        jnp.asarray(lid), jnp.asarray(gh8), jnp.asarray(sl),
        num_columns_padded=C, num_bins_padded=B))
    np.testing.assert_array_equal(hd, hs)
    er, ef, ev, sc = sparse_window_streams(cols, binsv, C,
                                           num_bins_padded=B)
    hp = np.asarray(hist_sparse_pallas(
        jnp.asarray(er), jnp.asarray(ef), jnp.asarray(ev),
        jnp.asarray(sc), jnp.asarray(zb), jnp.asarray(lid),
        jnp.asarray(gh8), jnp.asarray(sl), num_columns_padded=C,
        num_bins_padded=B, input_dtype="float32", interpret=True))
    np.testing.assert_array_equal(hd, hp)


def test_sparse_window_streams_balanced_under_skew():
    """A power-law column distribution (the CTR acceptance shape) must
    not blow stream memory up by the skew factor: hot columns split
    across fixed-size slots, so total padded entries stay
    O(nnz + chunk * nonempty columns)."""
    from lightgbm_tpu.ops.histogram import (SPARSE_CHUNK,
                                            sparse_window_streams)
    rng = np.random.RandomState(0)
    N, C, R = 4096, 512, 16
    # heavy skew: most entries land in a handful of columns
    cols = np.minimum((C * rng.rand(N, R) ** 4).astype(np.int64),
                      C - 1).astype(np.int32)
    # dedupe within rows loosely: not required by the layout
    binsv = rng.randint(1, 8, (N, R)).astype(np.int32)
    er, ef, ev, sc = sparse_window_streams(cols, binsv, C,
                                           num_bins_padded=128)
    nnz = N * R
    padded = er.shape[0] * er.shape[1]
    assert padded <= 2 * (nnz + SPARSE_CHUNK * C)
    # every stored entry survives exactly once
    assert int(ev.sum()) == nnz
    # hot columns occupy multiple slots; each slot maps to one column
    assert (np.bincount(sc[sc < C], minlength=C) >= 1).sum() <= C
    assert sc.size == er.shape[0] * 8


def test_capi_sparse_predict_chunks_match_dense():
    scipy_sparse = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu as lgb
    import lightgbm_tpu.boosting.gbdt as gmod
    from lightgbm_tpu.capi import CApiBooster
    rng = np.random.RandomState(0)
    X = rng.randn(300, 8)
    y = (X[:, 0] > 0).astype(np.float64)
    ds = lgb.Dataset(X, y, params={"verbose": -1}).construct()
    p = {"verbose": -1, "objective": "binary"}
    bst = lgb.Booster(p, ds)
    for _ in range(3):
        bst.update()
    cb = CApiBooster(bst)
    Xq = rng.randn(70, 8) * (rng.rand(70, 8) < 0.4)
    ref = bst.predict(Xq)
    sp = scipy_sparse.csr_matrix(Xq)
    old = gmod.GBDT._PREDICT_CHUNK
    gmod.GBDT._PREDICT_CHUNK = 16       # force the multi-chunk path
    try:
        indptr = sp.indptr.astype(np.int64)
        ind = sp.indices.astype(np.int32)
        dat = sp.data.astype(np.float64)
        out = np.zeros(70, np.float64)
        n = cb.predict_for_csr(indptr.ctypes.data, 3, ind.ctypes.data,
                               dat.ctypes.data, 1, indptr.size, dat.size,
                               8, 0, -1, out.ctypes.data)
        assert n == 70
        np.testing.assert_allclose(out, ref, rtol=1e-6)
        spc = sp.tocsc()
        cp = spc.indptr.astype(np.int64)
        ic = spc.indices.astype(np.int32)
        dc = spc.data.astype(np.float64)
        out2 = np.zeros(70, np.float64)
        n2 = cb.predict_for_csc(cp.ctypes.data, 3, ic.ctypes.data,
                                dc.ctypes.data, 1, cp.size, dc.size, 70,
                                0, -1, out2.ctypes.data)
        assert n2 == 70
        np.testing.assert_allclose(out2, ref, rtol=1e-6)
    finally:
        gmod.GBDT._PREDICT_CHUNK = old


# ---------------------------------------------------------------------------
# int8 sparse kernels + trees
# ---------------------------------------------------------------------------

def test_sparse_int8_kernels_bitwise_xla_vs_pallas_skewed():
    """int8 sparse parity for ARBITRARY real-valued gradients: both
    kernels accumulate the SAME quantized integers exactly (int32
    scatter-add vs int8-MXU dot with int32 accumulation, integer slot
    totals + integer zero-bin residual, ONE dequantizing scale at the
    end), so XLA == Pallas(interpret) BITWISE.  A power-law column
    distribution makes the hottest column exceed SPARSE_CHUNK entries,
    exercising the hot-column slot fold in unscatter_slot_hist on the
    quantized path too."""
    from lightgbm_tpu.ops.histogram import (hist_multileaf_masked,
                                            hist_sparse_pallas,
                                            hist_sparse_xla,
                                            sparse_window_streams)
    rng = np.random.RandomState(11)
    N, C, B, draws = 1024, 64, 64, 12
    raw = np.minimum((C * rng.rand(N, draws) ** 4).astype(np.int64),
                     C - 1)
    zb = rng.randint(0, 3, C).astype(np.int32)
    R = nnz_capacity_tier(draws)
    cols = np.full((N, R), C, np.int32)
    binsv = np.zeros((N, R), np.int32)
    for i in range(N):               # unique per row: a valid ELL store
        u = np.unique(raw[i])
        cols[i, : u.size] = u
        binsv[i, : u.size] = rng.randint(1, B - 1, u.size)
    lid = rng.randint(0, 6, N).astype(np.int32)
    gh8 = np.zeros((8, N), np.float32)
    gh8[0] = rng.randn(N).astype(np.float32)          # real-valued
    gh8[1] = np.abs(rng.randn(N)).astype(np.float32)
    gh8[2] = (rng.rand(N) > 0.1).astype(np.float32)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    sl = np.array([0, 2, 5, -1], np.int32)
    hx = np.asarray(hist_sparse_xla(
        jnp.asarray(cols), jnp.asarray(binsv), jnp.asarray(zb),
        jnp.asarray(lid), jnp.asarray(gh8), jnp.asarray(sl),
        num_columns_padded=C, num_bins_padded=B, input_dtype="int8"))
    er, ef, ev, sc = sparse_window_streams(cols, binsv, C,
                                           num_bins_padded=B)
    # the skew actually split a hot column across slots
    assert np.bincount(sc[sc < C], minlength=C).max() >= 2
    hp = np.asarray(hist_sparse_pallas(
        jnp.asarray(er), jnp.asarray(ef), jnp.asarray(ev),
        jnp.asarray(sc), jnp.asarray(zb), jnp.asarray(lid),
        jnp.asarray(gh8), jnp.asarray(sl), num_columns_padded=C,
        num_bins_padded=B, input_dtype="int8", interpret=True))
    np.testing.assert_array_equal(hx, hp)
    # the count channel never quantizes (mask scale is exactly 1.0):
    # it must equal the f32 dense reference bitwise
    dense = np.tile(zb[:, None], (1, N)).astype(np.int32)
    live = cols < C
    rr, ss = np.nonzero(live)
    dense[cols[rr, ss], rr] = binsv[rr, ss]
    hd = np.asarray(hist_multileaf_masked(
        jnp.asarray(dense), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype="float32"))
    np.testing.assert_array_equal(hd[:, :, 2], hx[:, :, 2])
    # quantized grad/hess channels land within the per-entry bound
    np.testing.assert_allclose(hd[:, :, :2], hx[:, :, :2], rtol=0,
                               atol=N * max(np.abs(gh8[0]).max(),
                                            np.abs(gh8[1]).max()) / 254)


def test_sparse_int8_trees_bitwise_vs_dense_int8():
    """histogram_dtype=int8 through the rounds learner: gradients of
    +-127 quantize at scale exactly 1.0 and hessians of 63.5 at scale
    exactly 0.5, so the dense path's per-entry dequantized f32 sums and
    the sparse path's integer sums describe the SAME exact numbers —
    int8 sparse trees must equal int8 dense trees bitwise."""
    X, y = _sparse_X()
    g = jnp.asarray(np.where(y > 0, -127.0, 127.0).astype(np.float32))
    h = jnp.asarray(np.full(len(y), 63.5, np.float32))
    trees = {}
    for store in ("dense", "csr"):
        cfg = _cfg(sparse_store=store, histogram_dtype="int8")
        ds = RawDataset(X, y, config=cfg)
        t, lid = RoundsTreeLearner(ds, cfg).train(g, h)
        trees[store] = (t, np.asarray(lid))
    td, ts = trees["dense"][0], trees["csr"][0]
    assert td.num_leaves == ts.num_leaves > 1
    assert _splits(td) == _splits(ts)
    np.testing.assert_array_equal(
        td.leaf_value[: td.num_leaves], ts.leaf_value[: ts.num_leaves])
    np.testing.assert_array_equal(trees["dense"][1], trees["csr"][1])


# ---------------------------------------------------------------------------
# sparse binned score replay
# ---------------------------------------------------------------------------

def _replay_booster(store, Xtr, ytr, Xv, yv, rounds=4):
    """Booster with a csr/dense train store and a SAME-store valid set,
    boosted with dyadic custom gradients (every histogram partial sum
    exact in f32 -> trees and leaf values bitwise across stores)."""
    import lightgbm_tpu as lgb
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "min_data_in_leaf": 10, "tree_growth": "rounds",
         "enable_bundle": False, "sparse_store": store}
    ds = lgb.Dataset(Xtr, ytr, params=p).construct()
    vds = lgb.Dataset(Xv, yv, params=p, reference=ds).construct()
    assert (ds._inner.sparse is not None) == (store == "csr")
    assert (vds._inner.sparse is not None) == (store == "csr")
    bst = lgb.Booster(p, ds)
    bst.add_valid(vds, "v")
    ys = np.where(ytr > 0, 1.0, -1.0)
    step = {"i": 0}

    def fobj(preds, dtrain):
        step["i"] += 1
        g = np.where(preds >= ys * step["i"] * 0.125, 0.25, -0.25)
        return g.astype(np.float32), np.full(len(g), 0.5, np.float32)

    for _ in range(rounds):
        bst.update(fobj=fobj)
    bst._gbdt._flush_pending()
    train = np.asarray(bst._gbdt.train_score.get()).ravel().copy()
    valid = np.asarray(bst._gbdt.valid_sets[0][2].get()).ravel().copy()
    return bst, train, valid


def test_sparse_replay_bitwise_vs_dense_replay_dyadic():
    """The sparse binned valid replay (ELL walk, no densify) must land
    EXACTLY where the dense binned replay lands: with dyadic custom
    gradients the two stores grow bitwise-identical trees, traversal
    decisions are exact bin compares either way, and leaf values
    accumulate in the same order -> train AND valid scores bitwise."""
    Xtr, ytr = _sparse_X(seed=3)
    Xv, yv = _sparse_X(seed=9)
    c0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
    _, tr_s, va_s = _replay_booster("csr", Xtr, ytr, Xv, yv)
    # the whole csr leg -- construct, train, valid replay -- never
    # densified
    assert profiling.counter_value(profiling.SPARSE_FALLBACKS) == c0
    _, tr_d, va_d = _replay_booster("dense", Xtr, ytr, Xv, yv)
    np.testing.assert_array_equal(tr_d, tr_s)
    np.testing.assert_array_equal(va_d, va_s)


def test_sparse_fallbacks_zero_csr_train_and_valid():
    """Pinned acceptance criterion: a csr train + valid-eval run keeps
    tree/sparse_fallbacks EXACTLY at zero — histograms, partitions,
    score replay, and metric evaluation all walk the ELL store."""
    import lightgbm_tpu as lgb
    Xtr, ytr = _sparse_X(seed=3)
    Xv, yv = _sparse_X(seed=9)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "min_data_in_leaf": 10, "tree_growth": "rounds",
         "enable_bundle": False, "sparse_store": "csr",
         "metric": "binary_logloss"}
    c0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
    ds = lgb.Dataset(Xtr, ytr, params=p).construct()
    vds = lgb.Dataset(Xv, yv, params=p, reference=ds).construct()
    bst = lgb.Booster(p, ds)
    bst.add_valid(vds, "v")
    for _ in range(4):
        bst.update()
    bst._gbdt._flush_pending()
    res = bst.eval_valid()
    assert res and np.isfinite(res[0][2])
    assert profiling.counter_value(profiling.SPARSE_FALLBACKS) == c0


def test_sparse_replay_steady_state_sanitized_zero_retrace():
    """Sanitize-marked 0/0 loop WITH a sparse valid set attached: the
    steady-state train + replay iteration neither retraces nor
    implicitly transfers after warmup (the sparse walk programs are as
    shape-stable as the dense ones)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.diagnostics.sanitize import HotPathSanitizer
    Xtr, ytr = _sparse_X(seed=3)
    Xv, yv = _sparse_X(seed=5)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "min_data_in_leaf": 10, "tree_growth": "rounds",
         "enable_bundle": False, "sparse_store": "csr"}
    ds = lgb.Dataset(Xtr, ytr, params=p).construct()
    vds = lgb.Dataset(Xv, yv, params=p, reference=ds).construct()
    bst = lgb.Booster(p, ds)
    bst.add_valid(vds, "v")
    c0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
    for _ in range(3):                 # warm: compiles train + replay
        bst.update()
    with HotPathSanitizer(warmup=1, label="sparse/replay") as san:
        for _ in range(3):
            with san.step():
                bst.update()
    assert san.retraces == 0, san.report()
    assert san.implicit_transfers == 0, san.report()
    assert profiling.counter_value(profiling.SPARSE_FALLBACKS) == c0


# ---------------------------------------------------------------------------
# sharded sparse feeds (fused learners)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lt,mesh_kind", [
    ("serial", None), ("data", "data"), ("feature", "feature"),
    ("data2d", "data2d"), ("voting", "voting")])
def test_fused_sparse_feed_trees_bitwise_vs_dense(lt, mesh_kind):
    """Every fused learner consumes the sparse ELL feed directly —
    per-shard windows for feature sharding, the EFB-decoded unbundled
    feed when a bundle plan exists — and grows BITWISE-identical trees
    and leaf routes vs its dense feed, with zero sparse fallbacks."""
    from lightgbm_tpu.learner.fused import FusedTreeLearner, make_mesh
    rng = np.random.RandomState(7)
    n = 1201
    dense_part = rng.randn(n, 4) * (rng.rand(n, 4) < 0.3)
    onehot = np.zeros((n, 16))
    onehot[np.arange(n), rng.randint(0, 16, n)] = rng.rand(n) + 0.5
    X = np.concatenate([dense_part, onehot], axis=1)  # EFB-bundleable
    y = (X[:, 0] + 0.5 * X[:, 1] - X[:, 2]
         + 0.1 * rng.randn(n) > 0).astype(np.float64)
    grad = jnp.asarray((rng.randint(-8, 9, size=n) * 0.125)
                       .astype(np.float32))           # dyadic: exact
    hess = jnp.asarray(np.ones(n, np.float32))
    mesh = make_mesh(mesh_kind) if mesh_kind else None
    if mesh_kind and mesh is None:
        pytest.skip(f"not enough devices for a {mesh_kind} mesh")

    def sig(t):
        k = t.num_leaves - 1
        return (t.num_leaves, t.split_feature_inner[:k].tolist(),
                t.threshold_in_bin[:k].tolist(),
                t.left_child[:k].tolist(),
                t.leaf_value[: t.num_leaves].tobytes())

    for bundle in (False, True):
        trees = {}
        for store in ("dense", "csr"):
            cfg = config_from_params({
                "objective": "binary", "num_leaves": 15,
                "min_data_in_leaf": 20, "verbose": -1, "top_k": 6,
                "sparse_store": store, "enable_bundle": bundle,
                "tree_learner": lt})
            ds = RawDataset(X, y, config=cfg)
            if store == "csr":
                assert ds.sparse is not None
                assert (ds.bundle_plan is not None) == bundle
                c0 = profiling.counter_value(profiling.SPARSE_FALLBACKS)
            t, lid = FusedTreeLearner(ds, cfg, mesh).train(grad, hess)
            if store == "csr":
                assert profiling.counter_value(
                    profiling.SPARSE_FALLBACKS) == c0, (bundle, lt)
            trees[store] = (t, np.asarray(lid))
        assert sig(trees["dense"][0]) == sig(trees["csr"][0]), \
            (bundle, lt)
        np.testing.assert_array_equal(trees["dense"][1],
                                      trees["csr"][1])
