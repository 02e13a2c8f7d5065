"""Gathered ("ordered") histograms and the device-resident row
partition (learner/rounds.py hist_rows=gathered, ops/histogram.py
hist_multileaf_gathered).

The gathered kernel must produce EXACTLY the masked kernel's
histograms: tests construct gradients on a dyadic grid (multiples of
2^-7 with bounded magnitude) so every fp32 partial sum is exactly
representable regardless of summation order — bitwise equality then
holds even though the two paths visit rows in different orders.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import (gather_segments,
                                        hist_multileaf_gathered,
                                        hist_multileaf_masked)

pytestmark = pytest.mark.quick


def _dyadic(rng, n, lo=-512, hi=512, scale=64.0):
    """fp32 values whose sums are exact in any order (integer grid)."""
    return (rng.randint(lo, hi, size=n) / scale).astype(np.float32)


def _partition_problem(rng, n, f, b, n_leaves, live_frac=1.0,
                       goss_amp=None, int8_store=False):
    """A random leaf partition with optional bagged-out rows and
    GOSS-style amplified gradients; returns everything both kernel
    feeds need plus the permutation/segment tables of the live rows."""
    bins = rng.randint(0, b, size=(f, n)).astype(np.int32)
    lid = rng.randint(0, n_leaves, size=n).astype(np.int32)
    live = (rng.rand(n) < live_frac)
    gh8 = np.zeros((8, n), np.float32)
    gh8[0] = _dyadic(rng, n)
    gh8[1] = (rng.randint(0, 256, size=n) / 128.0).astype(np.float32)
    if goss_amp is not None:
        # GOSS amplifies the sampled small-gradient rows by a constant;
        # a power of two keeps the sums exact
        amp_rows = rng.rand(n) < 0.5
        gh8[0][amp_rows] *= goss_amp
        gh8[1][amp_rows] *= goss_amp
    gh8[2] = live.astype(np.float32)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    # permutation: live rows grouped by leaf (stable), as the learner's
    # compaction maintains it; bagged-out rows never enter
    live_idx = np.flatnonzero(live)
    order = live_idx[np.argsort(lid[live_idx], kind="stable")]
    perm = np.full(n, 0, np.int32)
    perm[: len(order)] = order
    if len(order) < n:
        perm[len(order):] = np.setdiff1d(np.arange(n), order)
    cnt = np.bincount(lid[live_idx], minlength=n_leaves).astype(np.int32)
    off = (np.cumsum(cnt) - cnt).astype(np.int32)
    store = bins
    if int8_store:
        store = (bins.astype(np.int16) - 128).astype(np.int8)
    return store, lid, gh8, perm, off, cnt


def test_gather_segments_layout():
    rng = np.random.RandomState(0)
    perm = rng.permutation(100).astype(np.int32)
    seg_off = np.array([10, 0, 40], np.int32)
    seg_cnt = np.array([5, 0, 7], np.int32)       # middle slot empty
    idx, slot, total = gather_segments(
        jnp.asarray(perm), jnp.asarray(seg_off), jnp.asarray(seg_cnt),
        capacity=16)
    assert int(total) == 12
    np.testing.assert_array_equal(np.asarray(idx)[:5], perm[10:15])
    np.testing.assert_array_equal(np.asarray(idx)[5:12], perm[40:47])
    np.testing.assert_array_equal(np.asarray(slot)[:5], 0)
    np.testing.assert_array_equal(np.asarray(slot)[5:12], 2)
    np.testing.assert_array_equal(np.asarray(slot)[12:], -2)


@pytest.mark.parametrize("live_frac,goss_amp,int8_store", [
    (1.0, None, False),          # all rows live
    (0.6, None, False),          # bagged-out rows never gathered
    (1.0, 2.0, False),           # GOSS-amplified gradients
    (0.8, 2.0, True),            # int8 value-128 store (bundled layout)
])
def test_gathered_matches_masked_bitwise(live_frac, goss_amp, int8_store):
    """Exact (bitwise) fp32 parity of sums and counts between the
    gathered kernel and the masked full-stream kernel on a random leaf
    partition — the acceptance bar of the ordered-histograms path."""
    rng = np.random.RandomState(11)
    n, f, b, L = 4097, 9, 250, 12                # odd n: chunk padding
    B = 256
    store, lid, gh8, perm, off, cnt = _partition_problem(
        rng, n, f, b, L, live_frac, goss_amp, int8_store)
    # histogram leaves [3, 7, (empty), 0] — empty slot via cnt 0
    leaves = np.array([3, 7, 5, 0], np.int32)
    seg_off = off[leaves]
    seg_cnt = cnt[leaves].copy()
    seg_cnt[2] = 0                               # force an empty slot
    seg_off[2] = 0
    h_g = hist_multileaf_gathered(
        jnp.asarray(store), jnp.asarray(gh8), jnp.asarray(perm),
        jnp.asarray(seg_off), jnp.asarray(seg_cnt), capacity=4096,
        num_bins_padded=B, backend="xla", input_dtype="float32")
    sl = leaves.copy()
    sl[2] = -1                                   # masked empty slot
    h_m = hist_multileaf_masked(
        jnp.asarray(store), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype="float32")
    np.testing.assert_array_equal(np.asarray(h_g), np.asarray(h_m))
    assert np.asarray(h_g)[2].max() == 0.0       # empty slot exact zero


def test_gathered_int8_counts_exact_and_tight_scales():
    """int8 (quantized) gathered path: counts are exact; grad/hess match
    the masked kernel within the quantization bound — the scales differ
    (gathered quantizes over the live subset only, a tighter bound)."""
    rng = np.random.RandomState(5)
    n, f, b, L = 3000, 6, 120, 8
    B = 128
    store, lid, gh8, perm, off, cnt = _partition_problem(
        rng, n, f, b, L, live_frac=0.7)
    leaves = np.array([0, 3, 7], np.int32)
    h_g = hist_multileaf_gathered(
        jnp.asarray(store), jnp.asarray(gh8), jnp.asarray(perm),
        jnp.asarray(off[leaves]), jnp.asarray(cnt[leaves]), capacity=3072,
        num_bins_padded=B, backend="xla", input_dtype="int8")
    h_m = hist_multileaf_masked(
        jnp.asarray(store), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(leaves), num_bins_padded=B, backend="xla",
        input_dtype="int8")
    np.testing.assert_array_equal(np.asarray(h_g)[:, :, 2],
                                  np.asarray(h_m)[:, :, 2])
    cnts = np.asarray(h_m)[:, :, 2]
    bg = cnts * (np.abs(gh8[0]).max() / 127.0) + 1e-4
    bh = cnts * (np.abs(gh8[1]).max() / 127.0) + 1e-4
    assert (np.abs(np.asarray(h_g)[:, :, 0] - np.asarray(h_m)[:, :, 0])
            <= bg).all()
    assert (np.abs(np.asarray(h_g)[:, :, 1] - np.asarray(h_m)[:, :, 1])
            <= bh).all()


def _train_pair(X, y, g, h, params_extra, bag=None, bag_cnt=None,
                leaves_per_batch=None, monkeypatch=None):
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.learner import rounds as rounds_mod
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    if leaves_per_batch is not None:
        monkeypatch.setattr(rounds_mod, "LEAVES_PER_BATCH",
                            leaves_per_batch)
    trees = {}
    for mode in ("masked", "gathered"):
        cfg = config_from_params(dict(params_extra, hist_rows=mode))
        ds = RawDataset(X, y, config=cfg)
        lrn = RoundsTreeLearner(ds, cfg, None)
        assert lrn.hist_rows == mode
        trees[mode] = lrn.train(jnp.asarray(g), jnp.asarray(h),
                                None if bag is None else jnp.asarray(bag),
                                bag_cnt)
    return trees


def _splits(t):
    return sorted(zip(t.split_feature_inner[: t.num_leaves - 1],
                      t.threshold_in_bin[: t.num_leaves - 1]))


def test_trees_identical_masked_vs_gathered(monkeypatch):
    """Same seed, same data: the gathered learner must grow the
    IDENTICAL tree (±1 gradients and constant hessians make every
    histogram sum exact, so even split ties resolve the same way).
    Small LEAVES_PER_BATCH forces multiple chunks incl. a short last
    chunk; the bag drops 40% of rows from the permutation."""
    rng = np.random.RandomState(3)
    N = 3000
    X = rng.randn(N, 10)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    g = np.where(y > 0, -1.0, 1.0).astype(np.float32)
    h = np.full(N, 0.5, np.float32)
    bag = np.sort(rng.choice(N, size=int(N * 0.6),
                             replace=False)).astype(np.int32)
    trees = _train_pair(
        X, y, g, h,
        {"objective": "binary", "num_leaves": 13, "min_data_in_leaf": 5,
         "verbose": -1},
        bag=bag, bag_cnt=len(bag), leaves_per_batch=5,
        monkeypatch=monkeypatch)
    tm, lm = trees["masked"]
    tg, lg = trees["gathered"]
    assert tm.num_leaves == tg.num_leaves > 1
    assert _splits(tm) == _splits(tg)
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lg))
    np.testing.assert_allclose(tm.leaf_value[: tm.num_leaves],
                               tg.leaf_value[: tg.num_leaves], rtol=1e-6)


def test_trees_identical_no_parent_cache(monkeypatch):
    """Bounded-memory mode (both children histogrammed directly): the
    gathered large-child pass runs at the full-capacity tiers and must
    still grow the identical tree."""
    rng = np.random.RandomState(9)
    N = 2000
    X = rng.randn(N, 6)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float64)
    g = np.where(y > 0, -1.0, 1.0).astype(np.float32)
    h = np.full(N, 0.5, np.float32)
    trees = _train_pair(
        X, y, g, h,
        {"objective": "binary", "num_leaves": 9, "min_data_in_leaf": 10,
         "verbose": -1, "histogram_pool_size": 0.001})
    tm, _ = trees["masked"]
    tg, _ = trees["gathered"]
    assert _splits(tm) == _splits(tg)


def test_gathered_rows_touched_reduction():
    """The point of the whole exercise: the gathered learner's measured
    histogram row traffic must be >= 2x lower than masked on the same
    problem (tier-1 analog of the bench.py CPU A/B)."""
    from lightgbm_tpu import profiling
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    rng = np.random.RandomState(7)
    N = 4000
    X = rng.randn(N, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(N, 0.5, np.float32))
    rows = {}
    for mode in ("masked", "gathered"):
        cfg = config_from_params({
            "objective": "binary", "num_leaves": 31,
            "min_data_in_leaf": 10, "verbose": -1, "hist_rows": mode})
        ds = RawDataset(X, y, config=cfg)
        profiling.reset()
        RoundsTreeLearner(ds, cfg, None).train(g, h)
        rows[mode] = profiling.counter_value("tree/hist_rows_touched")
    assert rows["gathered"] > 0
    assert rows["masked"] / rows["gathered"] >= 2.0, rows


def test_efb_bundled_store_gathered_matches_masked():
    """EFB-bundled store columns through the gathered path: identical
    models masked vs gathered on one-hot data that bundles heavily."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(21)
    n, groups, card = 1500, 8, 4
    codes = rng.randint(0, card, size=(n, groups))
    X = np.zeros((n, groups * card), np.float64)
    for gi in range(groups):
        X[np.arange(n), gi * card + codes[:, gi]] = 1.0
    w = np.random.RandomState(0).randn(groups * card)
    y = (X @ w > 0).astype(np.float64)
    preds = {}
    for mode in ("masked", "gathered"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 10, "enable_bundle": True,
                  "tree_growth": "rounds", "hist_rows": mode}
        ds = lgb.Dataset(X, y)
        bst = lgb.train(params, ds, num_boost_round=5)
        assert bst._gbdt.train_set.num_store_columns < groups * card
        preds[mode] = bst.predict(X[:200])
    np.testing.assert_allclose(preds["masked"], preds["gathered"],
                               rtol=1e-6, atol=1e-7)


def test_gather_capacity_model_and_the_key():
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.learner.common import (gather_capacity_tiers,
                                             gather_scratch_capacity)
    cap = gather_scratch_capacity(10_500_000)
    assert cap >= (10_500_000 + 1) // 2 and cap % 128 == 0
    tiers = gather_capacity_tiers(cap)
    assert tiers == (328_064, 1_312_512, 5_250_048)     # Higgs, one chip
    assert tiers[-1] == cap and len(tiers) == 3
    assert all(t % 128 == 0 for t in tiers)
    assert list(tiers) == sorted(tiers)
    # tiny shapes collapse to fewer tiers but never below one lane tile
    assert gather_capacity_tiers(128) == (128,)
    assert config_from_params({"verbose": -1}).hist_rows == "auto"
    with pytest.raises(ValueError):
        config_from_params({"hist_rows": "bogus", "verbose": -1})
    # alias
    assert config_from_params(
        {"ordered_histograms": "masked", "verbose": -1}).hist_rows == "masked"


# the store of each benchmark cell as RoundsTreeLearner hands it to the
# resolver (per-shard rows; int32 bins)
HIGGS = dict(num_columns=28, np_rows=10_500_000)
HIGGS_SHARD = dict(HIGGS, np_rows=2_625_000)            # higgs.data4
EPSILON = dict(num_columns=2000, np_rows=400_000)       # .full and .b63


@pytest.mark.parametrize("hist_rows,dtype,store,want", [
    # auto is the stream: it measured faster in all three cells (13x,
    # by 23 %, by 4 %: PERF.md section 6, PR 32) and, with float32
    # operands, in chip_smoke.py's Higgs run (2.4x)
    ("auto", "int8", HIGGS, "masked"),
    ("auto", "int8", EPSILON, "masked"),
    ("auto", "int8", HIGGS_SHARD, "masked"),
    ("auto", "float32", HIGGS, "masked"),
    # an explicit value wins, both ways
    ("gathered", "int8", HIGGS, "gathered"),
    ("gathered", "int8", EPSILON, "gathered"),
    ("masked", "int8", EPSILON, "masked"),
])
def test_resolve_hist_rows(hist_rows, dtype, store, want):
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.learner.common import resolve_hist_rows
    cfg = config_from_params({"hist_rows": hist_rows, "verbose": -1,
                              "histogram_dtype": dtype})
    assert resolve_hist_rows(cfg, bins_itemsize=4, **store) == want


def test_feature_importance_split_dtype_int32():
    """Reference C API returns int importance for 'split' (dtype parity,
    ADVICE.md round 5)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(2)
    X = rng.randn(400, 5)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 7, "min_data_in_leaf": 10},
                    lgb.Dataset(X, y), num_boost_round=3)
    assert bst.feature_importance("split").dtype == np.int32
    assert bst.feature_importance("gain").dtype == np.float64


def test_gather_chunk_cap_respects_vmem_budget():
    """ADVICE round 5: the 512-row floor let padded B >= 2048 exceed the
    stated 4 MB budget; the floor is now one 128-lane tile."""
    from lightgbm_tpu.ops.histogram import _gather_chunk_cap
    for B in (128, 256, 1024, 2048, 4096):
        ck = _gather_chunk_cap(B, 4)
        assert ck % 128 == 0 and ck >= 128
        if ck > 128:          # above the floor the budget must hold
            assert ck * B * 4 <= int(4e6)


def test_gathered_downgrade_is_counted():
    """The scratch memory gate still replaces gathered by masked, but no
    longer in silence: tree/hist_rows_downgrades moves (chip_smoke.py
    asserts it stays 0)."""
    from lightgbm_tpu import profiling
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.learner.common import resolve_hist_rows
    cfg = config_from_params({"hist_rows": "gathered", "verbose": -1})
    c0 = profiling.counter_value(profiling.HIST_ROWS_DOWNGRADES)
    # 50M rows x 2000 int32 columns: a 200 GB scratch fits no device
    assert resolve_hist_rows(cfg, num_columns=2000,
                             np_rows=50_000_000) == "masked"
    assert profiling.counter_value(profiling.HIST_ROWS_DOWNGRADES) == c0 + 1
    assert resolve_hist_rows(cfg, **HIGGS) == "gathered"
    assert profiling.counter_value(profiling.HIST_ROWS_DOWNGRADES) == c0 + 1
    # a feed that was never gathered is no downgrade
    auto = config_from_params({"verbose": -1})
    assert resolve_hist_rows(auto, num_columns=2000,
                             np_rows=50_000_000) == "masked"
    assert profiling.counter_value(profiling.HIST_ROWS_DOWNGRADES) == c0 + 1


@pytest.mark.parametrize("bins_itemsize,dtype,want", [
    (4, "int8", 8192), (4, "bfloat16", 8192), (4, "float32", 2048),
    (1, "int8", 2048), (1, "float32", 1024)])
def test_masked_chunk_is_the_compile_validated_table(bins_itemsize, dtype,
                                                     want):
    """The masked kernels' row chunk comes from the table that
    tests/test_tpu_compile.py validates against the TPU compiler, and
    shrinks in proportion for value-row blocks taller than K=84's."""
    from lightgbm_tpu.ops.histogram import _masked_chunk
    for Mp in (8, 24, 96, 256):
        assert _masked_chunk(Mp, bins_itemsize, dtype) == want
    tall = _masked_chunk(512, bins_itemsize, dtype)
    assert tall == want // 2 and tall % 128 == 0
