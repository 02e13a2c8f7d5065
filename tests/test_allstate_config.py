"""The sparse one-hot deployment (`allstate`, the benchmark's fourth
configuration) at sizes the CPU takes.

(a) `Dataset.from_csc` bins a scipy matrix by its stored entries: store, plan,
mappers and `bundle_conflict_rows` are bitwise what the dense-scratch-column
route it replaced gives, and what the dense-row constructor gives; (b) the
generator's contract; (c) on an Allstate-shaped toy the rounds learner grows
the same trees with `enable_bundle` on and off, and `Booster.predict` agrees
with the benchmark's plain walk; (d) `tree/split_cells` and
`tree/unbundle_gather_elems` read what the shapes say, folded on the host;
(e) a dense store's build program does not know the two counters exist.
"""
import os
import sys

import numpy as np
import pytest
import scipy.sparse as sps

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import lightgbm_tpu as lgb                                    # noqa: E402
from lightgbm_tpu import profiling                            # noqa: E402
from lightgbm_tpu.config import config_from_params            # noqa: E402
from lightgbm_tpu.dataset import Dataset                      # noqa: E402
from lightgbm_tpu.learner import rounds                       # noqa: E402
from lightgbm_tpu.quantize import bin_feature_column          # noqa: E402

from test_higgs_shape import assert_same_trees                # noqa: E402

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0,
          "verbose": -1, "tree_growth": "rounds", "sparse_store": "dense"}


# ---- (a) ingest by the non-zeros --------------------------------------------

def one_hot(rng, n, levels, p=None):
    """[n, levels] indicator block, exactly one 1 a row."""
    lv = rng.choice(levels, n, p=p)
    out = np.zeros((n, levels))
    out[np.arange(n), lv] = 1.0
    return out


def zipf(levels):
    p = 1.0 / np.arange(1, levels + 1)
    return p / p.sum()


def matrix(case: str):
    """-> (dense float64 [n, f] with the case's traits, params overrides)."""
    rng = np.random.default_rng(39)
    n = 4000
    groups = [one_hot(rng, n, 6, zipf(6)), one_hot(rng, n, 40, zipf(40))]
    extra = {}
    if case == "numeric_column":
        num = rng.standard_normal((n, 2))
        num[::7, 1] = 0.0                 # a numeric column with real zeros
        groups.append(num)
    elif case == "wide_group":
        groups.append(one_hot(rng, n, 300, zipf(300)))     # spans bundles
    elif case == "cross_group_conflict":
        # two sparse columns that meet in a tenth of their rows, bundled
        # under a budget that allows it: realised conflicts to count
        a = (rng.random(n) < 0.2) * rng.integers(1, 5, n)
        b = np.where(rng.random(n) < 0.1, a > 0, rng.random(n) < 0.05)
        groups.append(np.stack([a, b * 2.5], axis=1).astype(np.float64))
        extra["max_conflict_rate"] = 0.3
    elif case == "all_zero_column":
        groups.append(np.zeros((n, 2)))
    X = np.concatenate(groups, axis=1)
    return X, extra


def to_csc(X, case):
    sp = sps.csr_matrix(X)
    if case == "numeric_column":
        sp.data[5] = np.nan               # a stored NaN
        sp.data[11] = 0.0                 # a stored zero
    if case == "all_zero_column":
        # the last column stores entries, every one of them 0.0
        coo = sp.tocoo()
        rows = np.arange(50)
        sp = sps.csr_matrix(
            (np.r_[coo.data, np.zeros(50)],
             (np.r_[coo.row, rows], np.r_[coo.col, np.full(50, X.shape[1] - 1)])),
            shape=X.shape)
        assert sp.nnz == coo.nnz + 50
    return sp


def scratch_column_route(sp, cfg, like):
    """The route `from_csc` took before: every used feature's column filled
    dense over all rows and binned (quantize.bin_feature_column), in inner
    order, into a store of `like`'s mappers and plan."""
    n, f = sp.shape
    old = Dataset._empty_from_mappers(cfg, like.mappers,
                                      list(like.used_features), n, f, None,
                                      plan=like.bundle_plan)
    csc = sp.tocsc()
    col = np.empty(n, np.float64)
    plan = like.bundle_plan
    for k, i in enumerate(like.used_features):
        col[:] = 0.0
        s, e = csc.indptr[i], csc.indptr[i + 1]
        col[csc.indices[s:e]] = csc.data[s:e]
        c = k if plan is None else int(plan.feat_col[k])
        old.bundle_conflict_rows += bin_feature_column(
            k, col, like.mappers, like.used_features, plan, old.bins[c])
    return old


def same_plan(a, b):
    if a is None or b is None:
        return a is None and b is None
    return all(np.array_equal(getattr(a, f), getattr(b, f)) for f in (
        "feat_col", "feat_offset", "feat_default", "feat_nslots",
        "feat_packed", "col_num_bins"))


def same_mappers(a, b):
    return len(a) == len(b) and all(
        x.num_bin == y.num_bin and x.is_trivial == y.is_trivial
        and x.default_bin == y.default_bin
        and np.array_equal(x.bin_upper_bound, y.bin_upper_bound)
        for x, y in zip(a, b))


CASES = ["one_hot_groups", "numeric_column", "wide_group",
         "cross_group_conflict", "all_zero_column"]


@pytest.mark.parametrize("enable_bundle", [True, False])
@pytest.mark.parametrize("case", CASES)
def test_ingest_by_nonzeros_is_the_scratch_column_route(case, enable_bundle):
    X, extra = matrix(case)
    sp = to_csc(X, case)
    cfg = config_from_params(dict(PARAMS, enable_bundle=enable_bundle,
                                  **extra))
    y = (X[:, 0] > 0).astype(np.float64)
    ds = Dataset.from_csc(sp, y, cfg)
    old = scratch_column_route(sp, cfg, ds)
    assert ds.bins.dtype == old.bins.dtype
    np.testing.assert_array_equal(ds.bins, old.bins)
    assert ds.bundle_conflict_rows == old.bundle_conflict_rows
    if enable_bundle:
        assert ds.bundle_plan is not None
        assert ds.num_store_columns < ds.num_features
    else:
        assert ds.bundle_plan is None
    if case == "cross_group_conflict" and enable_bundle:
        assert ds.bundle_conflict_rows > 0
    if case == "wide_group" and enable_bundle:
        wide = [k for k, i in enumerate(ds.used_features) if i >= 46]
        assert len(set(ds.bundle_plan.feat_col[wide])) >= 2
    if case == "all_zero_column":
        assert X.shape[1] - 1 not in ds.used_features     # trivial: unused
    # the dense-row constructor over the same values: same mappers, same
    # plan, same store (4,000 rows: both see every row in their samples)
    dense = Dataset(np.asarray(sp.todense(), np.float64), y, cfg)
    assert same_mappers(ds.mappers, dense.mappers)
    assert ds.used_features == dense.used_features
    assert same_plan(ds.bundle_plan, dense.bundle_plan)
    np.testing.assert_array_equal(ds.bins, dense.bins)
    assert ds.bundle_conflict_rows == dense.bundle_conflict_rows


@pytest.mark.parametrize("enable_bundle", [True, False])
def test_a_validation_set_takes_its_references_layout(enable_bundle):
    X, extra = matrix("cross_group_conflict")
    cfg = config_from_params(dict(PARAMS, enable_bundle=enable_bundle,
                                  **extra))
    train = Dataset.from_csc(sps.csr_matrix(X[:3000]), None, cfg)
    sp = sps.csr_matrix(X[3000:])
    valid = Dataset.from_csc(sp, None, cfg, reference=train)
    assert valid.mappers is train.mappers
    assert same_plan(valid.bundle_plan, train.bundle_plan)
    old = scratch_column_route(sp, cfg, train)
    np.testing.assert_array_equal(valid.bins, old.bins)
    assert valid.bundle_conflict_rows == old.bundle_conflict_rows
    dense = Dataset(X[3000:], None, cfg, reference=train)
    np.testing.assert_array_equal(valid.bins, dense.bins)


def test_only_a_scipy_input_reaches_the_nonzero_route(monkeypatch):
    calls = []
    real = Dataset._bin_csc_into
    monkeypatch.setattr(Dataset, "_bin_csc_into",
                        lambda self, *a: calls.append(1) or real(self, *a))
    X, _ = matrix("one_hot_groups")
    y = (X[:, 0] > 0).astype(np.float64)
    lgb.Dataset(X, y).construct(PARAMS)
    assert calls == []
    lgb.Dataset(sps.csr_matrix(X), y).construct(PARAMS)
    assert calls == [1]


# ---- (b) the generator -------------------------------------------------------

def test_generator_is_deterministic_in_its_seed():
    from benchmark.generators.allstate import BLOCK, make
    X, y = make(3000, 4228, (0, 1))
    X2, y2 = make(3000, 4228, (0, 1))
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    X3, y3 = make(3000, 4228, (0, 2))
    assert not np.array_equal(X, X3)
    X4, _ = make(3000, 4228, (2147483659, 2))        # a driver-sized seed
    assert not np.array_equal(X, X4)
    # whole blocks depend on the seed alone, not on how many follow
    big, _ = make(BLOCK + 17, 64, (5, 0))
    one, _ = make(BLOCK, 64, (5, 0))
    assert np.array_equal(big[:BLOCK], one)


def test_generator_returns_csr_above_its_bound_and_dense_below(monkeypatch):
    from benchmark.generators import allstate
    X, y = allstate.make(2000, 4228, (0, 0))
    assert isinstance(X, np.ndarray) and X.dtype == np.float32
    assert X.shape == (2000, 4228) and y.shape == (2000,)
    monkeypatch.setattr(allstate, "CSR_ABOVE_ROWS", 1999)
    S, ys = allstate.make(2000, 4228, (0, 0))
    assert sps.isspmatrix_csr(S) and S.dtype == np.float32
    assert S.has_sorted_indices and S.nnz == 2000 * 32
    assert np.array_equal(np.asarray(S.todense()), X)
    assert np.array_equal(ys, y)
    assert allstate.CSR_ABOVE_ROWS < 12_184_290     # the cell's set: CSR
    assert 200_000 <= 250_000                       # its held-out rows: dense


def test_generator_ends_a_sparse_draw_on_a_program_without_the_route(
        monkeypatch):
    """The parent of PR 39 bins a dense column per feature (34 minutes at the
    cell's size): the driver needs that run to end at once, not to hang."""
    from benchmark.generators import allstate
    monkeypatch.setattr(allstate, "CSR_ABOVE_ROWS", 1999)
    monkeypatch.delattr(Dataset, "_bin_csc_into")
    with pytest.raises(RuntimeError, match="_bin_csc_into"):
        allstate.make(2000, 4228, (0, 0))
    X, _ = allstate.make(1999, 4228, (0, 0))     # dense draws ask nothing
    assert isinstance(X, np.ndarray)


def test_generator_sets_exactly_one_level_a_variable():
    from benchmark.generators.allstate import FEATURES, make, schema
    X, y = make(20_000, FEATURES, (0, 0))
    sc = schema(FEATURES)
    assert sc.numeric == 15 and len(sc.cards) == 17
    assert int(sc.cards.sum()) == 4213
    assert sorted(sc.cards)[-3:] == [75, 1300, 2700]
    assert (X[:, :15] != 0).all()
    for off, card in zip(sc.offsets, sc.cards):
        block = X[:, off:off + card]
        assert ((block == 0) | (block == 1)).all()
        assert (block.sum(1) == 1).all()
    assert ((X != 0).sum(1) == 32).all()
    # a sub-model implies its model and a model its make
    sub = X[:, sc.offsets[-1]:].argmax(1)
    model = X[:, sc.offsets[-2]:sc.offsets[-1]].argmax(1)
    make_ = X[:, sc.offsets[-3]:sc.offsets[-2]].argmax(1)
    assert np.array_equal(model, sc.model_of_sub[sub])
    assert np.array_equal(make_, sc.make_of_model[model])
    assert 0.003 < y.mean() < 0.012


# ---- (c) the toy: bundled against unbundled, predict against the walk -------

ROWS, WIDTH = 6000, 300


@pytest.fixture(scope="module")
def toy():
    from benchmark.generators.allstate import make
    X, y = make(ROWS, WIDTH, (39, 0))
    return sps.csr_matrix(X), X, y


def booster(toy, **over):
    sp, _, y = toy
    p = dict(PARAMS, **over)
    return lgb.Booster(p, lgb.Dataset(sp, y).construct(p))


@pytest.mark.parametrize("gradients", ["logloss_at_zero", "dyadic"])
def test_bundled_and_unbundled_stores_grow_the_same_tree(toy, gradients):
    """float32 histograms of dyadic gradients are exact sums, so a bundle's
    default bin by subtraction is the sum itself: node for node."""
    _, _, y = toy
    if gradients == "logloss_at_zero":
        g, h = 0.5 - y, np.full(len(y), 0.25)
    else:
        rng = np.random.default_rng(7)
        g = rng.integers(-64, 65, len(y)) / 64.0
        h = rng.integers(1, 65, len(y)) / 64.0
    trees = []
    for bundle in (True, False):
        lr = booster(toy, enable_bundle=bundle, num_leaves=63)._gbdt.learner
        assert type(lr).__name__ == "RoundsTreeLearner"
        assert (lr.dataset.bundle_plan is not None) == bundle
        if bundle:
            assert lr.dataset.bundle_conflict_rows == 0
            assert lr.bins_dev.shape[0] < 40 < lr.F
        tree, _ = lr.train(jnp.asarray(g, jnp.float32),
                           jnp.asarray(h, jnp.float32))
        trees.append(tree)
    assert trees[0].num_leaves == trees[1].num_leaves > 30
    assert_same_trees([trees[0]], [trees[1]])


def test_predict_agrees_with_the_benchmarks_walk(toy):
    from benchmark.harness import walk
    _, X, y = toy
    bst = booster(toy)
    for _ in range(4):
        bst.update()
    trees = walk.parse_model(bst.model_to_string())
    assert len(trees) == 4 and min(t["num_leaves"] for t in trees) > 1
    raw = bst.predict(X, raw_score=True)
    flips, err = walk.routing_flips(trees, X, raw, 1e-6)
    assert flips == 0 and err <= 1e-6
    assert walk.logloss(y, raw) < walk.logloss(y, np.zeros(len(y)))


# ---- (d) the two counters ----------------------------------------------------

def counters_after_one_tree(toy, **over):
    profiling.reset()
    bst = booster(toy, **over)
    bst.update()
    lr = bst._gbdt.learner
    got = profiling.counters("tree/")
    profiling.reset()
    return lr, got


def searched_slots(got, cache):
    """The root's one slot, then the tier every executed chunk ran at
    (8 or 15 of K = 15: rounds of up to 8 splits, then 15), as the
    launches' tree/hist_slots count it: searched twice where the cache
    gives the larger child, once a launch where a second launch does."""
    launches = got[profiling.HIST_PASSES] - 1
    chunk_slots = got[profiling.HIST_SLOTS] - 1
    assert launches >= 1
    assert 8 * launches <= chunk_slots <= 15 * launches
    return 1 + (2 if cache else 1) * chunk_slots


@pytest.mark.parametrize("cache", [True, False])
def test_counters_read_what_the_shapes_say_on_a_bundled_store(toy, cache):
    over = {} if cache else {"histogram_pool_size": 1e-6}
    lr, got = counters_after_one_tree(toy, **over)
    assert lr.cache_parent_hist == cache and lr.dataset.bundle_plan is not None
    chunks = (got[profiling.HIST_PASSES] - 1) / (1 if cache else 2)
    assert chunks >= 1 and chunks == int(chunks)
    assert got[profiling.SPLIT_CELLS] == (
        searched_slots(got, cache) * lr.Fpad * lr.B)
    assert got[profiling.UNBUNDLE_GATHER_ELEMS] == 0
    assert lr.F > lr.Fpad                # searched in store space


def test_counters_on_a_store_with_no_plan(toy):
    lr, got = counters_after_one_tree(toy, enable_bundle=False)
    assert lr.dataset.bundle_plan is None
    assert got[profiling.SPLIT_CELLS] == (
        searched_slots(got, True) * lr.Fpad * lr.B)
    assert got[profiling.UNBUNDLE_GATHER_ELEMS] == 0     # the key is there


def test_a_searched_slot_is_what_best_split_is_traced_with(toy, monkeypatch):
    """The fold's static half against the program: every search the build
    traces is over the store's own [Fpad, 3, B] per slot, batched at the
    root's one slot and at each tier a chunk can run at (8 and 15 where
    K = 15), and no search over [F, 3, B] is traced at all."""
    seen, widths, gathered = [], set(), []
    real = rounds.best_split_in_store

    def spy(hist, *a, **kw):
        seen.append(tuple(hist.shape))
        # under vmap the slot axis is the batch tracer's
        widths.add(hist.val.shape[hist.batch_dim]
                   if hasattr(hist, "batch_dim") else None)
        return real(hist, *a, **kw)

    monkeypatch.setattr(rounds, "best_split_in_store", spy)
    monkeypatch.setattr(rounds, "best_split",
                        lambda hist, *a, **kw: gathered.append(hist.shape))
    lr, got = counters_after_one_tree(toy)
    assert set(seen) == {(lr.Fpad, 3, lr.B)} and not gathered
    assert widths == {1} | set(rounds.chunk_tiers(15)) == {1, 8, 15}
    totals = np.zeros(len(rounds.STATS_COUNTERS))
    totals[rounds.S_SLOTS] = 1                           # a root-only tree
    assert dict(lr._fold_stats(totals, 1))[profiling.SPLIT_CELLS] == (
        lr.Fpad * lr.B)
    # two trees: their roots, then chunks run at 8, 15 and 8 slots
    totals[rounds.S_SLOTS] = 2 + 8 + 15 + 8
    assert dict(lr._fold_stats(totals, 2))[profiling.SPLIT_CELLS] == (
        (2 + 2 * (8 + 15 + 8)) * lr.Fpad * lr.B)


# ---- (e) a dense store's program does not know the counters -----------------

def test_a_dense_build_lowers_the_same_without_the_counters(monkeypatch):
    """Lower the dense toy's build with the host fold in place and with it
    patched out: one text.  The stats vector is the [12] it was."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((500, 6))
    y = (X[:, 0] > 0).astype(np.float64)

    def lowered():
        bst = lgb.Booster(PARAMS, lgb.Dataset(X, y).construct(PARAMS))
        lr = bst._gbdt.learner
        g = jnp.zeros(lr.Np, jnp.float32)
        mask, fmask = lr._masks(None)
        args = (lr.bins_dev, g, g, mask, lr.num_bins_dev, lr.is_cat_dev,
                fmask)
        stats = jax.eval_shape(lr._build, *args)[2]
        return lr._build.lower(*args).as_text(), stats.shape

    with_fold = lowered()
    monkeypatch.setattr(rounds.RoundsTreeLearner, "_search_counters",
                        lambda self, bundled: None)
    without = lowered()
    assert with_fold == without
    assert with_fold[1] == (len(rounds.STATS_COUNTERS),) == (12,)
    assert "lgbt.unbundle" not in with_fold[0]
