"""Batched-rounds learner (learner/rounds.py): equivalence with exact
leaf-wise growth when the num_leaves cap does not bind, sharded and not."""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as RawDataset
from lightgbm_tpu.learner.serial import SerialTreeLearner
from lightgbm_tpu.learner.rounds import RoundsTreeLearner
from lightgbm_tpu.learner.fused import make_mesh


@pytest.fixture(scope="module")
def problem():
    rng = np.random.RandomState(7)
    X = rng.randn(1200, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.randn(1200) > 0
         ).astype(np.float64)
    cfg = config_from_params({
        "objective": "binary", "num_leaves": 31, "min_data_in_leaf": 25,
        "verbose": -1, "min_gain_to_split": 0.1})
    ds = RawDataset(X, y, config=cfg)
    p = 0.5
    g = jnp.asarray(((p - y) * 2).astype(np.float32))
    h = jnp.asarray(np.full(len(y), p * (1 - p) * 2, np.float32))
    return ds, cfg, g, h


def _splits(t):
    return sorted(zip(t.split_feature_inner[: t.num_leaves - 1],
                      t.threshold_in_bin[: t.num_leaves - 1]))


def test_rounds_equals_exact_when_cap_loose(problem):
    ds, cfg, g, h = problem
    ts, _ = SerialTreeLearner(ds, cfg).train(g, h)
    tr, lid = RoundsTreeLearner(ds, cfg, None).train(g, h)
    assert tr.num_leaves == ts.num_leaves
    assert _splits(tr) == _splits(ts)
    np.testing.assert_allclose(
        np.sort(tr.leaf_value[: tr.num_leaves]),
        np.sort(ts.leaf_value[: ts.num_leaves]), rtol=1e-4, atol=1e-6)
    counts = np.bincount(np.asarray(lid), minlength=tr.num_leaves)
    np.testing.assert_array_equal(counts, tr.leaf_count[: tr.num_leaves])


@pytest.mark.parametrize("cache", [True, False], ids=["cache", "no_cache"])
def test_bagged_multichunk_tree_is_the_serial_learners(monkeypatch, cache):
    """The build against learner/serial.py where the schedule is least
    like it: LEAVES_PER_BATCH=5 cuts the 13-slot leaf table into three
    chunks with a short last one, a bag drops 40 % of the rows (they
    stream through every pass under a zero row mask), and without the
    parent-histogram cache both children of a split are histogrammed
    directly where the serial learner subtracts.  min_data_in_leaf keeps
    the tree under 13 leaves, so the cap never binds and the two
    schedules must grow one tree; +-1 gradients and constant hessians
    make every histogram sum exact."""
    from lightgbm_tpu.learner import rounds as rounds_mod
    monkeypatch.setattr(rounds_mod, "LEAVES_PER_BATCH", 5)
    rng = np.random.RandomState(3)
    N = 3000
    X = rng.randn(N, 10)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(N, 0.5, np.float32))
    bag = jnp.asarray(np.sort(rng.choice(
        N, size=int(N * 0.6), replace=False)).astype(np.int32))
    cfg = config_from_params({
        "objective": "binary", "num_leaves": 13, "min_data_in_leaf": 150,
        "verbose": -1, **({} if cache else {"histogram_pool_size": 0.001})})
    ds = RawDataset(X, y, config=cfg)
    lrn = RoundsTreeLearner(ds, cfg, None)
    assert lrn.cache_parent_hist == cache
    tr, lid = lrn.train(g, h, bag, len(bag))
    ts, lid_s = SerialTreeLearner(ds, cfg).train(g, h, bag, len(bag))
    assert 5 < tr.num_leaves == ts.num_leaves < 13
    assert _splits(tr) == _splits(ts)
    np.testing.assert_array_equal(np.sort(tr.leaf_count[: tr.num_leaves]),
                                  np.sort(ts.leaf_count[: ts.num_leaves]))
    assert tr.leaf_count[: tr.num_leaves].sum() == len(bag)
    np.testing.assert_allclose(
        np.sort(tr.leaf_value[: tr.num_leaves]),
        np.sort(ts.leaf_value[: ts.num_leaves]), rtol=1e-6)
    # the bag's rows sit in the leaves the serial learner put them in
    inbag = np.asarray(bag)
    by_rounds = np.asarray(tr.leaf_value)[np.asarray(lid)[inbag]]
    by_serial = np.asarray(ts.leaf_value)[np.asarray(lid_s)[inbag]]
    np.testing.assert_allclose(by_rounds, by_serial, rtol=1e-6)


def test_rounds_sharded_matches_unsharded(problem):
    ds, cfg, g, h = problem
    tr, _ = RoundsTreeLearner(ds, cfg, None).train(g, h)
    mesh = make_mesh("data")
    tm, _ = RoundsTreeLearner(ds, cfg, mesh).train(g, h)
    assert tm.num_leaves == tr.num_leaves
    assert _splits(tm) == _splits(tr)


def test_rounds_chain_tree_reaches_num_leaves():
    """Skewed data forcing a chain-shaped tree: each round can split only
    one leaf (the one holding the exponential tail), so the tree needs
    num_leaves-1 rounds.  Regression test for the old fixed round budget
    R = min(L-1, ceil(log2 L)+8) that silently truncated such trees."""
    n, L = 64, 16
    X = np.arange(n, dtype=np.float64).reshape(-1, 1)
    y = 1.6 ** np.arange(n)          # variance dominated by the top row
    cfg = config_from_params({
        "objective": "regression", "num_leaves": L, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 1e-3, "max_bin": 255, "verbose": -1})
    ds = RawDataset(X, y, config=cfg)
    g = jnp.asarray((0.0 - y).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    ts, _ = SerialTreeLearner(ds, cfg).train(g, h)
    tr, _ = RoundsTreeLearner(ds, cfg, None).train(g, h)
    assert ts.num_leaves == L        # exact leaf-wise fills the cap
    assert tr.num_leaves == ts.num_leaves
    depths = np.asarray(tr.leaf_depth[: tr.num_leaves])
    np.testing.assert_array_equal(
        np.sort(depths), np.sort(np.asarray(ts.leaf_depth[: ts.num_leaves])))
    # deeper than the old cap (min(L-1, ceil(log2 L)+8) = 12 rounds) allowed
    assert depths.max() > 12


def test_rounds_respects_num_leaves_cap(problem):
    ds, cfg, g, h = problem
    cfg2 = config_from_params({
        "objective": "binary", "num_leaves": 8, "min_data_in_leaf": 50,
        "verbose": -1})
    tr, _ = RoundsTreeLearner(ds, cfg2, None).train(g, h)
    assert 1 < tr.num_leaves <= 8


def test_pipelined_valid_scoring_matches_host_predict(binary_example):
    """The pipelined path scores valid sets by traversing DEVICE
    TreeArrays over binned values (score_updater.traverse_tree_device);
    the final valid logloss must equal what the host raw-threshold tree
    walk computes over the same model."""
    import lightgbm_tpu as lgb
    X, y, Xt, yt = binary_example
    params = {"objective": "binary", "metric": "binary_logloss",
              "num_leaves": 15, "verbose": -1, "min_data_in_leaf": 10}
    train = lgb.Dataset(X, y)
    valid = lgb.Dataset(Xt, yt, reference=train)
    ev = {}
    bst = lgb.train(params, train, num_boost_round=10, valid_sets=[valid],
                    evals_result=ev, verbose_eval=False)
    raw = bst.predict(Xt, raw_score=True)
    p = 1.0 / (1.0 + np.exp(-raw))
    p = np.clip(p, 1e-15, 1 - 1e-15)
    ll_host = float(np.mean(-(yt * np.log(p) + (1 - yt) * np.log1p(-p))))
    ll_dev = ev["valid_0"]["binary_logloss"][-1]
    assert abs(ll_host - ll_dev) < 2e-5, (ll_host, ll_dev)


def test_leaves_per_batch_k_independent(monkeypatch):
    """LEAVES_PER_BATCH is a perf knob: changing K only regroups the
    histogram matmuls, so grown models agree up to f32 summation-order
    ulps (XLA may tile the contraction differently per M, which can flip
    exact-tie splits; predictions must still agree to float tolerance)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner import rounds as rounds_mod
    rng = np.random.RandomState(12)
    X = rng.randn(1500, 8)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
              "min_data_in_leaf": 10, "tree_growth": "rounds"}

    def preds_at(k):
        monkeypatch.setattr(rounds_mod, "LEAVES_PER_BATCH", k)
        bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=4)
        return bst.predict(X), [t.num_leaves for t in bst._gbdt.models]

    p_small, n_small = preds_at(7)
    p_default, n_default = preds_at(84)
    assert n_small == n_default
    np.testing.assert_allclose(p_small, p_default, atol=2e-3)
    assert np.mean(np.abs(p_small - p_default) < 1e-6) > 0.95


def test_int8_stored_bins_grow_identical_trees():
    """The int8 value-128 HBM layout (chosen on TPU, rounds.py __init__)
    must grow the SAME TreeArrays as int32 storage through the XLA path
    — exercises the learner-level wiring (feature padding to the 32-
    sublane group, padded nbv/icv/fmask, the +128 partition correction
    at select_bin_by_feature) that otherwise only runs on real TPU."""
    import jax.numpy as jnp
    from lightgbm_tpu.learner.rounds import build_tree_rounds
    from lightgbm_tpu.learner.common import make_split_kw, padded_bin_count
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.binning import find_bin_mappers

    rng = np.random.RandomState(7)
    X = rng.randn(3000, 37)                   # 37 features: pads to 64
    y = (X[:, 0] + 0.4 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    cfg = Config(objective="binary", num_leaves=15, min_data_in_leaf=5)
    mappers = find_bin_mappers(X, cfg.max_bin, cfg.min_data_in_bin,
                               cfg.min_data_in_leaf, categorical=(),
                               sample_cnt=len(X), seed=1)
    bins = np.stack([m.values_to_bins(X[:, j]) if hasattr(m, "values_to_bins")
                     else m.value_to_bin(X[:, j]) for j, m in
                     enumerate(mappers)]).astype(np.int32)
    F = bins.shape[0]
    grad = (1.0 / (1.0 + np.exp(-0.0)) - y).astype(np.float32)
    hess = np.full_like(grad, 0.25)
    nb = np.asarray([m.num_bin for m in mappers], np.int32)
    B = padded_bin_count(int(nb.max()))
    kw = dict(num_leaves=15, num_bins_padded=B,
              split_kw=make_split_kw(cfg), max_depth=0,
              min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
              backend="xla", max_num_bin=int(nb.max()))
    common = (jnp.asarray(grad), jnp.asarray(hess),
              jnp.ones(len(y), jnp.float32))

    arrs32, lid32, _ = build_tree_rounds(
        jnp.asarray(bins), *common, jnp.asarray(nb),
        jnp.zeros(F, bool), jnp.ones(F, bool), **kw)

    # int8 storage exactly as the TPU learner builds it: value-128,
    # features padded to 32-multiple with trivial masked features
    Fpad = 32 * ((F + 31) // 32)
    bins8 = np.pad((bins.astype(np.int16) - 128).astype(np.int8),
                   ((0, Fpad - F), (0, 0)), constant_values=-128)
    nb8 = np.pad(nb, (0, Fpad - F), constant_values=1)
    fmask8 = np.pad(np.ones(F, bool), (0, Fpad - F))
    arrs8, lid8, _ = build_tree_rounds(
        jnp.asarray(bins8), *common, jnp.asarray(nb8),
        jnp.zeros(Fpad, bool), jnp.asarray(fmask8), **kw)

    assert int(arrs32.num_leaves) == int(arrs8.num_leaves) > 1
    np.testing.assert_array_equal(np.asarray(lid32), np.asarray(lid8))
    np.testing.assert_array_equal(np.asarray(arrs32.split_feature),
                                  np.asarray(arrs8.split_feature))
    np.testing.assert_array_equal(np.asarray(arrs32.threshold_bin),
                                  np.asarray(arrs8.threshold_bin))
    np.testing.assert_allclose(np.asarray(arrs32.leaf_value),
                               np.asarray(arrs8.leaf_value), rtol=1e-6)


def test_rounds_num_leaves_past_int8_gates():
    """num_leaves > 255 exceeds the fused partition kernel's int8 slot
    table — the gate must route to the XLA composition and grow a
    correct tree rather than alias mod-256."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(4000, 6)
    y = (X[:, 0] * X[:, 1] + 0.3 * X[:, 2] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 300, "verbose": -1,
              "min_data_in_leaf": 5, "tree_growth": "rounds"}
    bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=3)
    p = bst.predict(X)
    acc = ((p > 0.5) == (y > 0.5)).mean()
    assert acc > 0.9, acc
    assert max(t.num_leaves for t in bst._gbdt.models) > 255


def _tier_problem(store):
    """(X, y, params) on which a tree of up to 100 leaves runs chunks of
    LEAVES_PER_BATCH = 40 slots at every tier (8, 32, 40) and compiles a
    short last chunk of 20: 16 numeric columns, and for the bundled
    store six one-hot groups beside them that EFB packs into few
    columns.  +-1 gradients and constant hessians keep every histogram
    sum exact in any order."""
    rng = np.random.RandomState(5)
    N = 6000
    X = rng.randn(N, 16)
    y = (X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.5 * np.sin(3 * X[:, 3])
         > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 100, "verbose": -1,
              "min_data_in_leaf": 12, "tree_growth": "rounds",
              "min_sum_hessian_in_leaf": 1e-3}
    if store == "bundled":
        import scipy.sparse as sps
        groups = []
        for levels in (5, 7, 11, 13, 17, 19):
            lv = rng.randint(levels, size=N)
            groups.append(np.eye(levels)[lv])
        X = sps.csr_matrix(np.hstack([X] + groups))
        params.update(enable_bundle=True, sparse_store="dense")
    elif store == "psum_scatter4":
        params.update(tree_learner="data", num_machines=4,
                      hist_exchange="psum_scatter")
    return X, y, params


@pytest.mark.parametrize("cache", [True, False], ids=["cache", "no_cache"])
@pytest.mark.parametrize("store", ["dense", "bundled", "psum_scatter4"])
def test_a_chunk_runs_whole_at_its_slot_tier(monkeypatch, store, cache):
    """Every chunk of a round runs launch, exchange, subtraction, both
    searches and the cache update at the narrowest tier that holds its
    active slots (chunk_tiers).  The tree and every row's leaf are bit for
    bit what the same build grows with each chunk at its full width — the
    schedule before the tiers reached past the launch — and
    tree/split_cells counts the searched slots from the launched ones."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import profiling
    from lightgbm_tpu.learner import rounds as rounds_mod
    monkeypatch.setattr(rounds_mod, "LEAVES_PER_BATCH", 40)
    X, y, params = _tier_problem(store)
    if not cache:
        params["histogram_pool_size"] = 1e-6
    launched = []
    real = rounds_mod.hist_multileaf_masked

    def spy(bins, lid, gh, slots, **kw):
        # the width of each launch that RUNS, not of each one traced
        jax.debug.callback(lambda s: launched.append(s.shape[0]), slots)
        return real(bins, lid, gh, slots, **kw)

    monkeypatch.setattr(rounds_mod, "hist_multileaf_masked", spy)
    g = np.where(y > 0, -1.0, 1.0).astype(np.float32)
    h = np.full(len(y), 0.5, np.float32)

    def grow():
        lr = lgb.Booster(params, lgb.Dataset(X, y))._gbdt.learner
        assert isinstance(lr, RoundsTreeLearner)
        assert lr.cache_parent_hist == cache
        assert (lr.dataset.bundle_plan is not None) == (store == "bundled")
        assert (lr.hist_exchange, lr.dd) == (
            ("psum_scatter", 4) if store == "psum_scatter4" else ("psum", 1))
        profiling.reset()
        del launched[:]
        _, lid, arrs = lr.train_device(jnp.asarray(g), jnp.asarray(h))
        jax.effects_barrier()
        got = profiling.counters("tree/")
        profiling.reset()
        return (jax.tree_util.tree_map(np.asarray, arrs), np.asarray(lid),
                got, sorted(set(launched)), lr)

    tiered, lid, got, widths, lr = grow()
    assert widths == [1, 8, 32, 40]        # the root, then every tier ran
    assert 40 < int(tiered.num_leaves) <= 100
    monkeypatch.setattr(rounds_mod, "chunk_tiers", lambda chunk: (chunk,))
    whole, lid_w, got_w, widths_w, _ = grow()
    assert widths_w == [1, 40]
    for name, a, b in zip(tiered._fields, tiered, whole):
        np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(lid, lid_w)
    # the same launches, each at its tier: fewer slots, as many passes
    assert got[profiling.HIST_PASSES] == got_w[profiling.HIST_PASSES]
    assert got[profiling.HIST_SLOTS] < got_w[profiling.HIST_SLOTS]
    cells = (lr.Fpad // (4 if store == "psum_scatter4" else 1)) * lr.B
    slots = got[profiling.HIST_SLOTS]
    assert got[profiling.SPLIT_CELLS] == cells * (
        2 * slots - 1 if cache else slots)
