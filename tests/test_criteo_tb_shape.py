"""The benchmark's `criteo_tb` configuration at a size the CPU takes: rows of
`benchmark/generators/criteo_tb.make` (20,011 x 67 in place of 54M x 67)
under the configuration's own parameters, and its cell's learner —
`tree_learner=data` over four devices — against the one-device rounds
learner and the exact leaf-wise learner, learner/serial.py.

What is new beside tests/test_higgs_shape.py (one device) and
tests/test_parallel.py (hand-made rows of a few columns) is the cell's
width through `lgb.train` on a mesh: 67 store columns that the four-way
psum_scatter pads to a multiple of four, shards of 5,003 rows that are a
multiple of no block, 255 leaf slots in four chunks of which only the first
ever runs, and the counters the cell's exchange metrics read.

`min_sum_hessian_in_leaf` is 100 in the configuration, which 13.5M rows a
shard carry; 20,011 rows at a hessian of 0.25 would grow a dozen leaves, so
the parity runs lower it to 10 and grow about eighty.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# trees without their numbering, splits and counts exact, leaf values to
# float32 rounding (pytest puts tests/ on the path)
from test_higgs_shape import assert_same_trees      # noqa: E402

ROWS, FEATURES, DEVICES = 20_011, 67, 4
ITERS = 1       # through lgb.train; later trees: see the quantised-gradient test


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "criteo_tb.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rows():
    from benchmark.generators.criteo_tb import make
    return make(ROWS, FEATURES, (0, 0))


# ---- (a) the generator -------------------------------------------------------

def test_generator_shape_and_seed_contract(rows):
    from benchmark.generators.criteo_tb import BLOCK, make
    X, y = rows
    assert X.shape == (ROWS, FEATURES) and X.dtype == np.float32
    assert y.shape == (ROWS,) and set(np.unique(y)) == {0.0, 1.0}
    assert np.isfinite(X).all()
    X2, y2 = make(ROWS, FEATURES, (0, 0))            # the same seed pair
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    X3, y3 = make(ROWS, FEATURES, (0, 1))            # another stream
    assert not np.array_equal(X, X3) and not np.array_equal(y, y3)
    X4, _ = make(ROWS, FEATURES, (2147483659, 2))    # a driver-sized seed
    assert not np.array_equal(X, X4)
    # whole blocks depend on the seed alone, not on how many follow
    big, _ = make(2 * BLOCK + 17, FEATURES, (5, 0))
    one, _ = make(BLOCK, FEATURES, (5, 0))
    assert np.array_equal(big[:BLOCK], one)
    assert not np.array_equal(big[BLOCK:2 * BLOCK], one)


def test_generator_kinds_and_click_rate():
    from benchmark.generators.criteo_tb import make
    X, y = make(200_000, FEATURES, (0, 0))
    assert 0.02 < y.mean() < 0.04
    counters, rates, counts = X[:, :13], X[:, 13:39], X[:, 39:65]
    assert (counters >= 0).all() and (counters == np.floor(counters)).all()
    assert counters.max() > 1000                     # heavy-tailed
    assert (rates > 0).all() and (rates < 1).all()
    assert 0.01 < np.median(rates) < 0.06
    assert (counts >= 0).all() and counts.max() < 25    # a log scale
    assert (X[:, 65] >= 0).all() and (X[:, 65] < 24).all()
    for j in range(13, FEATURES):                    # all but the counters
        assert len(np.unique(X[:, j])) > 255, j
    # the label leans on the columns: a click's first rate is higher
    assert X[y == 1, 13].mean() > 1.3 * X[y == 0, 13].mean()


def test_every_binned_column_is_dense_and_nothing_is_bundled(rows):
    """No bin of any column, the zero's or another, holds half of the
    rows; so the EFB planner takes no column into its conflict graph and
    the store keeps the source's 67 columns."""
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset
    X, y = rows
    cfg = config_from_params(config()["params"])
    assert cfg.enable_bundle
    ds = Dataset(X, y, config=cfg)
    assert ds.bundle_plan is None
    assert ds.bins.shape == (FEATURES, ROWS)
    assert (ds.num_bins[13:] > 200).all() and (ds.num_bins > 50).all()
    for j in range(FEATURES):
        share = np.bincount(ds.bins[j]).max() / ROWS
        assert share < 0.5, (j, share)


# ---- (b) the cell's learner against the one-device and the exact one ---------

@pytest.fixture(scope="module")
def trained(rows):
    """`lgb.train` under the configuration's parameters: name -> (booster,
    how far the `tree/` counters moved), one run per name."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import profiling
    X, y = rows
    base = dict(config()["params"], min_sum_hessian_in_leaf=10.0,
                histogram_dtype="float32")
    recipes = {
        "exact": dict(tree_growth="exact"),
        "one": dict(tree_growth="rounds"),
        "psum": dict(tree_growth="rounds", tree_learner="data",
                     num_machines=DEVICES, hist_exchange="psum"),
        "psum_scatter": dict(tree_growth="rounds", tree_learner="data",
                             num_machines=DEVICES,
                             hist_exchange="psum_scatter"),
        # the cell as it is: int8 operands, the exchange left to resolve
        "cell_one": dict(tree_growth="rounds", histogram_dtype="int8"),
        "cell": dict(tree_growth="rounds", histogram_dtype="int8",
                     tree_learner="data", num_machines=DEVICES),
    }
    runs = {}

    def run(name, iters=ITERS):
        name, iters = (name, 3) if name == "exact" else (name, iters)
        if (name, iters) not in runs:
            before = profiling.counters("tree/")
            bst = lgb.train({**base, **recipes[name]}, lgb.Dataset(X, y),
                            num_boost_round=iters)
            bst._gbdt._flush_pending()
            after = profiling.counters("tree/")
            runs[name, iters] = bst, {k: v - before.get(k, 0.0)
                                      for k, v in after.items()}
        return runs[name, iters]
    return run


@pytest.mark.parametrize("exchange", ["psum", "psum_scatter"])
def test_four_device_trees_are_the_one_device_and_the_exact_learners(
        trained, exchange):
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    plain, _ = trained("exact")
    one, _ = trained("one")
    four, moved = trained(exchange)
    assert isinstance(plain._gbdt.learner, SerialTreeLearner)
    lr = four._gbdt.learner
    assert isinstance(lr, RoundsTreeLearner) and lr.mesh is not None
    assert (lr.dd, lr.df, lr.hist_exchange) == (DEVICES, 1, exchange)
    # a shard is ceil(20,011 / 4) = 5,003 rows: a multiple of no block
    assert lr.Np == DEVICES * 5_003 and lr.N == ROWS
    assert len(lr.bins_dev.sharding.device_set) == DEVICES
    # tree 1: gradients of +-0.5 and hessians of 0.25, whose float32
    # sums are exact in any order, so nothing is left to rounding
    assert_same_trees(four._gbdt.models, one._gbdt.models)
    assert_same_trees(four._gbdt.models, plain._gbdt.models[:ITERS])
    assert moved["tree/store_copy_rows"] == 0


@pytest.mark.parametrize("exchange", ["psum", "psum_scatter"])
def test_later_trees_on_quantised_gradients(rows, trained, exchange):
    """Past the first tree the gradients take many values, and four
    partial sums meet in another order than one device's: near-ties among
    leaves of forty rows then flip.  Given the int8 path's levels instead
    (whole numbers up to +-127, `ops/histogram.quantize_gh`, the hessian
    floor in the same units, raised to a hundred rows a leaf so that the
    cap of 255 leaves never binds) every float32 sum is exact in any order, and
    on the gradients of the second and third iteration the four-device
    build has to grow the one-device tree and the exact learner's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.ops.histogram import quantize_gh
    X, y = rows
    plain, _ = trained("exact")
    mesh = Mesh(np.asarray(jax.devices()[:DEVICES]).reshape(DEVICES, 1),
                ("data", "feature"))
    got, one, want = [], [], []
    for done in (1, 2):
        score = plain.predict(X, raw_score=True, num_iteration=done)
        p = 1.0 / (1.0 + np.exp(-score))
        gh8 = (jnp.zeros((8, ROWS), jnp.float32)
               .at[0].set((p - y).astype(np.float32))
               .at[1].set((p * (1 - p)).astype(np.float32)))
        ghq, _, sh = quantize_gh(gh8)
        g, h = ghq[0].astype(jnp.float32), ghq[1].astype(jnp.float32)
        assert len(np.unique(np.asarray(g))) > 10           # not two levels
        cfg = config_from_params(dict(
            config()["params"], tree_growth="rounds",
            histogram_dtype="float32", hist_exchange=exchange,
            min_sum_hessian_in_leaf=25.0 / float(sh)))
        ds = RawDataset(X, y, config=cfg)
        got.append(RoundsTreeLearner(ds, cfg, mesh).train(g, h)[0])
        one.append(RoundsTreeLearner(ds, cfg, None).train(g, h)[0])
        want.append(SerialTreeLearner(ds, cfg).train(g, h)[0])
    assert max(t.num_leaves for t in want) < 255        # the cap never binds
    assert_same_trees(got, one)
    assert_same_trees(got, want)


def test_the_cell_resolves_the_scattered_exchange_and_pads_the_columns(
        trained):
    """`hist_exchange=auto` at 67 columns by 256 bins by K = 84 is far
    over the scatter's threshold; the store's columns are padded so that
    four devices own whole slices (68 off the chip; 72 on it, where the
    feature group of 8 comes first: test below)."""
    bst, moved = trained("cell")
    lr = bst._gbdt.learner
    assert lr.hist_exchange == "psum_scatter"
    assert lr.bins_dev.shape == (68, DEVICES * 5_003)
    assert str(lr.bins_dev.dtype) == "int32"
    # the train score lies where the store's rows lie: the learner's row
    # sharding, a quarter of the padded rows on each device; `score` is
    # the view of the real rows that every reader outside the hot path
    # takes
    su, layout = bst._gbdt.train_score, lr.row_layout
    assert (layout.num_data, layout.num_rows) == (ROWS, lr.Np)
    assert su.rows.shape == (1, lr.Np)
    assert su.rows.sharding.is_equivalent_to(layout.sharding_of(2), 2)
    assert lr.bins_dev.sharding.spec[1] == layout.sharding.spec[0]
    assert ([s.data.shape for s in su.rows.addressable_shards]
            == [(1, lr.Np // DEVICES)] * DEVICES)
    assert su.score.shape == (1, ROWS)
    # tree 1 of the int8 path is the one-device int8 learner's: at a
    # score of 0 the gradients are +-0.5 and the hessians 0.25 on every
    # shard, which each shard's own scale takes to +-127 exactly
    one, _ = trained("cell_one")
    assert_same_trees(bst._gbdt.models[:1], one._gbdt.models[:1])


# ---- the train score in the learner's row layout -----------------------------

@pytest.mark.parametrize("name", ["cell_one", "cell"])
def test_the_score_view_is_the_models_margin_of_every_train_row(
        rows, trained, name):
    """After five iterations the [K, N] view of the padded (and, on the
    mesh, sharded) score is what the model predicts for the training
    rows, and no update fetched a leaf id from another device."""
    X, _ = rows
    bst, moved = trained(name, 5)
    su = bst._gbdt.train_score
    assert len(su.rows.sharding.device_set) == (DEVICES if name == "cell"
                                                else 1)
    assert su.score.shape == (1, ROWS)
    np.testing.assert_allclose(np.asarray(su.score)[0],
                               bst.predict(X, raw_score=True),
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(su.get()[0], np.asarray(su.score)[0])
    assert moved["tree/score_gather_rows"] == 0     # registered, and 0


def test_padded_rows_carry_no_gradient_and_move_no_scale():
    """A shard's int8 scale is its largest |g| and |h| over every row it
    is handed, so the gradient program zeroes the padded rows whatever
    their score holds: at score 3 and label 0 a padded row's gradient
    would lead the shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Metadata
    from lightgbm_tpu.jaxutil import RowLayout
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops.histogram import quantize_gh
    n, n_pad = 1_003, 1_024
    mesh = Mesh(np.asarray(jax.devices()[:DEVICES]), ("data",))
    layout = RowLayout(n, n_pad, NamedSharding(mesh, P("data")))
    rng = np.random.RandomState(0)
    meta = Metadata(n)
    meta.label = (rng.rand(n) < 0.03).astype(np.float32)
    meta.weights = rng.rand(n).astype(np.float32) + 0.5
    obj = create_objective(config_from_params({"objective": "binary"}))
    obj.init(meta, n, layout)
    assert obj.label.shape == obj.weights.shape == (n_pad,)
    assert obj.label.sharding.is_equivalent_to(layout.sharding, 1)
    score = np.full((1, n_pad), 3.0, np.float32)
    score[0, :n] = rng.randn(n)
    g, h = obj.get_gradients(layout.place(score))
    assert g.shape == h.shape == (1, n_pad)
    assert g.sharding.is_equivalent_to(layout.sharding_of(2), 2)
    g, h = np.asarray(g), np.asarray(h)
    assert not g[0, n:].any() and not h[0, n:].any()        # exactly 0.0
    assert np.abs(g[0, :n]).min() > 0

    plain = create_objective(config_from_params({"objective": "binary"}))
    plain.init(meta, n)
    g1, h1 = plain.get_gradients(jnp.asarray(score[:, :n]))
    np.testing.assert_array_equal(g[:, :n], np.asarray(g1))
    np.testing.assert_array_equal(h[:, :n], np.asarray(h1))

    def scales(g, h):
        gh8 = jnp.zeros((8, g.shape[1]), jnp.float32).at[0].set(
            g[0]).at[1].set(h[0])
        return [float(s) for s in quantize_gh(gh8)[1:]]
    assert scales(g, h) == scales(g[:, :n], h[:, :n])


def test_no_program_of_an_iteration_but_the_build_crosses_devices(trained):
    """Gradients, shrinkage, the score update and the [K, N] view's
    readers aside, everything an iteration runs outside
    jit_build_tree_rounds is per-row work on the device that holds the
    row: compiled for the four devices, none holds a collective."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.score_updater import (
        _add_const_to_row, _add_leaf_to_row_sharded, select_class_row,
        shrink_clip_leaves)
    from lightgbm_tpu.learner.fused import pack_tree_arrays
    bst, _ = trained("cell")
    g = bst._gbdt
    su, obj, layout = g.train_score, g.objective, g.learner.row_layout
    assert obj.layout is layout and su.layout is layout
    ids = jax.ShapeDtypeStruct((layout.num_rows,), jnp.int32,
                               sharding=layout.sharding)
    mask, fmask = g.learner._masks(None)
    assert mask.sharding.is_equivalent_to(layout.sharding, 1)
    grad, hess = g.boosting_gradients()
    _, leaf_id, arrs = g.learner.train_device(grad.reshape(-1),
                                              hess.reshape(-1))
    assert leaf_id.sharding.is_equivalent_to(layout.sharding, 1)
    update = _add_leaf_to_row_sharded(layout.sharding, 0)
    programs = {
        "gradients": obj._f.lower(su.rows, obj.label, obj.weights),
        "class_row": select_class_row.lower(grad, k=0),
        "flat": jax.jit(lambda x: x.reshape(-1)).lower(grad),
        "constant": _add_const_to_row.lower(su.rows, g._shrink_dev(),
                                            tree_id=0),
        "shrink": shrink_clip_leaves.lower(arrs.leaf_value, arrs.num_leaves,
                                           g._shrink_dev()),
        "update": update.lower(su.rows, ids, arrs.leaf_value),
        "pack": jax.jit(pack_tree_arrays).lower(arrs),
    }
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op not in text, (name, op)
    assert "add_leaf_to_row_sharded" in programs["update"].as_text()
    assert not programs["update"].as_text().startswith(
        "module @jit_build_tree")


def _logistic(preds, train_set):
    p = 1.0 / (1.0 + np.exp(-preds))
    y = train_set.get_label()
    return p - y, p * (1.0 - p)


@pytest.mark.parametrize("name,params,iters", [
    # learning_rate 0.5: GOSS samples from its third iteration on
    ("goss", dict(boosting="goss", learning_rate=0.5, top_rate=0.3,
                  other_rate=0.2), 4),
    ("bagging", dict(bagging_fraction=0.6, bagging_freq=1), 3),
    ("dart", dict(boosting="dart", drop_rate=0.6, skip_drop=0.0), 4),
    ("fobj", dict(), 3),
    ("multiclass", dict(objective="multiclass", num_class=3), 2),
    ("rollback", dict(), 3),
])
def test_every_other_path_still_trains_on_the_mesh(rows, name, params,
                                                   iters):
    """The paths that read or write the score through its [K, N] view —
    GOSS's selection over the real rows, the out-of-bag walk, DART's drop
    and re-add, a custom objective's host gradients, K > 1, a rollback —
    on four devices and on one: the view is the model's margin on every
    training row on both, and the two models agree."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    X, y = rows
    X, y = X[:4_003, 13:29], y[:4_003]
    if name == "multiclass":    # three classes of two columns and noise
        z = (X[:, 0] / X[:, 0].std() + X[:, 1] / X[:, 1].std()
             + 0.5 * np.random.RandomState(3).randn(len(X)))
        y = np.digitize(z, np.quantile(z, [1 / 3, 2 / 3])).astype(np.float32)
    base = {**dict(objective="binary", num_leaves=7, min_data_in_leaf=100,
                   learning_rate=0.1, tree_growth="rounds", verbose=-1,
                   histogram_dtype="float32"), **params}
    margins = {}
    for side, extra in (("one", {}), ("four", dict(tree_learner="data",
                                                   num_machines=DEVICES))):
        bst = lgb.Booster({**base, **extra}, lgb.Dataset(X, y))
        for _ in range(iters):
            bst.update(fobj=_logistic if name == "fobj" else None)
        if name == "rollback":
            bst.rollback_one_iter()
        g = bst._gbdt
        g._flush_pending()
        assert isinstance(g.learner, RoundsTreeLearner)
        assert (g.learner.mesh is not None) == (side == "four")
        su = g.train_score
        assert su.rows.shape == (g.K, g.learner.Np)
        assert len(su.rows.sharding.device_set) == (
            DEVICES if side == "four" else 1)
        assert g.current_iteration() == iters - (name == "rollback")
        assert all(t.num_leaves > 1 for t in g.models[-g.K:])
        raw = bst.predict(X, raw_score=True)
        np.testing.assert_allclose(np.asarray(su.score).T.squeeze(), raw,
                                   rtol=0, atol=2e-6)
        margins[side] = raw
    np.testing.assert_allclose(margins["four"], margins["one"], rtol=0,
                               atol=1e-5)


def test_exchange_counters_on_four_devices_and_on_one(trained):
    """What `exchange_bytes_per_iter`, `split_records_bytes_per_iter` and
    `collectives_per_iter` read, and that the sums over shards are counted
    once."""
    _, one = trained("one")
    assert one["tree/exchange_collectives"] == 0
    assert one["tree/hist_exchange_bytes"] == 0
    assert one["tree/split_records_bytes"] == 0
    rounds_, passes = one["tree/rounds"], one["tree/hist_passes"]
    # one slot chunk a round (under 84 splits in every round): the root's
    # launch and one launch a round
    assert passes == rounds_ + ITERS
    K, B = 84, 256
    for name, cols, legs in (("psum", 67, 1), ("psum_scatter", 68 // 4, 1)):
        _, moved = trained(name)
        assert moved["tree/rounds"] == rounds_
        assert moved["tree/hist_passes"] == passes
        # _exchange_bytes summed over the passes: the root's one slot,
        # then the chunk's K slots a round, whatever tier was launched
        assert moved["tree/hist_exchange_bytes"] == (
            4.0 * cols * 3 * B * legs * (ITERS + K * rounds_))
        # sums over the shards, counted once: 4 x 5,003 rows a pass
        assert moved["tree/hist_rows_touched"] == passes * DEVICES * 5_003
        assert moved["tree/partition_rows"] == rounds_ * DEVICES * 5_003
    _, moved = trained("psum")
    assert moved["tree/split_records_bytes"] == 0
    assert moved["tree/exchange_collectives"] == passes     # one psum each
    assert moved["tree/hist_mxu_ops"] == pytest.approx(
        one["tree/hist_mxu_ops"] * (DEVICES * 5_003) / ROWS, rel=1e-6)
    _, moved = trained("psum_scatter")
    # a [4, k, 11] float32 gather for the root and two a round
    assert moved["tree/split_records_bytes"] == (
        4.0 * DEVICES * 11 * (ITERS + 2 * K * rounds_))
    # root: leaf totals, scatter, records; a round: scatter, two gathers
    assert moved["tree/exchange_collectives"] == 3 * ITERS + 3 * rounds_


# ---- the chip's layout under the mesh, by the Pallas interpreter -------------

CHUNK = 512


def test_on_the_chip_the_sharded_store_is_72_columns_and_copies_nothing(
        monkeypatch):
    """As on the chip (`_kernel_backend` answers "pallas", the kernels run
    interpreted, row chunks of 512): 67 columns go to the feature group,
    72, which four devices scatter in slices of 18; each shard's rows go to
    the row chunk on their own; neither kernel pads the store; and the
    tree is the one-device learner's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.sharding import Mesh
    from benchmark.generators.criteo_tb import make
    from lightgbm_tpu import profiling
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset
    from lightgbm_tpu.learner import rounds
    from lightgbm_tpu.ops import histogram, partition
    real_call = pl.pallas_call

    def interpreted(*args, **kw):
        kw["interpret"] = True
        return real_call(*args, **kw)

    jax.clear_caches()
    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(rounds, "_kernel_backend", lambda: "pallas")
    monkeypatch.setattr(histogram, "_MASKED_CHUNK",
                        dict.fromkeys(histogram._MASKED_CHUNK, CHUNK))
    monkeypatch.setattr(partition, "_PARTITION_CHUNK", CHUNK)
    try:
        n = 2_411
        X, y = make(n, FEATURES, (3, 0))
        cfg = config_from_params(dict(
            config()["params"], num_leaves=7, min_sum_hessian_in_leaf=1.0,
            min_data_in_leaf=n // 12, tree_learner="data"))
        ds = Dataset(X, y, config=cfg)
        g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
        h = jnp.asarray(np.full(n, 0.5, np.float32))
        mesh = Mesh(np.asarray(jax.devices()[:DEVICES]).reshape(DEVICES, 1),
                    ("data", "feature"))
        lr = rounds.RoundsTreeLearner(ds, cfg, mesh)
        per = -(-n // DEVICES)
        assert lr.hist_exchange == "psum_scatter"
        assert lr.bins_dev.shape == (72, DEVICES * (per + (-per) % CHUNK))
        before = profiling.counters("tree/")
        _, lid, arrs = lr.train_device(g, h, None, None)
        after = profiling.counters("tree/")
        assert after["tree/store_copy_rows"] == before.get(
            "tree/store_copy_rows", 0.0)
        assert after["tree/exchange_collectives"] > before.get(
            "tree/exchange_collectives", 0.0)
        _, lid1, one = rounds.RoundsTreeLearner(ds, cfg, None).train_device(
            g, h, None, None)
        assert 3 < int(arrs.num_leaves) == int(one.num_leaves)
        for name in ("split_feature", "threshold_bin", "left_child",
                     "right_child", "leaf_count"):
            np.testing.assert_array_equal(np.asarray(getattr(arrs, name)),
                                          np.asarray(getattr(one, name)),
                                          name)
        # leaf ids come back in each learner's row layout: four shards
        # padded to the chunk on their own, one padded as a whole
        assert lid.shape == (lr.Np,) and lid1.shape == (n + (-n) % CHUNK,)
        assert lid.sharding.is_equivalent_to(lr.row_layout.sharding, 1)
        np.testing.assert_array_equal(np.asarray(lid)[:n],
                                      np.asarray(lid1)[:n])
    finally:
        jax.clear_caches()
