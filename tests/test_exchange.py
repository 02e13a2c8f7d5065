"""Data-parallel histogram exchange (hist_exchange=psum|psum_scatter)
under shard_map — the comms layer of
learner/rounds.py and learner/fused.py on the virtual 8-device CPU mesh
(conftest.py).

Tree-identity tests use dyadic-grid gradients (±1 grads, power-of-two
hessians) so every fp32 partial sum is exact in any reduction order:
psum and psum_scatter then produce bitwise-identical gains and the
grown trees must match exactly, not just approximately.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu import profiling
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as RawDataset
from lightgbm_tpu.learner.common import resolve_hist_exchange
from lightgbm_tpu.learner.fused import FusedTreeLearner, make_mesh
from lightgbm_tpu.learner.rounds import RoundsTreeLearner

pytestmark = pytest.mark.quick


def _splits(t):
    return sorted(zip(t.split_feature_inner[: t.num_leaves - 1],
                      t.threshold_in_bin[: t.num_leaves - 1]))


def _dyadic_problem(n=4096, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    g = np.where(y > 0, -1.0, 1.0).astype(np.float32)
    h = np.full(n, 0.5, np.float32)
    return X, y, jnp.asarray(g), jnp.asarray(h)


def test_resolve_hist_exchange_auto_small_payload_picks_psum():
    """Acceptance (c): the auto mode's small-payload fallback — tiny
    per-pass histograms take the plain psum (collective latency
    dominates), large payloads take the scattered exchange."""
    cfg = config_from_params({"verbose": -1})
    assert cfg.hist_exchange == "auto"
    # single device: never an exchange
    assert resolve_hist_exchange(cfg, ndev=1, payload_bytes=1e9) == "psum"
    # small payload on a mesh: psum
    assert resolve_hist_exchange(cfg, ndev=8,
                                 payload_bytes=64 * 1024) == "psum"
    # north-star payload (84*28*3*256*4 ≈ 7 MB): psum_scatter
    assert resolve_hist_exchange(
        cfg, ndev=8, payload_bytes=4.0 * 84 * 28 * 3 * 256) == "psum_scatter"
    # explicit requests are respected on a mesh
    for mode in ("psum", "psum_scatter"):
        cfg_m = config_from_params({"verbose": -1, "hist_exchange": mode})
        assert resolve_hist_exchange(cfg_m, ndev=8,
                                     payload_bytes=1.0) == mode
    # alias
    assert config_from_params(
        {"histogram_reduce": "psum", "verbose": -1}).hist_exchange == "psum"
    with pytest.raises(ValueError):
        config_from_params({"hist_exchange": "bogus", "verbose": -1})


def test_learner_auto_resolves_psum_at_tiny_shape():
    """Learner-level auto fallback: a tiny dataset's per-pass payload is
    under the threshold, so the resolved exchange is psum even on the
    8-device mesh."""
    X, y, g, h = _dyadic_problem(n=600, f=4)
    cfg = config_from_params({"objective": "binary", "num_leaves": 7,
                              "min_data_in_leaf": 5, "verbose": -1})
    ds = RawDataset(X, y, config=cfg)
    lrn = RoundsTreeLearner(ds, cfg, mesh=make_mesh("data"))
    assert lrn.hist_exchange == "psum"
    t, _ = lrn.train(g, h)
    assert t.num_leaves > 1


def test_rounds_trees_identical_psum_vs_psum_scatter():
    """Acceptance (a): with 8 virtual devices, hist_exchange=psum_scatter
    trains trees identical to psum on exact-sum gradients, and the
    per-device exchange-bytes counter drops >= 4x."""
    X, y, g, h = _dyadic_problem()
    mesh = make_mesh("data")
    assert mesh is not None, "expected 8 virtual devices (see conftest)"
    out = {}
    for hx in ("psum", "psum_scatter"):
        cfg = config_from_params({
            "objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
            "verbose": -1, "hist_exchange": hx})
        ds = RawDataset(X, y, config=cfg)
        lrn = RoundsTreeLearner(ds, cfg, mesh=mesh)
        assert lrn.hist_exchange == hx
        profiling.reset()
        t, lid = lrn.train(g, h)
        out[hx] = (t, np.asarray(lid),
                   profiling.counter_value(profiling.HIST_EXCHANGE_BYTES),
                   profiling.counter_value(profiling.SPLIT_RECORDS_BYTES))
    tp, lp, bp, rp = out["psum"]
    ts, ls, bs, rs = out["psum_scatter"]
    assert tp.num_leaves == ts.num_leaves > 1
    assert _splits(tp) == _splits(ts)
    np.testing.assert_array_equal(lp, ls)
    np.testing.assert_allclose(tp.leaf_value[: tp.num_leaves],
                               ts.leaf_value[: ts.num_leaves], rtol=1e-6)
    # comms accounting: psum pays no record exchange, scatter's
    # histogram payload is >= 4x smaller per device
    assert bp > 0 and bs > 0
    assert rp == 0.0 and rs > 0
    assert bp / bs >= 4.0, (bp, bs)
    # unsharded reference grows the same tree
    cfg1 = config_from_params({"objective": "binary", "num_leaves": 15,
                               "min_data_in_leaf": 5, "verbose": -1})
    ds1 = RawDataset(X, y, config=cfg1)
    t1, _ = RoundsTreeLearner(ds1, cfg1, None).train(g, h)
    assert _splits(t1) == _splits(tp)


def test_fused_trees_identical_psum_vs_psum_scatter():
    """The fused (leaf-wise SPMD) learner through the same switch, on
    the data and hybrid data2d meshes."""
    X, y, g, h = _dyadic_problem(n=1500, f=12, seed=9)
    cfg1 = config_from_params({"objective": "binary", "num_leaves": 15,
                               "min_data_in_leaf": 20, "verbose": -1})
    ds = RawDataset(X, y, config=cfg1)
    t_ref, _ = FusedTreeLearner(ds, cfg1, None).train(g, h)
    for lt in ("data", "data2d"):
        for hx in ("psum", "psum_scatter"):
            cfg = config_from_params({
                "objective": "binary", "num_leaves": 15,
                "min_data_in_leaf": 20, "verbose": -1,
                "hist_exchange": hx})
            t, _ = FusedTreeLearner(ds, cfg, make_mesh(lt)).train(g, h)
            assert _splits(t) == _splits(t_ref), (lt, hx)


def _one_device_and_sharded(X, y, g, h, params, bag=None):
    """The same build on one device and, under both exchanges, on the
    8-device data mesh: {"one": ..., "psum": ..., "psum_scatter": ...}
    -> (tree, leaf ids)."""
    bag_args = () if bag is None else (jnp.asarray(bag), len(bag))
    out = {}
    for key, mesh in (("one", None), ("psum", make_mesh("data")),
                      ("psum_scatter", make_mesh("data"))):
        cfg = config_from_params(dict(
            params, **({} if mesh is None else {"hist_exchange": key})))
        ds = RawDataset(X, y, config=cfg)
        t, lid = RoundsTreeLearner(ds, cfg, mesh=mesh).train(g, h, *bag_args)
        out[key] = (ds, t, np.asarray(lid))
    return out


def test_sharded_stream_grows_the_one_device_tree_under_bagging_goss():
    """Rows a bag drops carry a zero row mask on whichever shard holds
    them, and GOSS-style amplified gradients (a power of two, so every
    sum stays exact) ride the same value rows: under the 8-device
    shard_map, with both exchanges, the stream must grow the tree one
    device grows, leaf id for leaf id."""
    X, y, g, h = _dyadic_problem()
    rng = np.random.RandomState(11)
    N = len(y)
    amp = np.where(rng.rand(N) < 0.5, 2.0, 1.0).astype(np.float32)
    g, h = jnp.asarray(amp * np.asarray(g)), jnp.asarray(amp * np.asarray(h))
    bag = np.sort(rng.choice(N, size=int(N * 0.6),
                             replace=False)).astype(np.int32)
    out = _one_device_and_sharded(
        X, y, g, h, {"objective": "binary", "num_leaves": 31,
                     "min_data_in_leaf": 5, "verbose": -1}, bag)
    _, t0, l0 = out["one"]
    assert t0.num_leaves > 1
    # only the bag's rows are counted
    assert t0.leaf_count[: t0.num_leaves].sum() == len(bag)
    for key, (_, t, lid) in out.items():
        assert _splits(t) == _splits(t0), key
        np.testing.assert_array_equal(lid, l0)
        np.testing.assert_array_equal(t.leaf_count[: t.num_leaves],
                                      t0.leaf_count[: t0.num_leaves])


def test_sharded_stream_grows_the_one_device_tree_under_efb():
    """A bundled store under shard_map: psum == psum_scatter == one
    device, with the per-shard unbundle (ops/split.unbundle_hist_local)
    reconstructing original-feature histograms from each shard's column
    slice."""
    rng = np.random.RandomState(21)
    n, groups, card = 2000, 8, 4
    codes = rng.randint(0, card, size=(n, groups))
    X = np.zeros((n, groups * card), np.float64)
    for gi in range(groups):
        X[np.arange(n), gi * card + codes[:, gi]] = 1.0
    w = np.random.RandomState(0).randn(groups * card)
    y = (X @ w > 0).astype(np.float64)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(n, 0.5, np.float32))
    out = _one_device_and_sharded(
        X, y, g, h, {"objective": "binary", "num_leaves": 15,
                     "min_data_in_leaf": 10, "verbose": -1,
                     "enable_bundle": True})
    _, base, _ = out["one"]
    assert base.num_leaves > 1
    for key, (ds, t, _) in out.items():
        assert ds.bundle_plan is not None
        assert ds.bins.shape[0] < groups * card
        assert _splits(t) == _splits(base), key


def test_voting_routes_through_exchange_switch():
    """Satellite: the voting learner's selected-histogram exchange runs
    through hist_exchange too — with top_k >= F every feature is
    exchanged, so both modes must equal plain data-parallel."""
    X, y, g, h = _dyadic_problem(n=1500, f=30, seed=7)
    cfg_d = config_from_params({
        "objective": "binary", "num_leaves": 15, "verbose": -1,
        "tree_learner": "data", "min_data_in_leaf": 20})
    ds = RawDataset(X, y, config=cfg_d)
    t_data, _ = FusedTreeLearner(ds, cfg_d, make_mesh("data")).train(g, h)
    for hx in ("psum", "psum_scatter"):
        cfg_v = config_from_params({
            "objective": "binary", "num_leaves": 15, "verbose": -1,
            "tree_learner": "voting", "top_k": X.shape[1],
            "min_data_in_leaf": 20, "hist_exchange": hx})
        lrn = FusedTreeLearner(ds, cfg_v, make_mesh("voting"))
        profiling.reset()
        t_vote, _ = lrn.train(g, h)
        assert _splits(t_vote) == _splits(t_data), hx
        hx_bytes = profiling.counter_value(profiling.HIST_EXCHANGE_BYTES)
        sr_bytes = profiling.counter_value(profiling.SPLIT_RECORDS_BYTES)
        assert hx_bytes > 0
        assert (sr_bytes > 0) == (hx == "psum_scatter")
