"""2-D (data x feature) mesh for the rounds learner
(lightgbm_tpu/sharded/mesh.py + learner/rounds.py): tree identity
against the 1-D psum / psum_scatter paths on the virtual 8-device CPU
mesh, learner routing, and the lifted sharded-primitive helpers
(ISSUE 10 tentpole pillar 3)."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as RawDataset
from lightgbm_tpu.learner.rounds import RoundsTreeLearner
from lightgbm_tpu.sharded.mesh import (make_mesh, mesh_axes,
                                       pad_cols_to_ndev, row_shard_axes)

NDEV = len(jax.devices())


def _problem(n=4096, f=7, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.4 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(n, 0.5, np.float32))
    return X, y, g, h


def _splits(t):
    return sorted(zip(t.split_feature_inner[: t.num_leaves - 1],
                      t.threshold_in_bin[: t.num_leaves - 1]))


def _mesh2d(dd, df):
    devs = np.asarray(jax.devices()[: dd * df])
    return jax.sharding.Mesh(devs.reshape(dd, df), ("data", "feature"))


@pytest.mark.quick
def test_row_shard_axes_and_mesh_axes():
    assert row_shard_axes(1, 1) is None
    assert row_shard_axes(4, 1) == ("data",)
    assert row_shard_axes(1, 2) == ("feature",)
    assert row_shard_axes(4, 2) == ("data", "feature")
    m = make_mesh("data2d")
    if m is not None:
        ax = mesh_axes(m)
        assert set(ax) == {"data", "feature"}
        assert ax["data"] * ax["feature"] == min(NDEV, NDEV)


@pytest.mark.quick
def test_pad_cols_2d_unit():
    # 2-D scatter: the per-feature-shard slice must tile; lcm keeps the
    # int8 32-sublane alignment
    assert pad_cols_to_ndev(7, 2) == 8
    assert pad_cols_to_ndev(33, 2, align=32) == 64
    with pytest.raises(ValueError):
        pad_cols_to_ndev(8, 0)


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
@pytest.mark.parametrize("hx", ["psum", "psum_scatter"])
def test_2d_mesh_trees_identical_to_1d(hx):
    """The ISSUE acceptance gate shape: a 4x2 (data x feature) mesh
    grows trees identical to the 1-D paths, through both exchanges."""
    X, y, g, h = _problem()
    cfg = config_from_params({"objective": "binary", "num_leaves": 31,
                              "min_data_in_leaf": 5, "verbose": -1,
                              "hist_exchange": hx})
    ds = RawDataset(X, y, config=cfg)
    t_uns, _ = RoundsTreeLearner(ds, cfg, None).train(g, h)
    mesh1d = jax.sharding.Mesh(np.asarray(jax.devices()[:8]).reshape(8),
                               ("data",))
    t_1d, _ = RoundsTreeLearner(ds, cfg, mesh=mesh1d).train(g, h)
    lr = RoundsTreeLearner(ds, cfg, mesh=_mesh2d(4, 2))
    assert lr.dd == 4 and lr.df == 2
    t_2d, leaf_id = lr.train(g, h)
    assert t_2d.num_leaves == t_uns.num_leaves > 1
    assert _splits(t_2d) == _splits(t_1d) == _splits(t_uns)
    # leaf ids must cover the real rows identically to the unsharded run
    _, lid_uns = RoundsTreeLearner(ds, cfg, None).train(g, h)
    assert np.array_equal(np.asarray(leaf_id), np.asarray(lid_uns))


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
def test_2d_mesh_grows_the_one_device_tree_under_bagging():
    """Rows shard over both axes, so a bag's dropped rows (zero row
    mask) fall on every device of the 4x2 mesh: the tree, its counts and
    the leaf ids must be the one-device build's."""
    X, y, g, h = _problem(n=8192)
    bag = np.sort(np.random.RandomState(4).choice(
        len(y), size=int(len(y) * 0.6), replace=False)).astype(np.int32)
    cfg = config_from_params({"objective": "binary", "num_leaves": 31,
                              "min_data_in_leaf": 5, "verbose": -1,
                              "hist_exchange": "psum_scatter"})
    ds = RawDataset(X, y, config=cfg)
    t_uns, lid_uns = RoundsTreeLearner(ds, cfg, None).train(
        g, h, jnp.asarray(bag), len(bag))
    t_2d, lid_2d = RoundsTreeLearner(ds, cfg, mesh=_mesh2d(4, 2)).train(
        g, h, jnp.asarray(bag), len(bag))
    assert t_uns.num_leaves > 1
    assert t_uns.leaf_count[: t_uns.num_leaves].sum() == len(bag)
    assert _splits(t_2d) == _splits(t_uns)
    np.testing.assert_array_equal(t_2d.leaf_count[: t_2d.num_leaves],
                                  t_uns.leaf_count[: t_uns.num_leaves])
    np.testing.assert_array_equal(np.asarray(lid_2d), np.asarray(lid_uns))


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
def test_2d_mesh_efb_bundled_store():
    """Bundled (EFB) store under the 2-D exchange: the scattered column
    slices unbundle per shard exactly like the 1-D path."""
    rng = np.random.RandomState(5)
    n, groups, card = 4096, 4, 6
    X = np.zeros((n, groups * card))
    codes = rng.randint(0, card, size=(n, groups))
    for gi in range(groups):
        X[np.arange(n), gi * card + codes[:, gi]] = 1.0
    y = (X @ rng.randn(groups * card) > 0).astype(float)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(n, 0.5, np.float32))
    cfg = config_from_params({"objective": "binary", "num_leaves": 15,
                              "min_data_in_leaf": 5, "verbose": -1,
                              "hist_exchange": "psum_scatter"})
    ds = RawDataset(X, y, config=cfg)
    assert ds.bundle_plan is not None
    t_uns, _ = RoundsTreeLearner(ds, cfg, None).train(g, h)
    t_2d, _ = RoundsTreeLearner(ds, cfg, mesh=_mesh2d(4, 2)).train(g, h)
    assert _splits(t_2d) == _splits(t_uns)


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
def test_create_tree_learner_routes_data2d_rounds():
    """tree_learner=data2d + tree_growth=rounds runs the rounds builder
    on the 2-D mesh (it used to silently fall back to the fused exact
    builder)."""
    from lightgbm_tpu.learner.fused import create_tree_learner
    X, y, _g, _h = _problem()
    cfg = config_from_params({"objective": "binary", "num_leaves": 15,
                              "tree_learner": "data2d",
                              "tree_growth": "rounds", "verbose": -1,
                              "min_data_in_leaf": 5})
    ds = RawDataset(X, y, config=cfg)
    lrn = create_tree_learner(ds, cfg)
    assert isinstance(lrn, RoundsTreeLearner)
    assert lrn.df > 1 and lrn.dd * lrn.df == min(NDEV, 8)


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
def test_2d_booster_end_to_end_matches_1d():
    """Boosting through the engine on the 2-D mesh equals the 1-D
    data-parallel model: STRUCTURE exactly, float report fields to
    tight tolerance — the 2-D exchange reduces histograms in a
    different f32 order than the 1-D psum (data-psum then
    feature-scatter vs one flat reduce), so leaf-value ulps drift
    across iterations exactly like the multi-host-vs-single-process
    case (tests/test_distributed.py's model comparison)."""
    import lightgbm_tpu as lgb
    X, y, _g, _h = _problem(n=4096)
    models = {}
    for lt in ("data", "data2d"):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "min_data_in_leaf": 5, "tree_learner": lt,
                  "tree_growth": "rounds"}
        bst = lgb.Booster(params, lgb.Dataset(X, y).construct(params))
        bst._gbdt._can_pipeline = lambda: False
        for _ in range(5):
            bst.update()
        models[lt] = bst._gbdt.save_model_to_string()
    _assert_models_equal_to_ulps(models["data2d"], models["data"])


def _assert_models_equal_to_ulps(a: str, b: str):
    """Structure exactly equal; float report fields to tight tolerance
    (same comparator as tests/test_distributed.py — gains amplify
    ulp-level histogram-reduction-order differences)."""
    fa, fb = a.splitlines(), b.splitlines()
    assert len(fa) == len(fb)
    float_fields = ("split_gain=", "leaf_value=", "internal_value=",
                    "threshold=", "leaf_weight=", "internal_weight=")
    for la, lb in zip(fa, fb):
        if la == lb:
            continue
        key = la.split("=", 1)[0] + "="
        assert key in float_fields, f"non-float field differs: {la} != {lb}"
        va = np.asarray([float(t) for t in la.split("=", 1)[1].split()])
        vb = np.asarray([float(t) for t in lb.split("=", 1)[1].split()])
        np.testing.assert_allclose(va, vb, rtol=1e-3, atol=1e-6,
                                   err_msg=key)
