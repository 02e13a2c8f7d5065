"""Exclusive Feature Bundling (EFB) tests.

Acceptance (ISSUE 2): on a one-hot-heavy dataset (>= 200 features,
>= 95% exclusive) the effective histogrammed feature count drops >= 4x;
zero-conflict bundling is exactly lossless (bundled and unbundled
training grow identical trees); save/load + predict round-trips stay in
original feature space; a served /predict answers identically for a
bundled model.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import plan_bundles
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as InnerDataset

pytestmark = pytest.mark.quick


def _one_hot_data(n=1500, groups=40, card=6, seed=0, noise=0.3):
    """One-hot encodes `groups` categorical variables: groups*card
    columns, exactly one non-zero per group per row (zero conflicts)."""
    rng = np.random.RandomState(seed)
    codes = rng.randint(0, card, size=(n, groups))
    X = np.zeros((n, groups * card))
    for g in range(groups):
        X[np.arange(n), g * card + codes[:, g]] = 1.0
    w = rng.randn(groups * card)
    y = (X @ w + noise * rng.randn(n) > 0).astype(float)
    return X, y


def _train(X, y, enable_bundle, tree_growth="exact", rounds=6, **extra):
    params = dict(dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                       verbose=-1, enable_bundle=enable_bundle,
                       tree_growth=tree_growth), **extra)
    ds = lgb.Dataset(X, y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(rounds):
        bst.update()
    bst._gbdt._flush_pending()
    return bst, ds


def _structure(bst):
    out = []
    for t in bst._gbdt.models:
        n = t.num_leaves
        out.append((n, t.split_feature[: n - 1].tolist(),
                    t.threshold[: n - 1].tolist(),
                    t.decision_type[: n - 1].tolist()))
    return out


# -- planner ------------------------------------------------------------


def test_onehot_compaction_at_least_4x():
    # acceptance shape: >= 200 features, >= 95% exclusive (here: 100%)
    X, y = _one_hot_data(n=1200, groups=40, card=6)
    assert X.shape[1] >= 200
    _, ds = _train(X, y, enable_bundle=True, rounds=1)
    inner = ds._inner
    assert inner.num_features >= 200
    assert inner.num_store_columns * 4 <= inner.num_features
    assert inner.bundle_conflict_rows == 0
    assert inner.realized_conflict_rate() == 0.0


def test_planner_respects_conflict_budget_zero():
    # two features that collide on every row must NOT bundle at rate 0
    S = 400
    sample = np.zeros((2, S), np.int64)
    sample[0, :] = 1
    sample[1, :] = 1
    plan = plan_bundles(sample, np.array([2, 2]), np.array([0, 0]),
                        max_conflict_rate=0.0)
    assert plan is None  # both singleton -> no multi-feature bundle

    # disjoint non-default rows bundle fine
    sample2 = np.zeros((2, S), np.int64)
    sample2[0, :100] = 1
    sample2[1, 200:300] = 1
    plan2 = plan_bundles(sample2, np.array([2, 2]), np.array([0, 0]),
                         max_conflict_rate=0.0)
    assert plan2 is not None and plan2.num_columns == 1
    assert plan2.feat_packed.all()


def test_planner_conflict_budget_admits_overlap():
    S = 1000
    sample = np.zeros((2, S), np.int64)
    sample[0, :110] = 1
    sample[1, 100:210] = 1          # 10 conflicting rows = 1%
    nb = np.array([2, 2])
    db = np.array([0, 0])
    assert plan_bundles(sample, nb, db, max_conflict_rate=0.0) is None
    plan = plan_bundles(sample, nb, db, max_conflict_rate=0.02)
    assert plan is not None and plan.num_columns == 1


def test_bundle_bin_budget_caps_column_width():
    # 5 features x 100 bins each cannot all share one <=256-bin column
    rng = np.random.RandomState(0)
    F, S = 5, 2000
    sample = np.zeros((F, S), np.int64)
    for f in range(F):
        rows = slice(f * (S // F), (f + 1) * (S // F))
        sample[f, rows] = rng.randint(1, 100, S // F)
    nb = np.full(F, 100)
    db = np.zeros(F, np.int64)
    plan = plan_bundles(sample, nb, db, max_conflict_rate=0.0)
    assert plan is not None
    assert (plan.col_num_bins <= 256).all()
    assert plan.num_columns >= 3   # 1+99*k <= 256 -> k <= 2 per column


# -- losslessness -------------------------------------------------------


@pytest.mark.parametrize("growth", ["exact", "rounds"])
def test_zero_conflict_parity(growth):
    X, y = _one_hot_data(n=1200, groups=20, card=6, seed=1)
    a, dsa = _train(X, y, True, growth)
    b, _ = _train(X, y, False, growth)
    assert dsa._inner.bundle_plan is not None
    assert dsa._inner.bundle_conflict_rows == 0
    # identical tree STRUCTURE (features, thresholds, decisions); leaf
    # values agree to f32 reconstruction ulps (the default bin is
    # rebuilt as total - sum(others))
    assert _structure(a) == _structure(b)
    pa, pb = a.predict(X), b.predict(X)
    np.testing.assert_allclose(pa, pb, atol=1e-5)


def test_bundled_rounds_trees_are_the_exact_learners():
    """The stream over a bundled store against learner/serial.py over
    the same store: min_data_in_leaf keeps every tree under the cap of
    31 leaves, where the rounds schedule grows the leaf-wise tree."""
    X, y = _one_hot_data(n=1200, groups=20, card=6, seed=1)
    kw = dict(num_leaves=31, min_data_in_leaf=60, rounds=4)
    a, dsa = _train(X, y, True, "rounds", **kw)
    b, _ = _train(X, y, True, "exact", **kw)
    assert dsa._inner.bundle_plan is not None
    assert dsa._inner.num_store_columns < X.shape[1]
    assert type(a._gbdt.learner).__name__ == "RoundsTreeLearner"
    assert type(b._gbdt.learner).__name__ == "SerialTreeLearner"
    assert all(3 < t.num_leaves < 31 for t in a._gbdt.models)

    def splits(bst):
        return [sorted(zip(t.split_feature[: t.num_leaves - 1].tolist(),
                           t.threshold[: t.num_leaves - 1].tolist()))
                for t in bst._gbdt.models]
    assert splits(a) == splits(b)
    np.testing.assert_allclose(a.predict(X), b.predict(X), atol=1e-5)


def test_mixed_dense_and_sparse_features_parity():
    # dense numeric columns stay singleton; sparse ones bundle — the
    # split search must keep ranking both correctly
    rng = np.random.RandomState(2)
    n = 1200
    Xd = rng.randn(n, 5)
    Xs, _ = _one_hot_data(n=n, groups=10, card=5, seed=3)
    X = np.concatenate([Xd, Xs], axis=1)
    w = rng.randn(X.shape[1])
    y = (X @ w > 0).astype(float)
    a, dsa = _train(X, y, True)
    b, _ = _train(X, y, False)
    plan = dsa._inner.bundle_plan
    assert plan is not None
    # the 5 dense columns must not be packed
    assert not plan.feat_packed[:5].any()
    assert _structure(a) == _structure(b)
    np.testing.assert_allclose(a.predict(X), b.predict(X), atol=1e-5)


def test_bundled_valid_set_scores_match_predict():
    X, y = _one_hot_data(n=1000, groups=20, card=5, seed=4)
    Xv, yv = X[:250], y[:250]
    params = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                  verbose=-1, metric="binary_logloss")
    ds = lgb.Dataset(X, y, params=params)
    dv = ds.create_valid(Xv, yv)
    bst = lgb.Booster(params, ds)
    bst.add_valid(dv, "v0")
    for _ in range(5):
        bst.update()
    bst._gbdt._flush_pending()
    # the valid ScoreUpdater walked the BUNDLED store; compare to the
    # raw-feature host predict
    _, _, su, _ = bst._gbdt.valid_sets[0]
    raw_dev = np.asarray(su.get()).reshape(-1)
    raw_host = bst.predict(Xv, raw_score=True)
    np.testing.assert_allclose(raw_dev, raw_host, rtol=1e-4, atol=1e-5)


# -- persistence stays in original feature space ------------------------


def test_save_load_predict_roundtrip(tmp_path):
    X, y = _one_hot_data(n=1000, groups=20, card=5, seed=5)
    bst, ds = _train(X, y, True)
    assert ds._inner.bundle_plan is not None
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    text = open(path).read()
    # model text speaks ORIGINAL feature ids — every split feature must
    # be a real column index of X, not a bundle column
    for line in text.splitlines():
        if line.startswith("split_feature="):
            feats = [int(t) for t in line.split("=", 1)[1].split()]
            assert all(0 <= f < X.shape[1] for f in feats)
    back = lgb.Booster(model_file=path)
    np.testing.assert_allclose(back.predict(X), bst.predict(X), atol=1e-7)
    # feature importance also reports original columns
    imp = bst.feature_importance()
    assert imp.shape == (X.shape[1],)


def test_binary_cache_roundtrip_preserves_plan(tmp_path):
    X, y = _one_hot_data(n=800, groups=15, card=5, seed=6)
    params = dict(objective="binary", verbose=-1)
    cfg = config_from_params(params)
    inner = InnerDataset(X, y, cfg)
    assert inner.bundle_plan is not None
    path = str(tmp_path / "d.bin")
    inner.save_binary(path)
    back = InnerDataset.from_binary(path, cfg)
    assert np.array_equal(back.bins, inner.bins)
    assert back.bundle_plan is not None
    for field in ("feat_col", "feat_offset", "feat_default", "feat_nslots",
                  "feat_packed", "col_num_bins"):
        assert np.array_equal(getattr(back.bundle_plan, field),
                              getattr(inner.bundle_plan, field))
    assert np.array_equal(back.num_bins, inner.num_bins)
    assert back.num_store_columns == inner.num_store_columns


def test_binary_cache_rejects_other_bundle_setting(tmp_path):
    X, y = _one_hot_data(n=500, groups=10, card=5, seed=7)
    cfg_on = config_from_params({"verbose": -1, "enable_bundle": True})
    cfg_off = config_from_params({"verbose": -1, "enable_bundle": False})
    inner = InnerDataset(X, y, cfg_on)
    path = str(tmp_path / "d.bin")
    inner.save_binary(path)
    with pytest.raises(ValueError, match="enable_bundle"):
        InnerDataset.from_binary(path, cfg_off)


# -- unbundle / predicate units -----------------------------------------


def test_unbundle_hist_matches_direct_histogram():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import unbundle_hist
    X, y = _one_hot_data(n=600, groups=8, card=5, seed=8)
    cfg = config_from_params({"verbose": -1})
    bundled = InnerDataset(X, y, cfg)
    plain = InnerDataset(X, y, config_from_params(
        {"verbose": -1, "enable_bundle": False}))
    assert bundled.bundle_plan is not None
    B = 128
    rng = np.random.RandomState(0)
    g = rng.randn(bundled.num_data).astype(np.float32)
    h = np.abs(rng.randn(bundled.num_data)).astype(np.float32)

    def hist_of(bins, nb):
        F = bins.shape[0]
        out = np.zeros((F, 3, B), np.float32)
        for f in range(F):
            for b, gg, hh in zip(bins[f], g, h):
                out[f, 0, b] += gg
                out[f, 1, b] += hh
                out[f, 2, b] += 1.0
        return out

    hb = hist_of(np.asarray(bundled.bins, np.int64), None)
    hp = hist_of(np.asarray(plain.bins, np.int64), None)
    src, dmask = bundled.unbundle_tables(B)
    totals = jnp.asarray([g.sum(), h.sum(), float(len(g))])
    un = np.asarray(unbundle_hist(jnp.asarray(hb), jnp.asarray(src),
                                  jnp.asarray(dmask), totals))
    np.testing.assert_allclose(un, hp, rtol=1e-4, atol=1e-3)


def test_unbundle_sentinel_survives_padded_store_columns():
    """The rounds learner's int8 layout pads store columns to a multiple
    of 32, and padded columns put EVERY row at bin 0 — the gather
    sentinel must point past the PADDED histogram or the default-bin
    reconstruction absorbs the padded columns' totals (regression)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import unbundle_hist
    X, y = _one_hot_data(n=400, groups=8, card=5, seed=11)
    cfg = config_from_params({"verbose": -1})
    inner = InnerDataset(X, y, cfg)
    plan = inner.bundle_plan
    assert plan is not None
    C = plan.num_columns
    Fpad = 32 * ((C + 31) // 32)
    assert Fpad > C
    B = 128
    n = inner.num_data
    g = np.ones(n, np.float32)
    h = np.full(n, 0.5, np.float32)
    bins = np.asarray(inner.bins, np.int64)
    hist = np.zeros((Fpad, 3, B), np.float32)
    for f in range(C):
        for b in range(B):
            m = bins[f] == b
            hist[f, 0, b] = g[m].sum()
            hist[f, 1, b] = h[m].sum()
            hist[f, 2, b] = m.sum()
    # padded columns behave like the TPU kernel: all rows at bin 0
    for f in range(C, Fpad):
        hist[f, :, 0] = [g.sum(), h.sum(), float(n)]
    totals = jnp.asarray([g.sum(), h.sum(), float(n)])
    src, dmask = inner.unbundle_tables(B, Fpad)
    un = np.asarray(unbundle_hist(jnp.asarray(hist), jnp.asarray(src),
                                  jnp.asarray(dmask), totals))
    # every feature's counts must sum to n exactly (no padded-column
    # leakage into the default bin)
    np.testing.assert_allclose(un[:, 2, :].sum(axis=1), n, atol=1e-3)


def test_realized_conflict_warning_fires(capsys):
    from lightgbm_tpu import log
    X, y = _one_hot_data(n=400, groups=8, card=5, seed=12)
    cfg = config_from_params({"verbose": -1})
    inner = InnerDataset(X, y, cfg)
    assert inner.bundle_plan is not None
    old = log.level()
    log.configure(0)
    try:
        inner.bundle_conflict_rows = 7   # pretend binning found conflicts
        inner._check_realized_conflicts()
        err = capsys.readouterr().err
        assert "conflicting rows" in err
    finally:
        log.configure(old)


def test_bundle_predicate_matches_original_bins():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import (bundle_predicate_params,
                                        store_go_left)
    X, y = _one_hot_data(n=700, groups=10, card=6, seed=9)
    cfg = config_from_params({"verbose": -1})
    inner = InnerDataset(X, y, cfg)
    plan = inner.bundle_plan
    assert plan is not None
    ftbl = jnp.asarray(plan.feat_table())
    store = np.asarray(inner.bins, np.int32)
    orig = np.asarray(inner.unbundled_bins(), np.int32)
    rng = np.random.RandomState(0)
    for _ in range(40):
        f = int(rng.randint(inner.num_features))
        nb = int(inner.num_bins[f])
        thr = int(rng.randint(nb))
        for cat in (False, True):
            col, T, lo, hi1, dl = bundle_predicate_params(
                ftbl, jnp.int32(f), jnp.int32(thr), jnp.asarray(cat))
            got = np.asarray(store_go_left(
                jnp.asarray(store[int(col)]), T, lo, hi1, dl,
                jnp.asarray(cat)))
            want = (orig[f] == thr) if cat else (orig[f] <= thr)
            assert np.array_equal(got, want), (f, thr, cat)


def test_partition_pallas_bundled_predicate_matches_xla():
    """The int8 pallas kernel must decode the windowed (lo, hi, dl)
    predicate identically to the XLA composition (interpret mode)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.partition import partition_rows
    rng = np.random.RandomState(1)
    F, N, S = 4, 1024, 32
    bins = jnp.asarray(rng.randint(0, 40, size=(F, N)), jnp.int32)
    lid = jnp.asarray(rng.randint(0, 3, size=N), jnp.int32)
    tbl = np.zeros((7, S), np.float32)
    # leaf 1: packed numerical — column 2, slots [5, 12], T=8, default
    # goes left; leaf 2: packed categorical on the default bin (T never
    # matches in range, dl=1)
    tbl[:, 1] = [2, 8, 0, 4, 5, 12, 1]
    tbl[:, 2] = [0, 4, 1, 5, 6, 20, 1]
    out_xla = np.asarray(partition_rows(bins, lid, jnp.asarray(tbl),
                                        num_slots=S, backend="xla",
                                        num_bins_padded=256))
    out_pl = np.asarray(partition_rows(bins, lid, jnp.asarray(tbl),
                                       num_slots=S, backend="pallas",
                                       num_bins_padded=256, interpret=True))
    assert np.array_equal(out_xla, out_pl)
    # spot-check leaf 1 semantics directly
    b2 = np.asarray(bins)[2]
    in_r = (b2 >= 5) & (b2 <= 12)
    gl = np.where(in_r, b2 <= 8, True)
    want1 = np.where((np.asarray(lid) == 1) & ~gl, 4, np.asarray(lid))
    assert np.array_equal(out_xla[np.asarray(lid) == 1],
                          want1[np.asarray(lid) == 1])


def test_partition_rows_accepts_legacy_4row_table():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.partition import partition_rows
    rng = np.random.RandomState(0)
    F, N, S = 6, 512, 16
    bins = jnp.asarray(rng.randint(0, 20, size=(F, N)), jnp.int32)
    lid = jnp.asarray(rng.randint(0, 2, size=N), jnp.int32)
    tbl = np.zeros((4, S), np.float32)
    tbl[:, 1] = [3, 7, 0, 5]        # leaf 1 splits on feature 3, thr 7
    out = np.asarray(partition_rows(bins, lid, jnp.asarray(tbl),
                                    num_slots=S))
    want = np.where((np.asarray(lid) == 1)
                    & ~(np.asarray(bins)[3] <= 7), 5, np.asarray(lid))
    assert np.array_equal(out, want)


# -- sparse satellite ---------------------------------------------------


def test_scipy_sparse_streams_csc_and_matches_dense():
    sps = pytest.importorskip("scipy.sparse")
    rng = np.random.RandomState(3)
    n, F = 1000, 60
    dense = np.zeros((n, F))
    mask = rng.rand(n, F) < 0.04
    dense[mask] = rng.rand(int(mask.sum())) * 3 + 1
    y = (dense @ rng.randn(F) > 0).astype(float)
    params = dict(objective="binary", verbose=-1, min_data_in_leaf=5,
                  num_leaves=10)
    ds_sp = lgb.Dataset(sps.csr_matrix(dense), y, params=params).construct()
    ds_de = lgb.Dataset(dense, y, params=params).construct()
    assert np.array_equal(ds_sp._inner.bins, ds_de._inner.bins)
    assert ds_sp._inner.num_store_columns == ds_de._inner.num_store_columns


def test_scipy_sparse_densify_warns_once(capsys):
    sps = pytest.importorskip("scipy.sparse")
    import lightgbm_tpu.basic as basic
    from lightgbm_tpu import log
    old_level = log.level()
    log.configure(0)                 # earlier verbose=-1 tests muted it
    try:
        basic._sparse_densify_warned = False
        sp = sps.csr_matrix(np.eye(5))
        basic._to_numpy(sp)
        basic._to_numpy(sp)
        err = capsys.readouterr().err
        assert err.count("densifying a scipy sparse matrix") == 1
    finally:
        log.configure(old_level)


# -- serving parity -----------------------------------------------------


def test_served_predict_parity_for_bundled_model(tmp_path):
    from lightgbm_tpu.serving import ModelRegistry, PredictionServer
    import http.client

    X, y = _one_hot_data(n=800, groups=15, card=5, seed=10)
    bst, ds = _train(X, y, True, rounds=4)
    assert ds._inner.bundle_plan is not None
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    reg = ModelRegistry(path, params={"verbose": -1}, max_batch_rows=64)
    with PredictionServer(reg, flush_deadline_ms=2,
                          model_poll_seconds=0) as srv:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
        try:
            body = "\n".join(json.dumps([float(v) for v in row])
                             for row in X[:24])
            conn.request("POST", "/predict", body)
            r = conn.getresponse()
            assert r.status == 200
            preds = np.array([json.loads(l)
                              for l in r.read().decode().strip()
                              .splitlines()])
        finally:
            conn.close()
    np.testing.assert_allclose(preds, bst.predict(X[:24]), atol=1e-6)
