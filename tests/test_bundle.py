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


def _train(X, y, enable_bundle, tree_growth="exact", rounds=6,
           categorical_feature="auto", **extra):
    params = dict(dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                       verbose=-1, enable_bundle=enable_bundle,
                       tree_growth=tree_growth), **extra)
    ds = lgb.Dataset(X, y, params=params,
                     categorical_feature=categorical_feature)
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


# -- split search in the bundled store's own cells ------------------------
#
# ops/split.best_split_in_store against the oracle that stays:
# best_split(unbundle_hist(...)), the search learner/serial.py runs.

def _plan_of(columns, num_features):
    """A BundlePlan by hand.  `columns` lists the store's columns in store
    order: a list of (feature, num_bins, default_bin) members for a
    bundle, one (feature, num_bins, is_cat) for a column of its own.
    Returns (plan, num_bins [F], is_cat [F])."""
    from lightgbm_tpu.binning import BundlePlan
    F = num_features
    col, off, dflt = (np.zeros(F, np.int32) for _ in range(3))
    nb, packed, cat = np.ones(F, np.int32), np.zeros(F, bool), np.zeros(F, bool)
    col_bins = []
    for c, members in enumerate(columns):
        if isinstance(members, tuple):
            k, n, cat[k] = members
            col[k], nb[k] = c, n
            col_bins.append(n)
            continue
        o = 1
        for k, n, d in members:
            col[k], off[k], dflt[k], nb[k], packed[k] = c, o, d, n, True
            o += n - 1
        col_bins.append(o)
    plan = BundlePlan(feat_col=col, feat_offset=off, feat_default=dflt,
                      feat_nslots=nb - 1, feat_packed=packed,
                      col_num_bins=np.asarray(col_bins, np.int32))
    return plan, nb, cat


def _store_hists(plan, Cpad, B, rows, leaves, rng, dyadic):
    """[leaves, Cpad, 3, B] float32 store histograms of `leaves` random
    leaves (row subsets) and their [leaves, 3] totals: every row lies in
    exactly one bin of every column, so a column's bins sum to the leaf's
    totals; padded columns hold every row at bin 0, as the kernel's do."""
    C = plan.num_columns
    if dyadic:
        g = rng.integers(-64, 65, rows) / 64.0
        h = rng.integers(1, 65, rows) / 64.0
    else:
        g, h = rng.standard_normal(rows), rng.random(rows) + 0.05
    bins = np.zeros((Cpad, rows), np.int64)
    for c in range(C):
        # a skewed draw: bin 0 (every member at its default) is common
        w = rng.random(plan.col_num_bins[c]) ** 3 + 1e-3
        w[0] += w.sum()
        bins[c] = rng.choice(len(w), rows, p=w / w.sum())
    out = np.zeros((leaves, Cpad, 3, B))
    sums = np.zeros((leaves, 3))
    for i in range(leaves):
        m = rng.random(rows) < rng.uniform(0.2, 0.9)
        sums[i] = g[m].sum(), h[m].sum(), m.sum()
        for c in range(Cpad):
            for j, v in enumerate((g, h, np.ones(rows))):
                out[i, c, j] = np.bincount(bins[c][m], v[m], minlength=B)
    return out.astype(np.float32), sums.astype(np.float32)


def _both_searches(plan, nb, cat, hists, sums, B, Cpad, fmask=None, **kw):
    """Packed [leaves, 11] records: (store cells, gathered oracle)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import (best_split, best_split_in_store,
                                        store_search_operands,
                                        unbundle_hist)
    fmask = np.ones(len(nb), bool) if fmask is None else fmask
    cells = plan.search_tables(nb, cat, B, Cpad)
    src, dmask = plan.unbundle_tables(nb, B, Cpad)
    search = store_search_operands(cells, fmask)

    def store(h, s):
        return best_split_in_store(h, search, s[0], s[1], s[2],
                                   **kw).packed()

    def oracle(h, s):
        return best_split(unbundle_hist(h, src, dmask, s), jnp.asarray(nb),
                          jnp.asarray(cat), jnp.asarray(fmask),
                          s[0], s[1], s[2], **kw).packed()
    hists, sums = jnp.asarray(hists), jnp.asarray(sums)
    return (np.asarray(jax.vmap(store)(hists, sums)),
            np.asarray(jax.vmap(oracle)(hists, sums)))


def _assert_same_records(got, want, steps=0):
    """Feature, threshold and both counts exactly; sums and gains within
    `steps` float32 steps of the sums the search compares (0: bit for
    bit).  A leaf with no valid candidate reads -inf on both sides."""
    np.testing.assert_array_equal(np.isfinite(got[:, 0]),
                                  np.isfinite(want[:, 0]))
    ok = np.isfinite(want[:, 0])
    assert ok.any()
    got, want = got[ok], want[ok]
    np.testing.assert_array_equal(got[:, [1, 2, 5, 8]], want[:, [1, 2, 5, 8]])
    if steps == 0:
        np.testing.assert_array_equal(got, want)
        return
    # a sum is off by float32 steps of the leaf's totals (left sums at or
    # above a default bin are totals less a suffix); G^2/H moves with it
    # by 2 G/H dG + (G/H)^2 dH on either side
    w = want.astype(np.float64)
    d = steps * np.spacing(np.abs(want[:, 3:9]).max(axis=1)).astype(np.float64)
    assert (np.abs(got[:, 3:9] - w[:, 3:9]) <= d[:, None]).all()
    out = np.abs(w[:, [3, 6]] / w[:, [4, 7]])               # |G/H|, L and R
    assert (np.abs(got[:, 0] - w[:, 0])
            <= ((2 * out + out ** 2).sum(axis=1) + 1) * d).all()
    assert (np.abs(got[:, 9:] - w[:, 9:])
            <= (1 + out) * d[:, None] / w[:, [4, 7]] + 1e-6).all()


def _random_plan(rng, B=64):
    """Bundles of multi-slot members whose default bins are anywhere
    (first, middle, last), features numbered against the store order, a
    numerical and a categorical column of their own between them."""
    order = rng.permutation(14)
    it = iter(order)
    cols = []
    for _ in range(3):
        members, room = [], B - 1
        for _ in range(4):
            n = int(rng.integers(2, 9))
            if n - 1 > room:
                break
            members.append((int(next(it)), n, int(rng.integers(0, n))))
            room -= n - 1
        cols.append(members)
    cols.insert(1, (int(next(it)), 40, False))
    cols.append((int(next(it)), 9, True))
    used = [m for c in cols for m in ([c] if isinstance(c, tuple) else c)]
    remap = {k: i for i, k in enumerate(sorted(m[0] for m in used))}
    cols = [(remap[c[0]],) + c[1:] if isinstance(c, tuple)
            else [(remap[k], n, d) for k, n, d in c] for c in cols]
    return _plan_of(cols, len(used))


SEARCH_KW = dict(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=1,
                 min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)


SEARCH_CASES = ["defaults_anywhere", "padded_columns", "feature_mask",
                "min_data_binds", "min_hessian_binds", "l1_l2_min_gain",
                "float_sums"]


@pytest.mark.parametrize("case", SEARCH_CASES)
def test_store_search_is_the_gathered_search(case):
    """Random plans, eight random leaves each: the record out of the
    store's own cells is best_split(unbundle_hist(...))'s.  Dyadic
    gradients make every float32 sum exact, so whatever order the two
    searches add in they must agree to the bit; `float_sums` lets the
    orders show and allows a few float32 steps."""
    rng = np.random.default_rng(SEARCH_CASES.index(case))
    B = 64
    for _ in range(3):
        plan, nb, cat = _random_plan(rng, B)
        Cpad = plan.num_columns + (3 if case == "padded_columns" else 0)
        kw, fmask = dict(SEARCH_KW), None
        if case == "feature_mask":
            fmask = rng.random(len(nb)) < 0.5
            fmask[rng.integers(len(nb))] = True
        elif case == "min_data_binds":
            kw["min_data_in_leaf"] = 150
        elif case == "min_hessian_binds":
            kw["min_sum_hessian_in_leaf"] = 60.0
        elif case == "l1_l2_min_gain":
            kw.update(lambda_l1=0.5, lambda_l2=2.0, min_gain_to_split=0.01)
        hists, sums = _store_hists(plan, Cpad, B, 2000, 8, rng,
                                   dyadic=case != "float_sums")
        got, want = _both_searches(plan, nb, cat, hists, sums, B, Cpad,
                                   fmask, **kw)
        _assert_same_records(got, want, steps=8 if case == "float_sums"
                             else 0)
        if fmask is not None:
            assert fmask[want[:, 1].astype(int)].all()


@pytest.mark.parametrize("default_bin", [0, 1])
def test_one_slot_members_are_bit_for_bit(default_bin):
    """A full bundle — 255 indicator members in a 256-bin column — beside
    a second one, float gradients: a one-slot member's left sums are
    `totals - slot` (default 0) or the slot (default 1) in both searches,
    so the whole record agrees to the last bit, no tolerance."""
    rng = np.random.default_rng(5 + default_bin)
    B = 256
    ids = rng.permutation(300)
    cols = [[(int(k), 2, default_bin) for k in ids[:255]],
            [(int(k), 2, default_bin) for k in ids[255:]]]
    plan, nb, cat = _plan_of(cols, 300)
    assert plan.col_num_bins.tolist() == [256, 46]
    hists, sums = _store_hists(plan, 8, B, 4000, 8, rng, dyadic=False)
    got, want = _both_searches(plan, nb, cat, hists, sums, B, 8,
                               **SEARCH_KW)
    _assert_same_records(got, want, steps=0)


@pytest.mark.parametrize("first", ["larger_id_first", "smaller_id_first"])
def test_a_tie_goes_to_the_smaller_feature_then_threshold(first):
    """Two members of one column hold the same slots — an exact tie on
    every threshold — and the column stores the larger feature id first:
    the flat argmax over [F, B] takes the smaller id and its smallest
    threshold, and so must the search whose cells are in store order."""
    a, b = (7, 2) if first == "larger_id_first" else (2, 7)
    cols = [[(a, 4, 0), (b, 4, 0)], (0, 6, False)]
    plan, nb, cat = _plan_of(cols, 8)
    nb[[1, 3, 4, 5, 6]] = 1            # features no column holds: trivial
    B = 32
    h = np.zeros((1, 2, 3, B), np.float32)
    # the two members' three slots, twice the same; slots 1 and 2 tie too
    slots = np.array([[4.0, 4.0, -1.0], [2.0, 2.0, 2.0], [8.0, 8.0, 8.0]])
    h[0, 0, :, 1:4] = h[0, 0, :, 4:7] = slots
    sums = np.array([[0.0, 16.0, 64.0]], np.float32)
    h[0, 0, :, 0] = sums[0] - 2 * slots.sum(axis=1)
    h[0, 1, :, :6] = (sums[0] / 6)[:, None]     # a flat column: no gain
    got, want = _both_searches(plan, nb, cat, h, sums, B, 2, **SEARCH_KW)
    _assert_same_records(got, want, steps=0)
    assert got[0, 1] == 2 and got[0, 0] > 0


@pytest.mark.parametrize("shards", [2, 4])
def test_a_column_slice_searches_its_rows_of_the_tables(shards):
    """sharded_slice_search over each shard's store-column slice, with
    that slice's rows of the tables: the records, combined as
    combine_sharded_records does (largest gain, then smallest feature),
    are the one-device search's."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import (sharded_slice_search,
                                        store_search_operands)
    rng = np.random.default_rng(40 + shards)
    B = 64
    plan, nb, cat = _random_plan(rng, B)
    Cpad = shards * -(-plan.num_columns // shards)
    fmask = np.ones(len(nb), bool)
    fmask[rng.integers(len(nb))] = False
    hists, sums = _store_hists(plan, Cpad, B, 2000, 6, rng, dyadic=True)
    got, want = _both_searches(plan, nb, cat, hists, sums, B, Cpad, fmask,
                               **SEARCH_KW)
    _assert_same_records(got, want, steps=0)
    cells = plan.search_tables(nb, cat, B, Cpad)
    Cs = Cpad // shards

    def shard(off):
        rows = store_search_operands(cells, fmask, jnp.int32(off), Cs)

        def one(h, s):
            return sharded_slice_search(
                h, s, off=off, nb_s=None, ic_s=None, fm_s=None,
                num_bins=None, is_cat=None, fmask=None, unb=rows,
                skw=SEARCH_KW)
        return np.asarray(jax.vmap(one)(
            jnp.asarray(hists[:, off:off + Cs]), jnp.asarray(sums)))
    recs = np.stack([shard(i * Cs) for i in range(shards)])   # [nd, K, 11]
    gains = recs[..., 0]
    cand = np.where(gains == gains.max(axis=0), recs[..., 1], np.inf)
    best = cand.argmin(axis=0)
    combined = recs[best, np.arange(recs.shape[1])]
    np.testing.assert_array_equal(combined, got)


def test_member_sums_keep_float32():
    """What the search adds up, alone, at sizes a 12M-row leaf has: a sum
    of one cell (a one-slot member, a cell that is no candidate) is the
    cell to the last bit, a longer one is within float32 rounding of the
    float64 sum of its cells — a contraction at the matrix unit's default
    precision would keep 8 bits of either (it did, on the chip: PERF.md
    section 6, PR 40)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import _member_sums, store_search_operands
    rng = np.random.default_rng(12)
    B = 64
    plan, nb, cat = _random_plan(rng, B)
    Cpad = plan.num_columns + 1
    hists, _ = _store_hists(plan, Cpad, B, 3000, 4, rng, dyadic=False)
    hists = hists * np.float32(4099.0)
    cells = plan.search_tables(nb, cat, B, Cpad)
    member = store_search_operands(cells, np.ones(len(nb), bool)).member
    got = np.asarray(jax.vmap(lambda h: _member_sums(h, member))(
        jnp.asarray(hists)))
    m = np.asarray(member, np.float64)
    assert set(np.unique(m)) == {0.0, 1.0}
    want = np.einsum("kcjs,cst->kcjt", hists.astype(np.float64), m)
    room = np.einsum("kcjs,cst->kcjt", np.abs(hists).astype(np.float64), m)
    assert (np.abs(got - want) <= 8 * 2.0 ** -24 * room).all()
    alone = np.broadcast_to((cells.lo == cells.hi)[None, :, None, :],
                            got.shape)
    assert alone.sum() > got.size / 2
    np.testing.assert_array_equal(got[alone], hists[alone])


def test_search_tables_are_the_gather_tables_cell_by_cell():
    """BundlePlan.search_tables against unbundle_tables: a candidate cell
    (c, s) of feature k at threshold t is where src sends bin t of k (a
    prefix candidate) or bin t + 1 (a suffix one, the default bin lying
    at or under t); every threshold of every feature has exactly one
    cell; a cell's sum stays inside its member; the rest is no
    candidate."""
    rng = np.random.default_rng(9)
    B = 64
    for _ in range(5):
        plan, nb, cat = _random_plan(rng, B)
        Cpad = plan.num_columns + 2
        cells = plan.search_tables(nb, cat, B, Cpad)
        src, dmask = plan.unbundle_tables(nb, B, Cpad)
        seen = set()
        for c, s in zip(*np.nonzero(cells.feat >= 0)):
            k, t = int(cells.feat[c, s]), int(cells.thr[c, s])
            b = t + int(cells.suffix[c, s])
            assert src[k, b] == c * B + s and not dmask[k, b]
            assert (k, t) not in seen
            seen.add((k, t))
            d = int(plan.feat_default[k]) if plan.feat_packed[k] else -1
            assert cells.suffix[c, s] == (plan.feat_packed[k] and t >= d)
            member = [int(x) % B for x in src[k] if x // B == c]
            if cat[k]:
                assert cells.lo[c, s] == cells.hi[c, s] == s
            elif cells.suffix[c, s]:
                assert cells.lo[c, s] == s and cells.hi[c, s] == max(member)
            else:
                assert cells.hi[c, s] == s and cells.lo[c, s] == min(member)
        want = {(k, t) for k in range(len(nb))
                for t in range(nb[k] if cat[k] else nb[k] - 1)}
        assert seen == want
        rest = cells.feat < 0
        own = np.tile(np.arange(B), (Cpad, 1))
        assert (cells.lo[rest] == own[rest]).all()
        assert (cells.hi[rest] == own[rest]).all()
        assert rest[plan.num_columns:].all()


def _splits_of(tree):
    """A tree's splits whatever order its nodes were numbered in (the
    rounds schedule numbers them its own way)."""
    n = tree.num_leaves - 1
    return sorted(zip(tree.split_feature[:n].tolist(),
                      tree.threshold_in_bin[:n].tolist(),
                      tree.decision_type[:n].tolist()))


def test_a_packed_categorical_feature_keeps_the_gather():
    """One-vs-rest has a candidate on the default bin, which no slot
    holds: a plan that packs a categorical feature has no cell tables,
    the rounds learner unbundles for it as learner/serial.py does, counts
    the gather, and grows the serial learner's trees."""
    from lightgbm_tpu import profiling
    rng = np.random.RandomState(4)
    n = 1500
    Xs, _ = _one_hot_data(n=n, groups=6, card=5, seed=4)
    # three sparse categorical columns, mostly 0 (the default) and never
    # set in the same row, so the planner packs them into one column
    which = rng.randint(0, 6, n)
    cats = np.where(which[:, None] == np.arange(3), rng.randint(1, 5, (n, 3)),
                    0)
    X = np.concatenate([cats.astype(float), Xs], axis=1)
    y = ((cats[:, 0] == 2) | (Xs[:, 3] > 0) ^ (cats[:, 1] == 1)).astype(float)
    kw = dict(num_leaves=31, min_data_in_leaf=40, rounds=3,
              categorical_feature=[0, 1, 2])
    profiling.reset()
    a, dsa = _train(X, y, True, "rounds", **kw)
    got = profiling.counters("tree/")
    profiling.reset()
    b, _ = _train(X, y, True, "exact", **kw)
    inner = dsa._inner
    plan = inner.bundle_plan
    assert plan is not None
    assert (plan.feat_packed & inner.is_categorical).any()
    assert inner.search_tables(64) is None
    assert got[profiling.UNBUNDLE_GATHER_ELEMS] == (
        3 * got[profiling.SPLIT_CELLS]) > 0
    sa = [_splits_of(t) for t in a._gbdt.models]
    assert sa == [_splits_of(t) for t in b._gbdt.models]
    assert any(f < 3 for t in sa for f, _, _ in t)      # a packed one won
    np.testing.assert_allclose(a.predict(X), b.predict(X), atol=1e-5)


@pytest.mark.parametrize("packs", ["indicators", "a_categorical_feature"])
@pytest.mark.parametrize("learner", ["rounds", "fused"])
def test_a_bundled_store_over_a_mesh_grows_the_serial_learners_tree(
        learner, packs):
    """Both learners, both searches of a bundled store — its own cells
    (indicators only), the gather (a packed categorical feature) — on one
    device and under psum_scatter on the 8-device mesh, where a device
    holds a slice of the store's columns: the plain learner's tree, which
    searches best_split(unbundle_hist(...)).  Dyadic gradients, so every
    float32 sum is exact."""
    import jax.numpy as jnp
    from lightgbm_tpu.binning import StoreCells
    from lightgbm_tpu.learner.fused import FusedTreeLearner, make_mesh
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    rng = np.random.RandomState(31)
    n = 2000
    X, _ = _one_hot_data(n=n, groups=8, card=4, seed=21)
    cats = ()
    if packs == "a_categorical_feature":
        which = rng.randint(0, 5, n)
        c = np.where(which[:, None] == np.arange(2),
                     rng.randint(1, 5, (n, 2)), 0)
        X, cats = np.concatenate([c.astype(float), X], axis=1), (0, 1)
    y = (X @ rng.randn(X.shape[1]) > 0).astype(np.float64)
    g = jnp.asarray(rng.randint(-32, 33, n) / 32.0, jnp.float32)
    h = jnp.asarray(rng.randint(8, 33, n) / 32.0, jnp.float32)
    # min_data_in_leaf keeps the tree under the cap of 31 leaves, where
    # the rounds schedule grows the leaf-wise tree
    base = {"objective": "binary", "num_leaves": 31, "verbose": -1,
            "min_data_in_leaf": 90, "enable_bundle": True}
    cfg = config_from_params(base)
    ds = InnerDataset(X, y, cfg, categorical_feature=cats)
    assert ds.bundle_plan is not None
    in_store = ds.search_tables(64) is not None
    assert in_store == (packs == "indicators")
    want, _ = SerialTreeLearner(ds, cfg).train(g, h)
    assert 4 < want.num_leaves < 31
    make = RoundsTreeLearner if learner == "rounds" else FusedTreeLearner
    for mesh in (None, make_mesh("data")):
        cfg_m = config_from_params(dict(
            base, **({} if mesh is None else {"hist_exchange":
                                              "psum_scatter"})))
        lr = make(ds, cfg_m, mesh)
        assert mesh is None or lr.hist_exchange == "psum_scatter"
        got, _ = lr.train(g, h)
        assert _splits_of(got) == _splits_of(want), mesh
