"""Missing values as the reference handles them (use_missing, default
true): a numerical column that holds NaN gets a NaN bin of its own, split
search tries that bin on both sides and the split records the side
(decision_type's default_left bit), and every predictor routes a raw NaN
by one rule — a split of missing type NaN to its default side, one of
type None as 0.0, a categorical one right.  Checked at a small size
against plain references: a numpy brute-force split finder with both NaN
sides (`brute_force_split`) and the benchmark's walk_missing."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark.harness import walk_missing
from lightgbm_tpu.binning import MISSING_NAN, MISSING_NONE
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Dataset

pytestmark = pytest.mark.quick


def _data(seed=0, n=3000, f=5, nan_share=0.3, left=True):
    """Rows with NaN in columns 0, 1 and 3, and a label that ties NaN in
    column 0 to low values (left=True) or to high ones."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f))
    for j in (0, 1, 3):
        X[rng.random(n) < nan_share, j] = np.nan
    x0 = np.where(np.isnan(X[:, 0]), -3.0 if left else 3.0, X[:, 0])
    y = (x0 + 0.5 * np.nan_to_num(X[:, 2]) + 0.3 * rng.standard_normal(n)
         > 0.2).astype(np.float64)
    return X, y


def brute_force_split(hist, split_bins, is_cat, nan_bins, min_data,
                      min_hess):
    """(feature, threshold bin, default_left, gain) of the best split of
    a [F, 3, B] float64 histogram, by the textbook gain over every
    candidate: a numerical feature's `bin <= t` for t below its last
    bin, its NaN bin (where it has one, the last) on the right, and
    again for t below its last value bin with the NaN bin on the left;
    a categorical feature's `bin == t`.  A threshold keeps its better
    side (the right on a tie), the first maximum in (feature, threshold)
    order wins."""
    G, H, C = (float(hist[0, k].sum()) for k in range(3))
    parent = G * G / H
    best = (-1, -1, False, -np.inf)
    for f in range(hist.shape[0]):
        nb = int(split_bins[f])
        for t in range(nb if is_cat[f] else nb - 1):
            sides = [False]
            if nan_bins[f] >= 0 and not is_cat[f] and t < nb - 2:
                sides.append(True)
            here = None
            for left_nan in sides:
                sel = (np.arange(hist.shape[2]) == t if is_cat[f]
                       else np.arange(hist.shape[2]) <= t)
                if left_nan:
                    sel = sel | (np.arange(hist.shape[2]) == nan_bins[f])
                gl, hl, cl = (float(hist[f, k, sel].sum()) for k in range(3))
                gr, hr, cr = G - gl, H - hl, C - cl
                if min(cl, cr) < min_data or min(hl, hr) < min_hess:
                    continue
                gain = gl * gl / hl + gr * gr / hr - parent
                if here is None or gain > here[3]:
                    here = (f, t, left_nan, gain)
            if here is not None and here[3] > best[3]:
                best = here
    return best


def _root_hist(ds, grad, hess, B):
    store = ds.bins.astype(np.int64)
    out = np.zeros((store.shape[0], 3, B))
    for f in range(store.shape[0]):
        out[f, 0] = np.bincount(store[f], grad, B)
        out[f, 1] = np.bincount(store[f], hess, B)
        out[f, 2] = np.bincount(store[f], minlength=B)
    return out


# -- binning ---------------------------------------------------------------


def test_nan_gets_the_last_bin_and_the_binary_file_keeps_it(tmp_path):
    X, y = _data()
    ds = Dataset(X, y, config=Config(min_data_in_leaf=5))
    for j in range(X.shape[1]):
        m = ds.mappers[j]
        if j in (0, 1, 3):
            assert m.missing_type == MISSING_NAN
            assert m.nan_bin == m.num_bin - 1 == len(m.bin_upper_bound)
            col = ds.bins[ds.real_to_inner(j)]
            assert np.array_equal(col == m.nan_bin, np.isnan(X[:, j]))
        else:
            assert m.missing_type == MISSING_NONE and m.nan_bin == -1
    assert np.array_equal(ds.missing_bins,
                          [ds.mappers[i].nan_bin for i in ds.used_features])
    path = str(tmp_path / "d.bin")
    ds.save_binary(path)
    back = Dataset.from_binary(path, Config(min_data_in_leaf=5))
    assert [m.nan_bin for m in back.mappers] == [m.nan_bin
                                                for m in ds.mappers]
    assert np.array_equal(back.bins, ds.bins)
    with pytest.raises(ValueError, match="use_missing"):
        Dataset.from_binary(path, Config(use_missing=False))


def test_use_missing_false_bins_nan_as_zero_bit_for_bit():
    """use_missing=false: the binning of before (NaN as 0.0 everywhere),
    which is the binning of the rows with NaN replaced by 0.0."""
    X, y = _data(1)
    off = Dataset(X, y, config=Config(use_missing=False))
    zero = Dataset(np.nan_to_num(X, nan=0.0), y, config=Config())
    assert off.missing_bins is None
    assert np.array_equal(off.bins, zero.bins)
    for a, b in zip(off.mappers, zero.mappers):
        assert np.array_equal(a.bin_upper_bound, b.bin_upper_bound)


def test_a_column_with_a_nan_bin_is_never_bundled():
    rng = np.random.default_rng(4)
    n = 4000
    X = np.zeros((n, 6))
    code = rng.integers(0, 6, n)
    X[np.arange(n), code] = 1.0               # six exclusive one-hot columns
    X[rng.random(n) < 0.2, 2] = np.nan
    ds = Dataset(X, (code > 2).astype(float), config=Config())
    plan = ds.bundle_plan
    assert plan is not None
    k = ds.real_to_inner(2)
    assert not plan.feat_packed[k] and plan.num_packed >= 2
    # searched in the store's own cells, the column's NaN bin named
    cells = ds.search_tables(256)
    col = plan.feat_col[k]
    assert cells.nan[col] == ds.missing_bins[k] == ds.mappers[2].nan_bin
    assert (np.delete(cells.nan, col) == -1).all()


# -- split search ----------------------------------------------------------


def _learners(ds, cfg):
    from lightgbm_tpu.learner.fused import FusedTreeLearner
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    return {"serial": SerialTreeLearner(ds, cfg),
            "fused": FusedTreeLearner(ds, cfg, None),
            "rounds": RoundsTreeLearner(ds, cfg)}


@pytest.mark.parametrize("left", [True, False])
def test_every_learner_finds_the_brute_force_split(left):
    import jax.numpy as jnp
    X, y = _data(2, left=left)
    cfg = Config(num_leaves=2, min_data_in_leaf=5,
                 min_sum_hessian_in_leaf=1e-3)
    ds = Dataset(X, y, config=cfg)
    rng = np.random.default_rng(3)
    grad = (rng.random(len(y)) - y).astype(np.float32)
    hess = (0.1 + rng.random(len(y))).astype(np.float32)
    B = 256
    want = brute_force_split(
        _root_hist(ds, grad.astype(np.float64), hess.astype(np.float64), B),
        ds.split_num_bins, ds.is_categorical, ds.missing_bins, 5, 1e-3)
    assert want[0] == ds.real_to_inner(0) and want[2] == left
    for name, lr in _learners(ds, cfg).items():
        tree, _ = lr.train(jnp.asarray(grad), jnp.asarray(hess))
        dt = int(tree.decision_type[0])
        got = (int(tree.split_feature_inner[0]),
               int(tree.threshold_in_bin[0]), bool(dt & 2))
        assert got == want[:3], name
        assert (dt >> 2) & 3 == MISSING_NAN, name
        assert tree.split_gain[0] == pytest.approx(want[3], rel=1e-4), name


@pytest.mark.parametrize("left", [True, False])
def test_a_bundled_store_searches_the_nan_bin_in_its_own_cells(left):
    """Six exclusive one-hot columns, bundled, beside a numerical column
    with NaN: the rounds and fused learners search the store's own cells
    (the NaN bin's two sides included) and find the brute-force split,
    as the serial learner's gather does."""
    import jax.numpy as jnp
    rng = np.random.default_rng(6)
    n = 4000
    X = np.zeros((n, 7))
    code = rng.integers(0, 6, n)
    X[np.arange(n), code] = 1.0
    X[:, 6] = rng.standard_normal(n)
    nan = rng.random(n) < 0.3
    X[nan, 6] = np.nan
    x = np.where(nan, -3.0 if left else 3.0, X[:, 6])
    y = (x + 0.3 * (code > 2) + 0.3 * rng.standard_normal(n) > 0.2
         ).astype(np.float64)
    cfg = Config(num_leaves=2, min_data_in_leaf=5,
                 min_sum_hessian_in_leaf=1e-3)
    ds = Dataset(X, y, config=cfg)
    assert ds.bundle_plan is not None and ds.bundle_plan.num_packed >= 2
    assert ds.search_tables(256) is not None
    grad = (rng.random(n) - y).astype(np.float32)
    hess = (0.1 + rng.random(n)).astype(np.float32)
    per_feature = ds.unbundled_bins().astype(np.int64)
    hist = np.zeros((per_feature.shape[0], 3, 256))
    for f in range(per_feature.shape[0]):
        hist[f, 0] = np.bincount(per_feature[f], grad.astype(np.float64), 256)
        hist[f, 1] = np.bincount(per_feature[f], hess.astype(np.float64), 256)
        hist[f, 2] = np.bincount(per_feature[f], minlength=256)
    want = brute_force_split(hist, ds.split_num_bins, ds.is_categorical,
                             ds.missing_bins, 5, 1e-3)
    assert want[0] == ds.real_to_inner(6) and want[2] == left
    for name, lr in _learners(ds, cfg).items():
        tree, _ = lr.train(jnp.asarray(grad), jnp.asarray(hess))
        dt = int(tree.decision_type[0])
        got = (int(tree.split_feature_inner[0]),
               int(tree.threshold_in_bin[0]), bool(dt & 2))
        assert got == want[:3], name
        assert tree.split_gain[0] == pytest.approx(want[3], rel=1e-4), name


def test_split_search_on_its_own_against_brute_force():
    """ops/split.best_split on random histograms with NaN bins: every
    feature, threshold and side, gain included."""
    from lightgbm_tpu.ops.split import best_split
    rng = np.random.default_rng(5)
    for trial in range(20):
        F, B = 6, 16
        nb = rng.integers(3, B + 1, F)
        nan_bins = np.where(rng.random(F) < 0.6, nb - 1, -1)
        is_cat = (rng.random(F) < 0.2) & (nan_bins < 0)
        hist = np.zeros((F, 3, B))
        for f in range(F):
            c = rng.integers(0, 40, nb[f]).astype(float)
            hist[f, 2, :nb[f]] = c
            hist[f, 1, :nb[f]] = c * 0.25
            hist[f, 0, :nb[f]] = c * rng.normal(0, 0.5, nb[f])
        # every feature sees the same rows: rescale to the same totals
        tot = hist[0, :, :].sum(axis=1)
        for f in range(1, F):
            s = hist[f].sum(axis=1)
            hist[f] *= (tot / np.where(s == 0, 1, s))[:, None]
        want = brute_force_split(hist, nb, is_cat, nan_bins, 1, 1e-3)
        rec = best_split(jnp_f32(hist), jnp_i32(nb), jnp_b(is_cat),
                         jnp_b(np.ones(F, bool)), *map(float, tot),
                         jnp_i32(nan_bins), min_data_in_leaf=1,
                         min_sum_hessian_in_leaf=1e-3)
        got = (int(rec.feature), int(rec.threshold_bin),
               bool(rec.default_left))
        assert got == want[:3], trial
        assert float(rec.gain) == pytest.approx(want[3], rel=1e-4)


def jnp_f32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.float32)


def jnp_i32(a):
    import jax.numpy as jnp
    return jnp.asarray(a, jnp.int32)


def jnp_b(a):
    import jax.numpy as jnp
    return jnp.asarray(a, bool)


# -- training and the model text -------------------------------------------


@pytest.mark.parametrize("growth", ["exact", "rounds"])
def test_use_missing_false_trains_todays_trees(growth):
    """use_missing=false: the trees of before, bit for bit — those of the
    rows with NaN replaced by 0.0 (a store without NaN), and no split of
    missing type NaN."""
    X, y = _data(6)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tree_growth": growth}
    off = lgb.train(dict(p, use_missing=False), lgb.Dataset(X, y), 4)
    zero = lgb.train(p, lgb.Dataset(np.nan_to_num(X, nan=0.0), y), 4)
    a, b = off.model_to_string(), zero.model_to_string()
    strip = [ln for ln in a.splitlines() if "use_missing" not in ln]
    assert strip == [ln for ln in b.splitlines() if "use_missing" not in ln]
    for t in off._gbdt.models:
        assert not ((t.decision_type[:t.num_leaves - 1] >> 2) & 3).any()


@pytest.mark.parametrize("growth", ["exact", "rounds"])
def test_model_text_round_trips_the_decision_bits(growth):
    X, y = _data(7, left=False)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "min_data_in_leaf": 5, "verbose": -1,
                     "tree_growth": growth}, lgb.Dataset(X, y), 4)
    text = bst.model_to_string()
    dts = {int(v) for ln in text.splitlines()
           if ln.startswith("decision_type=") for v in ln[14:].split()}
    assert dts & {8, 10} and dts <= {0, 8, 10}
    back = lgb.Booster(model_str=text)
    assert back.model_to_string() == text
    Xq, _ = _data(8)
    raw = bst.predict(Xq, raw_score=True)
    assert np.array_equal(back.predict(Xq, raw_score=True), raw)
    ref = walk_missing.raw_margins(walk_missing.parse_model(text), Xq)
    assert np.abs(raw - ref).max() < 1e-6


# -- predictors --------------------------------------------------------------


def _model_and_rows(seed=9, rounds=5, **params):
    X, y = _data(seed)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tree_growth": "rounds"}
    ds = lgb.Dataset(X, y)
    bst = lgb.train(dict(p, **params), ds, rounds)
    Xq, _ = _data(seed + 100)
    Xq[::7, 2] = np.nan          # a column with no NaN bin: NaN as 0.0
    text = bst.model_to_string()
    ref = walk_missing.raw_margins(walk_missing.parse_model(text), Xq)
    return bst, ds, Xq, ref


@pytest.mark.parametrize("use_missing", [True, False])
def test_every_device_predictor_matches_walk_missing(use_missing):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import predict as P
    bst, _ds, Xq, ref = _model_and_rows(use_missing=use_missing)
    trees = bst._gbdt.models
    Xj = jnp.asarray(Xq, jnp.float32)
    got = {}
    stack = jax.device_put(P.stack_trees(trees, binned=False))
    got["walk"] = P.predict_trees(stack, Xj, depth=max(
        t.max_depth_grown for t in trees))
    for layout in ("soa", "perfect"):
        st, meta = P.build_ensemble([trees], layout=layout)
        got[layout] = P.predict_ensemble_any(jax.device_put(st), Xj,
                                             meta=meta)[0]
    # a raw stack of shallow trees takes the perfect layout, NaN sides
    # and all (a binned one of missing type NaN has no room for them)
    assert isinstance(P.build_ensemble([trees])[0], P.PerfectEnsemble)
    if use_missing:
        assert isinstance(P.build_ensemble([trees], binned=True)[0],
                          P.EnsembleStack)
        with pytest.raises(ValueError, match="perfect"):
            P.build_ensemble([trees], binned=True, layout="perfect")
    gst, gmeta = P.stack_ensemble_group([[trees], [trees[:2]]])
    gst = jax.device_put(gst)
    tids = jnp.zeros(len(Xq), jnp.int32)
    got["grouped"] = P.predict_ensemble_grouped(gst, Xj, tids,
                                                meta=gmeta)[0]
    got["segment"] = P.predict_ensemble_grouped_segment(gst, Xj, tids,
                                                        meta=gmeta)[0]
    got["booster"] = bst.predict(Xq, raw_score=True)
    got["host"] = sum(t.predict_raw(Xq) for t in trees)
    for name, v in got.items():
        assert np.abs(np.asarray(v, np.float64) - ref).max() < 1e-5, name


def test_binned_serving_routes_as_the_raw_kernel():
    from lightgbm_tpu.serving import PredictorRuntime
    bst, ds, Xq, ref = _model_and_rows()
    inner = ds.construct()._inner
    assert inner.missing_bins is not None
    raw = PredictorRuntime(bst, replicas=1)
    binned = PredictorRuntime(bst, replicas=1, quantize="binned",
                              refbin=inner)
    assert binned.variant == "binned"
    a = raw.predict(Xq, kind="raw")
    b = binned.predict(Xq, kind="raw")
    assert np.array_equal(a, b)
    assert np.abs(np.asarray(b, np.float64) - ref).max() < 1e-5


def test_valid_set_scores_route_nan_bins_by_the_default_side():
    """The boosting loop's valid-set scores (the binned walks over the
    valid store: the device TreeArrays on the pipelined path, the host
    tree's otherwise) equal the raw predictor on the same rows."""
    X, y = _data(10)
    Xv, yv = _data(11)
    for growth in ("rounds", "exact"):
        train = lgb.Dataset(X, y)
        valid = lgb.Dataset(Xv, yv, reference=train)
        ev = {}
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "min_data_in_leaf": 5, "verbose": -1,
                         "tree_growth": growth, "metric": "binary_logloss"},
                        train, 4, valid_sets=[valid], valid_names=["v"],
                        callbacks=[lgb.record_evaluation(ev)])
        p = 1.0 / (1.0 + np.exp(-bst.predict(Xv, raw_score=True)))
        want = -np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p))
        assert ev["v"]["binary_logloss"][-1] == pytest.approx(want,
                                                              rel=1e-5)


def test_pmml_names_the_nan_side_as_the_default_child():
    from lightgbm_tpu.pmml import model_to_pmml
    bst, _ds, _Xq, _ref = _model_and_rows(rounds=2)
    doc = model_to_pmml(bst)
    assert 'missingValueStrategy="defaultChild"' in doc
    root = bst._gbdt.models[0]
    tree0 = bst._gbdt.to_json()["tree_info"][0]["tree_structure"]
    side = ("left_child" if walk_missing.go_left(
        np.array([int(root.decision_type[0])]), np.array([root.threshold[0]]),
        np.array([np.nan]))[0] else "right_child")
    child = tree0[side]
    cid = (f'leaf{child["leaf_index"]}' if "leaf_index" in child
           else f'split{child["split_index"]}')
    assert f'<Node id="split0" ' in doc
    line = next(ln for ln in doc.splitlines() if 'id="split0"' in ln)
    assert f'defaultChild="{cid}"' in line


@pytest.mark.parametrize("growth,learner", [
    ("rounds", "data"), ("rounds", "data2d"), ("exact", "data"),
    ("exact", "feature"), ("exact", "voting")])
def test_mesh_learners_grow_the_single_device_trees(growth, learner):
    """The sharded searches (slices under psum_scatter, feature shards,
    the voted subset) carry the NaN side like the single-device one:
    the same model up to float32 summation order."""
    X, y = _data(12)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tree_growth": growth, "top_k": 8,
         "num_machines": 4}
    one = lgb.train(p, lgb.Dataset(X, y), 3)
    mesh = lgb.train(dict(p, tree_learner=learner,
                          hist_exchange="psum_scatter"), lgb.Dataset(X, y), 3)
    assert type(mesh._gbdt.learner).__name__ != "SerialTreeLearner"
    a = one.predict(X, raw_score=True)
    b = mesh.predict(X, raw_score=True)
    # histograms summed over other shards in float32 move leaf values by
    # ~1e-5; a row sent to another leaf moves by a leaf value, 1e-2 up
    assert np.abs(a - b).max() < 1e-4
    dts = {int(v) for ln in mesh.model_to_string().splitlines()
           if ln.startswith("decision_type=") for v in ln[14:].split()}
    assert dts & {8, 10}


@pytest.mark.parametrize("growth,learner", [
    ("rounds", "data"), ("rounds", "data2d"), ("exact", "data")])
def test_mesh_learners_search_a_bundled_store_with_nan_bins(growth,
                                                            learner):
    """One-hot columns bundled beside columns with NaN: each shard
    searches its slice of the store's own cells, the NaN bins of its
    columns with them (StoreCells.nan), and grows the single-device
    model up to float32 summation order."""
    rng = np.random.default_rng(13)
    n = 4000
    X = np.zeros((n, 10))
    code = rng.integers(0, 6, n)
    X[np.arange(n), code] = 1.0
    X[:, 6:] = rng.standard_normal((n, 4))
    X[:, 8] = rng.integers(0, 20, n)        # NaN bins 255 and 20
    for j in (6, 8):
        X[rng.random(n) < 0.3, j] = np.nan
    # NaN ranks below every value in both columns: their splits send
    # it left, each column from a shard of its own
    x6 = np.where(np.isnan(X[:, 6]), -3.0, X[:, 6])
    x8 = np.where(np.isnan(X[:, 8]), -15.0, X[:, 8]) / 10.0
    y = (x6 + x8 + 0.4 * (code > 2) + 0.3 * rng.standard_normal(n) > 1.2
         ).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "tree_growth": growth, "num_machines": 4}
    one = lgb.train(p, lgb.Dataset(X, y), 3)
    assert one._gbdt.train_set.bundle_plan is not None
    assert one._gbdt.train_set.search_tables(256) is not None
    mesh = lgb.train(dict(p, tree_learner=learner,
                          hist_exchange="psum_scatter"), lgb.Dataset(X, y), 3)
    assert type(mesh._gbdt.learner).__name__ != "SerialTreeLearner"
    a = one.predict(X, raw_score=True)
    b = mesh.predict(X, raw_score=True)
    assert np.abs(a - b).max() < 1e-4
    dts = {int(v) for ln in mesh.model_to_string().splitlines()
           if ln.startswith("decision_type=") for v in ln[14:].split()}
    assert 10 in dts                   # a split sending NaN left
