"""The airline deployment (`airline`, the benchmark's fifth configuration:
six raw categorical columns, a store the program keeps int8) at sizes the
CPU takes.

(a) `categorical_feature` in params, by every alias and by `name:`, marks
the columns the constructor's argument marks; (b) a category the binning
drops shares one bin that no split takes, so the training score and
`Booster.predict` route its rows alike; (c) the rounds learner grows the
serial learner's tree on categorical and numerical columns, and (d) the
int8-stored layout grows the int32 layout's, under the Pallas interpreter;
(e) `tree/categorical_splits` counts the trees' categorical splits on the
host; (f) the generator's contract; (g) the launch counters of an int8
store; (h) the plain reference of a node's split.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import lightgbm_tpu as lgb                                    # noqa: E402
from lightgbm_tpu import profiling                            # noqa: E402
from lightgbm_tpu.binning import CATEGORICAL, find_bin        # noqa: E402
from lightgbm_tpu.config import config_from_params            # noqa: E402
from lightgbm_tpu.dataset import Dataset as RawDataset        # noqa: E402
from lightgbm_tpu.learner.rounds import RoundsTreeLearner     # noqa: E402
from lightgbm_tpu.learner.serial import SerialTreeLearner     # noqa: E402

from test_store_layout import pallas_interpreted  # noqa: E402,F401

NAMES = ["a", "b", "c", "d"]


def codes(n=600, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randint(0, 5, (n, 4)).astype(np.float64)
    return X, (X[:, 0] > 2).astype(np.float64)


# ---- (a) categorical columns from params ------------------------------------

@pytest.mark.parametrize("how", [
    ("constructor", [0, 2]), ("constructor", ["a", "c"]),
    ("categorical_feature", "0,2"), ("categorical_column", "0 2"),
    ("cat_feature", [0, 2]), ("cat_column", "name:a,c"),
    ("categorical_feature", "2"), ("none", None)],
    ids=["constructor", "constructor_names", "params", "canonical",
         "alias_list", "alias_names", "params_adds_to_constructor",
         "neither"])
def test_categorical_columns_from_params_or_constructor(how):
    X, y = codes()
    key, value = how
    ctor = value if key == "constructor" else (
        [0] if key == "categorical_feature" and value == "2" else "auto")
    params = {"verbose": -1}
    if key not in ("constructor", "none"):
        params[key] = value
    ds = lgb.Dataset(X, y, feature_name=NAMES, categorical_feature=ctor)
    ds.construct(params)
    kinds = [m.bin_type == CATEGORICAL for m in ds._inner.mappers]
    assert kinds == ([False] * 4 if key == "none"
                     else [True, False, True, False])


def test_lgb_train_takes_the_categorical_columns_from_params():
    X, y = codes(2000, seed=1)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 7,
              "min_data_in_leaf": 5, "categorical_feature": "0"}
    bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=2)
    assert "decision_type=1" in bst.model_to_string()


# ---- (b) one routing for every category --------------------------------------

def tail_problem(n=12_000, levels=300, seed=0):
    """A code column of `levels` categories of which the binning keeps
    255, the most frequent one the one that moves the label."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, levels + 1) ** 1.3
    c = rng.choice(levels, n, p=p / p.sum()).astype(np.float64)
    x = rng.standard_normal(n)
    y = ((c == 0) * 1.5 + 0.5 * x + rng.standard_normal(n) > 0.5)
    return np.stack([c, x], axis=1), y.astype(np.float64)


def test_dropped_categories_share_a_bin_no_split_takes():
    X, _ = tail_problem()
    m = find_bin(X[:, 0][X[:, 0] != 0], len(X), 255, bin_type=CATEGORICAL)
    assert m.other_bin and m.num_bin == 256 and len(m.bin_2_categorical) == 255
    assert m.split_num_bin == 255
    dropped = ~np.isin(X[:, 0], m.bin_2_categorical)
    assert dropped.any()
    assert (m.value_to_bin(X[dropped, 0]) == 255).all()
    kept = m.value_to_bin(np.asarray(m.bin_2_categorical, np.float64))
    np.testing.assert_array_equal(kept, np.arange(255))
    # a column whose sample holds every category has no such bin
    full = find_bin(np.arange(1, 30, dtype=np.float64).repeat(40), 1200, 255,
                    bin_type=CATEGORICAL)
    assert not full.other_bin and full.split_num_bin == full.num_bin


@pytest.mark.parametrize("growth", ["exact", "rounds"])
def test_train_score_routes_dropped_categories_as_predict(growth):
    """On a split on the most frequent category's bin, rows of a dropped
    category went left in training (they shared bin 0) and right in every
    predictor (the model text names one category)."""
    X, y = tail_problem()
    ds = lgb.Dataset(X, y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbose": -1,
                     "tree_growth": growth, "min_data_in_leaf": 1,
                     "learning_rate": 0.3}, ds, num_boost_round=5)
    m = ds._inner.mappers[0]
    dropped = ~np.isin(X[:, 0], m.bin_2_categorical)
    assert m.other_bin and dropped.sum() > 50
    assert "decision_type=1" in bst.model_to_string()
    score = np.asarray(bst._gbdt.train_score.score).reshape(-1)[:len(X)]
    pred = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(score[dropped], pred[dropped], atol=1e-6)
    np.testing.assert_allclose(score, pred, atol=1e-6)


def test_binary_file_keeps_the_bin_of_dropped_categories(tmp_path):
    X, y = tail_problem()
    ds = lgb.Dataset(X, y, categorical_feature=[0]).construct(
        {"verbose": -1})
    path = str(tmp_path / "tail.bin")
    ds.save_binary(path)
    back = lgb.Dataset(path).construct({"verbose": -1})._inner
    m, b = ds._inner.mappers[0], back.mappers[0]
    assert b.other_bin and (b.num_bin, b.bin_2_categorical) == (
        m.num_bin, m.bin_2_categorical)
    np.testing.assert_array_equal(back.split_num_bins,
                                  ds._inner.split_num_bins)
    np.testing.assert_array_equal(back.bins, ds._inner.bins)


# ---- (c) rounds against serial, (d) int8-stored against int32 ---------------

def mixed_problem(n, seed, num_leaves=13):
    """Airline-like columns: three code columns (one with a tail the
    binning drops) among numerical ones; +-1 gradients and constant
    hessians make every histogram sum exact."""
    from benchmark.generators import airline
    X, y = airline.make(n, 13, (seed, 0))
    cfg = config_from_params({
        "objective": "binary", "num_leaves": num_leaves,
        "min_data_in_leaf": n // 40,
        "max_bin": 255, "histogram_dtype": "int8", "verbose": -1})
    ds = RawDataset(X, y, config=cfg, categorical_feature=airline.CATEGORICAL)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(n, 0.5, np.float32))
    return ds, cfg, g, h


def _splits(t):
    k = t.num_leaves - 1
    return sorted(zip(t.split_feature_inner[:k], t.threshold_in_bin[:k],
                      t.decision_type[:k]))


def test_rounds_grows_the_serial_tree_on_categorical_columns():
    ds, cfg, g, h = mixed_problem(6000, seed=3, num_leaves=63)
    assert ds.is_categorical.sum() == 6
    ts, _ = SerialTreeLearner(ds, cfg).train(g, h)
    tr, lid = RoundsTreeLearner(ds, cfg, None).train(g, h)
    # min_data_in_leaf stops the tree before the cap binds: the two
    # schedules grow one tree
    assert 13 < tr.num_leaves == ts.num_leaves < 63
    assert _splits(tr) == _splits(ts)
    assert tr.has_categorical and any(d == 1 for *_, d in _splits(tr))
    counts = np.bincount(np.asarray(lid)[:ds.num_data],
                         minlength=tr.num_leaves)
    np.testing.assert_array_equal(counts, tr.leaf_count[:tr.num_leaves])


def test_int8_stored_layout_grows_the_int32_tree(pallas_interpreted,
                                                 monkeypatch):
    ds, cfg, g, h = mixed_problem(3000, seed=5)
    got = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("LGBT_BINS_INT8", flag)
        lr = RoundsTreeLearner(ds, cfg, None)
        _, lid, arrs = lr.train_device(g, h)
        got[flag] = (lr.bins_dev.dtype, lr.bins_dev.shape,
                     jax.device_get(arrs), np.asarray(lid)[:ds.num_data])
    (d8, s8, a8, l8), (d32, s32, a32, l32) = got["1"], got["0"]
    assert (d8, s8[0], d32, s32[0]) == (jnp.int8, 32, jnp.int32, 16)
    assert int(a8.num_leaves) == int(a32.num_leaves) > 5
    assert np.asarray(a8.is_cat).any()
    for name in a8._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a8, name)),
                                      np.asarray(getattr(a32, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(l8, l32)


# ---- (e) the counter --------------------------------------------------------

@pytest.mark.parametrize("cats", [[0], []], ids=["categorical", "dense"])
def test_categorical_splits_are_counted_on_the_host(cats):
    X, y = tail_problem(6000)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 15,
                       "verbose": -1, "tree_growth": "rounds",
                       "min_data_in_leaf": 1},
                      lgb.Dataset(X, y, categorical_feature=cats or "auto"))
    before = profiling.counter_value(profiling.CATEGORICAL_SPLITS)
    for _ in range(3):
        bst.update()
    bst._gbdt._flush_pending()
    moved = profiling.counter_value(profiling.CATEGORICAL_SPLITS) - before
    want = sum(int(np.count_nonzero(
        t.decision_type[:t.num_leaves - 1] == 1)) for t in bst._gbdt.models)
    assert moved == want
    assert (want > 0) == bool(cats)
    assert profiling.CATEGORICAL_SPLITS in profiling.counters("tree/")


# ---- (f) the generator -------------------------------------------------------

def test_generator_keeps_each_code_column_within_the_int8_layout():
    """At the binning's 200,000-row sample every categorical column keeps
    at most 255 categories, and Origin and Dest drop a tail into one more
    bin: 256 bins, which the int8-stored layout holds."""
    from benchmark.generators import airline
    X, y = airline.make(200_000, 13, (0, 0))
    assert X.dtype == np.float32 and X.shape == (200_000, 13)
    assert 0.42 < y.mean() < 0.48
    ds = lgb.Dataset(X, y).construct(
        {"categorical_feature": ",".join(map(str, airline.CATEGORICAL)),
         "max_bin": 255, "verbose": -1})._inner
    for j in airline.CATEGORICAL:
        m = ds.mappers[j]
        assert m.bin_type == CATEGORICAL and len(m.bin_2_categorical) <= 255
        assert (X[:, j] >= 0).all() and (X[:, j] == np.round(X[:, j])).all()
        assert m.other_bin == (j in (9, 10))
    assert ds.max_num_bin == 256
    # the same seed draws the same rows, another seed other ones
    X2, _ = airline.make(1000, 13, (0, 0))
    X3, _ = airline.make(1000, 13, (1, 0))
    np.testing.assert_array_equal(X2, airline.make(1000, 13, (0, 0))[0])
    assert not np.array_equal(X3, X2)


# ---- (g) an int8 store's launch counters ------------------------------------

@pytest.mark.parametrize("operands", ["int8", "bfloat16"])
def test_int8_store_launch_counts_32_columns_a_block(operands):
    """The int8-stored layout's contraction, as launched: 13 columns in
    one 32-column block, every row of a 2,048-row chunk grid, 3K value
    rows padded to 8, 256 bins, for the 13 real columns alone (the
    block's 19 padded ones build no one-hot and run no contraction);
    above 16M rows a launch takes bfloat16 operands through the same
    layout."""
    from lightgbm_tpu.ops.histogram import (INT8_EXACT_ROWS,
                                            masked_hist_mxu_ops,
                                            masked_pad_columns,
                                            store_alignment)
    assert store_alignment(1, 256, operands, 256) == (32, 2048)
    C = 115_000_000 + (-115_000_000) % 2048
    kw = dict(bins_itemsize=1, num_bins_padded=256, backend="pallas",
              input_dtype=operands, max_num_bin=256)
    for K, Mp in ((1, 8), (8, 24), (32, 96), (84, 256)):
        assert masked_hist_mxu_ops(32, C, K, real_columns=13,
                                   **kw) == 2.0 * C * Mp * 13 * 256
    assert masked_pad_columns(32, real_columns=13, **kw) == 19
    assert C > INT8_EXACT_ROWS


def test_build_quantises_only_what_its_launches_read():
    from lightgbm_tpu.ops.histogram import INT8_EXACT_ROWS, int8_operands
    small = jnp.ones((8, 1024), jnp.float32)
    ghq, sg, sh = int8_operands(small)
    assert ghq.dtype == jnp.int32 and float(sg) > 0
    big = jax.ShapeDtypeStruct((8, INT8_EXACT_ROWS + 1), jnp.float32)
    assert jax.eval_shape(int8_operands, big) is None


# ---- (h) the plain reference -------------------------------------------------

def test_split_reference_finds_the_learners_root_split():
    from benchmark.harness import split_reference as ref
    ds, cfg, g, h = mixed_problem(6000, seed=11)
    tree, _ = SerialTreeLearner(ds, cfg).train(g, h)
    gq, sg = ref.quantize(np.asarray(g))
    hq, sh = ref.quantize(np.asarray(h))
    hist = ref.histogram(np.asarray(ds.bins), gq, hq, 256)
    assert (hist[:, 2].sum(axis=1) == ds.num_data).all()
    seen = hist * np.array([sg, sh, 1.0])[None, :, None]
    want = ref.best_split(seen, ds.split_num_bins, ds.is_categorical,
                          cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf)
    assert (int(tree.split_feature_inner[0]),
            int(tree.threshold_in_bin[0])) == want[:2]
    f, t = want[:2]
    assert ref.gain_of(seen, f, t, bool(ds.is_categorical[f])) == want[2]
