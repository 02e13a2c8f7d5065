"""The benchmark's `higgs` configuration at a size the CPU takes: rows of
`benchmark/generators/higgs.make` (20,000 x 28 in place of 10.5M x 28)
under the configuration's own parameters — 255 leaves, `max_bin=255`,
`min_data_in_leaf=1`, `min_sum_hessian_in_leaf=100`.

What is new beside tests/test_rounds.py (1,200-3,000 rows by 6-10
columns, 9-31 leaves, one tree from hand-made gradients) is the cell's
shape ratio and leaf count through `lgb.train`: hundreds of rows a column,
a leaf table of 255 (four slot chunks of K = 84 a round, most of them
empty), several boosting iterations on the objective's real gradients.
The plain reference is the exact leaf-wise learner, learner/serial.py.
With `min_sum_hessian_in_leaf=100` a leaf needs 400 rows at p = 0.5, so
20,000 rows grow about fifty leaves and the cap of 255 never binds —
the condition under which the rounds schedule grows the leaf-wise tree
(tests/test_rounds.py).
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ROWS, FEATURES, ITERS = 20_000, 28, 3

# Leaf values, as tests/test_rounds.py holds them (rtol 1e-4, atol 1e-6):
# the plain learner sums a leaf's own rows in float32 and the rounds
# learner sums all rows under a mask, gets the larger child by
# subtraction, and from the second tree on both start from scores that
# already differ by such roundings; leaf values are ratios of those sums.
# Splits (feature, threshold bin) and counts are held exactly.
LEAF_TOL = dict(rtol=1e-4, atol=1e-6)


def cell_params(**over):
    with open(os.path.join(ROOT, "benchmark", "configs", "higgs.json")) as f:
        return dict(json.load(f)["params"], **over)


@pytest.fixture(scope="module")
def rows():
    from benchmark.generators.higgs import make
    return make(ROWS, FEATURES, (0, 0))


@pytest.fixture(scope="module")
def trained(rows):
    """`lgb.train` under the cell's parameters with the learner pinned:
    (growth, histogram dtype, iterations) -> the booster and how far
    the `tree/` counters moved over the run; one run per key."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import profiling
    X, y = rows
    runs = {}

    def run(growth, dtype, iters):
        key = (growth, dtype, iters)
        if key not in runs:
            params = cell_params(tree_growth=growth, histogram_dtype=dtype)
            before = profiling.counters("tree/")
            bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=iters)
            bst._gbdt._flush_pending()      # the last tree is fetched lazily
            after = profiling.counters("tree/")
            runs[key] = bst, {k: v - before.get(k, 0.0)
                              for k, v in after.items()}
        return runs[key]
    return run


def paths(tree):
    """The tree without its numbering: for every leaf the splits from the
    root down — (inner feature, threshold bin, went left) — with the
    leaf's value and count."""
    out = {}

    def walk(node, path):
        if node < 0:
            leaf = ~node
            out[path] = (tree.leaf_value[leaf], tree.leaf_count[leaf])
            return
        step = (int(tree.split_feature_inner[node]),
                int(tree.threshold_in_bin[node]))
        walk(int(tree.left_child[node]), path + (step + (True,),))
        walk(int(tree.right_child[node]), path + (step + (False,),))

    if tree.num_leaves > 1:
        walk(0, ())
    return out


def assert_same_trees(got, want):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        pa, pb = paths(a), paths(b)
        assert a.num_leaves == b.num_leaves > 30, i
        assert set(pa) == set(pb), f"tree {i}: splits differ"
        keys = sorted(pa)
        np.testing.assert_array_equal([pa[k][1] for k in keys],
                                      [pb[k][1] for k in keys])
        np.testing.assert_allclose([pa[k][0] for k in keys],
                                   [pb[k][0] for k in keys], **LEAF_TOL)


def test_float32_trees_are_the_plain_learners(trained):
    """Three boosting iterations, tree for tree, leaf for leaf."""
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    plain, _ = trained("exact", "float32", ITERS)
    bst, _ = trained("rounds", "float32", ITERS)
    assert isinstance(plain._gbdt.learner, SerialTreeLearner)
    assert isinstance(bst._gbdt.learner, RoundsTreeLearner)
    assert_same_trees(bst._gbdt.models, plain._gbdt.models)


def test_int8_first_tree_is_the_plain_learners(trained):
    """`histogram_dtype=int8` quantises the gradients of each pass by the
    largest of all rows.  At the first tree of the binary objective every
    gradient is +-0.5 and every hessian 0.25, which that scale takes to
    +-127 exactly, so the kernels get what the plain learner gets and the
    build must grow its tree."""
    plain, _ = trained("exact", "float32", ITERS)
    bst, _ = trained("rounds", "int8", 1)
    assert_same_trees(bst._gbdt.models, plain._gbdt.models[:1])


def test_int8_later_trees_on_the_same_quantised_gradients(rows, trained):
    """Past the first tree the gradients take many values.  The plain
    learner can be given the int8 path's quantised values — one scale
    over all rows, `ops/histogram.quantize_gh`.  Both learners
    get the int8 levels themselves (whole numbers up to +-127, which the
    int8 path requantises to themselves at a scale of exactly 1, and
    whose float32 sums are exact in any order), with the hessian floor
    in the same units: on the gradients of the second and third
    iteration the int8 build grows the plain learner's tree."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.learner.rounds import RoundsTreeLearner
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    from lightgbm_tpu.ops.histogram import quantize_gh
    X, y = rows
    plain, _ = trained("exact", "float32", ITERS)
    floor = cell_params()["min_sum_hessian_in_leaf"]
    got, want = [], []
    for done in (1, 2):
        score = plain.predict(X, raw_score=True, num_iteration=done)
        p = 1.0 / (1.0 + np.exp(-score))
        gh8 = (jnp.zeros((8, ROWS), jnp.float32)
               .at[0].set((p - y).astype(np.float32))
               .at[1].set((p * (1 - p)).astype(np.float32)))
        ghq, _, sh = quantize_gh(gh8)
        g, h = ghq[0].astype(jnp.float32), ghq[1].astype(jnp.float32)
        assert len(np.unique(np.asarray(g))) > 30           # not two levels
        assert float(jnp.abs(g).max()) == float(h.max()) == 127.0
        cfg = config_from_params(cell_params(
            tree_growth="rounds",
            min_sum_hessian_in_leaf=floor / float(sh)))
        assert cfg.histogram_dtype == "int8"
        ds = RawDataset(X, y, config=cfg)
        got.append(RoundsTreeLearner(ds, cfg, None).train(g, h)[0])
        want.append(SerialTreeLearner(ds, cfg).train(g, h)[0])
    assert_same_trees(got, want)


def test_pass_and_partition_counters(trained):
    """`tree/hist_rows_touched` and `tree/partition_rows` over the three
    iterations of the float32 run: every launch streams all rows, every
    round rewrites the leaf id of every row, and no row is copied."""
    _, moved = trained("rounds", "float32", ITERS)
    rounds, passes = moved["tree/rounds"], moved["tree/hist_passes"]
    assert rounds >= 6 * ITERS and passes >= rounds + ITERS
    assert moved["tree/partition_rows"] == rounds * ROWS
    assert moved["tree/hist_rows_touched"] == passes * ROWS
    assert moved["tree/feed_rows"] == 0 and moved["tree/feed_live_rows"] == 0
