"""The numpy walk and the quality figures against values worked out by hand."""
import numpy as np

from benchmark.harness import walk

# Three internal nodes, four leaves:
#            x0 <= 0.5 ?
#          yes /        \ no
#      x1 <= -1 ?       x0 <= 2 ?
#      /      \         /      \
#   leaf0   leaf1    leaf2    leaf3
MODEL = """tree
num_class=1
objective=binary sigmoid:1
feature_names=a b

Tree=0
num_leaves=4
split_feature=0 1 0
threshold=0.5 -1 2
decision_type=0 0 0
left_child=1 -1 -3
right_child=2 -2 -4
leaf_value=0.1 0.2 0.3 0.4
shrinkage=1

Tree=1
num_leaves=1
leaf_value=0.05

feature importances:
a=2
b=1
"""


def test_parse_and_walk():
    trees = walk.parse_model(MODEL)
    assert [t["num_leaves"] for t in trees] == [4, 1]
    X = np.array([[0.5, -1.0],     # left (<=), left (<=)  -> leaf0
                  [0.0, 0.0],      # left, right           -> leaf1
                  [2.0, 9.0],      # right, left (<=)      -> leaf2
                  [2.5, 9.0]])     # right, right          -> leaf3
    assert walk.tree_leaves(trees[0], X).tolist() == [0, 1, 2, 3]
    assert walk.tree_leaves(trees[1], X).tolist() == [0, 0, 0, 0]
    np.testing.assert_allclose(walk.raw_margins(trees, X),
                               [0.15, 0.25, 0.35, 0.45])


def test_routing_flips():
    trees = walk.parse_model(MODEL)
    X = np.array([[0.0, 0.0], [2.5, 9.0]])
    flips, err = walk.routing_flips(trees, X, [0.25 + 4e-7, 0.45], 1e-6)
    assert flips == 0 and 3e-7 < err < 5e-7
    flips, err = walk.routing_flips(trees, X, [0.15, 0.45], 1e-6)   # leaf0
    assert flips == 1 and abs(err - 0.1) < 1e-12


def test_auc_and_logloss():
    y = np.array([0, 0, 1, 1.0])
    assert walk.auc(y, np.array([0.1, 0.4, 0.35, 0.8])) == 0.75
    assert walk.auc(y, np.array([0.0, 0.0, 1.0, 1.0])) == 1.0
    assert walk.auc(y, np.array([0.5, 0.5, 0.5, 0.5])) == 0.5     # ties
    np.testing.assert_allclose(walk.logloss(y, np.zeros(4)), np.log(2))
    np.testing.assert_allclose(
        walk.logloss(np.array([1.0]), np.array([2.0])),
        np.log1p(np.exp(-2.0)))
