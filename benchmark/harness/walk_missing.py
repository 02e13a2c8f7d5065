"""The plain reference for a trained ensemble whose data holds NaN: parse
the model text that `Booster.model_to_string()` writes (the reference
LightGBM's format) and walk every tree in numpy on float64 rows, routing a
missing value by the reference's documented rule (Advanced-Topics, "Missing
Value Handle"; its `Tree::NumericalDecision` / `CategoricalDecision`):

- a numerical split whose decision_type holds missing type NaN (bits 2-3
  read 2) sends NaN to its default side: left where bit 1 (default_left)
  is set;
- a numerical split of missing type None compares NaN as 0.0;
- a categorical split (bit 0) sends a value left where its integer part
  is the split's category (the reference's int cast), and NaN right.

It shares no code with the program's predictors (`Tree.predict_raw`,
`ops/predict.py`), nor with `walk.py`'s parser and walk, and on a model
without missing bits it routes every row without NaN as `walk.py` does.
The two quality figures, binary logloss and AUC, are `walk.py`'s.
"""
import numpy as np

from benchmark.harness.walk import auc, logloss  # noqa: F401  (re-exported)

CATEGORICAL_BIT, DEFAULT_LEFT_BIT = 1, 2
MISSING_NAN = 2


def parse_model(text: str):
    """-> list of trees, each a dict of numpy arrays by the text's keys."""
    trees, kv = [], None
    for line in text.splitlines():
        if line.startswith("Tree="):
            kv = {}
            trees.append(kv)
        elif line.startswith("end of trees") or line.startswith(
                "feature importances"):
            kv = None
        elif kv is not None and "=" in line:
            key, _, val = line.partition("=")
            kv[key.strip()] = val.split()
    out = []
    for kv in trees:
        n = int(kv["num_leaves"][0])
        t = {"num_leaves": n,
             "leaf_value": np.asarray(kv["leaf_value"], np.float64)}
        if n > 1:
            t["split_feature"] = np.asarray(kv["split_feature"], np.int64)
            t["threshold"] = np.asarray(kv["threshold"], np.float64)
            t["decision_type"] = np.asarray(
                kv.get("decision_type", ["0"] * (n - 1)), np.int64)
            t["left_child"] = np.asarray(kv["left_child"], np.int64)
            t["right_child"] = np.asarray(kv["right_child"], np.int64)
        out.append(t)
    return out


def go_left(decision_type, threshold, x):
    """Each row's side at its node, by the rule above: numpy arrays of
    one value per row."""
    categorical = (decision_type & CATEGORICAL_BIT) != 0
    missing_nan = ((decision_type >> 2) & 3) == MISSING_NAN
    default_left = (decision_type & DEFAULT_LEFT_BIT) != 0
    nan = np.isnan(x)
    as_number = np.where(nan & ~missing_nan, 0.0, x)
    numerical = np.where(nan & missing_nan, default_left,
                         as_number <= threshold)
    finite = np.isfinite(x)
    category = finite & (np.trunc(np.where(finite, x, -1.0))
                         == np.trunc(np.where(np.isfinite(threshold),
                                              threshold, -2.0)))
    return np.where(categorical, category, numerical)


def leaves(tree, X: np.ndarray) -> np.ndarray:
    """The leaf each row of X ends in."""
    n = X.shape[0]
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    live = np.arange(n)
    while live.size:
        at = node[live]
        x = X[live, tree["split_feature"][at]].astype(np.float64)
        left = go_left(tree["decision_type"][at], tree["threshold"][at], x)
        node[live] = np.where(left, tree["left_child"][at],
                              tree["right_child"][at])
        live = live[node[live] >= 0]
    return -node - 1


def raw_margins(trees, X: np.ndarray) -> np.ndarray:
    """Sum of leaf values over the trees, float64."""
    raw = np.zeros(X.shape[0], np.float64)
    for t in trees:
        raw += t["leaf_value"][leaves(t, X)]
    return raw


def routing_flips(trees, X: np.ndarray, margins: np.ndarray, tol: float):
    """Rows whose margin is off the walk's by more than `tol`, and the
    largest distance (as walk.routing_flips)."""
    err = np.abs(np.asarray(margins, np.float64) - raw_margins(trees, X))
    return int((err > tol).sum()), float(err.max()) if err.size else 0.0
