"""The plain reference for a trained ensemble: parse the model text that
`Booster.model_to_string()` writes (the reference LightGBM's format) and walk
every tree in numpy on float64 rows.  It shares no code with the program's
own predictors (`Tree.predict_raw`, `ops/predict.py`).  Also the two quality
figures the benchmark reports, binary logloss and AUC, in plain numpy.
"""
import numpy as np

NUMERICAL, CATEGORICAL = 0, 1


def parse_model(text: str):
    """-> list of trees, each a dict of numpy arrays as the text names them."""
    trees, cur = [], None
    for line in text.splitlines():
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif line.startswith("feature importances"):
            cur = None                  # the trees end here
        elif cur is not None and "=" in line:
            key, val = line.split("=", 1)
            cur[key.strip()] = val.strip()
    out = []
    for kv in trees:
        n = int(kv["num_leaves"])
        t = {"num_leaves": n,
             "leaf_value": np.array(kv["leaf_value"].split(), np.float64)}
        if n > 1:
            for key in ("split_feature", "left_child", "right_child"):
                t[key] = np.array(kv[key].split(), np.int64)
            t["threshold"] = np.array(kv["threshold"].split(), np.float64)
            t["decision_type"] = (
                np.array(kv["decision_type"].split(), np.int64)
                if "decision_type" in kv else np.zeros(n - 1, np.int64))
        out.append(t)
    return out


def tree_leaves(tree, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row: left where x <= threshold (numerical) or
    int(x) == threshold (categorical); a child below 0 is leaf ~child."""
    n = X.shape[0]
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    while rows.size:
        cur = node[rows]
        x = X[rows, tree["split_feature"][cur]].astype(np.float64)
        thr = tree["threshold"][cur]
        if tree["decision_type"].any():
            finite = np.isfinite(x)
            as_cat = finite & (np.where(finite, x, -1.0).astype(np.int64)
                               == thr.astype(np.int64))
            left = np.where(tree["decision_type"][cur] == CATEGORICAL,
                            as_cat, x <= thr)
        else:
            left = x <= thr
        nxt = np.where(left, tree["left_child"][cur],
                       tree["right_child"][cur])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node


def raw_margins(trees, X: np.ndarray) -> np.ndarray:
    """Sum of leaf values over the trees, float64."""
    raw = np.zeros(X.shape[0], np.float64)
    for t in trees:
        raw += t["leaf_value"][tree_leaves(t, X)]
    return raw


def routing_flips(trees, X: np.ndarray, margins: np.ndarray, tol: float):
    """Rows whose margin is off the walk's by more than `tol`, and the
    largest distance.  With distinct leaf values a row routed to another
    leaf is off by the gap between two leaves (1e-3 and more here), so any
    row beyond a tolerance of rounding size is a routing flip."""
    ref = raw_margins(trees, X)
    err = np.abs(np.asarray(margins, np.float64) - ref)
    return int((err > tol).sum()), float(err.max()) if err.size else 0.0


def logloss(y: np.ndarray, margins: np.ndarray, sigmoid: float = 1.0):
    z = sigmoid * np.asarray(margins, np.float64)
    # log(1 + exp(-z)) for y = 1, log(1 + exp(z)) for y = 0, without overflow
    return float(np.mean(np.logaddexp(0.0, np.where(y > 0, -z, z))))


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve by the rank-sum formula, ties at mid-rank."""
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    ranks = np.empty(len(s), np.float64)
    # mid-ranks over runs of equal scores
    bounds = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    mid = (bounds[:-1] + bounds[1:] + 1) / 2.0        # 1-based mid-rank
    ranks[order] = np.repeat(mid, np.diff(bounds))
    pos = y > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return float("nan")
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))
