"""The job `train_missing`: the job `train` — its set-up, window, clock,
counters, quality and checks, imported from `train.py` — with the checks
that decide `correct` held against references that know missing values:

- the device's predictions against `harness/walk_missing.py`, which
  routes a missing value by the reference's rule (a split's default
  side, or as 0.0 where its missing type is None), and not against
  `harness/walk.py`, which sends NaN right.  The configuration's test
  split and the run's check rows hold NaN at its generator's share;
- the timed path itself, twice.  The train score, which the partition's
  leaf ids accumulated tree by tree, against walk_missing over one block
  of the training rows (drawn again from the generator, the block chosen
  by `--seed`): a partition that sends the NaN rows of a split against
  the side its record names puts them in another leaf than the model
  text does.  And the first learned tree's root split against
  `harness/split_missing.py`'s two-sided search over the root histogram
  of the cell's own binned store: a search that records the losing side
  of the NaN bin writes a model that is self-consistent but not the
  best split.
"""
import importlib

import numpy as np

from benchmark.harness import (dataset, split_missing, split_reference,
                               walk_missing)
from benchmark.jobs import train

# The device scores the root's candidates in float32: a bin's sum of
# int8 levels is exact, but the NaN bin's (four fifths of the rows, above
# 2^24 levels) rounds, a threshold's left side is a float32 prefix sum
# over up to 255 bins, and each gain adds three terms of the size of
# G^2/H.  A candidate whose float64 gain is within TIE_STEPS float32 steps
# of G^2/H of the best is a tie the device may turn; the side of the NaN
# bin moves four fifths of the rows, and its wrong side costs a share of
# the gain itself.
TIE_STEPS = 64.0
# A child's hessian within this share of min_sum_hessian_in_leaf is on
# the limit to a float32 rounding of a sum of a few hundred bins: the
# device is held to no such candidate, and may take one.
HESS_SLACK = 2.0 ** -16


def _train_block(config, seed: int):
    """(first row, rows [n, F], labels [n]) of one block of the
    configuration's training rows, the block chosen by `seed`."""
    gen = importlib.import_module(
        f"benchmark.generators.{config['generator']}")
    rows = int(config["rows"])
    block = seed % -(-rows // gen.BLOCK)
    X, y = gen.make_rows(rows, int(config["features"]),
                         (int(config["data_seed"]), dataset.TRAIN), [block])
    return block * gen.BLOCK, X, y


def train_score_check(bst, trees, config, seed: int):
    """The train score of one block of training rows against the walk
    of the model text over the same rows, regenerated.  A row's train
    score is a float32 sum of one leaf value a tree, each addition
    rounding by at most half a unit in the last place of the running
    score and each leaf value carrying one rounding of its shrinkage:
    under (trees) units of the largest margin in all.  A row sent to
    another leaf moves by that leaf's gap from its own, 1e-3 and more
    at a learning rate of 0.1."""
    lo, X, y = _train_block(config, seed)
    hi = lo + len(y)
    gb = bst._gbdt
    labels = np.asarray(gb.train_set.metadata.label, np.float64)[lo:hi]
    score = np.asarray(gb.train_score.score[0, lo:hi], np.float64)
    want = walk_missing.raw_margins(trees, X.astype(np.float64))
    tol = len(trees) * float(np.spacing(np.float32(np.abs(want).max())))
    err = np.abs(score - want)
    off = int((err > tol).sum())
    nan_rows = int(np.isnan(X).any(axis=1).sum())
    return ({"train_rows_are_the_generators": bool(np.array_equal(labels, y)),
             "train_score_matches_walk": off == 0},
            dict(train_score_rows=[lo, hi], train_score_nan_rows=nan_rows,
                 train_score_max_abs_err=float(err.max()),
                 train_score_tol=tol, train_score_rows_off=off))


def _threshold_bin(mapper, threshold: float) -> int:
    """The bin whose upper bound the model text's threshold names (it
    writes the bound as a float32), or -1 where none lies that near."""
    ub = np.asarray(mapper.bin_upper_bound, np.float64)
    t = int(np.argmin(np.abs(ub - threshold)))
    near = abs(ub[t] - threshold) <= 1e-6 * max(1.0, abs(threshold))
    return t if near else -1


def root_split_check(bst, trees, params):
    """The first learned tree's root split against the two-sided numpy
    search over the root histogram of the binned store: the gradients of
    a constant score, int8-quantised as the program does."""
    gb = bst._gbdt
    ds = gb.train_set
    if ds.bundle_plan is not None:
        raise SystemExit("train_missing: the root check reads a store of "
                         "one feature a column; this one is bundled")
    first = 1 if gb.boost_from_average_used else 0
    tree = trees[first]
    init = float(trees[0]["leaf_value"][0]) if first else 0.0
    y = np.asarray(ds.metadata.label, np.float64)
    p = 1.0 / (1.0 + np.exp(-init))
    gq, sg = split_reference.quantize(np.float32(p) - y.astype(np.float32))
    hq, sh = split_reference.quantize(
        np.full(len(y), np.float32(p) * np.float32(1.0 - p), np.float32))
    bins = ds.dense_bins()
    split_bins = np.asarray(ds.split_num_bins)
    hist = split_missing.histogram(bins, gq, hq, int(split_bins.max()))
    hist = hist * np.array([sg, sh, 1.0], np.float64)[None, :, None]
    nan_bins = ds.missing_bins
    nan_bins = (np.full(len(split_bins), -1) if nan_bins is None
                else np.asarray(nan_bins))
    is_cat = np.asarray(ds.is_categorical)
    limits = (int(params["min_data_in_leaf"]),
              float(params["min_sum_hessian_in_leaf"]))
    gains = split_missing.candidates(hist, split_bins, is_cat, nan_bins,
                                     *limits, -HESS_SLACK)
    want = split_missing.best_split(hist, split_bins, is_cat, nan_bins,
                                    *limits, HESS_SLACK)
    raw = int(tree["split_feature"][0])
    k = list(ds.used_features).index(raw)
    dt = int(tree["decision_type"][0])
    t = _threshold_bin(ds.mappers[raw], float(tree["threshold"][0]))
    left = bool(dt & walk_missing.DEFAULT_LEFT_BIT)
    got = float(gains[k, t, int(left)]) if t >= 0 else -np.inf
    G, H = float(hist[0, 0].sum()), float(hist[0, 1].sum())
    step = 2.0 ** -23 * G * G / H
    ok = bool(np.isfinite(got) and want[3] - got <= TIE_STEPS * step)
    return ({"root_split_is_the_references": ok},
            dict(root_split=[k, t, left], root_split_reference=list(want[:3]),
                 root_gain=got, root_gain_reference=want[3],
                 root_gain_tie=TIE_STEPS * step))


def run(ctx) -> dict:
    plain = train._checks

    def checks(bst, cell, win, store, Xv, yv, raw_q, Xc):
        ok, facts = plain(bst, cell, win, store, Xv, yv, raw_q, Xc)
        trees = walk_missing.parse_model(bst.model_to_string())
        params = {**ctx["config"]["params"], **cell.get("params", {})}
        for more, more_facts in (
                train_score_check(bst, trees, ctx["config"], ctx["seed"]),
                root_split_check(bst, trees, params)):
            ok.update(more)
            facts.update(more_facts)
        return ok, facts

    walk = train.walk
    train.walk, train._checks = walk_missing, checks
    try:
        return train.run(ctx)
    finally:
        train.walk, train._checks = walk, plain
