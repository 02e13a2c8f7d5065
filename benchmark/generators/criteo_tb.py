"""Synthetic rows at the shape of the reference's parallel experiment
(LightGBM `docs/Experiments.rst`, "Parallel Experiment"): the Criteo terabyte
click log after the page's feature engineering — 13 integer counters, and each
of the 26 categorical columns replaced by its click-through rate and its count
over the first ten days — 67 numerical columns in all.

Columns in the source's order of kinds:

  0-12   integer counters: heavy-tailed non-negative integers (the floor of a
         log-normal), a few hundred to a few thousand distinct values each
  13-38  click-through rates in [0, 1], centred on the log's click rate
  39-64  counts on a log scale (log1p of a log-normal count)
  65-66  two further numeric columns (a time of day in [0, 24) and a
         standard normal), which make the source's 67

Every column is dense as binned: no bin, the zero's or any other, holds half
of the rows, so the program's exclusive-feature bundling packs nothing and the
store keeps 67 columns; and every column but the counters has far more
distinct values than `max_bin`.  What of this resembles the real log is the
kinds and the ranges; everything else is `assumed` (configs/criteo_tb.json).

A row has one latent "intent" that the rates and the counts lean on, so the
columns are correlated as engineered click features are.  The label is a
fixed logistic function of a fixed subset of columns (weights below, not
drawn from the seed), click rate about 3 %: training rows, the test split and
a run's check rows share it.

`seed` is whatever `numpy.random.default_rng` takes (the harness hands a pair).
Rows are drawn in blocks of `BLOCK`, block i from the generator seeded with
`(*seed, i)`: the rows depend on the seed and on nothing else — not on how
many threads draw them — and no intermediate is wider than the block.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 17             # rows; 35 MB of float32 at 67 columns
COUNTERS, RATES, COUNTS = 13, 26, 26          # then what is left: numeric


def _kinds(features: int):
    """Column ranges of the four kinds; a narrower matrix (a rehearsal's)
    keeps the order and cuts each kind in proportion."""
    if features >= COUNTERS + RATES + COUNTS:
        a, b, c = COUNTERS, RATES, COUNTS
    else:
        a = max(1, features * COUNTERS // 67)
        b = max(1, features * RATES // 67)
        c = max(0, min(features - a - b, features * COUNTS // 67))
    return (slice(0, a), slice(a, a + b), slice(a + b, a + b + c),
            slice(a + b + c, features))


def _block(X, y, seed, i, features):
    """Fill rows [i * BLOCK, ...) of X and y in place."""
    rows = X[i * BLOCK:(i + 1) * BLOCK]
    n = len(rows)
    rng = np.random.default_rng((*seed, i))
    cnt, rate, logc, rest = _kinds(features)
    rng.standard_normal(out=rows, dtype=np.float32)
    z = rng.standard_normal(n, dtype=np.float32)[:, None]     # the intent
    k = np.arange(features, dtype=np.float32)

    # counters: floor(exp(mu + sigma * n)), mu 1.0 .. 3.4, sigma 1.3;
    # the zero holds 1 - 22 % of a column
    c = rows[:, cnt]
    c *= 1.3
    c += 1.0 + 0.2 * k[cnt]
    np.exp(c, out=c)
    np.floor(c, out=c)
    # rates: sigmoid(-3.5 + 0.6 * intent + 0.7 * n + a column's offset)
    r = rows[:, rate]
    r *= 0.7
    r += 0.6 * z
    r += -3.5 + 0.04 * (k[rate] - k[rate][0] - 12.5)
    np.negative(r, out=r)
    np.exp(r, out=r)
    r += 1.0
    np.reciprocal(r, out=r)
    # counts on a log scale: log1p(floor(exp(6 + 0.5 * intent + 1.8 * n)))
    g = rows[:, logc]
    g *= 1.8
    g += 0.5 * z
    g += 6.0 + 0.1 * (k[logc] - k[logc][0] - 12.5)
    np.exp(g, out=g)
    np.floor(g, out=g)
    np.log1p(g, out=g)
    # the rest: hour of day, then standard normals as drawn
    if rest.stop > rest.start:
        h = rows[:, rest.start]
        h *= 0.25
        h -= np.floor(h)
        h *= 24.0

    # the labelling function: fixed weights on the first columns of each
    # kind (log1p of a counter, logit of a rate, a log count), one
    # interaction, and the intercept that gives ~3 % clicks
    logit = np.full(n, -3.4, np.float32)
    for j, w in zip(range(cnt.start, cnt.stop), (0.25, -0.2, 0.15)):
        logit += w * (np.log1p(rows[:, j]) - 2.0)
    for j, w in zip(range(rate.start, rate.stop), (0.9, 0.6, 0.4, -0.3)):
        p = np.clip(rows[:, j], 1e-6, 1 - 1e-6)
        logit += w * (np.log(p / (1 - p)) + 3.5)
    for j, w in zip(range(logc.start, logc.stop), (0.12, -0.1)):
        logit += w * (rows[:, j] - 6.0)
    if rate.stop - rate.start > 1 and logc.stop > logc.start:
        logit += 2.0 * (rows[:, rate.start + 1] - 0.03) * (
            rows[:, logc.start] - 6.0)
    y[i * BLOCK:i * BLOCK + n] = (
        rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit)))


def make(rows: int, features: int, seed):
    seed = tuple(int(s) for s in np.atleast_1d(seed))
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float64)
    blocks = range(-(-rows // BLOCK))
    workers = max(1, min(len(blocks), os.cpu_count() or 1, 16))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(lambda i: _block(X, y, seed, i, features), blocks))
    return X, y
