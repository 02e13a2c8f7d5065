"""Synthetic rows at the shape of the Bosch Production Line Performance
data as the XGBoost GPU paper (arXiv 1806.11248) and NVIDIA's gbm-bench
train on it: Kaggle's `train_numeric`, 1,183,747 parts x 968 numerical
measurements, label `Response` (a part that failed quality control, 0.58 %
of them), NaN kept as NaN.

Columns are the source's `L{line}_S{station}_F{n}` in line and station
order: four lines of 24, 2, 3 and 23 stations (S0-S23, S24-S25, S26-S28,
S29-S51) holding 168, 513, 42 and 245 of the 968 columns, shared out over
each line's stations by a fixed draw.  A part takes one of `ROUTES` fixed
routes through the plant, drawn with a Zipf-like popularity: the front of
it either a run of 5-12 stations of line 0 or one of line 1's two, then
sometimes one to three stations of line 2, and mostly 6-14 stations of
line 3.  Every column of a station the route skips is NaN, and a measured
station leaves each of its values out with probability `DROP`: about 81 %
of the entries are NaN, as in the source.  A measurement is a column's
fixed centre (near 0) plus its fixed scale times a normal draw, rounded to
three decimals as the source's are: 0.0 lies amid every column's values,
so a NaN binned as 0.0 lands among measured parts and not apart from them.

The label is a fixed logistic function — of the route and of eight fixed
measurements, each counted only where it was taken — drawn from a fixed
generator and not from the seed: 0.58 % of the parts fail, and training
rows, the test split and a run's check rows share it.

`seed` is whatever `numpy.random.default_rng` takes (the harness hands a
pair).  Rows are drawn in blocks of `BLOCK`, block i from the generator
seeded with `(*seed, i)`: the rows depend on the seed and on nothing else.
A narrower matrix (a rehearsal's) keeps the first columns.

One thing here is not data: `make` first asks the program whether
`lgb.Dataset(X, y).construct()` gives a column that holds NaN a bin of its
own (`require_missing_values`).  A program that bins NaN as 0.0 would
train another model under the cell's name; the run ends at once with the
reason instead.
"""
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 16
FEATURES = 968
# (first station, stations, columns) of each line
LINES = ((0, 24, 168), (24, 2, 513), (26, 3, 42), (29, 23, 245))
STATIONS = 52
ROUTES = 96
DROP = 0.1                   # a measured station's value left out
KEYS = 8                     # measurements the label reads
INTERCEPT = -6.03            # 0.58 % of the parts fail
_FIXED = 2016                # seeds everything that is not drawn from `seed`


class Schema:
    """Stations, routes, column centres and scales and the label's
    effects: fixed, none drawn from the seed."""

    def __init__(self):
        rng = np.random.default_rng(_FIXED)
        counts = []
        for _first, n, cols in LINES:
            w = rng.gamma(2.0, 1.0, n)
            c = np.maximum(1, np.floor(w / w.sum() * cols)).astype(np.int64)
            while c.sum() < cols:
                c[np.argmax(w - c / c.sum())] += 1
            while c.sum() > cols:
                c[np.argmax(c)] -= 1
            counts.extend(c)
        self.station_of = np.repeat(np.arange(STATIONS), counts)   # [968]
        visits = np.zeros((ROUTES, STATIONS), bool)
        for r in range(ROUTES):
            if rng.random() < 0.6:
                k = rng.integers(5, 13)
                s = rng.integers(0, 24 - k + 1)
                visits[r, s:s + k] = True
            else:
                visits[r, 24 + rng.integers(0, 2)] = True
            if rng.random() < 0.35:
                visits[r, 26 + rng.choice(3, rng.integers(1, 4),
                                          replace=False)] = True
            if rng.random() < 0.9:
                k = rng.integers(6, 15)
                visits[r, 29 + rng.choice(23, k, replace=False)] = True
        self.route_cols = visits[:, self.station_of]               # [R, 968]
        pop = 1.0 / (np.arange(ROUTES) + 4.0) ** 1.1
        self.route_cdf = np.cumsum(pop / pop.sum())
        self.centre = (0.03 * rng.standard_normal(FEATURES)).astype(
            np.float32)
        self.scale = rng.uniform(0.03, 0.25, FEATURES).astype(np.float32)
        self.route_effect = 0.9 * rng.standard_normal(ROUTES)
        self.keys = np.sort(rng.choice(FEATURES, KEYS, replace=False))
        self.key_weight = rng.choice([-1.0, 1.0], KEYS) * rng.uniform(
            0.6, 1.2, KEYS)


@functools.lru_cache(maxsize=None)
def schema() -> Schema:
    return Schema()


def _block(sc: Schema, seed, i: int, n: int):
    """-> ([n, 968] float32 measurements, NaN where not taken; [n]
    labels)."""
    rng = np.random.default_rng((*seed, i))
    route = np.minimum(np.searchsorted(sc.route_cdf, rng.random(n)),
                       ROUTES - 1)
    taken = sc.route_cols[route]
    taken &= rng.random((n, FEATURES), np.float32) >= DROP
    z = rng.standard_normal((n, FEATURES), np.float32)
    zk = np.where(taken[:, sc.keys], z[:, sc.keys], 0.0)
    logit = INTERCEPT + sc.route_effect[route] + zk @ sc.key_weight
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))
    X = np.round(sc.centre + sc.scale * z, 3)
    X[~taken] = np.nan
    return X, y


def require_missing_values() -> None:
    """Ends the run, at once and with the reason, on a program whose
    `Dataset.construct()` bins NaN as 0.0 (the mechanism itself, asked of
    a 64-row dataset: no label is kept for this)."""
    import lightgbm_tpu as lgb
    x = np.tile(np.array([np.nan, -1.0, 0.0, 1.0]), 16)
    X = np.stack([x, np.arange(64.0)], axis=1)
    ds = lgb.Dataset(X, (np.arange(64) % 2).astype(np.float64)).construct(
        {"min_data_in_leaf": 1, "min_data_in_bin": 1, "verbose": -1})
    if getattr(ds._inner.mappers[0], "nan_bin", -1) < 0:
        raise RuntimeError(
            "bosch: this program's Dataset.construct() bins NaN as 0.0, "
            "so the configuration's missing measurements (81 % of the "
            "entries) would train as zeros: a different model under the "
            "cell's name (PERF.md section 6)")


def make(rows: int, features: int, seed):
    require_missing_values()
    return make_rows(rows, features, seed, range(-(-rows // BLOCK)))


def make_rows(rows: int, features: int, seed, blocks):
    """The rows of `make(rows, features, seed)` that lie in the given
    blocks of `BLOCK` rows, in block order, drawn alone: the same values
    and labels as in the whole matrix."""
    seed = tuple(int(s) for s in np.atleast_1d(seed))
    sc = schema()
    blocks = list(blocks)
    sizes = [min(BLOCK, rows - i * BLOCK) for i in blocks]
    X = np.empty((sum(sizes), min(int(features), FEATURES)), np.float32)
    y = np.empty(sum(sizes), np.float64)
    at = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)

    def fill(j):
        xb, yb = _block(sc, seed, blocks[j], sizes[j])
        X[at[j]:at[j + 1]] = xb[:, :X.shape[1]]
        y[at[j]:at[j + 1]] = yb

    workers = max(1, min(len(blocks), os.cpu_count() or 1, 16))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(len(blocks))))
    return X, y
