"""Synthetic rows at the shape of the ASA Data Expo 2009 airline on-time
data as the XGBoost GPU paper (arXiv 1806.11248) and NVIDIA's gbm-bench
train on it: 115M flights x 13 columns, label ArrDelay > 0.  The same data
is the "Expo" row of the reference's GPU-Performance table, whose runs
treat six of the columns as categorical.

Columns, in the source's order (`CATEGORICAL` marks the six):

  0  Year               1987-2008, later years more flights
  1  Month              1-12                      categorical
  2  DayofMonth         1-31                      categorical
  3  DayOfWeek          1-7                       categorical
  4  CRSDepTime         hhmm, 05:00-23:55 in steps of five minutes
  5  CRSArrTime         hhmm: departure + elapsed time + the time zones
  6  UniqueCarrier      code 0-28                 categorical
  7  FlightNum          1-7999, low numbers more frequent
  8  ActualElapsedTime  minutes, from the distance
  9  Origin             code 0-349                categorical
  10 Dest               code 0-349, not the Origin categorical
  11 Distance           miles between the two airports
  12 Diverted           0 / 1, 0.23 % ones

Category codes are non-negative integers stored as float32.  Airports follow
a Zipf-Mandelbrot popularity, `1 / (rank + 10) ** 2` over 350 airports (the
busiest 8.9 % of flights, the 30 busiest 76 %, the 255 busiest 98.9 %): so
a column keeps at most 255 bins at the binning's 200,000-row sample, and
the other 95 airports (1.1 % of rows) are a tail it drops.  Ranks are
scattered over the codes by a fixed permutation; each airport has fixed
coordinates, from which the distance and the time zones come.

The label is a fixed logistic function — of the hour of departure, the
month, the day of the week, the carrier, both airports' congestion (busier
airports delay more), the year and a diversion — drawn from a fixed
generator and not from the seed: about 45 % of flights arrive late, and
training rows, the test split and a run's check rows share it.

`seed` is whatever `numpy.random.default_rng` takes (the harness hands a
pair).  Rows are drawn in blocks of `BLOCK`, block i from the generator
seeded with `(*seed, i)`: the rows depend on the seed and on nothing else.
A narrower matrix (a rehearsal's) keeps the first columns.

One thing here is not data: `make` first asks the program whether
`lgb.Dataset(X, y).construct(params)` marks the columns that params name in
`categorical_feature` (`require_categorical_from_params`).  A program that
drops the key would train the six code columns as numbers, a different
model under the cell's name; the run ends at once with the reason instead.
"""
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 17
FEATURES = 13
CATEGORICAL = (1, 2, 3, 6, 9, 10)
AIRPORTS, CARRIERS = 350, 29
YEARS = np.arange(1987, 2009)
DIVERTED = 0.0023
INTERCEPT = -0.40            # about 45 % late arrivals
_FIXED = 2009                # seeds everything that is not drawn from `seed`


class Schema:
    """Popularities, coordinates and the label's effects: fixed, none drawn
    from the seed."""

    def __init__(self):
        rng = np.random.default_rng(_FIXED)
        rank = np.arange(1, AIRPORTS + 1)
        pop = 1.0 / (rank + 10.0) ** 2
        self.airport_cdf = np.cumsum(pop / pop.sum())
        self.airport_code = rng.permutation(AIRPORTS)        # rank -> code
        by_code = np.empty(AIRPORTS)
        by_code[self.airport_code] = rank
        # congestion: busier airports delay more, and each its own way
        self.congestion = (0.45 - 0.18 * np.log(by_code)
                           + 0.2 * rng.standard_normal(AIRPORTS))
        self.lat = rng.uniform(25.0, 48.0, AIRPORTS)
        self.lon = rng.uniform(-124.0, -70.0, AIRPORTS)
        self.zone = np.round((self.lon + 75.0) / 15.0).astype(np.int64)
        crank = np.arange(1, CARRIERS + 1)
        cpop = 1.0 / (crank + 2.0) ** 1.2
        self.carrier_cdf = np.cumsum(cpop / cpop.sum())
        self.carrier_code = rng.permutation(CARRIERS)
        self.carrier_effect = 0.25 * rng.standard_normal(CARRIERS)
        ypop = np.linspace(3.0, 7.5, len(YEARS))
        self.year_cdf = np.cumsum(ypop / ypop.sum())
        self.year_effect = 0.2 * np.sin((YEARS - 1987) / 3.0)
        self.month_effect = np.array([0.0, 0.15, 0.05, 0.0, -0.05, -0.05,
                                      0.25, 0.3, 0.15, -0.3, -0.2, -0.15,
                                      0.35])           # index 1..12
        self.dow_effect = np.array([0.0, 0.1, -0.05, -0.05, 0.1, 0.2,
                                    -0.2, 0.0])        # index 1..7
        # day of month 1-31, the 29th to 31st in fewer months
        dom = np.ones(31)
        dom[28], dom[29], dom[30] = 11 / 12, 11 / 12, 7 / 12
        self.dom_cdf = np.cumsum(dom / dom.sum())


@functools.lru_cache(maxsize=None)
def schema() -> Schema:
    return Schema()


def _draw(cdf, u):
    return np.minimum(np.searchsorted(cdf, u), len(cdf) - 1)


def _block(sc: Schema, seed, i: int, n: int):
    """-> ([n, 13] float32 columns, [n] labels)."""
    rng = np.random.default_rng((*seed, i))
    year = YEARS[_draw(sc.year_cdf, rng.random(n))]
    month = rng.integers(1, 13, n)
    dom = 1 + _draw(sc.dom_cdf, rng.random(n))
    dow = rng.integers(1, 8, n)
    # departures: minute of day 05:00-23:55, a morning and an evening bank
    bank = rng.random(n) < 0.55
    dep = np.where(bank, rng.normal(9.5 * 60, 150, n),
                   rng.normal(17.5 * 60, 170, n))
    dep = (np.clip(dep, 300, 1435) // 5 * 5).astype(np.int64)
    carrier = sc.carrier_code[_draw(sc.carrier_cdf, rng.random(n))]
    flight = np.minimum(1 + np.floor(rng.exponential(1200.0, n)),
                        7999).astype(np.int64)
    origin = sc.airport_code[_draw(sc.airport_cdf, rng.random(n))]
    dest = sc.airport_code[_draw(sc.airport_cdf, rng.random(n))]
    same = dest == origin
    dest[same] = (dest[same] + 1 + rng.integers(0, AIRPORTS - 1,
                                                int(same.sum()))) % AIRPORTS
    dlat = np.radians(sc.lat[dest] - sc.lat[origin])
    dlon = np.radians(sc.lon[dest] - sc.lon[origin]) * np.cos(
        np.radians(0.5 * (sc.lat[dest] + sc.lat[origin])))
    dist = np.maximum(np.rint(3959.0 * np.hypot(dlat, dlon)), 30.0)
    elapsed = np.maximum(np.rint(22.0 + dist / 7.8
                                 + 8.0 * rng.standard_normal(n)), 15.0)
    arr = (dep + elapsed.astype(np.int64)
           + 60 * (sc.zone[dest] - sc.zone[origin])) % 1440
    diverted = rng.random(n) < DIVERTED

    logit = (INTERCEPT + 0.09 * (dep / 60.0 - 13.0)
             + sc.month_effect[month] + sc.dow_effect[dow]
             + sc.carrier_effect[carrier] + sc.congestion[origin]
             + 0.8 * sc.congestion[dest] + sc.year_effect[year - 1987]
             + 3.0 * diverted)
    y = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))

    X = np.empty((n, FEATURES), np.float32)
    X[:, 0] = year
    X[:, 1] = month
    X[:, 2] = dom
    X[:, 3] = dow
    X[:, 4] = dep // 60 * 100 + dep % 60
    X[:, 5] = arr // 60 * 100 + arr % 60
    X[:, 6] = carrier
    X[:, 7] = flight
    X[:, 8] = elapsed
    X[:, 9] = origin
    X[:, 10] = dest
    X[:, 11] = dist
    X[:, 12] = diverted
    return X, y


def require_categorical_from_params() -> None:
    """Ends the run, at once and with the reason, on a program whose
    `Dataset.construct(params)` does not mark the columns params name in
    `categorical_feature` (the mechanism itself, asked of a 64-row
    dataset: no label is kept for this)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.binning import CATEGORICAL as CAT
    x = np.tile(np.arange(4, dtype=np.float64), 16)
    X = np.stack([x, x[::-1]], axis=1)
    ds = lgb.Dataset(X, (x > 1).astype(np.float64)).construct(
        {"categorical_feature": "0", "min_data_in_leaf": 1,
         "min_data_in_bin": 1, "verbose": -1})
    if ds._inner.mappers[0].bin_type != CAT:
        raise RuntimeError(
            "airline: this program's Dataset.construct(params) drops "
            "`categorical_feature`, so the configuration's six code columns "
            "(Month, DayofMonth, DayOfWeek, UniqueCarrier, Origin, Dest) "
            "would train as numbers: a different model under the cell's "
            "name (PERF.md section 6)")


def make(rows: int, features: int, seed):
    require_categorical_from_params()
    seed = tuple(int(s) for s in np.atleast_1d(seed))
    sc = schema()
    X = np.empty((rows, min(int(features), FEATURES)), np.float32)
    y = np.empty(rows, np.float64)

    def fill(i):
        lo = i * BLOCK
        n = min(BLOCK, rows - lo)
        xb, yb = _block(sc, seed, i, n)
        X[lo:lo + n] = xb[:, :X.shape[1]]
        y[lo:lo + n] = yb

    blocks = range(-(-rows // BLOCK))
    workers = max(1, min(len(blocks), os.cpu_count() or 1, 16))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, blocks))
    return X, y
