"""Synthetic rows at the shape of the reference's sparse one-hot dataset
(LightGBM `docs/Experiments.rst`, "Comparison Experiment", table "Data", row
Allstate: 13,184,290 x 4,228, binary, the last 1,000,000 rows the test set,
categorical columns one-hot coded): the public column list of the Allstate
Claim Prediction Challenge, 32 source variables.

Columns, in this order (the indicator columns of one variable lie together):

  0-14       fifteen numeric or ordinal columns kept as they are: vehicle
             number, calendar year, model year, `Var1-8`, `NVVar1-4`   (15)
  15-129     twelve categorical variables `Cat1-12` of 3 to 13 levels  (115)
  130-137    `OrdCat`, 8 levels                                        (8)
  138-152    `NVCat`, 15 levels                                        (15)
  153-227    vehicle make, 75 levels                                   (75)
  228-1527   vehicle model, 1,300 levels, each of one make             (1,300)
  1528-4227  vehicle sub-model, 2,700 levels, each of one model        (2,700)

15 numeric columns and 4,213 indicator columns make the source's 4,228.  A row
holds exactly one 1 in each of the 17 categorical variables and its 15 numeric
values, none of them zero: 32 stored values of 4,228 (0.76 %).  Level
frequencies are Zipf-like inside a variable (`1 / rank ** 1.05`, the ranks
scattered over the level numbers by a fixed permutation).  A sub-model belongs
to one model and a model to one make, so the indicators of a sub-model and of
another model never meet in a row, while those of two independent variables
meet wherever both levels are drawn — rarely for rare levels, which is what
exclusive-feature bundling judges on a row sample and what conflicts on the
full set.  Cardinalities, frequencies, numeric distributions and the label are
`assumed` (configs/allstate.json): what resembles the source is the column
list, the width and the sparsity.

The label is a fixed logistic function — an effect per level and a weight per
numeric column, drawn from a fixed generator and not from the seed; about
0.7 % positives — so training rows, the test split and a run's check rows
share it.

`seed` is whatever `numpy.random.default_rng` takes (the harness hands a pair).
Rows are drawn in blocks of `BLOCK`, block i from the generator seeded with
`(*seed, i)`: the rows depend on the seed and on nothing else.  **More than
`CSR_ABOVE_ROWS` rows come back as a `scipy.sparse.csr_matrix`** (float32,
sorted indices; 12.2M x 4,228 dense would be 206 GB) **and fewer as a dense
float32 array**, which is what the harness's walk and `Booster.predict` index
(the cell's test split and check rows: 200,000 and 50,000).  A narrower matrix
(a rehearsal's) keeps the order of the variables and cuts their cardinalities
in proportion.

One thing here is not data: before it draws a sparse matrix `make` asks the
program whether it can ingest one (`require_ingest_by_stored_entries`).  A
program without that route bins a dense 12.2M-row column for each of 4,227
features, 34 minutes before the first iteration (0.485 s a column; PERF.md
section 6), and the benchmark's driver stops a run at 20 and refuses the PR
whose cell hung; a run that ends at once with a reason is what it accepts
from a program that cannot run a configuration.
"""
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 17
FEATURES = 4228
NUMERIC = 15
CATS = (3, 4, 5, 7, 9, 10, 12, 13, 13, 13, 13, 13)      # Cat1 .. Cat12: 115
ORDCAT, NVCAT, MAKE, MODEL, SUBMODEL = 8, 15, 75, 1300, 2700
ZIPF = 1.05
INTERCEPT = -5.13
CSR_ABOVE_ROWS = 250_000      # 250,000 x 4,228 float32 is 4.2 GB on the host
_FIXED = 4228                 # seeds everything that is not drawn from `seed`


class Schema:
    """Cardinalities, column offsets, frequencies, the nesting and the
    label's effects at one width: all fixed, none drawn from the seed."""

    def __init__(self, features: int):
        full = CATS + (ORDCAT, NVCAT, MAKE, MODEL, SUBMODEL)
        if features >= FEATURES:
            cards, self.numeric = list(full), features - sum(full)
        else:
            self.numeric = max(2, min(NUMERIC, features // 8))
            room = features - self.numeric
            cards = [max(2, c * room // sum(full)) for c in full]
            while sum(cards) > room and len(cards) > 4:
                cards.pop(0)                  # a narrow rehearsal: fewer Cats
            cards[-1] += room - sum(cards)    # the sub-models take the rest
        self.cards = np.asarray(cards)
        self.offsets = (self.numeric + np.concatenate(
            [[0], np.cumsum(cards)[:-1]])).astype(np.int32)
        self.features = features
        rng = np.random.default_rng(_FIXED)
        self.cdf, self.ids = [], []
        for c in cards:
            p = 1.0 / np.arange(1, c + 1) ** ZIPF
            self.cdf.append(np.cumsum(p / p.sum()))
            self.ids.append(rng.permutation(c).astype(np.int32))
        n_make, n_model, n_sub = cards[-3], cards[-2], cards[-1]

        def parents(n_parent, n_child):
            # every parent holds a child; the popular ones hold more
            w = 1.0 / np.arange(1, n_parent + 1) ** ZIPF
            first = rng.permutation(n_parent)[:n_child]
            more = rng.choice(n_parent, max(0, n_child - n_parent),
                              p=w / w.sum())
            return rng.permutation(np.concatenate([first, more])).astype(
                np.int32)

        self.make_of_model = parents(n_make, n_model)
        self.model_of_sub = parents(n_model, n_sub)
        scale = ([0.12] * len(cards) + [0.15, 0.15, 0.25, 0.2, 0.12])[
            -len(cards):]
        self.effect = [(s * rng.standard_normal(c)).astype(np.float32)
                       for s, c in zip(scale, cards)]
        if len(cards) >= 5:         # the ordinal's effect rises with its level
            self.effect[-5] = np.linspace(-0.25, 0.25, cards[-5]).astype(
                np.float32)
        self.weight = (0.12 * rng.standard_normal(self.numeric)).astype(
            np.float32)


@functools.lru_cache(maxsize=None)
def schema(features: int) -> Schema:
    return Schema(features)


def _numerics(rng, n: int, k: int) -> np.ndarray:
    """[n, k] float32, none zero: vehicle number (1, 2, ... , mostly 1),
    calendar year, model year, Var1-8 standard normals, NVVar1-4 heavy-tailed
    and shifted; a narrower draw keeps the first k."""
    out = rng.standard_normal((n, k), dtype=np.float32)
    out[:, 0] = 1.0 + np.floor(-1.2 * np.log1p(-rng.random(n, dtype=np.float32)))
    if k > 1:
        out[:, 1] = 2005 + np.floor(3 * rng.random(n, dtype=np.float32))
    if k > 2:
        out[:, 2] = out[:, 1] - np.floor(
            27 * rng.random(n, dtype=np.float32) ** 1.5)
    if k > 11:
        nv = out[:, 11:]
        np.exp(nv, out=nv)
        nv -= 0.25
    out[out == 0.0] = 1e-6
    return out


def _block(sc: Schema, seed, i: int, n: int):
    """-> (column indices [n, S + numeric] int32 ascending in a row, values
    float32, labels [n])."""
    rng = np.random.default_rng((*seed, i))
    S, k = len(sc.cards), sc.numeric
    codes = np.empty((n, S), np.int32)
    for j in range(S):
        if S - 3 <= j < S - 1:
            continue                        # make and model: of the sub-model
        u = rng.random(n)
        codes[:, j] = sc.ids[j][np.minimum(np.searchsorted(sc.cdf[j], u),
                                           sc.cards[j] - 1)]
    codes[:, S - 2] = sc.model_of_sub[codes[:, S - 1]]
    codes[:, S - 3] = sc.make_of_model[codes[:, S - 2]]
    num = _numerics(rng, n, k)
    logit = np.full(n, INTERCEPT, np.float32)
    for j in range(S):
        logit += sc.effect[j][codes[:, j]]
    logit += num[:, 3:] @ sc.weight[3:] if k > 3 else 0.0
    if k > 2:
        logit += 0.02 * (num[:, 1] - num[:, 2] - 9.0)   # older vehicles claim more
    logit += 0.1 * (np.minimum(num[:, 0], 4.0) - 1.0)
    y = rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit))
    idx = np.empty((n, k + S), np.int32)
    idx[:, :k] = np.arange(k, dtype=np.int32)
    idx[:, k:] = codes + sc.offsets
    val = np.ones((n, k + S), np.float32)
    val[:, :k] = num
    return idx, val, y


def require_ingest_by_stored_entries(rows: int, features: int) -> None:
    """Ends the run, at once and with the reason, on a program whose
    `Dataset.from_csc` lacks the route that bins a scipy matrix's stored
    entries alone (`Dataset._bin_csc_into`, the mechanism itself: no label
    is kept for this)."""
    from lightgbm_tpu.dataset import Dataset
    if not hasattr(Dataset, "_bin_csc_into"):
        raise RuntimeError(
            f"allstate: {rows} x {features} rows come as a scipy matrix, and "
            "this program's Dataset.from_csc bins a dense column of all rows "
            "for every feature (no Dataset._bin_csc_into): 0.485 s a column "
            "at 12,184,290 rows, 34 minutes for the cell's 4,227, longer "
            "than a run of the benchmark may last (PERF.md section 6, PR 39)")


def make(rows: int, features: int, seed):
    seed = tuple(int(s) for s in np.atleast_1d(seed))
    sc = schema(int(features))
    sparse = rows > CSR_ABOVE_ROWS
    if sparse:
        require_ingest_by_stored_entries(rows, features)
    width = sc.numeric + len(sc.cards)
    y = np.empty(rows, np.float64)
    if sparse:
        indices = np.empty(rows * width, np.int32)
        data = np.empty(rows * width, np.float32)
    else:
        X = np.zeros((rows, features), np.float32)

    def fill(i):
        lo = i * BLOCK
        n = min(BLOCK, rows - lo)
        idx, val, yb = _block(sc, seed, i, n)
        y[lo:lo + n] = yb
        if sparse:
            indices[lo * width:(lo + n) * width] = idx.reshape(-1)
            data[lo * width:(lo + n) * width] = val.reshape(-1)
        else:
            np.put_along_axis(X[lo:lo + n], idx, val, axis=1)

    blocks = range(-(-rows // BLOCK))
    workers = max(1, min(len(blocks), os.cpu_count() or 1, 16))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, blocks))
    if not sparse:
        return X, y
    from scipy.sparse import csr_matrix
    indptr = np.arange(0, rows * width + 1, width, dtype=np.int64)
    if indptr[-1] < 2 ** 31:
        indptr = indptr.astype(np.int32)
    return csr_matrix((data, indices, indptr), shape=(rows, features)), y
