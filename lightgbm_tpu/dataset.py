"""Binned Dataset: the device-resident training matrix.

TPU re-design of the reference IO layer (/root/reference/src/io/):

- `Metadata` — labels/weights/query boundaries/init score incl. the
  `<data>.weight` / `<data>.init` / `<data>.query` side files
  (metadata.cpp:372-437).
- text `Parser` — CSV / TSV / LibSVM auto-detection (parser.cpp).
- `Dataset` — instead of the reference's FeatureGroup/DenseBin/SparseBin/
  OrderedBin class zoo (dense_bin.hpp, sparse_bin.hpp, ordered_sparse_bin.hpp),
  ONE dense `[num_used_features, num_rows]` uint8/uint16 array of bin ids,
  padded with a sentinel row slot so masked gathers are branch-free.  Binned
  values are ~1 byte each, so even Epsilon-scale data fits HBM dense; there
  is no sparse path on TPU (SURVEY.md §7 "start dense").

Validation datasets are binned with the training set's BinMappers
(reference Dataset::CheckAlign + LoadFromFileAlignWithOtherDataset,
dataset_loader.cpp:220-261).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .binning import (BinMapper, BundlePlan, find_bin_mappers,
                      pack_bundle_column, plan_bundles, CATEGORICAL)
from .config import Config

# ----------------------------------------------------------------------------
# Sparse binned store (docs/Sparse.md)
# ----------------------------------------------------------------------------

def nnz_capacity_tier(n: int, base: int = 4) -> int:
    """Smallest power-of-two >= n (floor `base`): the ELL row width R of
    a sparse store.  Device kernels key compiled shapes on R, so
    datasets whose max per-row entry count lands in the same tier share
    every compiled program — the ladder bounds compiles at O(log nnz),
    the same contract as row_capacity_tier for streaming stores."""
    cap = max(int(base), 1)
    n = max(int(n), 1)
    while cap < n:
        cap <<= 1
    return cap


@dataclass
class SparseStore:
    """CSR/ELL-packed binned store: per row, up to R (store column,
    bin) entries for exactly the cells whose bin differs from the
    column's zero bin — the bin an implicit raw 0.0 maps to (the
    feature's default bin; 0 = "all members at default" for EFB-packed
    columns).  Implicit zeros are never stored: the histogram kernels
    reconstruct each column's zero-bin row from per-leaf totals
    (ops/histogram._apply_zero_bin), so compute and input bytes scale
    with nnz instead of F x N.  `densify()` reproduces the dense store
    bitwise — the entry set is lossless by construction."""
    cols: np.ndarray      # [N, R] int32 store-column ids; C = empty slot
    bins: np.ndarray      # [N, R] uint8/uint16 bin values
    zero_bin: np.ndarray  # [C] int32 implicit-zero bin per store column
    nnz: int = 0          # stored entries (excluding ELL padding)

    @property
    def num_columns(self) -> int:
        return int(self.zero_bin.shape[0])

    @property
    def nnz_capacity(self) -> int:
        return int(self.cols.shape[1])

    def densify(self, dtype) -> np.ndarray:
        """Materialize the dense [C, N] store (the fallback for
        consumers without a sparse path; callers count it)."""
        C = self.num_columns
        n = self.cols.shape[0]
        out = np.repeat(self.zero_bin.astype(dtype)[:, None], n, axis=1)
        ri, sj = np.nonzero(self.cols < C)
        out[self.cols[ri, sj], ri] = self.bins[ri, sj]
        return out


def _pack_ell(rows: np.ndarray, cols: np.ndarray, binvals: np.ndarray,
              n: int, num_columns: int, zero_bin: np.ndarray,
              dtype) -> SparseStore:
    """Row-sorted COO entries -> ELL arrays at the nnz capacity tier."""
    cnt = np.bincount(rows, minlength=n) if rows.size else \
        np.zeros(n, np.int64)
    R = nnz_capacity_tier(int(cnt.max(initial=1)))
    ell_c = np.full((n, R), num_columns, np.int32)
    ell_b = np.zeros((n, R), dtype)
    if rows.size:
        offs = np.concatenate([[0], np.cumsum(cnt)])
        pos = np.arange(rows.size, dtype=np.int64) - offs[rows]
        ell_c[rows, pos] = cols
        ell_b[rows, pos] = binvals
    return SparseStore(cols=ell_c, bins=ell_b,
                       zero_bin=np.asarray(zero_bin, np.int32),
                       nnz=int(rows.size))


def store_zero_bins(mappers: List[BinMapper], used: Sequence[int],
                    plan: Optional[BundlePlan]) -> np.ndarray:
    """[C] int32 bin an implicit raw zero maps to, per STORE column:
    the member feature's default bin for singleton columns, 0 ("every
    member at its default") for EFB-packed columns."""
    if plan is None:
        return np.asarray([mappers[i].default_bin for i in used],
                          np.int32)
    zb = np.zeros(plan.num_columns, np.int32)
    for k, i in enumerate(used):
        if not plan.feat_packed[k]:
            zb[int(plan.feat_col[k])] = int(mappers[i].default_bin)
    return zb


def resolve_sparse_store(cfg: Config, mappers: List[BinMapper],
                         used: Sequence[int],
                         plan: Optional[BundlePlan]) -> bool:
    """Resolve the `sparse_store` knob for a store about to be built.

    "auto" picks csr only when (1) `is_enable_sparse` is on (the
    reference's master sparse switch), (2) the store is wide enough
    that nnz-iteration can beat the dense kernels (>= 128 columns), and
    (3) the estimated zero-bin rate — the mean of the mappers'
    sampled `sparse_rate` over stored columns, with a packed column's
    rate the complement of its members' summed non-default rates —
    clears `sparse_threshold` (reference semantics: the zero fraction
    above which a feature is worth storing sparse)."""
    mode = getattr(cfg, "sparse_store", "dense")
    if mode == "csr":
        return True
    if mode != "auto" or not cfg.is_enable_sparse or not used:
        return False
    # auto never changes the growth schedule out from under a default
    # config: the nonzero-iterating kernels live in the rounds learner,
    # so auto engages only where rounds is already the resolved default
    # (TPU) or explicitly pinned — a CPU run with stock params keeps
    # the exact learner over the dense store, byte-identical to pre-
    # sparse behavior.  sparse_store=csr remains the explicit opt-in
    # everywhere.
    growth = getattr(cfg, "tree_growth", "auto")
    if growth == "auto":
        import jax
        if jax.default_backend() != "tpu":
            return False
    elif growth != "rounds":
        return False
    C = plan.num_columns if plan is not None else len(used)
    if C < 128:
        return False
    if plan is None:
        rates = np.asarray([mappers[i].sparse_rate for i in used])
    else:
        nd = np.zeros(plan.num_columns)
        for k, i in enumerate(used):
            nd[int(plan.feat_col[k])] += 1.0 - mappers[i].sparse_rate
        rates = 1.0 - np.minimum(nd, 1.0)
    return float(np.mean(rates)) >= float(cfg.sparse_threshold)


# rows used to estimate pairwise feature conflicts when planning bundles;
# planning is O(sparse_features^2 * rows) so the sample is capped tighter
# than bin_construct_sample_cnt (the estimate only gates which features
# share a column — realized conflicts are counted exactly during binning)
BUNDLE_PLAN_SAMPLE_CNT = 50_000

# smallest row capacity of a streaming (appendable) dataset store; growth
# doubles from here so the capacity ladder is a power-of-two tier set
STREAM_CAPACITY_BASE = 1024


def row_capacity_tier(n: int, base: int = STREAM_CAPACITY_BASE) -> int:
    """Smallest power-of-two-of-`base` capacity >= n.  Device kernels over
    a streaming store (online refit, binned replay) key their compiled
    shapes on the CAPACITY, so appends within a tier never retrace and
    the ladder bounds the total compile count at O(log rows)."""
    cap = max(int(base), 1)
    n = max(int(n), 1)
    while cap < n:
        cap <<= 1
    return cap


def _plan_bundles_from_sample(sample: np.ndarray, mappers: List[BinMapper],
                              used: List[int], cfg: Config
                              ) -> Optional[BundlePlan]:
    """Bundle plan from a raw-valued row sample (bins each used feature
    with its mapper, then runs the greedy conflict-graph planner).
    Returns None when bundling is off or nothing bundles."""
    if not cfg.enable_bundle or not used:
        return None
    n = len(sample)
    if n == 0:
        return None
    if n > BUNDLE_PLAN_SAMPLE_CNT:
        rng = np.random.RandomState(cfg.data_random_seed)
        sample = sample[np.sort(rng.choice(n, BUNDLE_PLAN_SAMPLE_CNT,
                                           replace=False))]
    sb = np.stack([mappers[i].value_to_bin(
        np.asarray(sample[:, i], np.float64)) for i in used])
    nb = np.asarray([mappers[i].num_bin for i in used], np.int32)
    db = np.asarray([mappers[i].default_bin for i in used], np.int32)
    return plan_bundles(sb, nb, db, cfg.max_conflict_rate)


def _log_bundle_state(plan: Optional[BundlePlan], num_used: int,
                      cfg: Config) -> None:
    """The one-line construction log the enable_bundle satellite asks for,
    plus always-on profiling counters for /stats and bench.py."""
    from . import log, profiling
    if plan is None:
        if cfg.verbose >= 1:
            log.info(f"EFB: bundling {'off' if not cfg.enable_bundle else 'inactive (no exclusive features)'}; "
                     f"{num_used} features histogrammed directly")
        return
    n_multi = plan.num_bundles
    profiling.count("bundle.features", num_used)
    profiling.count("bundle.columns", plan.num_columns)
    profiling.count("bundle.packed_features", plan.num_packed)
    if cfg.verbose >= 1:
        log.info(
            f"EFB: bundled {num_used} features into {plan.num_columns} "
            f"columns ({n_multi} bundles holding {plan.num_packed} "
            f"features; sampled conflict rate {plan.est_conflict_rate:.4f} "
            f"summed over bundles, budget {cfg.max_conflict_rate:g} each)")


# ----------------------------------------------------------------------------
# Text parsing (reference src/io/parser.cpp)
# ----------------------------------------------------------------------------

def _detect_format(line: str) -> str:
    """Probe one line: 'libsvm' | 'tsv' | 'csv' (parser.cpp format probing)."""
    toks = line.strip().split()
    if len(toks) > 1 and ":" in toks[1]:
        return "libsvm"
    if "\t" in line:
        return "tsv"
    if "," in line:
        return "csv"
    return "tsv"  # space separated handled like tsv


def parse_text_file(path: str, has_header: bool = False, label_idx: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray, Optional[List[str]]]:
    """Parse a CSV/TSV/LibSVM data file into (X, y, feature_names).

    Auto-detects the format from the first data line like the reference
    Parser::CreateParser.  The label is column `label_idx` for csv/tsv and
    the first token for libsvm.

    The native C++ parser (src/native/loader.cpp) is used when built;
    header names are only needed for has_header files, which keep the
    Python path.
    """
    if not has_header:
        from .native import parse_text_native
        res = parse_text_native(path, has_header, label_idx)
        if res is not None:
            return res[0], res[1], None
    with open(path, "r") as f:
        first = f.readline()
        if not first:
            raise ValueError(f"empty data file: {path}")
    header_names: Optional[List[str]] = None
    skip = 0
    if has_header:
        sep = "\t" if "\t" in first else ("," if "," in first else None)
        header_names = [t.strip() for t in first.strip().split(sep)]
        skip = 1
        with open(path, "r") as f:
            f.readline()
            first = f.readline()
    fmt = _detect_format(first)
    if fmt == "libsvm":
        labels: List[float] = []
        rows: List[Dict[int, float]] = []
        max_idx = -1
        with open(path, "r") as f:
            for _ in range(skip):
                f.readline()
            for line in f:
                line = line.strip()
                if not line:
                    continue
                toks = line.split()
                labels.append(float(toks[0]))
                row: Dict[int, float] = {}
                for t in toks[1:]:
                    if ":" not in t:
                        continue
                    k, v = t.split(":", 1)
                    ki = int(k)
                    row[ki] = float(v)
                    max_idx = max(max_idx, ki)
                rows.append(row)
        X = np.zeros((len(rows), max_idx + 1), dtype=np.float64)
        for i, row in enumerate(rows):
            for k, v in row.items():
                X[i, k] = v
        return X, np.asarray(labels, dtype=np.float64), header_names
    sep = "\t" if fmt == "tsv" else ","
    raw = np.loadtxt(path, delimiter=None if sep == "\t" else sep,
                     skiprows=skip, dtype=np.float64, ndmin=2)
    y = raw[:, label_idx].copy()
    X = np.delete(raw, label_idx, axis=1)
    return X, y, header_names


# ----------------------------------------------------------------------------
# Metadata (reference include/LightGBM/dataset.h:36-248, src/io/metadata.cpp)
# ----------------------------------------------------------------------------

@dataclass
class Metadata:
    label: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float32))
    weights: Optional[np.ndarray] = None        # fp32 [N]
    query_boundaries: Optional[np.ndarray] = None  # int32 [num_queries+1]
    init_score: Optional[np.ndarray] = None     # fp64 [N * num_tree_per_iter]

    @property
    def num_data(self) -> int:
        return int(self.label.shape[0])

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1

    def set_query_from_sizes(self, sizes: np.ndarray) -> None:
        """group sizes -> boundaries (metadata.cpp query loading)."""
        sizes = np.asarray(sizes, dtype=np.int64)
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(sizes)]).astype(np.int32)

    @property
    def query_weights(self) -> Optional[np.ndarray]:
        """Per-query weight = MEAN of the row weights over the query's
        rows, derived only when both row weights and query boundaries
        exist (metadata.cpp:457-470 LoadQueryWeights).  NDCG/MAP average
        per-query results by these (rank_metric.hpp:113-136,
        map_metric.hpp:113-130); lambdarank itself uses ROW weights
        directly (rank_objective.hpp:164-167)."""
        if self.weights is None or self.query_boundaries is None:
            return None
        qb = self.query_boundaries.astype(np.int64)
        sizes = np.diff(qb)
        # prefix-sum differences instead of add.reduceat: reduceat
        # raises/mis-sums on zero-size queries, this is exact for them
        # (an empty query gets weight 0)
        csum = np.concatenate([[0.0], np.cumsum(
            self.weights.astype(np.float64))])
        sums = csum[qb[1:]] - csum[qb[:-1]]
        return (sums / np.maximum(sizes, 1)).astype(np.float32)

    @staticmethod
    def load_side_files(data_path: str, num_data: int) -> "Metadata":
        """Load `<data>.weight`, `<data>.init`, `<data>.query` if present
        (metadata.cpp:372-437)."""
        md = Metadata()
        wpath = data_path + ".weight"
        if os.path.exists(wpath):
            md.weights = np.loadtxt(wpath, dtype=np.float32).reshape(-1)
        ipath = data_path + ".init"
        if os.path.exists(ipath):
            md.init_score = np.loadtxt(ipath, dtype=np.float64).reshape(-1)
        qpath = data_path + ".query"
        if os.path.exists(qpath):
            sizes = np.loadtxt(qpath, dtype=np.int64).reshape(-1)
            md.set_query_from_sizes(sizes)
        return md


# ----------------------------------------------------------------------------
# Dataset
# ----------------------------------------------------------------------------

def _parse_categorical_column(spec: str, feature_names: Optional[List[str]],
                              num_features: int) -> List[int]:
    """Parse the `categorical_column` selector (index list or name: prefix,
    dataset_loader.cpp:22-157)."""
    if not spec:
        return []
    out: List[int] = []
    if spec.startswith("name:"):
        if not feature_names:
            raise ValueError("categorical_column=name: requires a header")
        wanted = spec[5:].split(",")
        for w in wanted:
            out.append(feature_names.index(w.strip()))
    else:
        for tok in spec.replace(",", " ").split():
            out.append(int(tok))
    return [i for i in out if 0 <= i < num_features]


def _resolve_column_selectors(cfg: Config, names: Optional[List[str]],
                              label_idx: int, n_xcols: int
                              ) -> Tuple[Optional[int], Optional[int],
                                         List[int]]:
    """Resolve `weight_column` / `group_column` / `ignore_column` to
    X-space column indices (file columns with the label removed),
    validating ranges and `name:` selectors (dataset_loader.cpp:22-157).
    Returns (weight_xcol, group_xcol, drop_xcols) — weight/group columns
    are included in drop_xcols."""

    def _resolve(spec: str, what: str) -> Optional[int]:
        spec = spec.strip()
        if not spec:
            return None
        if spec.startswith("name:"):
            if not names:
                raise ValueError(
                    f"{what}={spec} needs has_header=true with a header")
            nm = spec[5:].strip()
            if nm not in names:
                raise ValueError(f"{what}: no column named {nm!r}")
            return names.index(nm)
        return int(spec)

    def _xcol(c: int, what: str) -> int:
        if c == label_idx:
            raise ValueError(f"{what} column {c} is the label column")
        if not 0 <= c <= n_xcols:
            raise ValueError(f"{what} column {c} out of range")
        return c - 1 if c > label_idx else c

    drop: List[int] = []
    wi = _resolve(cfg.weight_column, "weight_column")
    xw = None
    if wi is not None:
        xw = _xcol(wi, "weight_column")
        drop.append(xw)
    gi = _resolve(cfg.group_column, "group_column")
    xg = None
    if gi is not None:
        xg = _xcol(gi, "group_column")
        drop.append(xg)
    ign = cfg.ignore_column.strip()
    if ign.startswith("name:"):
        # `name:` prefixes the WHOLE comma-separated list
        # (dataset_loader.cpp ignore-column parsing)
        for nm in ign[5:].split(","):
            ci = _resolve(f"name:{nm.strip()}", "ignore_column")
            if ci is not None:
                drop.append(_xcol(ci, "ignore_column"))
    elif ign:
        for tok in ign.replace(",", " ").split():
            drop.append(_xcol(int(tok), "ignore_column"))
    return xw, xg, drop


def _query_boundaries_from_ids(qid: np.ndarray) -> np.ndarray:
    """Per-row query ids -> boundaries (metadata.cpp group-column
    handling): rows of one query must be contiguous."""
    change = np.nonzero(qid[1:] != qid[:-1])[0] + 1
    starts = np.concatenate([[0], change])
    if len(np.unique(qid)) != len(starts):
        raise ValueError("group_column: rows of the same query must be "
                         "contiguous in the data file")
    return np.concatenate([starts, [len(qid)]]).astype(np.int32)


def load_file_two_round(path: str, cfg: Config,
                        reference: Optional["Dataset"] = None,
                        chunk_rows: int = 0) -> "Dataset":
    """Streaming two-round ingestion for bigger-than-RAM text files
    (reference DatasetLoader two-round mode, dataset_loader.cpp:159-216):

    - pass 1 streams the file in chunks, reservoir-sampling
      `bin_construct_sample_cnt` rows for BinMapper construction and
      collecting only the label/selector columns in full;
    - pass 2 streams again, binning each chunk straight into the uint8/16
      store — the full float64 matrix never exists.

    Peak memory ≈ binned store + one chunk (~60 MB at 28 features), vs
    ~2.4 GB float64 for the one-shot path at HIGGS scale.
    CSV/TSV only (LibSVM keeps the one-shot path).
    """
    import pandas as pd

    # the shared ingestion chunk knob (docs/Distributed-Data.md): peak
    # parse memory of both streaming loaders scales with this, not N
    chunk_rows = chunk_rows or int(cfg.stream_chunk_rows)
    label_idx = 0
    if cfg.label_column.startswith("name:"):
        raise NotImplementedError("label by name requires header support")
    elif cfg.label_column:
        label_idx = int(cfg.label_column)

    with open(path, "r") as f:
        first = f.readline()
        if cfg.has_header:
            first = f.readline()  # probe a DATA line, not the header
    fmt = _detect_format(first)
    if fmt == "libsvm":
        raise ValueError("use_two_round_loading supports csv/tsv only")
    # "tsv" covers any whitespace separation (one-shot path passes
    # delimiter=None to np.loadtxt)
    sep = r"\s+" if fmt == "tsv" else ","

    def chunks():
        return pd.read_csv(path, sep=sep, header=0 if cfg.has_header
                           else None, chunksize=chunk_rows,
                           dtype=np.float64)

    # ---- pass 1: count rows, reservoir-sample, collect label ------------
    # (and the weight/group selector columns in full, like the one-shot
    # path: the reference streams selector columns during its first pass,
    # dataset_loader.cpp:159-216 + :22-157)
    S = int(cfg.bin_construct_sample_cnt)
    rng = np.random.RandomState(cfg.data_random_seed)
    sample: Optional[np.ndarray] = None     # [S, F] reservoir
    filled = 0
    labels: List[np.ndarray] = []
    wvals: List[np.ndarray] = []
    gvals: List[np.ndarray] = []
    names: Optional[List[str]] = None
    sel = None                               # (weight_x, group_x, keep)
    n_seen = 0
    for ch in chunks():
        arr = ch.to_numpy(dtype=np.float64)
        if names is None and cfg.has_header:
            names = [str(c) for c in ch.columns]
        labels.append(arr[:, label_idx].copy())
        if sel is None:
            n_x = arr.shape[1] - 1
            xw, xg, drop = _resolve_column_selectors(cfg, names, label_idx,
                                                     n_x)
            # map every selector to FILE-space once (X-space -> file-space
            # is +1 past the label column); per-chunk reads index arr
            # directly, and the feature take is ONE fused column take of
            # the kept file columns
            def _fcol(c):
                return c + 1 if c >= label_idx else c
            dropped = set(drop)
            use_cols = [_fcol(c) for c in range(n_x) if c not in dropped]
            keep = ([c for c in range(n_x) if c not in dropped]
                    if drop else None)
            sel = (None if xw is None else _fcol(xw),
                   None if xg is None else _fcol(xg), keep, use_cols)
        wcol, gcol, keep, use_cols = sel
        if wcol is not None:
            wvals.append(arr[:, wcol].copy())
        if gcol is not None:
            gvals.append(arr[:, gcol].copy())
        X = arr[:, use_cols]
        if sample is None:
            sample = np.empty((S, X.shape[1]), np.float64)
        take = min(S - filled, len(X))       # fill phase
        if take > 0:
            sample[filled:filled + take] = X[:take]
            filled += take
        rest = X[take:]                      # replacement phase
        if len(rest):
            gidx = np.arange(n_seen + take, n_seen + take + len(rest))
            accept = rng.rand(len(rest)) < S / (gidx + 1.0)
            if accept.any():
                slots = rng.randint(0, S, size=int(accept.sum()))
                sample[slots] = rest[accept]
        n_seen += len(X)
    if sel is None or n_seen == 0:
        # match the one-shot loader's error instead of an opaque unpack
        # failure further down
        raise ValueError(f"empty data file: {path}")
    y = np.concatenate(labels)
    n = len(y)
    sample = sample[:filled]
    md = Metadata.load_side_files(path, n)
    md.label = np.asarray(y, np.float32)
    wcol, gcol, keep, use_cols = sel
    if wcol is not None:
        if md.weights is not None:
            from . import log
            log.warning("weight_column overrides the .weight side file")
        md.weights = np.concatenate(wvals).astype(np.float32)
    if gcol is not None:
        if md.query_boundaries is not None:
            from . import log
            log.warning("group_column overrides the .query side file")
        md.query_boundaries = _query_boundaries_from_ids(
            np.concatenate(gvals))

    x_names = None
    if names:
        x_names = [nm for c, nm in enumerate(names) if c != label_idx]
        if keep is not None:
            x_names = [x_names[c] for c in keep]

    # ---- mappers from the sample ----------------------------------------
    cats = _parse_categorical_column(cfg.categorical_column, x_names,
                                     sample.shape[1])
    if reference is not None:
        if sample.shape[1] != reference.num_total_features:
            raise ValueError("validation data has different #features")
        mappers = reference.mappers
        used = reference.used_features
        plan = reference.bundle_plan
    else:
        mappers = find_bin_mappers(
            sample, cfg.max_bin, cfg.min_data_in_bin, cfg.min_data_in_leaf,
            categorical=cats, sample_cnt=len(sample),
            seed=cfg.data_random_seed, bin_budget=cfg.bin_budget)
        used = [i for i, m in enumerate(mappers) if not m.is_trivial]
        plan = _plan_bundles_from_sample(sample, mappers, used, cfg)
        _log_bundle_state(plan, len(used), cfg)

    # ---- pass 2: bin straight into the store ----------------------------
    ds = Dataset._empty_from_mappers(cfg, mappers, used, n,
                                     sample.shape[1], x_names, plan=plan)
    row = 0
    for ch in chunks():
        arr = ch.to_numpy(dtype=np.float64)
        ds._bin_rows_into(arr[:, use_cols], row)
        row += len(arr)
    ds._check_realized_conflicts()
    ds.metadata = md
    return ds


class Dataset:
    """Binned feature matrix + metadata.

    Attributes
    ----------
    bins : np.ndarray  [num_used_features, num_data] uint8/uint16 bin ids
    num_bins : np.ndarray [num_used_features] int32 per-feature bin counts
    mappers : list[BinMapper], one per RAW feature
    used_features : list[int] raw indices of non-trivial features
    """

    def __init__(self, X: np.ndarray, label: Optional[np.ndarray] = None,
                 config: Optional[Config] = None,
                 reference: Optional["Dataset"] = None,
                 metadata: Optional[Metadata] = None,
                 feature_names: Optional[List[str]] = None,
                 categorical_feature: Sequence[int] = ()):
        cfg = config or Config()
        X = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
        if X.ndim != 2:
            raise ValueError("X must be 2-dimensional")
        n, num_raw = X.shape
        self.num_data = n
        self.num_total_features = num_raw
        self.config = cfg
        self.feature_names = feature_names or [f"Column_{i}" for i in range(num_raw)]

        if reference is not None:
            # align with reference (valid set): reuse its mappers AND its
            # bundle plan — a valid set binned into a different column
            # layout could not share the training walk/unbundle tables
            if num_raw != reference.num_total_features:
                raise ValueError("validation data has different #features")
            self.mappers = reference.mappers
            self.used_features = reference.used_features
            plan = reference.bundle_plan
        else:
            if cfg.bin_find == "sketch":
                # explicit sketch opt-in: mappers from the mergeable
                # quantile summaries over ALL rows (exact whenever eps
                # is tight enough to hold every distinct value) — the
                # same derivation the distributed and streamed
                # construction paths run, so tree parity with those
                # paths is testable from the batch API
                from .sharded.sketch import sketch_columns
                self.mappers = sketch_columns(
                    X, cfg, categorical=categorical_feature
                ).mappers_from_config(cfg)
            else:
                self.mappers = find_bin_mappers(
                    X, cfg.max_bin, cfg.min_data_in_bin,
                    cfg.min_data_in_leaf,
                    categorical=categorical_feature,
                    sample_cnt=cfg.bin_construct_sample_cnt,
                    seed=cfg.data_random_seed,
                    bin_budget=cfg.bin_budget)
            self.used_features = [i for i, m in enumerate(self.mappers)
                                  if not m.is_trivial]
            plan = _plan_bundles_from_sample(X, self.mappers,
                                             self.used_features, cfg)
            _log_bundle_state(plan, len(self.used_features), cfg)
        self._init_store(plan, n)
        # numerical columns go through the native bulk binner when built
        # (src/native/loader.cpp lgbt_bin_numerical); the rest via NumPy
        self._bin_rows_into(X, 0)
        self._check_realized_conflicts()
        # sparse store: training sets by the resolver; valid sets follow
        # their reference's layout — the score updater walks the ELL
        # segments directly (predict_ensemble_binned_sparse), so a csr
        # run never densifies for valid-set scoring (docs/Sparse.md)
        if ((reference is None or reference.sparse is not None)
                and resolve_sparse_store(
                    cfg, self.mappers, self.used_features,
                    self.bundle_plan)):
            self._sparsify_store()

        md = metadata or Metadata()
        if label is not None:
            md.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if md.label.size == 0:
            md.label = np.zeros(n, dtype=np.float32)
        if md.label.size != n:
            raise ValueError("label length mismatch")
        self.metadata = md
        self._device_bins = None

    # -- store access --------------------------------------------------------

    @property
    def bins(self) -> np.ndarray:
        """[C, N] dense binned store.  A sparse dataset materializes it
        LAZILY on first access — counted as tree/sparse_fallbacks so
        silent densification is operator-visible (docs/Sparse.md lists
        the consumers without a sparse path: bundled feature-sharded
        feeds, binary-cache writes, C-API subsets).  Consumers that can
        name themselves call `dense_bins(site=...)` instead, which also
        bumps the site-labeled series."""
        return self.dense_bins()

    def dense_bins(self, site: str = "unlabeled") -> np.ndarray:
        """`bins` with the densifying consumer named: the canonical
        tree/sparse_fallbacks total stays (alerts key on it), and a
        site-labeled series (same registry discipline as the serve/*
        labels) tells operators WHICH consumer densified."""
        if self._bins is None and self.sparse is not None:
            from . import log, profiling
            profiling.count(profiling.SPARSE_FALLBACKS)
            profiling.count(profiling.labeled(profiling.SPARSE_FALLBACKS,
                                              site=site))
            log.warning(
                f"sparse store materialized dense ({self.num_store_columns}"
                f" x {self.num_data} cells) for a consumer without a "
                f"sparse path (site={site})")
            self._bins = self.sparse.densify(self._store_dtype)
        return self._bins

    @bins.setter
    def bins(self, value) -> None:
        self._bins = value

    def sparse_triple(self):
        """Device (cols [N, R] int32, binsv [N, R] int32, zero_bin [C]
        int32) view of the sparse store — the ELL traversal feed for
        the ScoreUpdater / `predict_ensemble_binned_sparse` consumers
        (bin per (row, column) answered by probing the row's stored
        entries, zero bin otherwise).  None for dense datasets."""
        if self.sparse is None:
            return None
        import jax.numpy as jnp
        sp = self.sparse
        n = self.num_data
        return (jnp.asarray(np.ascontiguousarray(sp.cols[:n]),
                            dtype=jnp.int32),
                jnp.asarray(np.ascontiguousarray(
                    sp.bins[:n].astype(np.int32))),
                jnp.asarray(sp.zero_bin, dtype=jnp.int32))

    def _sparsify_store(self) -> None:
        """Convert the freshly-binned dense store to the CSR/ELL sparse
        layout and drop the dense matrix.  The entry set — cells whose
        bin differs from the column's zero bin — is lossless: densify()
        reproduces the dense store bitwise, so sparse and dense
        datasets built from the same rows train identical trees."""
        zb = store_zero_bins(self.mappers, self.used_features,
                             self.bundle_plan)
        dense = self._bins
        nz = dense != zb[:, None].astype(dense.dtype)
        nzr, nzc = np.nonzero(nz.T)          # row-major entry order
        self.sparse = _pack_ell(nzr, nzc, dense[nzc, nzr], dense.shape[1],
                                dense.shape[0], zb, self._store_dtype)
        self._bins = None
        self._device_bins = None

    # -- helpers ------------------------------------------------------------

    def _init_store(self, plan: Optional[BundlePlan], n: int) -> None:
        """Derive the per-feature metadata and allocate the binned store.

        `num_bins` / `is_categorical` keep their ORIGINAL per-used-feature
        semantics (split search and tree building never see bundles);
        `bins` / `store_num_bins` / `max_num_bin` describe the STORED
        columns — identical to the original view when plan is None, the
        narrower bundled layout otherwise."""
        used = self.used_features
        F = len(used)
        self.num_bins = np.array([self.mappers[i].num_bin for i in used],
                                 dtype=np.int32)
        # what split search scans: a categorical feature's bin of the
        # categories its binning dropped is stored, never a threshold
        self.split_num_bins = np.array(
            [self.mappers[i].split_num_bin for i in used], dtype=np.int32)
        self.is_categorical = np.array(
            [self.mappers[i].bin_type == CATEGORICAL for i in used],
            dtype=bool)
        self.bundle_plan = plan
        self.bundle_conflict_rows = 0
        if plan is None:
            self.store_num_bins = self.num_bins
        else:
            self.store_num_bins = plan.col_num_bins
        C = len(self.store_num_bins)
        self.max_num_bin = int(self.store_num_bins.max()) if C else 1
        dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
        self._store_dtype = dtype
        self.sparse = None
        # packed columns rely on 0 meaning "all members at default"
        self.bins = (np.empty((C, n), dtype=dtype) if plan is None
                     else np.zeros((C, n), dtype=dtype))
        self._device_bins = None

    @classmethod
    def _empty_from_mappers(cls, cfg: Config, mappers: List[BinMapper],
                            used: List[int], n: int, num_total: int,
                            feature_names: Optional[List[str]],
                            plan: Optional[BundlePlan] = None) -> "Dataset":
        """Allocate a Dataset shell (store + derived per-feature metadata)
        from existing bin mappers; callers fill `bins` and `metadata`.
        The single place the mapper→store derivation lives — __init__ and
        the streaming two-round loader both use it."""
        ds = cls.__new__(cls)
        ds.config = cfg
        ds.num_data = n
        ds.num_total_features = num_total
        ds.feature_names = (feature_names
                            or [f"Column_{i}" for i in range(num_total)])
        ds.mappers = mappers
        ds.used_features = used
        ds._init_store(plan, n)
        ds.metadata = Metadata()
        return ds

    def _bin_rows_into(self, X: np.ndarray, row0: int) -> None:
        """Bin raw rows X into self.bins[:, row0:row0+len(X)] through
        the SHARED quantization module (quantize.bin_rows_into — the
        train-policy mapper application dataset construction, streaming
        ingestion, and the serving ingress all derive from, so mappers
        can never drift between train and serve).  With a bundle plan,
        packed features fold into their shared column (last writer wins
        on conflicting rows; realized conflicts are counted into
        `bundle_conflict_rows`)."""
        from .quantize import bin_rows_into
        self.bundle_conflict_rows += bin_rows_into(
            X, self.mappers, self.used_features, self.bundle_plan,
            self.bins, row0)

    def _bin_csc_into(self, indptr, indices, data) -> None:
        """Fill the dense store from scipy CSC arrays by the STORED
        entries alone: every store column starts at its zero bin (what
        an absent cell bins to: the member's default bin, 0 for a packed
        column), then each used feature, in inner order, bins
        `data[s:e]` and writes the result at rows `indices[s:e]` under
        quantize.bin_feature_column's rule — a singleton column takes
        the bin, a packed member goes through binning.pack_bundle_column
        over the column's cells at those rows (offset + slot where its
        bin is not the default, last writer wins, rows already non-zero
        counted as conflicts).  O(nnz + C x N) where a dense scratch column per
        feature was O(F x N), and bitwise the same store and conflict
        count: absent cells bin to the default either way, and a stored
        0.0 or NaN goes through the same `value_to_bin`."""
        plan, used, store = self.bundle_plan, self.used_features, self.bins
        store[...] = store_zero_bins(self.mappers, used, plan)[:, None]
        for k, i in enumerate(used):
            s, e = int(indptr[i]), int(indptr[i + 1])
            if s == e:
                continue
            rows = indices[s:e]
            b = self.mappers[i].value_to_bin(data[s:e])
            if plan is None or not plan.feat_packed[k]:
                out = store[k if plan is None else int(plan.feat_col[k])]
                out[rows] = b.astype(store.dtype)
                continue
            out = store[int(plan.feat_col[k])]
            at_rows = out[rows]
            self.bundle_conflict_rows += pack_bundle_column(
                b, int(plan.feat_default[k]), int(plan.feat_offset[k]),
                at_rows)
            out[rows] = at_rows

    # -- streaming append path (online ingestion; ROADMAP items 1 + 5) ------
    #
    # A streaming dataset shares a reference dataset's FROZEN BinMappers
    # and BundlePlan (no re-quantization — incoming chunks bin into the
    # exact store layout the model's trees were rebinned to) and grows
    # its [F_eff, capacity] store along a power-of-two capacity ladder,
    # so the device kernels that consume it (online leaf refit, binned
    # replay) compile once per TIER instead of once per append.

    @property
    def row_capacity(self) -> int:
        """Allocated row slots of the store (== num_data except for
        streaming datasets, whose store grows in capacity tiers)."""
        if self._bins is None and self.sparse is not None:
            return int(self.num_data)
        return int(self.bins.shape[1])

    @classmethod
    def from_stream(cls, chunks, config: Optional[Config] = None,
                    reference: Optional["Dataset"] = None,
                    feature_names: Optional[List[str]] = None,
                    categorical_feature: Sequence[int] = (),
                    capacity: int = 0) -> "Dataset":
        """Out-of-core streamed construction (sharded/ingest.py): a
        sketch pass over the chunk stream derives the bin mappers, then
        each chunk bins straight into the capacity-tiered store — peak
        host memory scales with `stream_chunk_rows`, not the dataset
        length, and while the data fits the sample budget the result is
        BITWISE the batch construction.  `chunks` is a callable
        returning a fresh iterator of (X, y, w) tuples, a list of such
        tuples, or an (X, y[, w]) array tuple; `reference` skips the
        sketch pass and bins against frozen mappers (the online-window
        path)."""
        from .sharded.ingest import dataset_from_stream
        return dataset_from_stream(
            chunks, config=config, reference=reference,
            feature_names=feature_names,
            categorical_feature=categorical_feature, capacity=capacity)

    @classmethod
    def streaming_from(cls, reference: "Dataset",
                       config: Optional[Config] = None,
                       capacity: int = STREAM_CAPACITY_BASE) -> "Dataset":
        """Empty appendable Dataset binning against `reference`'s frozen
        mappers + bundle plan.  `capacity` seeds the tier ladder."""
        cfg = config or reference.config
        cap = row_capacity_tier(capacity)
        ds = cls._empty_from_mappers(cfg, reference.mappers,
                                     list(reference.used_features), cap,
                                     reference.num_total_features,
                                     list(reference.feature_names),
                                     plan=reference.bundle_plan)
        # the unbundled store allocates with np.empty; streaming slots
        # beyond num_data must hold bin 0 (the branch-free sentinel
        # value, and "all members at default" for packed columns)
        ds.bins[:] = 0
        ds.num_data = 0
        return ds

    def _reserve_rows(self, n: int) -> None:
        """Grow the store to the next capacity tier holding n rows."""
        cap = self.row_capacity
        if n <= cap:
            return
        new_cap = row_capacity_tier(n, base=max(cap, 1) * 2)
        grown = np.zeros((self.bins.shape[0], new_cap), self.bins.dtype)
        grown[:, :cap] = self.bins
        self.bins = grown
        self._device_bins = None

    def append_rows(self, X: np.ndarray, label=None, weight=None) -> int:
        """Bin a chunk of raw rows into the store (frozen mappers, no
        re-quantization) and append its labels/weights; returns the new
        row count.  Appends within a capacity tier keep the store (and
        therefore every compiled kernel shape over it) stable."""
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        if X.ndim != 2 or X.shape[1] != self.num_total_features:
            raise ValueError(
                f"append_rows expects [rows, {self.num_total_features}] "
                f"features, got {X.shape}")
        n0, add = self.num_data, len(X)
        if add == 0:
            return n0
        self._reserve_rows(n0 + add)
        self._bin_rows_into(X, n0)
        md = self.metadata
        if label is not None:
            lab = np.asarray(label, np.float32).reshape(-1)
            if lab.size != add:
                raise ValueError("label length mismatch")
            if n0 and md.label.size != n0:
                raise ValueError(
                    "cannot append labeled rows to an unlabeled dataset")
            md.label = np.concatenate([md.label, lab]) if n0 else lab
        elif md.label.size:
            raise ValueError(
                "cannot append unlabeled rows to a labeled dataset")
        if weight is not None:
            w = np.asarray(weight, np.float32).reshape(-1)
            if w.size != add:
                raise ValueError("weight length mismatch")
            if md.weights is None:
                md.weights = (np.concatenate(
                    [np.ones(n0, np.float32), w]) if n0 else w)
            else:
                md.weights = np.concatenate([md.weights, w])
        elif md.weights is not None:
            md.weights = np.concatenate(
                [md.weights, np.ones(add, np.float32)])
        self.num_data = n0 + add
        self._device_bins = None
        return self.num_data

    def reset_rows(self) -> None:
        """Drop all rows but KEEP the capacity tier — the online
        trainer's per-refresh window: compiled kernel shapes over the
        store survive the reset, so steady-state refits never retrace."""
        self.bins[:] = 0
        self.num_data = 0
        self.bundle_conflict_rows = 0
        self.metadata = Metadata()
        self._device_bins = None

    def compacted(self) -> "Dataset":
        """Trimmed [F_eff, num_data] copy of a streaming dataset (the
        capacity slack dropped) — what the training learners consume
        (they size scores and partitions off the store width).  Metadata
        is shared (its arrays are already logical-length)."""
        ds = Dataset._empty_from_mappers(
            self.config, self.mappers, list(self.used_features),
            self.num_data, self.num_total_features,
            list(self.feature_names), plan=self.bundle_plan)
        # explicit copy: at num_data == capacity the slice is the whole
        # array and ascontiguousarray would alias it — reset_rows()
        # would then zero the "copy" in place
        ds.bins = self.bins[:, : self.num_data].copy()
        ds.bundle_conflict_rows = self.bundle_conflict_rows
        ds.metadata = self.metadata
        return ds

    @classmethod
    def from_csc(cls, sp_matrix, label: Optional[np.ndarray],
                 cfg: Config, metadata: Optional[Metadata] = None,
                 feature_names: Optional[List[str]] = None,
                 categorical_feature: Sequence[int] = (),
                 reference: Optional["Dataset"] = None) -> "Dataset":
        """Construct from a scipy sparse matrix: a row sample is
        densified once for BinMapper construction (exactly what the
        dense path samples anyway); then, when `sparse_store` resolves
        sparse, the CSR/ELL store is built DIRECTLY from the CSC
        columns — one dense scratch column at a time, entries extracted
        per store column, so peak memory is sample + one column + the
        nnz-scaled store.  Otherwise (the dense store, bundled or not)
        the columns' stored entries alone are binned and written into
        the [C, N] store over its zero bins (_bin_csc_into): set-up
        costs by the non-zeros, and neither the N×F matrix nor a dense
        column of it ever exists."""
        sp = sp_matrix.tocsc()
        n, num_raw = sp.shape
        # ---- dense row sample for FindBin ---------------------------------
        S = min(int(cfg.bin_construct_sample_cnt), n)
        rng = np.random.RandomState(cfg.data_random_seed)
        rows = (np.sort(rng.choice(n, S, replace=False)) if n > S
                else np.arange(n))
        sample = np.zeros((len(rows), num_raw), np.float64)
        indptr, indices, data = sp.indptr, sp.indices, sp.data
        for j in range(num_raw):
            s, e = int(indptr[j]), int(indptr[j + 1])
            if s == e:
                continue
            pos = np.searchsorted(rows, indices[s:e])
            hit = (pos < len(rows))
            hit[hit] = rows[pos[hit]] == indices[s:e][hit]
            sample[pos[hit], j] = np.asarray(data[s:e], np.float64)[hit]
        if reference is not None:
            if num_raw != reference.num_total_features:
                raise ValueError("validation data has different #features")
            mappers = reference.mappers
            used = reference.used_features
            plan = reference.bundle_plan
        else:
            mappers = find_bin_mappers(
                sample, cfg.max_bin, cfg.min_data_in_bin,
                cfg.min_data_in_leaf, categorical=categorical_feature,
                sample_cnt=len(sample), seed=cfg.data_random_seed,
                bin_budget=cfg.bin_budget)
            used = [i for i, m in enumerate(mappers) if not m.is_trivial]
            plan = _plan_bundles_from_sample(sample, mappers, used, cfg)
            _log_bundle_state(plan, len(used), cfg)
        ds = cls._empty_from_mappers(cfg, mappers, used, n, num_raw,
                                     feature_names, plan=plan)
        if reference is None and resolve_sparse_store(cfg, mappers, used,
                                                      plan):
            ds._build_sparse_from_csc(indptr, indices, data)
        else:
            ds._bin_csc_into(indptr, indices, data)
        ds._check_realized_conflicts()
        md = metadata or Metadata()
        if label is not None:
            md.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if md.label.size == 0:
            md.label = np.zeros(n, dtype=np.float32)
        if md.label.size != n:
            raise ValueError("label length mismatch")
        ds.metadata = md
        return ds

    def _build_sparse_from_csc(self, indptr, indices, data) -> None:
        """Construct the CSR/ELL store STRAIGHT from scipy CSC arrays:
        store columns are binned one dense [N] scratch at a time (the
        dense route's exact per-column semantics, including EFB
        last-writer-wins packing, so entries match the dense store
        bitwise) and only the non-zero-bin cells are kept.  The dense
        [C, N] matrix never materializes."""
        from .quantize import bin_feature_column
        n = self.num_data
        plan = self.bundle_plan
        used = self.used_features
        zb = store_zero_bins(self.mappers, used, plan)
        C = self.num_store_columns
        members: List[List[int]] = [[] for _ in range(C)]
        for k in range(len(used)):
            c = k if plan is None else int(plan.feat_col[k])
            members[c].append(k)
        col = np.empty(n, np.float64)
        scratch = np.zeros(n, self._store_dtype)
        rows_l: List[np.ndarray] = []
        cols_l: List[np.ndarray] = []
        bins_l: List[np.ndarray] = []
        for c in range(C):
            scratch[:] = 0
            for k in members[c]:
                i = used[k]
                col[:] = 0.0
                s, e = int(indptr[i]), int(indptr[i + 1])
                col[indices[s:e]] = data[s:e]
                self.bundle_conflict_rows += bin_feature_column(
                    k, col, self.mappers, used, plan, scratch)
            nz = np.flatnonzero(scratch != int(zb[c]))
            if nz.size:
                rows_l.append(nz.astype(np.int64))
                cols_l.append(np.full(nz.size, c, np.int64))
                bins_l.append(scratch[nz].copy())
        if rows_l:
            rows = np.concatenate(rows_l)
            colsv = np.concatenate(cols_l)
            binsv = np.concatenate(bins_l)
            order = np.argsort(rows, kind="stable")
            rows, colsv, binsv = rows[order], colsv[order], binsv[order]
        else:
            rows = np.zeros(0, np.int64)
            colsv = np.zeros(0, np.int64)
            binsv = np.zeros(0, self._store_dtype)
        self.sparse = _pack_ell(rows, colsv, binsv, n, C, zb,
                                self._store_dtype)
        self._bins = None
        self._device_bins = None

    # -- bundle views --------------------------------------------------------

    @property
    def num_store_columns(self) -> int:
        """Stored (histogrammed) columns — F_eff <= num_features.
        Derived from the per-column metadata so a sparse store answers
        without materializing the dense matrix."""
        return int(len(self.store_num_bins))

    def bundle_feat_table(self) -> Optional[np.ndarray]:
        """[5, F] f32 walk/predicate table, or None when unbundled."""
        if self.bundle_plan is None:
            return None
        return self.bundle_plan.feat_table()

    def unbundle_tables(self, num_bins_padded: int,
                        num_columns_padded: int = 0):
        """(src, dmask) gather tables for ops/split.unbundle_hist, or
        None when the store already is the original per-feature layout.
        num_columns_padded: pass the learner's padded column count when
        it pads the store (see BundlePlan.unbundle_tables)."""
        if self.bundle_plan is None:
            return None
        return self.bundle_plan.unbundle_tables(self.num_bins,
                                                num_bins_padded,
                                                num_columns_padded)

    def search_tables(self, num_bins_padded: int,
                      num_columns_padded: int = 0):
        """StoreCells for a split search in the bundled store's own
        cells (BundlePlan.search_tables), or None: no plan, or a plan
        that packs a categorical feature, which is searched through
        unbundle_tables."""
        if self.bundle_plan is None:
            return None
        # a categorical member packs nothing here (None above), so its
        # bins are its candidates: the split count, not the stored one
        return self.bundle_plan.search_tables(
            self.split_num_bins, self.is_categorical, num_bins_padded,
            num_columns_padded)

    def unbundled_bins(self) -> np.ndarray:
        """Materialize the ORIGINAL [num_features, N] per-feature store
        from the bundled columns (feature-sharded learners need per-
        feature rows; everything else consumes the bundled store)."""
        if self.bundle_plan is None:
            return self.dense_bins(site="unbundled_bins")
        store = self.dense_bins(site="unbundled_bins")
        plan = self.bundle_plan
        F = len(self.used_features)
        out = np.empty((F, self.num_data), store.dtype)
        for k in range(F):
            col = store[int(plan.feat_col[k])]
            if not plan.feat_packed[k]:
                out[k] = col
                continue
            off = int(plan.feat_offset[k])
            d = int(plan.feat_default[k])
            s = col.astype(np.int32) - off
            in_r = (s >= 0) & (s < int(plan.feat_nslots[k]))
            orig = np.where(in_r, s + (s >= d), d)
            out[k] = orig.astype(store.dtype)
        return out

    def sparse_entries(self):
        """Host COO view of the sparse store — (rows int64, cols int32,
        binv int32, zero_bin int32) over exactly the stored cells in
        row-major entry order.  None for dense datasets.  Streaming
        capacity rows past num_data are sliced off, matching
        sparse_triple."""
        if self.sparse is None:
            return None
        sp = self.sparse
        n = self.num_data
        ri, sj = np.nonzero(sp.cols[:n] < sp.num_columns)
        return (ri.astype(np.int64), sp.cols[ri, sj].astype(np.int32),
                sp.bins[ri, sj].astype(np.int32),
                sp.zero_bin.astype(np.int32))

    def unbundled_sparse_entries(self):
        """COO entries of `unbundled_bins()` WITHOUT densifying — the
        feature-sharded / voting learners' sparse feed under EFB.

        Each stored (row, store column, bin) entry decodes to at most
        ONE (row, original feature, original bin) nonzero: the bundle's
        slot windows are disjoint, and an in-window slot value never
        decodes to its member's default bin (s < d -> orig = s != d;
        s >= d -> orig = s + 1 > d — the same decode as unbundled_bins,
        which maps out-of-window values to the default).  Singleton
        columns copy through (stored bins differ from the column zero
        bin, which IS the feature default).  Conflict-remainder entries
        outside every member's window decode to all-defaults and drop.

        Returns (rows int64, feats int32, binv int32, zero_bin_f int32)
        with entries in row-major order and zero_bin_f the per-ORIGINAL-
        feature default bins."""
        ent = self.sparse_entries()
        if ent is None:
            raise ValueError("unbundled_sparse_entries needs a sparse store")
        ri, ci, bi, _ = ent
        zb_f = store_zero_bins(self.mappers, self.used_features, None)
        plan = self.bundle_plan
        if plan is None:
            return ri, ci, bi, zb_f
        order = np.argsort(ci, kind="stable")
        ri, ci, bi = ri[order], ci[order], bi[order]
        out_r, out_f, out_b = [], [], []
        for k in range(len(self.used_features)):
            col = int(plan.feat_col[k])
            lo = np.searchsorted(ci, col, side="left")
            hi = np.searchsorted(ci, col, side="right")
            if lo == hi:
                continue
            rk, bk = ri[lo:hi], bi[lo:hi]
            if plan.feat_packed[k]:
                s = bk - int(plan.feat_offset[k])
                m = (s >= 0) & (s < int(plan.feat_nslots[k]))
                rk, s = rk[m], s[m]
                bk = s + (s >= int(plan.feat_default[k]))
            out_r.append(rk)
            out_f.append(np.full(rk.size, k, np.int32))
            out_b.append(bk.astype(np.int32))
        if not out_r:
            z = np.zeros(0, np.int64)
            return z, z.astype(np.int32), z.astype(np.int32), zb_f
        rows = np.concatenate(out_r)
        order = np.argsort(rows, kind="stable")
        return (rows[order], np.concatenate(out_f)[order],
                np.concatenate(out_b)[order], zb_f)

    def realized_conflict_rate(self) -> float:
        if self.bundle_plan is None or self.num_data == 0:
            return 0.0
        return float(self.bundle_conflict_rows) / float(self.num_data)

    def _check_realized_conflicts(self) -> None:
        """The plan judges exclusivity on a row SAMPLE; binning counts
        conflicts exactly.  When the full data conflicts more than the
        budget promised — in particular ANY conflict under
        max_conflict_rate=0, which is advertised as exactly lossless —
        say so loudly instead of silently degrading."""
        if self.bundle_plan is None or self.bundle_conflict_rows == 0:
            return
        rate = self.realized_conflict_rate()
        budget = float(self.config.max_conflict_rate)
        if budget == 0.0 or rate > budget * max(self.bundle_plan.num_bundles, 1):
            from . import log
            log.warning(
                f"EFB: {self.bundle_conflict_rows} conflicting rows "
                f"(rate {rate:.5f}) exceed what the planning sample "
                f"promised (budget {budget:g}/bundle); conflicting rows "
                "keep only the last-bundled feature's bin. Set "
                "enable_bundle=false (or raise bin_construct_sample_cnt) "
                "for exact training")

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    def inner_to_real(self, inner: int) -> int:
        return self.used_features[inner]

    def real_to_inner(self, real: int) -> int:
        """Inner (used-feature) index, or -1 when the raw feature was
        filtered as trivial."""
        try:
            return self.used_features.index(real)
        except ValueError:
            return -1

    def device_bins(self):
        """[F, N+1] device array with a sentinel row slot at index N
        (bin 0, weight 0) so padded gathers need no branches."""
        if self._device_bins is None:
            import jax.numpy as jnp
            store = self.dense_bins(site="device_bins")
            padded = np.concatenate(
                [store, np.zeros((store.shape[0], 1), store.dtype)],
                axis=1)
            self._device_bins = jnp.asarray(padded.astype(np.int8 if
                padded.dtype == np.uint8 else np.int16))
        return self._device_bins

    def feature_infos(self) -> List[str]:
        return [m.feature_info() for m in self.mappers]

    # -- binary cache (reference dataset.cpp:18,323-407 SaveBinaryFile /
    #    LoadFromBinFile with magic token) --------------------------------
    # Stored as a magic line + npz (allow_pickle=False on load: a data
    # file is untrusted input and must never reach pickle).

    BINARY_MAGIC = "lightgbm_tpu.dataset.v3"

    def save_binary(self, path: str) -> None:
        """Serialize the binned dataset so reloads skip parse+bin.

        A streaming dataset's capacity slack (store columns past
        num_data) is trimmed on the way out, so the cache round-trips
        as a normal dataset — bitwise the store a batch construction of
        the same rows would write — instead of freezing one run's
        capacity tier into the file."""
        md = self.metadata
        store = self.dense_bins(site="binary_cache")
        arrays = {
            "bins": (store if store.shape[1] == self.num_data
                     else np.ascontiguousarray(
                         store[:, : self.num_data])),
            "num_data": np.int64(self.num_data),
            "num_total_features": np.int64(self.num_total_features),
            "used_features": np.asarray(self.used_features, np.int64),
            "feature_names": np.asarray(self.feature_names, dtype="U"),
            "label": md.label,
            "max_bin": np.int64(self.config.max_bin),
            "enable_bundle": np.int64(1 if self.config.enable_bundle else 0),
            "bundle_conflict_rows": np.int64(self.bundle_conflict_rows),
        }
        if self.bundle_plan is not None:
            p = self.bundle_plan
            arrays["bundle_feat"] = np.stack([
                p.feat_col, p.feat_offset, p.feat_default, p.feat_nslots,
                p.feat_packed.astype(np.int32)]).astype(np.int64)
            arrays["bundle_col_bins"] = p.col_num_bins.astype(np.int64)
        for opt, name in ((md.weights, "weights"),
                          (md.query_boundaries, "query_boundaries"),
                          (md.init_score, "init_score")):
            if opt is not None:
                arrays[name] = opt
        for i, m in enumerate(self.mappers):
            arrays[f"m{i}_meta"] = np.asarray(
                [m.bin_type, m.num_bin, 1 if m.is_trivial else 0,
                 m.default_bin], np.int64)
            arrays[f"m{i}_fl"] = np.asarray(
                [m.min_val, m.max_val, m.sparse_rate], np.float64)
            arrays[f"m{i}_upper"] = np.asarray(m.bin_upper_bound, np.float64)
            arrays[f"m{i}_cats"] = np.asarray(m.bin_2_categorical, np.int64)
        # stream straight to disk: for a store of 100M rows a BytesIO
        # staging copy would add a multi-GB compressed buffer to peak
        # RSS at exactly the moment the raw matrix is also resident
        with open(path, "wb") as f:
            f.write(self.BINARY_MAGIC.encode() + b"\n")
            np.savez_compressed(f, **arrays)

    def save_refbin(self, path: str) -> None:
        """Persist ONLY the frozen mapper set (+ bundle plan + used
        features) as a 0-row binary-dataset shell — the serving
        registry's ``.refbin`` sidecar contract for models trained
        offline (docs/serving.md "Binned inference"; the online trainer
        publishes its whole window store instead).  Loads back through
        `quantize.load_refbin` / `from_binary` like any binary
        dataset."""
        shell = Dataset._empty_from_mappers(
            self.config, self.mappers, list(self.used_features), 0,
            self.num_total_features, list(self.feature_names),
            plan=self.bundle_plan)
        shell.save_binary(path)

    @classmethod
    def from_binary(cls, path: str, config: Optional[Config] = None
                    ) -> "Dataset":
        cfg = config or Config()
        with open(path, "rb") as f:
            first = f.readline()
            if first.strip().decode(errors="replace") != cls.BINARY_MAGIC:
                raise ValueError(
                    f"{path} is not a lightgbm_tpu binary dataset")
            npz = np.load(f, allow_pickle=False)
            d = {k: npz[k] for k in npz.files}  # materialize before close
        return cls._from_binary_dict(d, cfg, path)

    @classmethod
    def _from_binary_dict(cls, d: Dict[str, np.ndarray], cfg: Config,
                          path: str) -> "Dataset":
        """Rebuild a Dataset from the already-parsed npz payload — the
        body of `from_binary`, split out so `quantize.load_refbin` can
        hash + parse a sidecar's bytes ONCE instead of re-reading the
        file per stage (`path` is for error messages only)."""
        if int(d["max_bin"]) != cfg.max_bin:
            raise ValueError(
                f"binary dataset {path} was built with max_bin="
                f"{int(d['max_bin'])}, config wants {cfg.max_bin}; "
                "delete the cache to rebuild")
        cached_eb = bool(int(d.get("enable_bundle", 0)))
        if cached_eb != bool(cfg.enable_bundle):
            # a cache built with the other bundling setting would silently
            # change the measured kernel shape — force a rebin instead
            raise ValueError(
                f"binary dataset {path} was built with enable_bundle="
                f"{cached_eb}, config wants {cfg.enable_bundle}; "
                "delete the cache to rebuild")
        ds = cls.__new__(cls)
        ds.config = cfg
        ds.num_data = int(d["num_data"])
        ds.num_total_features = int(d["num_total_features"])
        ds.used_features = [int(i) for i in d["used_features"]]
        ds.feature_names = [str(s) for s in d["feature_names"]]
        ds.mappers = []
        for i in range(ds.num_total_features):
            meta = d[f"m{i}_meta"]
            fl = d[f"m{i}_fl"]
            cats = [int(c) for c in d[f"m{i}_cats"]]
            ds.mappers.append(BinMapper(
                bin_type=int(meta[0]), num_bin=int(meta[1]),
                is_trivial=bool(meta[2]), default_bin=int(meta[3]),
                min_val=float(fl[0]), max_val=float(fl[1]),
                sparse_rate=float(fl[2]),
                bin_upper_bound=d[f"m{i}_upper"],
                bin_2_categorical=cats))
        plan = None
        if "bundle_feat" in d:
            bf = d["bundle_feat"]
            plan = BundlePlan(
                feat_col=bf[0].astype(np.int32),
                feat_offset=bf[1].astype(np.int32),
                feat_default=bf[2].astype(np.int32),
                feat_nslots=bf[3].astype(np.int32),
                feat_packed=bf[4] > 0,
                col_num_bins=d["bundle_col_bins"].astype(np.int32))
        ds._init_store(plan, 0)
        ds.bins = d["bins"]
        ds.bundle_conflict_rows = int(d.get("bundle_conflict_rows", 0))
        ds.metadata = Metadata(
            label=d["label"],
            weights=d["weights"] if "weights" in d else None,
            query_boundaries=(d["query_boundaries"]
                              if "query_boundaries" in d else None),
            init_score=d["init_score"] if "init_score" in d else None)
        ds._device_bins = None
        # the binary cache stores the dense layout; re-derive the
        # sparse store when the config resolves csr so cache hits train
        # the same path as fresh constructions (0-row refbin shells
        # stay dense)
        if ds.num_data and resolve_sparse_store(
                cfg, ds.mappers, ds.used_features, ds.bundle_plan):
            ds._sparsify_store()
        return ds

    @staticmethod
    def _is_binary_file(path: str) -> bool:
        try:
            with open(path, "rb") as f:
                head = f.read(len(Dataset.BINARY_MAGIC) + 1)
            return head.startswith(Dataset.BINARY_MAGIC.encode())
        except OSError:
            return False

    @staticmethod
    def from_file(path: str, config: Optional[Config] = None,
                  reference: Optional["Dataset"] = None) -> "Dataset":
        cfg = config or Config()
        # binary cache: <data>.bin next to the file, or the file itself
        # (reference dataset_loader.cpp:263+ token detection)
        if cfg.enable_load_from_binary_file:
            bin_path = None
            if Dataset._is_binary_file(path):
                bin_path = path
            elif os.path.exists(path + ".bin") and \
                    Dataset._is_binary_file(path + ".bin") and \
                    os.path.getmtime(path + ".bin") >= os.path.getmtime(path):
                bin_path = path + ".bin"
            if bin_path is not None:
                if cfg.verbose >= 1:
                    print(f"[LightGBM-TPU] [Info] loading binary dataset "
                          f"cache {bin_path}", flush=True)
                ds = Dataset.from_binary(bin_path, cfg)
                if reference is not None:
                    # valid-set alignment (reference Dataset::CheckAlign,
                    # dataset.h:298-314): bin mappers must match the
                    # training set's
                    if (ds.num_total_features
                            != reference.num_total_features or
                            any(a.num_bin != b.num_bin for a, b in
                                zip(ds.mappers, reference.mappers))):
                        raise ValueError(
                            f"binary validation data {bin_path} was binned "
                            "differently from the training data")
                return ds
        if cfg.use_two_round_loading:
            # streaming two-pass ingestion: the full float64 matrix never
            # materializes (dataset_loader.cpp:159-216)
            return load_file_two_round(path, cfg, reference)
        label_idx = 0
        if cfg.label_column.startswith("name:"):
            raise NotImplementedError("label by name requires header support")
        elif cfg.label_column:
            label_idx = int(cfg.label_column)
        X, y, names = parse_text_file(path, cfg.has_header, label_idx)
        md = Metadata.load_side_files(path, len(y))

        # ---- in-file column selectors (dataset_loader.cpp:22-157) ----------
        # Indices count the FILE's columns (label included), the reference
        # CSV/TSV convention; `name:` selectors need has_header.
        xw, xg, drop = _resolve_column_selectors(cfg, names, label_idx,
                                                 X.shape[1])
        if xw is not None:
            if md.weights is not None:
                from . import log
                log.warning("weight_column overrides the .weight side file")
            md.weights = X[:, xw].astype(np.float32)
        if xg is not None:
            if md.query_boundaries is not None:
                from . import log
                log.warning("group_column overrides the .query side file")
            md.query_boundaries = _query_boundaries_from_ids(X[:, xg])

        x_names = None
        if names:
            if len(names) == X.shape[1] + 1:
                x_names = [nm for c, nm in enumerate(names) if c != label_idx]
            elif len(names) == X.shape[1]:
                x_names = list(names)
        if drop:
            keep = [c for c in range(X.shape[1]) if c not in set(drop)]
            X = X[:, keep]
            if x_names is not None:
                x_names = [x_names[c] for c in keep]

        cats = _parse_categorical_column(cfg.categorical_column, x_names,
                                         X.shape[1])

        # distributed pre-partition (reference dataset_loader.cpp:554-659
        # + distributed bin finding :733-833): in a multi-process world
        # each process keeps only its row block, with bin mappers derived
        # from a process-allgathered global sample so every rank bins
        # identically
        if cfg.is_pre_partition:
            import jax
            if jax.process_count() > 1:
                from .distributed import (find_bin_mappers_distributed,
                                          local_row_slice)
                if md.query_boundaries is not None:
                    raise NotImplementedError(
                        "pre_partition with query data is not supported "
                        "yet (queries would straddle row blocks)")
                sl = local_row_slice(len(y))
                n_local = sl.stop - sl.start
                if reference is not None:
                    if X.shape[1] != reference.num_total_features:
                        raise ValueError(
                            "validation data has different #features")
                    # valid sets bin with the TRAINING mappers, exactly
                    # like the non-partitioned paths (Dataset::CheckAlign)
                    mappers = reference.mappers
                    plan = reference.bundle_plan
                else:
                    rng = np.random.RandomState(cfg.data_random_seed)
                    take = min(cfg.bin_construct_sample_cnt
                               // jax.process_count() + 1, max(n_local, 1))
                    samp = (np.sort(rng.choice(n_local, take,
                                               replace=False))
                            if n_local > 0 else np.zeros(0, np.int64))
                    # bundling is decided ONCE from the allgathered global
                    # sample: every rank derives the identical plan, so
                    # the sharded stores stay column-aligned
                    mappers, gsample = find_bin_mappers_distributed(
                        X[sl][samp], cfg, categorical=cats,
                        return_sample=True)
                    used0 = [i for i, m in enumerate(mappers)
                             if not m.is_trivial]
                    plan = _plan_bundles_from_sample(gsample, mappers,
                                                     used0, cfg)
                    _log_bundle_state(plan, len(used0), cfg)
                used = [i for i, m in enumerate(mappers) if not m.is_trivial]
                ds = Dataset._empty_from_mappers(
                    cfg, mappers, used, n_local, X.shape[1], x_names,
                    plan=plan)
                ds._bin_rows_into(X[sl], 0)
                ds._check_realized_conflicts()
                init_local = None
                if md.init_score is not None:
                    # init_score may be flattened [N * K] class-major
                    # (score_updater.py consumption): slice per class
                    n_all = len(y)
                    if md.init_score.size % n_all:
                        raise ValueError("init score size mismatch")
                    k = md.init_score.size // n_all
                    init_local = md.init_score.reshape(
                        k, n_all)[:, sl].reshape(-1)
                ds.metadata = Metadata(
                    label=np.asarray(y[sl], np.float32),
                    weights=(None if md.weights is None
                             else md.weights[sl]),
                    init_score=init_local)
                return ds

        ds = Dataset(X, y, cfg, reference=reference, metadata=md,
                     feature_names=x_names, categorical_feature=cats)
        return ds
