"""Shared feature quantization against a frozen BinMapper set.

ONE module owns "raw feature rows → integer bin indices", used by all
three consumers so the mapper application can never drift between
training and serving (ROADMAP item: binned int8 inference):

- **Dataset construction** (`dataset.Dataset.__init__` / the two-round
  loader; `from_csc` applies the same mappers and packing rule to a
  scipy column's stored entries) and **online ingestion**
  (`Dataset.streaming_from` → `append_rows`) both route their row
  chunks through `bin_rows_into` — the TRAIN policy: float64
  searchsorted against the mapper's float64 bounds, NaN mapped to the
  mapper's NaN bin where it has one, else to the bin of value 0.0.
- **Serving ingress** (`serving.PredictorRuntime` with
  ``serve_quantize=binned``) quantizes each request chunk with a
  `FeatureQuantizer` — the SERVE policy, engineered to be
  bitwise-equivalent to the RAW f32 traversal kernel on every possible
  input (see below), so binned scores are bit-identical to raw scores.

Serve-policy exactness argument
-------------------------------

Model thresholds ARE bin upper bounds (`Tree.rebin_to_dataset`: saved
thresholds round-trip through `value_to_bin` exactly), and the raw
kernels compare in float32 (``f32(v) <= f32(t)``).  Quantizing with a
float32 searchsorted over the float32-cast upper bounds makes the
integer compare exact for EVERY raw value: ``bin(v) <= bin(t)`` iff
``uppers32[bin(t)] >= f32(v)`` iff ``f32(v) <= f32(t)`` — including
values that straddle a float64 boundary but collapse onto it in f32
(a float64 searchsorted would misroute those against the f32 kernel).
Non-finite handling mirrors the raw kernels' decisions exactly:

- NaN in a numerical column quantizes to the column's NaN bin where
  its mapper has one (the binned walk sends that bin to the split's
  default side, as the raw kernels send NaN), else to the bin of 0.0
  (the raw kernels compare NaN as 0.0 there).  NaN in a categorical
  column quantizes to the MISSING sentinel — one code past every real
  bin, equal to no categorical bin: it routes RIGHT, as the raw
  kernel's finite mask sends it.
- +/-inf land on the last/first real bin (the raw compare's outcome).
- A finite category absent from the mapper's table quantizes to the
  sentinel too: the raw categorical compare (int truncation behind a
  finite mask) matches no category either.  Exact for category values
  below 2^24 (the raw kernel's own f32 exactness domain).

The sentinel derivation is the mapper set's missing-bin convention for
serving — it replaces the never-populated ``default_left`` node lane
the raw stacks used to carry.
"""
from __future__ import annotations

import hashlib
from typing import List, Optional, Sequence

import numpy as np

from .binning import CATEGORICAL, NUMERICAL, BinMapper, pack_bundle_column
from .log import LightGBMError


def file_sha1(path: str) -> str:
    """sha1 of a file's bytes — the refbin integrity fingerprint (the
    online trainer stamps it into the publish ``.meta.json``; the
    serving registry refuses a binned swap on mismatch)."""
    h = hashlib.sha1()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# ----------------------------------------------------------------------
# TRAIN policy: the store-filling quantization every Dataset build and
# every streaming append runs (moved verbatim from Dataset._bin_rows_into
# so serving could share the module, not re-derived — bitwise identical
# to the pre-refactor binning).
# ----------------------------------------------------------------------

def bin_rows_into(X: np.ndarray, mappers: Sequence[BinMapper],
                  used_features: Sequence[int], plan, store: np.ndarray,
                  row0: int) -> int:
    """Bin raw rows X into ``store[:, row0:row0+len(X)]`` against frozen
    mappers, using the native bulk binner for uint8 numerical columns
    when built.  With a bundle plan, packed features fold into their
    shared column (last writer wins on conflicting rows).  Returns the
    number of realized bundle conflicts observed."""
    dtype = store.dtype
    sl = slice(row0, row0 + len(X))
    conflicts = 0
    num_ks = [k for k, i in enumerate(used_features)
              if mappers[i].bin_type == NUMERICAL
              and (plan is None or not plan.feat_packed[k])]
    done = set()
    if dtype == np.uint8 and num_ks:
        from .native import bin_numerical_native
        cols = [used_features[k] for k in num_ks]
        uppers = [mappers[i].bin_upper_bound for i in cols]
        out = bin_numerical_native(np.ascontiguousarray(X), cols, uppers)
        if out is not None:
            for j, k in enumerate(num_ks):
                c = k if plan is None else int(plan.feat_col[k])
                store[c, sl] = out[j]
                nb = mappers[used_features[k]].nan_bin
                if nb >= 0:
                    # the native binner reads NaN as 0.0; a feature with
                    # a NaN bin takes it here
                    store[c, sl][np.isnan(X[:, used_features[k]])] = nb
            done = set(num_ks)
    for k, i in enumerate(used_features):
        if k in done:
            continue
        b = mappers[i].value_to_bin(X[:, i])
        if plan is None or not plan.feat_packed[k]:
            c = k if plan is None else int(plan.feat_col[k])
            store[c, sl] = b.astype(dtype)
        else:
            conflicts += pack_bundle_column(
                b, int(plan.feat_default[k]), int(plan.feat_offset[k]),
                store[int(plan.feat_col[k]), sl])
    return conflicts


def bin_feature_column(k: int, values: np.ndarray,
                       mappers: Sequence[BinMapper],
                       used_features: Sequence[int], plan,
                       out: np.ndarray) -> int:
    """Bin ONE used feature's raw column into the [N] scratch row `out`
    of its own store column, so the sparse CSR construction can fill a
    per-column scratch without allocating the dense store.  EFB
    last-writer-wins packing semantics are identical to the dense
    route (and to Dataset._bin_csc_into, which applies the same rule
    to a CSC column's stored entries alone).  Returns realized bundle
    conflicts."""
    b = mappers[used_features[k]].value_to_bin(values)
    if plan is None or not plan.feat_packed[k]:
        out[:] = b.astype(out.dtype)
        return 0
    return pack_bundle_column(
        b, int(plan.feat_default[k]), int(plan.feat_offset[k]), out)


# ----------------------------------------------------------------------
# SERVE policy: request-path ingress quantization
# ----------------------------------------------------------------------

# grid-accelerated numeric binning: cells are uniform in the float32
# TOTAL-ORDER KEY space (integer arithmetic end to end — no rounding
# anywhere), each cell stores the bin of its smallest key, and at most
# _GRID_ADJUST boundaries may fall inside any cell (checked at build;
# the grid refines until the budget holds or the feature falls back to
# searchsorted).  Lookup = shift + clip + one table gather + _GRID_ADJUST
# compare-increment steps — ~5x the throughput of numpy's per-value
# binary search on the serving ingress path.
_GRID_TARGET_BITS = 13          # ~8192 cells to start
_GRID_MAX_CELLS = 1 << 16
_GRID_ADJUST = 2


def _f32_keys(a32: np.ndarray) -> np.ndarray:
    """Monotone int64 keys of float32 values: a <= b in f32 iff
    key(a) <= key(b) for non-NaN values with -0.0 pre-normalized to
    +0.0 (the caller adds +0.0f, which is the identity everywhere
    else)."""
    u = np.asarray(a32, np.float32).view(np.uint32).astype(np.int64)
    return np.where(u >> 31, 0xFFFFFFFF - u, u + 0x80000000)


class _NumericGrid:
    """Per-feature acceleration index over the f32-cast upper bounds."""

    __slots__ = ("key0", "shift", "cells", "base", "fkeys_padded", "ok")

    def __init__(self, ub32: np.ndarray):
        fin = (ub32[:-1] + np.float32(0.0)).astype(np.float32)
        self.ok = False
        if fin.size == 0 or not np.isfinite(fin).all():
            return                        # 1-bin feature / inf bounds:
                                          # searchsorted fallback (rare)
        fkeys = _f32_keys(fin)
        span = int(fkeys[-1] - fkeys[0])
        shift = max(0, span.bit_length() - _GRID_TARGET_BITS)
        while True:
            cells = (span >> shift) + 1
            if cells > _GRID_MAX_CELLS:
                return                    # budget unreachable: fallback
            edges = fkeys[0] + (np.arange(cells + 1,
                                          dtype=np.int64) << shift)
            base = np.searchsorted(fkeys, edges, side="left")
            if np.diff(base).max(initial=0) <= _GRID_ADJUST:
                break
            if shift == 0:
                return
            shift -= 1
        self.key0 = int(fkeys[0])
        self.shift = shift
        self.cells = cells
        self.base = base.astype(np.int64)
        self.fkeys_padded = np.concatenate(
            [fkeys, np.full(_GRID_ADJUST, np.iinfo(np.int64).max)])
        self.ok = True

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """bin(v32) for the column's keys — exact: the cell's base bin
        is <= the true bin, at most _GRID_ADJUST boundaries sit in any
        cell, and each adjustment step advances iff the current bound's
        key is still below the value's (NaN keys yield garbage bins the
        caller overwrites with the MISSING sentinel)."""
        idx = np.clip((keys - self.key0) >> self.shift, 0, self.cells - 1)
        b = self.base[idx]
        for _ in range(_GRID_ADJUST):
            b = b + (self.fkeys_padded[b] < keys)
        return b

class FeatureQuantizer:
    """Frozen-mapper ingress quantizer for the binned serving path.

    ``quantize(X)`` maps raw ``[rows, num_total_features]`` requests to
    a ``[rows, num_columns]`` uint8 (uint16 past 255 bins) buffer of
    ORIGINAL per-feature bin ids over the used features — 4x (resp. 2x)
    smaller than the f32 buffer the raw kernel ships to the device, and
    bitwise-routing-equivalent to the raw f32 traversal (module
    docstring).  Bundled (EFB) stores need no remap here: trees speak
    original (feature, bin) space and the request buffer is built in
    it, so ``feat_tbl`` stays None on the request path.
    """

    __slots__ = ("used_features", "num_total_features", "num_columns",
                 "dtype", "missing_bin", "_numeric", "_tables",
                 "_num_ks", "_num_raw", "_num_uppers64", "_grids",
                 "_use_native", "_nan_codes")

    def __init__(self, mappers: Sequence[BinMapper],
                 used_features: Sequence[int]):
        self.used_features = [int(i) for i in used_features]
        self.num_total_features = len(mappers)
        # at least one buffer column so a stump-only model still has a
        # gatherable [rows, 1] buffer
        self.num_columns = max(len(self.used_features), 1)
        max_nb = max((mappers[i].num_bin for i in self.used_features),
                     default=1)
        # the MISSING sentinel needs one free code past every real bin
        if max_nb <= 0xFF:
            self.dtype = np.uint8
            self.missing_bin = 0xFF
        elif max_nb <= 0xFFFF:
            self.dtype = np.uint16
            self.missing_bin = 0xFFFF
        else:
            raise LightGBMError(
                f"cannot quantize serving requests: a mapper has "
                f"{max_nb} bins (> 65535)")
        self._numeric: List[bool] = []
        self._tables: List = []
        for i in self.used_features:
            m = mappers[i]
            if m.bin_type == CATEGORICAL:
                cats = np.asarray(m.bin_2_categorical, np.int64)
                order = np.argsort(cats)
                self._numeric.append(False)
                self._tables.append(
                    (cats[order],
                     np.arange(len(cats), dtype=np.int64)[order]))
            else:
                # f32 bounds: the compare domain of the raw kernels
                self._numeric.append(True)
                self._tables.append(
                    np.asarray(m.bin_upper_bound, np.float64)
                    .astype(np.float32))
        # native bulk-binner plumbing for the numeric block: the f32
        # bounds embedded exactly into f64 (float comparisons agree
        # across the embedding), so the C binary search reproduces the
        # f32 searchsorted bit-for-bit at ~10x the numpy throughput
        self._num_ks = [k for k, isn in enumerate(self._numeric) if isn]
        self._num_raw = [self.used_features[k] for k in self._num_ks]
        self._num_uppers64 = [self._tables[k].astype(np.float64)
                              for k in self._num_ks]
        # NaN's code per numeric feature: its NaN bin where the mapper
        # has one (the split's default side decides, as in the raw
        # kernels), else the bin of 0.0 (NaN compares as 0.0)
        self._nan_codes = np.asarray(
            [mappers[self.used_features[k]].nan_bin
             if mappers[self.used_features[k]].nan_bin >= 0
             else np.searchsorted(self._tables[k], np.float32(0.0),
                                  side="left")
             for k in self._num_ks], self.dtype)
        # probe native availability ONCE: quantize() must not pay the
        # f64 staging copy of every chunk just to learn the library was
        # never built (the common pure-Python install)
        from .native import get_lib
        self._use_native = self.dtype == np.uint8 and get_lib() is not None
        # pure-numpy acceleration when the native library is not built:
        # integer-keyed grid index per numeric feature (exact, with a
        # per-feature searchsorted fallback when its cell budget fails)
        self._grids = [_NumericGrid(self._tables[k])
                       for k in self._num_ks]

    def quantize(self, X: np.ndarray) -> np.ndarray:
        """[rows, num_total_features] raw → [rows, num_columns] bins."""
        X = np.asarray(X)
        n = X.shape[0]
        out = np.zeros((n, self.num_columns), self.dtype)
        miss = self.missing_bin
        # ---- numeric block: one cast, bulk native binning when built ----
        sub32 = None
        if self._num_ks:
            sub32 = X[:, self._num_raw].astype(np.float32)
            nanmask = np.isnan(sub32)
            native_bins = None
            if self._use_native:
                from .native import bin_numerical_native
                sub64 = sub32.astype(np.float64)
                if nanmask.any():
                    # +inf lands in the last real bin; NaN's own code
                    # overwrites these positions below
                    sub64[nanmask] = np.inf
                native_bins = bin_numerical_native(
                    np.ascontiguousarray(sub64),
                    list(range(len(self._num_ks))), self._num_uppers64)
            if native_bins is not None:
                for j, k in enumerate(self._num_ks):
                    out[:, k] = native_bins[j]
            else:
                # grid path: one key pass for the whole block (+0.0f
                # normalizes -0.0 so keys agree with f32 compares)
                keys = _f32_keys(sub32 + np.float32(0.0))
                for j, k in enumerate(self._num_ks):
                    g = self._grids[j]
                    if g.ok:
                        out[:, k] = g.lookup(keys[:, j])
                    else:
                        out[:, k] = np.searchsorted(
                            self._tables[k], sub32[:, j], side="left")
            if nanmask.any():
                out[:, self._num_ks] = np.where(
                    nanmask, self._nan_codes[None, :], out[:, self._num_ks])
        # ---- categorical columns --------------------------------------
        for k, i in enumerate(self.used_features):
            if self._numeric[k]:
                continue
            col = X[:, i].astype(np.float32)
            cats, bins = self._tables[k]
            finite = np.isfinite(col)
            # int truncation behind the finite mask — the raw kernels'
            # categorical compare; the clip only silences the f32→int64
            # overflow warning (clipped magnitudes can match no
            # category either way)
            vi = np.clip(np.where(finite, col, np.float32(-1.0)),
                         -9.2e18, 9.2e18).astype(np.int64)
            if cats.size:
                pos = np.clip(np.searchsorted(cats, vi), 0,
                              cats.size - 1)
                hit = finite & (cats[pos] == vi)
                b = np.where(hit, bins[pos], miss)
            else:
                b = np.full(n, miss, np.int64)
            out[:, k] = b
        return out


# ----------------------------------------------------------------------
# refbin sidecar: the frozen-mapper contract between publisher and fleet
# ----------------------------------------------------------------------

def load_refbin(path: str, expected_sha1: Optional[str] = None):
    """Load a ``.refbin`` frozen-mapper sidecar (binary-dataset format:
    the online trainer publishes the window store, offline models write
    a 0-row `Dataset.save_refbin` shell).  The stored max_bin /
    enable_bundle settings are adopted from the file itself — a refbin
    is self-describing, not subject to the serving process's config.
    With ``expected_sha1`` (the publish meta's fingerprint), a
    mismatching file is refused before it is parsed.  The file is read
    and parsed ONCE (an online-published sidecar is a whole window
    store, and this runs on the registry's hot-swap path)."""
    import io

    from .config import Config
    from .dataset import Dataset
    with open(path, "rb") as f:
        blob = f.read()
    if expected_sha1:
        actual = hashlib.sha1(blob).hexdigest()
        if actual != expected_sha1:
            raise LightGBMError(
                f"refbin sidecar {path} sha1 {actual[:12]} does not match "
                f"the publish meta's {str(expected_sha1)[:12]} (torn "
                "write or stale sidecar); refusing the binned mapper set")
    bio = io.BytesIO(blob)
    first = bio.readline().strip().decode(errors="replace")
    if first != Dataset.BINARY_MAGIC:
        raise LightGBMError(
            f"{path} is not a lightgbm_tpu refbin sidecar")
    npz = np.load(bio, allow_pickle=False)
    d = {k: npz[k] for k in npz.files}
    # sparse_store pinned dense: a refbin is a mapper-set contract —
    # serving consumers read its mappers/plan, never histogram it, so
    # re-deriving a CSR store (then densifying on first .bins read)
    # would be pure hot-swap-path churn
    cfg = Config(max_bin=int(d["max_bin"]),
                 enable_bundle=bool(int(d["enable_bundle"])),
                 sparse_store="dense", verbose=-1)
    return Dataset._from_binary_dict(d, cfg, path)


def _check_thresholds_representable(t, k, refbin, sf: np.ndarray) -> None:
    """Every threshold must BE a bin boundary of the refbin's mappers —
    the condition the bitwise argument actually requires: ``bin(v) <=
    bin(t)`` collapses to the raw ``v <= t`` only when
    ``upper[bin(t)] == t`` exactly (and a categorical threshold must be
    IN the mapper's table, else the rebin maps it to bin 0 and the
    binned walk would match the wrong category).  A sidecar frozen from
    OTHER data — e.g. an online daemon's window mappers when the input
    model trained elsewhere — fails here instead of silently misrouting
    the rows that fall between a model threshold and the sidecar's next
    boundary."""
    thr = np.asarray(t.threshold[:k], np.float64)
    tib = np.asarray(t.threshold_in_bin[:k], np.int64)
    for f in np.unique(sf):
        m = refbin.mappers[int(f)]
        sel = sf == f
        tb = tib[sel]
        if m.bin_type == CATEGORICAL:
            cats = np.asarray(m.bin_2_categorical, np.int64)
            ok = ((tb >= 0) & (tb < cats.size)
                  & (cats[np.clip(tb, 0, max(cats.size - 1, 0))]
                     == thr[sel].astype(np.int64)))
        else:
            ub = np.asarray(m.bin_upper_bound, np.float64)
            ok = ((tb >= 0) & (tb < ub.size)
                  & (ub[np.clip(tb, 0, ub.size - 1)] == thr[sel]))
        if not bool(ok.all()):
            raise LightGBMError(
                "refbin mapper set cannot represent the model's "
                "thresholds exactly (a threshold is not a bin boundary "
                "of the sidecar's mappers); binned serving would "
                "misroute — serve raw, or ship the model's own training "
                "mappers as the sidecar (Dataset.save_refbin; the "
                "online daemon adopts input_model's sidecar)")


def rebin_models_for_serving(models, refbin) -> None:
    """Give every tree in-bin thresholds/inner features for the refbin
    mapper set, refusing combinations that cannot route exactly.

    Loaded trees (the registry path) rebin from their real-valued
    thresholds; in-session trees already carry in-bin data for their
    TRAINING mappers, which is verified to agree with the refbin's.
    EVERY tree then passes the threshold-representability check — the
    actual exactness condition (see `_check_thresholds_representable`).
    A model splitting on a feature the refbin filtered as trivial is
    refused outright: the rebin would freeze that node's routing to one
    side while the raw kernel still compares per-row.
    """
    nt = int(refbin.num_total_features)
    inner_map = np.full(nt, -1, np.int64)
    inner_map[np.asarray(refbin.used_features, np.int64)] = np.arange(
        len(refbin.used_features))
    for t in models:
        k = t.num_leaves - 1
        if k <= 0:
            continue
        sf = np.asarray(t.split_feature[:k], np.int64)
        if int(sf.max(initial=-1)) >= nt or bool(np.any(inner_map[sf] < 0)):
            raise LightGBMError(
                "model splits on a feature that is trivial or absent in "
                "the refbin mapper set; binned serving cannot route it "
                "exactly (serve raw instead)")
        if getattr(t, "needs_rebin", False):
            t.rebin_to_dataset(refbin)
        elif not np.array_equal(inner_map[sf],
                                np.asarray(t.split_feature_inner[:k],
                                           np.int64)):
            raise LightGBMError(
                "refbin sidecar does not match the model's training "
                "mappers (used-feature mapping differs)")
        _check_thresholds_representable(t, k, refbin, sf)
