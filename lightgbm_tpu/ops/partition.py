"""Fused row partition for the rounds learner.

One boosting round reassigns every row: look up its leaf's split
(feature, threshold, is-categorical, new-leaf id), read the row's bin of
that feature, and move the row right when the split sends it there.  The
reference does this as random-access loads per row
(data_partition.hpp:80-130, dense_bin.hpp:67-120); XLA:TPU expresses it
as two one-hot matmuls plus elementwise selects (ops/lookup.py), which
materialize [N, ·] one-hots in HBM — measured 41 ms/round at the
north-star shape (profile_hotpath_measured.json, older than this
kernel).

The pallas kernel fuses the whole step in VMEM per row-chunk:

- ONE int8 [8, S] @ [S, Ck] matmul performs ALL table lookups: the
  slot one-hot (an int32 compare whose 0/1 result narrows to int8;
  S <= 256) and the table rows carry threshold-128, is-cat|default-left flags,
  new-leaf-128, the in-range window bounds lo-128 / hi-128, and the
  split column as two base-128 digits (c_hi, c_lo), every entry in
  int8 range, each product exact, int32 accumulation of a single
  non-zero per column.
- the row's bin of its split column is a compare-reduce over the
  feature axis of the SAME bins block the histogram kernel streams
  (no [N, F] one-hot ever leaves VMEM).
- the left/right decision and the new leaf id are elementwise.

With Exclusive Feature Bundling the stored column packs several original
features; the per-leaf table then carries the STORE-space predicate from
ops/split.bundle_predicate_params: rows inside the feature's slot window
[lo, hi] compare against T, rows outside sit at the feature's default
bin and take the precomputed default-left bit.  An unbundled split is
the degenerate window [0, inf) — the same kernel serves both.

HBM traffic collapses to: bins read once, lid read once, lid2 written
once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .lookup import table_lookup, select_bin_by_feature

# row-chunk ceiling per grid cell; the VMEM model in _partition_layout
# shrinks it for wide stores
_PARTITION_CHUNK = 8192
# budget of that model, bytes of VMEM per grid cell
_PARTITION_VMEM = int(10e6)


def _augment_tbl(tbl: jax.Array) -> jax.Array:
    """Accept the legacy [4, S] (feature, threshold, is-cat, new-leaf)
    table and pad it to the 7-row store-space form with the degenerate
    always-in-range window (lo=0, hi1=2^30, dl=0)."""
    if tbl.shape[0] >= 7:
        return tbl
    S = tbl.shape[1]
    return jnp.concatenate([
        tbl,
        jnp.zeros((1, S), tbl.dtype),                       # lo
        jnp.full((1, S), float(1 << 30), tbl.dtype),        # hi1
        jnp.zeros((1, S), tbl.dtype)])                      # dl


def _partition_kernel(tbl_ref, gb_ref, lid_ref, out_ref, *, S: int,
                      bin_offset: int):
    """tbl_ref [8, S] int8 rows (c_hi, c_lo, T-128, cat, nli-128, lo-128,
    hi1-128, dl); gb_ref [1, F, Ck] int bins (int8 holds value-128 when
    bin_offset); lid_ref/out_ref [1, Ck] int32."""
    lidv = lid_ref[0, :]                                     # [Ck] i32
    # the slot compare runs in int32 and only its result narrows (via
    # i32: an i1 mask has no relayout to the int8 tile) — the v5e VPU
    # has no int8 vector compare
    iota = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    oh = (iota == lidv[None, :]).astype(jnp.int32).astype(jnp.int8)
    r = jnp.dot(tbl_ref[:, :], oh,
                preferred_element_type=jnp.int32)            # [8, Ck]
    fi = r[0] * 128 + r[1]
    ti = r[2] + 128
    ci = r[3] > 0
    nli = r[4] + 128
    lo = r[5] + 128
    hi1 = r[6] + 128
    dl = r[7] > 0

    gb = gb_ref[0]                                           # [F, Ck]
    F = gb.shape[0]
    iof = jax.lax.broadcasted_iota(jnp.int32, (F, 1), 0)
    # exactly one feature row matches per column, so the sum IS the
    # selected bin; padded feature rows are never selected (fi < F)
    vi = jnp.sum(jnp.where(fi[None, :] == iof, gb.astype(jnp.int32), 0),
                 axis=0) + bin_offset                        # [Ck]
    # mask logic, not selects: a select between two i1 vectors lowers
    # through i8 and Mosaic has no i8 -> i1 truncation on this target
    gl = (ci & (vi == ti)) | (~ci & (vi <= ti))
    inwin = (vi >= lo) & (vi <= hi1)
    gl = (inwin & gl) | (~inwin & dl)
    out_ref[0, :] = jnp.where((nli > 0) & ~gl, nli, lidv)


def _slot_lanes(num_slots: int) -> int:
    """The slot axis as the kernel holds it: padded to whole lanes."""
    return 256 if num_slots > 128 else 128


def _partition_layout(F: int, C: int, isz: int, num_slots: int):
    """(Fp, Ck, Cp) of one fused launch over [F, C] bins of `isz` bytes:
    columns as padded to the sublane tile (int8 tiles are (32, 128)),
    the row chunk, rows as padded to it.

    VMEM model: bins block Fp*Ck*isz, its int32 widen Fp*Ck*4, the
    [S, Ck] one-hot — keep under ~10 MB, at most _PARTITION_CHUNK rows
    and at least 512.  Under that ceiling the chunk is the longest
    multiple of 128 rows that divides C, so that a store laid out to the
    histogram kernel's row chunk (ops/histogram.store_alignment: 8192,
    2048 or 1024 rows) is tiled as it stands, whatever its width; only
    where no such length of 512 or more divides C are the rows padded to
    the ceiling."""
    sub = 32 if isz == 1 else 8
    Fp = F + (-F) % sub
    per_row = Fp * (isz + 4) + num_slots
    cap = min(C, _PARTITION_CHUNK,
              max(512, (_PARTITION_VMEM // per_row) // 128 * 128))
    Ck = cap
    if C % cap:
        Ck = next((ck for ck in range(cap // 128 * 128, 511, -128)
                   if C % ck == 0), cap)
    return Fp, Ck, C + (-C) % Ck


def _fused_fits(F: int, isz: int, num_slots: int, backend: str,
                num_bins_padded: int) -> bool:
    """Whether partition_rows takes the fused kernel: the int8 encodings
    are exact (slots <= 256, bins <= 256) and the kernel, which holds
    ALL F feature rows (bins + their int32 widen) per block, gets a
    chunk of 512 rows or more from the VMEM model — that bounds F at
    ~3.8k int8 / ~2.4k int32 features; larger goes to the XLA path."""
    f_fits = 512 * (F * (isz + 4) + 256) <= _PARTITION_VMEM
    return (backend == "pallas" and num_slots <= 256
            and 0 < num_bins_padded <= 256 and f_fits)


def partition_store_copy_rows(F: int, C: int, *, bins_itemsize: int,
                              num_slots: int, backend: str,
                              num_bins_padded: int) -> int:
    """Rows of the store that one partition_rows call over [F, C] bins
    copies into a padded form before its kernel: all C when the columns
    do not fill the sublane tile or no chunk divides the rows, none for
    a store laid out by ops/histogram.store_alignment (and none on the
    XLA path, which pads nothing)."""
    if not _fused_fits(F, bins_itemsize, num_slots, backend,
                       num_bins_padded):
        return 0
    Fp, _, Cp = _partition_layout(F, C, bins_itemsize,
                                  _slot_lanes(num_slots))
    return C if (Fp > F or Cp > C) else 0


@functools.partial(jax.jit, static_argnames=("num_slots", "interpret"))
def _partition_pallas(tbl8, gb_t, lid, *, num_slots: int,
                      interpret: bool = False):
    from jax.experimental import pallas as pl

    F, C = gb_t.shape
    bin_offset = 128 if gb_t.dtype == jnp.int8 else 0
    Fp, Ck, Cp = _partition_layout(F, C, jnp.dtype(gb_t.dtype).itemsize,
                                   num_slots)
    # the fallback for a caller whose store is not laid out to the
    # tiles (static conditions: an aligned store compiles to no pad).
    # Padded feature rows are never selected — fi always names a real
    # feature; pad rows sit in slot 0 and their lid2 is discarded by
    # the caller
    if Fp > F or Cp > C:
        gb_t = jnp.pad(gb_t, ((0, Fp - F), (0, Cp - C)))
        lid = jnp.pad(lid, (0, Cp - C))
        F, C = Fp, Cp
    grid = (C // Ck,)
    out = pl.pallas_call(
        functools.partial(_partition_kernel, S=num_slots,
                          bin_offset=bin_offset),
        out_shape=jax.ShapeDtypeStruct((1, C), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, num_slots), lambda k: (0, 0)),
            pl.BlockSpec((1, F, Ck), lambda k: (0, 0, k)),
            pl.BlockSpec((1, Ck), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, Ck), lambda k: (0, k)),
        interpret=interpret,
    )(tbl8, gb_t[None], lid[None, :])
    return out[0]


def partition_rows(bins_fn: jax.Array, leaf_id: jax.Array,
                   tbl: jax.Array, *, num_slots: int, backend: str = "xla",
                   num_bins_padded: int = 0,
                   interpret: bool = False) -> jax.Array:
    """New leaf id per row after this round's splits.

    bins_fn [F, N] int STORE bins (int8 = value-128 storage); leaf_id [N]
    int32 in [0, num_slots-1); tbl [7, num_slots] f32 rows
    (store column, threshold T, is-categorical, new leaf id, window lo,
    window hi inclusive, default-left) indexed by leaf — the store-space
    predicate of ops/split.bundle_predicate_params.  The legacy [4, S]
    layout is accepted and padded with the always-in-range window.  Row
    values of non-splitting leaves must be 0 (new leaf 0 means "stay",
    leaf 0 is never a NEW leaf).

    Routes to the fused pallas kernel when the int8 encodings are exact
    (slots <= 256, thresholds < 256, column ids < 2^14 i.e. two base-128
    digits); otherwise composes the XLA one-hot lookups.
    """
    tbl = _augment_tbl(tbl)
    if not _fused_fits(bins_fn.shape[0], jnp.dtype(bins_fn.dtype).itemsize,
                       num_slots, backend, num_bins_padded):
        r = table_lookup(tbl, leaf_id, num_slots=num_slots)
        fi = r[0].astype(jnp.int32)
        ti = r[1].astype(jnp.int32)
        ci = r[2] > 0
        nli = r[3].astype(jnp.int32)
        lo = r[4].astype(jnp.int32)
        hi1 = r[5].astype(jnp.int32)
        dl = r[6] > 0
        off = 128 if bins_fn.dtype == jnp.int8 else 0
        vi = select_bin_by_feature(bins_fn, fi) + off
        gl = jnp.where(ci, vi == ti, vi <= ti)
        gl = jnp.where((vi >= lo) & (vi <= hi1), gl, dl)
        return jnp.where((nli > 0) & ~gl, nli, leaf_id)

    S = _slot_lanes(num_slots)
    # pad the slot axis BEFORE the -128 shifts: padded slots must decode
    # to thr=0/nli=0 ("stay"), matching the XLA path's zero table rows —
    # padding the shifted rows with 0 would decode to thr=128/nli=128 and
    # silently MOVE any out-of-contract leaf id to leaf 128
    pad = ((0, S - num_slots),)
    feat = jnp.pad(tbl[0].astype(jnp.int32), pad)
    thr = jnp.pad(tbl[1].astype(jnp.int32), pad)
    cat = jnp.pad(tbl[2].astype(jnp.int32), pad)
    nli = jnp.pad(tbl[3].astype(jnp.int32), pad)
    lo = jnp.pad(tbl[4].astype(jnp.int32), pad)
    # store bins are < 256 on this path, so clamping the degenerate
    # 2^30 window top to 255 keeps the predicate identical in int8
    hi1 = jnp.clip(jnp.pad(tbl[5].astype(jnp.int32), pad), 0, 255)
    dl = jnp.pad(tbl[6].astype(jnp.int32), pad)
    tbl8 = jnp.stack([feat // 128, feat % 128, thr - 128, cat, nli - 128,
                      lo - 128, hi1 - 128, dl]).astype(jnp.int8)
    N = leaf_id.shape[0]
    return _partition_pallas(tbl8, bins_fn, leaf_id, num_slots=S,
                             interpret=interpret)[:N]


def partition_rows_sparse(cols: jax.Array, binsv: jax.Array,
                          zero_bin: jax.Array, leaf_id: jax.Array,
                          tbl: jax.Array, *, num_slots: int) -> jax.Array:
    """partition_rows over the CSR/ELL sparse store (docs/Sparse.md).

    cols/binsv [N, R] per-row (store column, bin) entries (col sentinel
    >= C marks an empty slot); zero_bin [C] int32.  The row's bin of
    its leaf's split column is an ELL probe — at most R compares per
    row, nnz-scaled like the sparse histogram — falling back to the
    column's zero bin when the row stores no entry there.  Table
    semantics match partition_rows exactly (new-leaf 0 = stay)."""
    tbl = _augment_tbl(tbl)
    r = table_lookup(tbl, leaf_id, num_slots=num_slots)
    fi = r[0].astype(jnp.int32)
    ti = r[1].astype(jnp.int32)
    ci = r[2] > 0
    nli = r[3].astype(jnp.int32)
    lo = r[4].astype(jnp.int32)
    hi1 = r[5].astype(jnp.int32)
    dl = r[6] > 0
    hit = cols == fi[:, None]                            # [N, R]
    vi = jnp.sum(jnp.where(hit, binsv, 0), axis=1)
    C = zero_bin.shape[0]
    zb = jnp.maximum(zero_bin[jnp.clip(fi, 0, C - 1)], 0)
    vi = jnp.where(jnp.any(hit, axis=1), vi, zb)
    gl = jnp.where(ci, vi == ti, vi <= ti)
    gl = jnp.where((vi >= lo) & (vi <= hi1), gl, dl)
    return jnp.where((nli > 0) & ~gl, nli, leaf_id)
