"""Histogram construction — the hottest op in the framework.

Replaces the reference's scalar CPU kernels (dense_bin.hpp:67-120) and the
OpenCL local-memory-atomic kernels (ocl/histogram{16,64,256}.cl) with a
TPU-idiomatic formulation: bins are one-hot encoded on the fly and reduced
with a matmul so the accumulation runs on the MXU — there are no fast
device atomics on TPU, but `one_hot(bins).T @ [grad, hess, 1]` is exactly a
`[B, C] @ [C, 3]` contraction (SURVEY.md §7 "hard parts").

Canonical output layout: `[F, 3, B]` float32 — (sum_grad, sum_hess, count)
per feature per bin; B is the padded per-feature bin count.  Accumulation
is fp32 (the reference GPU learner also uses single precision by default,
gpu_tree_learner.h:79-83, and reports accuracy parity).

Two implementations:
- `hist_xla`: chunked one-hot einsum, pure XLA.  Used on CPU (tests) and as
  the fallback.
- `hist_pallas`: Pallas TPU kernel; grid over (feature, row-chunk), one-hot
  built in VMEM and contracted immediately, fp32 accumulate in the output
  block across row-chunks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _pick_chunk(F: int, B: int, target_bytes: int = 1 << 26) -> int:
    """Row-chunk size so the transient one-hot stays ~64MB."""
    per_row = max(F * B * 2, 1)
    c = max(256, target_bytes // per_row)
    return int(2 ** int(np.floor(np.log2(c))))


@functools.partial(jax.jit, static_argnames=("num_bins_padded", "input_dtype"))
def hist_xla(gb: jax.Array, vals: jax.Array, *, num_bins_padded: int,
             input_dtype: str = "float32") -> jax.Array:
    """Chunked one-hot matmul histogram.

    Parameters
    ----------
    gb : [C, F] integer bin ids of the rows taken (sentinel rows have
         arbitrary bins but zero vals).
    vals : [3, C] float32 rows (grad, hess, count-mask).
    Returns [F, 3, B] float32.
    """
    input_dtype = _coerce_dtype(input_dtype)
    C, F = gb.shape
    B = num_bins_padded
    dt = jnp.dtype(input_dtype)
    chunk = min(_pick_chunk(F, B), C)
    n_chunks = max(C // chunk, 1)
    rem = C - n_chunks * chunk

    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def body(acc, args):
        gbc, vc = args  # [chunk, F], [3, chunk]
        oh = (gbc[:, :, None] == jax.lax.broadcasted_iota(
            gbc.dtype, (1, 1, B), 2)).astype(dt)
        acc = acc + jnp.einsum(
            "sc,cfb->fsb", vc.astype(dt), oh,
            preferred_element_type=jnp.float32, precision=prec)
        return acc, None

    acc0 = jnp.zeros((F, 3, B), jnp.float32)
    main = (gb[: n_chunks * chunk].reshape(n_chunks, chunk, F),
            vals[:, : n_chunks * chunk].reshape(3, n_chunks, chunk)
            .transpose(1, 0, 2))
    acc, _ = jax.lax.scan(body, acc0, main)
    if rem:
        acc, _ = body(acc, (gb[n_chunks * chunk:], vals[:, n_chunks * chunk:]))
    return acc


# ----------------------------------------------------------------------------
# Pallas TPU kernel
# ----------------------------------------------------------------------------

FEATURE_GROUP = 8  # features per kernel block (TPU second-minor tiling)

# Row-chunk length per grid cell of the gather-fed kernels (hist_pallas,
# hist_pallas_multileaf): their f32 one-hot transient is 4x the masked
# kernel's int8 one, so they keep a short chunk and self-cap by B.
HIST_CHUNK = 2048


def _gather_chunk_cap(B: int, itemsize: int = 4) -> int:
    """VMEM self-cap for the gather-fed kernels' one-hot transient
    ([Ck, B] in the compute dtype).  Budget 4 MB, 128-aligned.
    The floor is one 128-lane tile — a 512-row floor would let padded
    B >= 2048 blow the stated budget (512*2048*4 = 4.2 MB+)."""
    cap = int(4e6) // (itemsize * max(B, 1))
    return max(128, (cap // 128) * 128)


def _coerce_dtype(input_dtype: str) -> str:
    """int8 means caller-side gradient quantization, which only the
    rounds learner's kernels implement (the dense masked kernel and the
    sparse XLA/pallas pair); a bare int8 cast would TRUNCATE real-valued
    grads, so every other kernel runs f32 and says so (the warning fires
    once per compile, at trace time)."""
    if input_dtype == "int8":
        from .. import log
        # graftlint: allow(retrace-hazard) — deliberate ONE-shot warning at trace time (static branch, never re-fires per iteration)
        log.warning("histogram_dtype=int8 is only supported by the "
                    "batched-rounds learner; using float32 here")
        return "float32"
    return input_dtype



def _hist_kernel(gb_ref, vals_ref, out_ref, *, B: int, input_dtype):
    """One (feature-group, row-chunk) grid cell.

    gb_ref: [1, G, Ck] int32 bins of G features for this row chunk
    vals_ref: [8, Ck] float32 (grad, hess, mask, 5 pad rows)
    out_ref: [1, G, 8, B] float32 accumulated across the chunk grid axis

    TPU block shapes need the last two dims (8|16|32, 128)-aligned
    (pallas guide "tiling"): grouping G=8 features per block keeps every
    ref legal, and the G one-hot matmuls unroll inside the kernel.
    """
    from jax.experimental import pallas as pl

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vals = vals_ref[:].astype(input_dtype)      # [8, Ck]
    # f32 inputs get full-precision (3-pass) MXU matmuls; bf16 runs fast
    prec = (jax.lax.Precision.HIGHEST if input_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    G = gb_ref.shape[1]
    for g in range(G):
        oh = _simple_onehot(gb_ref[0, g, :], B, input_dtype)  # [Ck, B]
        out_ref[0, g, :, :] += jnp.dot(
            vals, oh, preferred_element_type=jnp.float32, precision=prec)


@functools.partial(jax.jit, static_argnames=("num_bins_padded", "input_dtype",
                                             "interpret"))
def hist_pallas(gb_t: jax.Array, vals8: jax.Array, *, num_bins_padded: int,
                input_dtype: str = "bfloat16",
                interpret: bool = False) -> jax.Array:
    """Pallas histogram.  gb_t: [F, C] int32, vals8: [8, C] float32.

    Returns [F, 3, B] float32.
    """
    input_dtype = _coerce_dtype(input_dtype)
    from jax.experimental import pallas as pl

    F, C = gb_t.shape
    B = num_bins_padded
    G = FEATURE_GROUP
    Ck = min(C, HIST_CHUNK, _gather_chunk_cap(B, jnp.dtype(input_dtype).itemsize))
    if C % Ck:
        # pad rows to a chunk multiple; padded slots have zero vals so they
        # contribute nothing to any bin
        pad = Ck - C % Ck
        gb_t = jnp.pad(gb_t, ((0, 0), (0, pad)))
        vals8 = jnp.pad(vals8, ((0, 0), (0, pad)))
        C += pad
    Fg = G * ((F + G - 1) // G)
    if Fg > F:
        gb_t = jnp.pad(gb_t, ((0, Fg - F), (0, 0)))
    gb_g = gb_t.reshape(Fg // G, G, C)
    grid = (Fg // G, C // Ck)
    dt = jnp.dtype(input_dtype)

    out = pl.pallas_call(
        functools.partial(_hist_kernel, B=B, input_dtype=dt),
        out_shape=jax.ShapeDtypeStruct((Fg // G, G, 8, B), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, Ck), lambda f, k: (f, 0, k)),
            pl.BlockSpec((8, Ck), lambda f, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, G, 8, B), lambda f, k: (f, 0, 0, 0)),
        interpret=interpret,
    )(gb_g, vals8)
    return out.reshape(Fg, 8, B)[:F, :3, :]


def _hist_kernel_ml(gb_ref, vals_ref, out_ref, *, B: int, input_dtype):
    """Multi-leaf variant: vals carries M = 3·K channel rows (grad, hess,
    mask for K leaves), so one pass over the rows histograms K leaves at
    once — the M dimension of the MXU matmul is what the per-leaf version
    wastes (M=8, ~6% utilization); at M=128 the systolic array is full.

    gb_ref: [1, G, Ck] int32 ; vals_ref: [M, Ck] f32 ; out_ref: [1, G, M, B]
    """
    from jax.experimental import pallas as pl

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vals = vals_ref[:].astype(input_dtype)
    prec = (jax.lax.Precision.HIGHEST if input_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    G = gb_ref.shape[1]
    for g in range(G):
        oh = _simple_onehot(gb_ref[0, g, :], B, input_dtype)
        out_ref[0, g, :, :] += jnp.dot(
            vals, oh, preferred_element_type=jnp.float32, precision=prec)


@functools.partial(jax.jit, static_argnames=("num_bins_padded", "input_dtype",
                                             "interpret"))
def hist_pallas_multileaf(gb_t: jax.Array, vals: jax.Array, *,
                          num_bins_padded: int,
                          input_dtype: str = "bfloat16",
                          interpret: bool = False) -> jax.Array:
    """Multi-leaf pallas histogram.  gb_t: [F, C] int, vals: [M, C] f32
    (M a multiple of 8, ≤ 128).  Returns [F, M, B] f32."""
    input_dtype = _coerce_dtype(input_dtype)
    from jax.experimental import pallas as pl

    F, C = gb_t.shape
    M = vals.shape[0]
    B = num_bins_padded
    G = FEATURE_GROUP
    Ck = min(C, HIST_CHUNK, _gather_chunk_cap(B, jnp.dtype(input_dtype).itemsize))
    if C % Ck:
        pad = Ck - C % Ck
        gb_t = jnp.pad(gb_t, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))
        C += pad
    Fg = G * ((F + G - 1) // G)
    if Fg > F:
        gb_t = jnp.pad(gb_t, ((0, Fg - F), (0, 0)))
    gb_g = gb_t.reshape(Fg // G, G, C).astype(jnp.int32)
    grid = (Fg // G, C // Ck)
    dt = jnp.dtype(input_dtype)

    out = pl.pallas_call(
        functools.partial(_hist_kernel_ml, B=B, input_dtype=dt),
        out_shape=jax.ShapeDtypeStruct((Fg // G, G, M, B), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, Ck), lambda f, k: (f, 0, k)),
            pl.BlockSpec((M, Ck), lambda f, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, G, M, B), lambda f, k: (f, 0, 0, 0)),
        interpret=interpret,
    )(gb_g, vals)
    return out.reshape(Fg, M, B)[:F]


def hist_multileaf_xla(gb_t: jax.Array, vals: jax.Array, *,
                       num_bins_padded: int,
                       input_dtype: str = "float32") -> jax.Array:
    """XLA fallback for the multi-leaf histogram (CPU tests / non-TPU).
    gb_t: [F, C] int, vals: [M, C] f32 → [F, M, B] f32."""
    input_dtype = _coerce_dtype(input_dtype)
    B = num_bins_padded
    dt = jnp.dtype(input_dtype)
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    C = gb_t.shape[1]
    chunk = min(C, 1 << 16)
    n_chunks = (C + chunk - 1) // chunk
    if C % chunk:
        pad = chunk * n_chunks - C
        gb_t = jnp.pad(gb_t, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, 0), (0, pad)))

    def body(acc, args):
        gbc, vc = args  # [F, chunk], [M, chunk]
        oh = (gbc[:, :, None] == jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, B), 2)).astype(dt)
        return acc + jnp.einsum("mc,fcb->fmb", vc.astype(dt), oh,
                                preferred_element_type=jnp.float32,
                                precision=prec), None

    F = gb_t.shape[0]
    M = vals.shape[0]
    acc0 = jnp.zeros((F, M, B), jnp.float32)
    gbs = gb_t.reshape(F, n_chunks, chunk).transpose(1, 0, 2).astype(jnp.int32)
    vs = vals.reshape(M, n_chunks, chunk).transpose(1, 0, 2)
    acc, _ = jax.lax.scan(body, acc0, (gbs, vs))
    return acc


def _simple_onehot(gb, B, input_dtype):
    """Unpacked one-hot for the gather-fed kernels.  The compare runs in
    int32 whatever the matmul operand dtype: the v5e VPU has no int8 or
    bf16 vector compare (Mosaic: "Target does not support this
    comparison"), so only the RESULT narrows."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    return (gb[:, None] == iota).astype(input_dtype)


def _packed_onehot(gb_ref, g_, B, pack, bins_sub, out_dtype,
                   bin_offset=0, bwin=0):
    """One-hot block [B, Ck] — bins on the sublanes, rows on the lanes —
    for `pack` features sharing the 128 bins of one output lane block:
    feature s of the pack occupies bins [s·bins_sub, (s+1)·bins_sub), so
    ONE [M, Ck] x [B, Ck] contraction over the rows histograms all `pack`
    features — the fix for the 2x bin-axis padding tax at max_bin<=63
    (the reference GPU sweet spot, docs/GPU-Performance.md:153-156):
    without packing a 64-bin histogram still pays full 128-lane MXU work.

    Rows stay on the lanes, where the bins block holds them: a column's
    bins are read as a row vector and compared against a [B, 1] iota, so
    each vreg of eight bins x 128 rows costs one sublane replicate of
    the bins and one compare.  A [Ck, B] one-hot (`gb[:, None] == iota`)
    has to move every bin value from a lane to a sublane and spread it
    over 128 lanes first: one `vbcast_sublane_chunk` per eight rows of
    every column, which sets the kernel's pace at every K and bin count
    (PERF.md section 6, PR 30: 1.9x / 5.9x the kernel time at Epsilon).

    bin_offset: bins may arrive stored as int8 `bin - 128` (the HBM
    layout that fits Expo-scale 11M x 700 on one chip); the widen +
    un-offset runs here in VMEM, never materializing wide bins.

    bwin: first bin of this grid cell's output window (the bin axis may
    be split across a grid dimension so the per-cell output block stays
    one 128-lane tile).  B here is the WINDOW width (the out block's
    lane count), not the full bin count.

    The [B, Ck] equality runs in int32 and only its result narrows to
    the matmul operand dtype.  A compare in the operand dtype (int8 /
    bf16 tiles hold 4x / 2x the lanes) does not exist on the v5e VPU:
    Mosaic refuses `arith.cmpi` on i8 vectors and `arith.cmpf` on bf16
    ("Target does not support this comparison"), and an i1 mask has no
    relayout to the (32, 128) int8 tile, hence the i32 hop below."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0) + bwin
    acc = None
    for s in range(pack):
        gb = gb_ref[0, g_ * pack + s, :].astype(jnp.int32) + bin_offset
        cmp = (gb[None, :] + (s * bins_sub)) == iota
        acc = cmp if acc is None else acc | cmp
    if out_dtype == jnp.int8:
        return acc.astype(jnp.int32).astype(jnp.int8)
    return acc.astype(out_dtype)


# Both operands of the masked kernels' contraction hold the rows on
# their last (lane) axis: vals [Mp, Ck] x one-hot [Bs, Ck] -> [Mp, Bs].
_CONTRACT_ROWS = (((1,), (1,)), ((), ()))


def _hist_kernel_masked(sl_ref, gb_ref, lid_ref, gh_ref, out_ref, *,
                        B: int, K: int, input_dtype, pack: int = 1,
                        bins_sub: int = 0, bin_offset: int = 0,
                        windowed: bool = False):
    """Multi-leaf histogram with the leaf masks built in VMEM.

    sl_ref : [Kp, 128] int32 — small-leaf id per slot, replicated across
             lanes (-1 for empty slots, matches nothing)
    gb_ref : [1, G, Ck] int32, or int8 holding value-128 when
             bin_offset=128 (widened per feature row in _packed_onehot)
    lid_ref: [1, Ck] int32 leaf id per row
    gh_ref : [8, Ck] f32 rows (grad·rm, hess·rm, rm, pad…)
    out_ref: [1, G/pack, Mp, B] f32 — rows [0:K)=grad, [K:2K)=hess,
             [2K:3K)=count; with pack>1 each lane block holds `pack`
             features' bins_sub-wide histograms side by side

    Fusing the mask construction here avoids materializing the [3K, N]
    values matrix in HBM per chunk (the XLA-level formulation round-trips
    ~0.5 GB per histogram pass at N=1M).

    Every per-row operand keeps the rows on the lanes, as the blocks
    deliver them: the leaf mask `lid[None, :] == sl` is [K, Ck], vals is
    [Mp, Ck], the one-hot of a (packed) column is [B, Ck]
    (_packed_onehot), and the contraction runs over the last axis of
    both — no per-row value is ever moved from a lane to a sublane.

    Grid is (feature-blocks, row-chunks), or (feature-blocks,
    bin-windows, row-chunks) when `windowed` — the out block then
    covers one 128-lane bin window.
    """
    from jax.experimental import pallas as pl

    if windowed:
        k = pl.program_id(2)
        bwin = pl.program_id(1) * out_ref.shape[3]
    else:
        k = pl.program_id(1)
        bwin = 0
    Bs = out_ref.shape[3]

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    lid = lid_ref[0, :]                                  # [Ck]
    sl = sl_ref[:K, 0:1]                                 # [K, 1]
    m = (lid[None, :] == sl).astype(input_dtype)         # [K, Ck]
    g = gh_ref[0:1, :].astype(input_dtype)
    h = gh_ref[1:2, :].astype(input_dtype)
    rm = gh_ref[2:3, :].astype(input_dtype)
    vals = jnp.concatenate([m * g, m * h, m * rm], axis=0)   # [3K, Ck]
    Mp = out_ref.shape[2]
    if Mp > 3 * K:
        vals = jnp.concatenate(
            [vals, jnp.zeros((Mp - 3 * K, vals.shape[1]), input_dtype)],
            axis=0)
    prec = (jax.lax.Precision.HIGHEST if input_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    G = gb_ref.shape[1]
    for g_ in range(G // pack):
        oh = _packed_onehot(gb_ref, g_, Bs, pack, bins_sub, input_dtype,
                            bin_offset, bwin)
        out_ref[0, g_, :, :] += jax.lax.dot_general(
            vals, oh, _CONTRACT_ROWS, preferred_element_type=jnp.float32,
            precision=prec)


def _hist_kernel_masked_q(sl_ref, gb_ref, lid_ref, ghq_ref, out_ref, *,
                          B: int, K: int, pack: int = 1,
                          bins_sub: int = 0, bin_offset: int = 0,
                          windowed: bool = False):
    """int8-quantized variant of _hist_kernel_masked: vals and one-hot
    are int8 and the contraction accumulates exactly in int32 (v5e runs
    int8 MXU matmuls at 2x bf16 throughput).  ghq rows are pre-quantized
    (round(grad/scale_g), round(hess/scale_h), 0/1 mask) stored widened
    as int32; dequantization happens in the caller.  Every product is
    exact: masks are 0/1 and |q| <= 127.  Accumulation is exact while
    127 * rows_per_device < 2^31 — the caller enforces a 16M-row bound
    and falls back to bfloat16 beyond it.  Grid as in
    _hist_kernel_masked (bin-window axis only when `windowed`)."""
    from jax.experimental import pallas as pl

    if windowed:
        k = pl.program_id(2)
        bwin = pl.program_id(1) * out_ref.shape[3]
    else:
        k = pl.program_id(1)
        bwin = 0
    Bs = out_ref.shape[3]

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    Mp = out_ref.shape[2]
    lid = lid_ref[0, :]
    sl = sl_ref[:K, 0:1]
    # elementwise mask work stays in i32 (Mosaic has neither int8
    # 'arith.muli' nor an i1->(32,128)-tile relayout on this target);
    # only the matmul OPERANDS are int8 — that is where the 2x
    # throughput lives, and i32->i8 truncation is a supported cast
    m = (lid[None, :] == sl).astype(jnp.int32)       # [K, Ck]
    vals32 = jnp.concatenate([m * ghq_ref[0:1, :], m * ghq_ref[1:2, :],
                              m * ghq_ref[2:3, :]], axis=0)  # [3K, Ck]
    if Mp > 3 * K:
        vals32 = jnp.concatenate(
            [vals32, jnp.zeros((Mp - 3 * K, vals32.shape[1]),
                               jnp.int32)], axis=0)
    vals = vals32.astype(jnp.int8)
    G = gb_ref.shape[1]
    for g_ in range(G // pack):
        oh = _packed_onehot(gb_ref, g_, Bs, pack, bins_sub, jnp.int8,
                            bin_offset, bwin)
        out_ref[0, g_, :, :] += jax.lax.dot_general(
            vals, oh, _CONTRACT_ROWS, preferred_element_type=jnp.int32)


def quantize_gh(gh8):
    """Symmetric int8 quantization of the grad/hess rows.  Returns
    (ghq [8, C] int32 holding int8-ranged values, scale_g, scale_h).
    The mask row is carried through exactly (0/1).  Every launch of a
    tree is over the same gh8, so the rounds learner quantises once a
    tree and hands the triple to hist_multileaf_masked (`ghq=`)."""
    sg = jnp.maximum(jnp.max(jnp.abs(gh8[0])), 1e-30) / 127.0
    sh = jnp.maximum(jnp.max(jnp.abs(gh8[1])), 1e-30) / 127.0
    ghq = jnp.concatenate([
        jnp.round(gh8[0:1] / sg), jnp.round(gh8[1:2] / sh), gh8[2:3],
        jnp.zeros_like(gh8[3:])], axis=0).astype(jnp.int32)
    return ghq, sg, sh


# Row-chunk length per grid cell of the masked kernels at the reference
# block (Mp <= 256 value rows, <= 256 output lanes), keyed by (bin
# storage itemsize, float32 operands?).  These are not a model of the
# transients: Mosaic's scheduler decides how many of the G unrolled
# [Bs, Ck] one-hots and how much of the [Mp, Ck] vals block are live at
# once, and what it asks of the 16 MB VMEM scope is neither linear nor
# monotone in K (int8 bins, int8 operands: 46.6 MB at K=8 but < 16 MB at
# K=84, both at Ck=8192; float32 at K=84 needs 4.4 KB per chunk row,
# three bf16 passes per operand).  Each entry is the largest power of
# two that the TPU compiler accepts for a v5e at EVERY tier the rounds
# learner runs (K = 1, 3, 8, 32, 84; B = 256) — tests/test_tpu_compile.py
# holds the main-path cases, so a change here is checked without a chip.
# The rounds learner lays its store out to these lengths once, on the
# host (store_alignment), so that no launch pads it: a retuned entry
# moves the store's padded row count with it, and has to stay a length
# that ops/partition.py can tile too (tree/store_copy_rows says if not).
_MASKED_CHUNK = {
    (4, False): 8192,   # int32 bins, bf16 / int8 operands
    (4, True): 2048,    # int32 bins, float32 operands
    (1, False): 2048,   # int8 bins (G = 32), bf16 / int8 operands
    (1, True): 1024,    # int8 bins (G = 32), float32 operands
}


def _masked_chunk(Mp: int, bins_itemsize: int, input_dtype: str) -> int:
    """Rows per grid cell of the masked kernels: the compile-validated
    base, shrunk in proportion when the value-row block is taller than
    the reference 256 (LGBT_LEAVES_PER_BATCH > 84)."""
    ck = _MASKED_CHUNK[(bins_itemsize, input_dtype == "float32")]
    if Mp > 256:
        ck = ck * 256 // Mp
    return max(128, ck // 128 * 128)


def packed_bins_layout(max_num_bin: int, num_bins_padded: int):
    """(bins_sub, pack) for the feature-packing optimization: when every
    feature has <= 64 bins, `pack` features share one 128-lane block so
    the one-hot matmul does no padded-lane work (docs/GPU-Performance.md
    :153-156 — max_bin=63 is the accelerator sweet spot the reference
    serves with a dedicated histogram64 kernel).  (0, 1) = no packing."""
    if num_bins_padded != 128 or max_num_bin <= 0:
        return 0, 1
    for bs in (16, 32, 64):
        if max_num_bin <= bs:
            return bs, 128 // bs
    return 0, 1


class _MaskedLayout(NamedTuple):
    """Static shapes of one masked Pallas launch: the feature group and
    its packed size, value rows and slots as padded to the sublane tile,
    the bin window, the row chunk, rows as padded to it and columns as
    padded to the group."""
    G: int
    Gp: int
    pack: int
    bins_sub: int
    Mp: int
    Kp: int
    Bs: int
    Ck: int
    Cp: int
    Fg: int


def _masked_layout(F: int, C: int, K: int, bins_itemsize: int, B: int,
                   input_dtype: str, max_num_bin: int) -> _MaskedLayout:
    """The layout of one masked launch over [F, C] bins for K slots."""
    # int8 bins keep their narrow dtype into the kernel; the int8 VMEM
    # tile is (32, 128), so the feature-group sublane dim grows to 32
    narrow = bins_itemsize == 1
    G = 32 if narrow else FEATURE_GROUP
    Mp = 8 * ((3 * K + 7) // 8)
    Kp = 8 * ((K + 7) // 8)
    bins_sub, pack = packed_bins_layout(max_num_bin, B)
    # bin windows: the output block of one grid cell is at most 256 lanes
    # wide (128 at G=32), the bin axis beyond that goes over the grid.
    # The full [1, Gp, Mp, B] f32 block double-buffers to 4 MB at G=8,
    # Mp=256, B=256 — a quarter of the VMEM scope, and what
    # _MASKED_CHUNK was validated against; G=32 or B=512 would double
    # it.  The one-hot compare is redone per window (cheap), the matmul
    # work is unchanged.
    Bs = min(B, 128 if (narrow or B % 256) else 256)
    Ck = min(C, _masked_chunk(Mp, bins_itemsize, input_dtype))
    Cp = C + (-C) % Ck
    Fg = G * ((F + G - 1) // G)
    return _MaskedLayout(G, G // pack, pack, bins_sub, Mp, Kp, Bs, Ck, Cp, Fg)


def store_alignment(bins_itemsize: int, num_bins_padded: int,
                    input_dtype: str, max_num_bin: int = 0
                    ) -> Tuple[int, int]:
    """(column multiple, row multiple) of a [F, C] store that every
    masked launch takes as it is: at such a shape _masked_layout gives
    Fg == F and Cp == C at every slot tier up to K = 84 (a taller value
    block shrinks the row chunk, _masked_chunk), so hist_multileaf_masked
    compiles to no pad.  The feature group is also the sublane tile that
    ops/partition.py aligns the columns to, and the row chunk a length
    its own chunk divides.  A store of fewer rows than the row multiple
    is one block as it stands (Ck = C)."""
    # read off the layout of a launch over more rows than any chunk
    lay = _masked_layout(1, 1 << 30, 1, bins_itemsize, num_bins_padded,
                         input_dtype, max_num_bin)
    return lay.G, lay.Ck


def masked_store_copy_rows(F: int, C: int, K: int, *, bins_itemsize: int,
                           num_bins_padded: int, backend: str,
                           input_dtype: str, max_num_bin: int = 0) -> int:
    """Rows of the store that one `hist_multileaf_masked` launch over
    [F, C] bins copies into a padded form before its kernel: all C when
    the columns do not fill the feature groups or the rows the row
    chunks, none for a store laid out by store_alignment (and none on
    the XLA fallback, which pads nothing)."""
    if backend != "pallas":
        return 0
    lay = _masked_layout(F, C, K, bins_itemsize, num_bins_padded,
                         input_dtype, max_num_bin)
    return C if (lay.Cp > C or lay.Fg > F) else 0


def masked_hist_mxu_ops(F: int, C: int, K: int, *, bins_itemsize: int,
                        num_bins_padded: int, backend: str,
                        input_dtype: str, max_num_bin: int = 0) -> float:
    """Operations (2 per multiply-add) that the contraction of one
    `hist_multileaf_masked` launch over [F, C] bins and K slots
    performs, padding included — what the MXU is asked to do, not what
    the histogram needs.  Pallas: every row of the padded chunk grid
    against Mp value rows, for each (packed) column of the padded
    feature groups, over the padded bins.  XLA fallback: the plain
    [3K, C] x [F, C, B] einsum."""
    B = num_bins_padded
    if backend != "pallas":
        return 2.0 * C * 3 * K * F * B
    lay = _masked_layout(F, C, K, bins_itemsize, B, input_dtype, max_num_bin)
    return 2.0 * lay.Cp * lay.Mp * (lay.Fg // lay.pack) * B


@functools.partial(jax.jit, static_argnames=("num_bins_padded", "backend",
                                             "input_dtype", "interpret",
                                             "max_num_bin"))
def hist_multileaf_masked(gb_t: jax.Array, lid: jax.Array, gh8: jax.Array,
                          sl: jax.Array, *, num_bins_padded: int,
                          backend: str = "xla",
                          input_dtype: str = "float32",
                          interpret: bool = False,
                          max_num_bin: int = 0, ghq=None) -> jax.Array:
    """Histogram K leaves in one pass, masks built on the fly.

    gb_t: [F, C] int bins; lid: [C] int32 leaf ids; gh8: [8, C] f32
    (grad·rm, hess·rm, rm, pads); sl: [K] int32 leaf ids to histogram
    (-1 = empty slot).  Returns [K, F, 3, B] f32.

    ghq: quantize_gh(gh8), for a caller that launches many passes over
    one gh8 (every launch of a tree) and quantises it once; None
    quantises here.  Read only with int8 operands.

    On the pallas path a store whose shape is not a multiple of
    store_alignment's is padded here, with lid, gh8 and ghq, in every
    launch (masked_store_copy_rows counts it); the conditions are
    static, so a store laid out to those multiples compiles to a
    reshape and no copy.

    max_num_bin (static; 0 = unknown) enables feature packing on the
    pallas path when all bins fit a 16/32/64-lane sub-block.

    input_dtype "int8" (the validated bench default) selects per-pass symmetric
    gradient quantization with exact int32 accumulation: counts are
    exact, grad/hess entries carry <= |max|/254 absolute rounding error
    each — far finer than LightGBM-4-style 2-5 bit quantized training.
    The XLA fallback emulates the same dequantized values so CPU runs
    reproduce the TPU behavior.
    """
    from jax.experimental import pallas as pl

    F, C = gb_t.shape
    K = sl.shape[0]
    B = num_bins_padded
    quant = input_dtype == "int8"
    # int8-STORED bins (value - 128): the HBM layout that fits wide
    # datasets (Expo 11M x 700 = 7.7 GB instead of 30.8 GB int32); the
    # pallas path widens blocks in VMEM, the XLA path fuses the widen
    bin_offset = 128 if gb_t.dtype == jnp.int8 else 0
    # int32-accumulator safety: with constant hessians every row
    # quantizes to exactly 127, so one bin can accumulate 127*C — keep
    # 127*C < 2^31 (and per-bin counts < 2^24 so the f32 conversion
    # stays exact).  Shapes are static, so this resolves at trace time.
    if quant and C > 16_000_000:
        from .. import log
        # graftlint: allow(retrace-hazard) — deliberate ONE-shot warning at trace time (shape is static, fires once per compile)
        log.warning("histogram_dtype=int8 disabled for this pass: "
                    f"{C} rows exceeds the int32-exactness bound "
                    "(16M rows per device); using bfloat16")
        quant = False
        input_dtype = "bfloat16"

    # `lgbt.feed` names what prepares the operands of a pass in a trace.
    # The contraction and its unpacking take the caller's scope
    # (`lgbt.hist`, `lgbt.root`): a scope opened here around the
    # pallas_call would give the custom call its name in place of this
    # function's, which is how a trace finds the kernel
    if backend != "pallas":
        with jax.named_scope("lgbt.feed"):
            if bin_offset:
                gb_t = gb_t.astype(jnp.int32) + bin_offset
            if quant:
                ghq, sg, sh = ghq if ghq is not None else quantize_gh(gh8)
                gh8 = jnp.concatenate([
                    ghq[0:1].astype(jnp.float32) * sg,
                    ghq[1:2].astype(jnp.float32) * sh,
                    gh8[2:3], gh8[3:]], axis=0)
                input_dtype = "float32"
            m = (lid[None, :] == sl[:, None]).astype(jnp.float32)
            vals = jnp.concatenate(
                [m * gh8[0:1], m * gh8[1:2], m * gh8[2:3]], axis=0)  # [3K, C]
        h = hist_multileaf_xla(gb_t, vals, num_bins_padded=B,
                               input_dtype=input_dtype)          # [F, 3K, B]
        return jnp.stack([h[:, :K], h[:, K:2 * K], h[:, 2 * K:3 * K]],
                         axis=2).transpose(1, 0, 2, 3)

    G, Gp, pack, bins_sub, Mp, Kp, Bs, Ck, Cp, Fg = _masked_layout(
        F, C, K, gb_t.dtype.itemsize, B, input_dtype, max_num_bin)
    nB = B // Bs
    with jax.named_scope("lgbt.feed"):
        if quant:
            ghq, sg, sh = ghq if ghq is not None else quantize_gh(gh8)
        if Cp > C:
            # the fallback for a caller whose rows are not laid out to
            # the row chunk: padded rows match no slot and carry zeros
            # (which quantise to zeros and leave the scales alone)
            pad = Cp - C
            gb_t = jnp.pad(gb_t, ((0, 0), (0, pad)))
            lid = jnp.pad(lid, (0, pad), constant_values=-2)
            if quant:
                ghq = jnp.pad(ghq, ((0, 0), (0, pad)))
            else:
                gh8 = jnp.pad(gh8, ((0, 0), (0, pad)))
            C = Cp
        if Fg > F:
            gb_t = jnp.pad(gb_t, ((0, Fg - F), (0, 0)))
        gb_g = gb_t.reshape(Fg // G, G, C)
        if not bin_offset:
            gb_g = gb_g.astype(jnp.int32)
        sl2 = jnp.broadcast_to(
            jnp.pad(sl, (0, Kp - K), constant_values=-1)[:, None], (Kp, 128))
    if nB > 1:
        grid = (Fg // G, nB, C // Ck)
        in_specs = [
            pl.BlockSpec((Kp, 128), lambda f, b, k: (0, 0)),
            pl.BlockSpec((1, G, Ck), lambda f, b, k: (f, 0, k)),
            pl.BlockSpec((1, Ck), lambda f, b, k: (0, k)),
            pl.BlockSpec((8, Ck), lambda f, b, k: (0, k)),
        ]
        out_spec = pl.BlockSpec((1, Gp, Mp, Bs),
                                lambda f, b, k: (f, 0, 0, b))
    else:
        # keep the plain 2-axis grid when no windowing is needed: the
        # singleton middle axis measurably deoptimized Mosaic's
        # pipelining (learner-level 2.5x at Epsilon 63-bin)
        grid = (Fg // G, C // Ck)
        in_specs = [
            pl.BlockSpec((Kp, 128), lambda f, k: (0, 0)),
            pl.BlockSpec((1, G, Ck), lambda f, k: (f, 0, k)),
            pl.BlockSpec((1, Ck), lambda f, k: (0, k)),
            pl.BlockSpec((8, Ck), lambda f, k: (0, k)),
        ]
        out_spec = pl.BlockSpec((1, Gp, Mp, Bs), lambda f, k: (f, 0, 0, 0))

    def unpack(out):
        """[Fg/G, G/pack, Mp, B] kernel output -> [F, Mp, B] with each
        packed feature's bins_sub-wide histogram moved back to lanes
        [0, bins_sub) and the bin axis zero-padded to B (bins >= the
        sub-block width never occur, so zero is exact)."""
        if pack == 1:
            return out.reshape(Fg, Mp, B)[:F]
        h = out.reshape(Fg // G, Gp, Mp, pack, bins_sub)
        h = h.transpose(0, 1, 3, 2, 4).reshape(Fg, Mp, bins_sub)
        return jnp.pad(h, ((0, 0), (0, 0), (0, B - bins_sub)))[:F]

    if quant:
        out = pl.pallas_call(
            functools.partial(_hist_kernel_masked_q, B=B, K=K, pack=pack,
                              bins_sub=bins_sub, bin_offset=bin_offset,
                              windowed=nB > 1),
            out_shape=jax.ShapeDtypeStruct((Fg // G, Gp, Mp, B), jnp.int32),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            interpret=interpret,
        )(sl2, gb_g, lid[None, :], ghq)
        h = unpack(out).astype(jnp.float32)
        return jnp.stack([h[:, :K] * sg, h[:, K:2 * K] * sh,
                          h[:, 2 * K:3 * K]],
                         axis=2).transpose(1, 0, 2, 3)

    dt = jnp.dtype(input_dtype)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_masked, B=B, K=K, input_dtype=dt,
                          pack=pack, bins_sub=bins_sub,
                          bin_offset=bin_offset, windowed=nB > 1),
        out_shape=jax.ShapeDtypeStruct((Fg // G, Gp, Mp, B), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret,
    )(sl2, gb_g, lid[None, :], gh8)
    h = unpack(out)                                      # [F, Mp, B]
    return jnp.stack([h[:, :K], h[:, K:2 * K], h[:, 2 * K:3 * K]],
                     axis=2).transpose(1, 0, 2, 3)


# ----------------------------------------------------------------------------
# Public entry: gather + histogram
# ----------------------------------------------------------------------------

def histogram_from_indices(bins_t: jax.Array, grad_pad: jax.Array,
                           hess_pad: jax.Array, idx: jax.Array, *,
                           num_bins_padded: int, backend: str = "xla",
                           input_dtype: str = "float32") -> jax.Array:
    """hist [F, 3, B] over the rows named by `idx`.

    bins_t : [N+1, F] integer bins, row N is the sentinel (any value).
    grad_pad, hess_pad : [N+1] float32 with [N] == 0.
    idx : [C] int32 row indices, padded with N.

    The sentinel convention makes padded gathers branch-free: padded slots
    contribute zero grad/hess/count (reference instead tracks explicit
    leaf counts via DataPartition, data_partition.hpp:17-208).
    """
    N = grad_pad.shape[0] - 1
    gb = jnp.take(bins_t, idx, axis=0)                  # [C, F]
    g = jnp.take(grad_pad, idx)
    h = jnp.take(hess_pad, idx)
    mask = (idx < N).astype(jnp.float32)
    if backend == "pallas":
        C = idx.shape[0]
        F = bins_t.shape[1]
        vals8 = jnp.zeros((8, C), jnp.float32)
        vals8 = vals8.at[0].set(g).at[1].set(h).at[2].set(mask)
        return hist_pallas(gb.T.astype(jnp.int32), vals8,
                           num_bins_padded=num_bins_padded,
                           input_dtype=input_dtype)
    vals = jnp.stack([g, h, mask])                      # [3, C]
    return hist_xla(gb.astype(jnp.int32), vals,
                    num_bins_padded=num_bins_padded, input_dtype=input_dtype)


# ----------------------------------------------------------------------------
# Sparse (nonzero-iterating) histogram pair — docs/Sparse.md
#
# The store is CSR/ELL-packed: each row carries up to R (column id, bin)
# entries for the cells whose bin differs from the column's known zero
# bin; implicit zeros are reconstructed per leaf as
# `leaf_totals - sum(stored bins)` (exactly the subtraction the dense
# paths already run for larger siblings and EFB default bins,
# ops/split.unbundle_hist).  Compute and histogram input bytes scale
# with nnz instead of F x N — the kernel shape of the sparse GPU
# histogram (arXiv:1706.08359).  Two implementations mirror the dense
# masked pair:
# - `hist_sparse_xla`: per-entry scatter-add (segment-sum), pure XLA —
#   the CPU/test path and the fallback.
# - `hist_sparse_pallas`: entries pre-sorted into FEATURE_GROUP-column
#   windows (ELL-per-window, built once per dataset by
#   `sparse_window_streams`); each grid cell runs the masked kernel's
#   leaf-mask + one-hot matmul over a [Eblk] entry block against the
#   window's flat W*B bin axis, so the MXU contraction idiom carries
#   over unchanged.
# ----------------------------------------------------------------------------

# entry-block length of the sparse pallas kernel: the [Eblk, W*B] f32
# one-hot is the VMEM-dominant transient (512 * 1024 * 4 = 2 MB)
SPARSE_CHUNK = 512


def _slot_of_rows(lid: jax.Array, sl: jax.Array) -> jax.Array:
    """Slot index per row (position of the row's leaf id in `sl`), or K
    for rows whose leaf is not histogrammed this pass — K rows land in
    the scratch slot every scatter below slices off."""
    K = sl.shape[0]
    eq = lid[:, None] == sl[None, :]                     # [N, K]
    return jnp.where(jnp.any(eq, axis=1),
                     jnp.argmax(eq, axis=1).astype(jnp.int32),
                     jnp.int32(K))


def _slot_totals(srow: jax.Array, gh8: jax.Array, K: int) -> jax.Array:
    """[K, 3] per-slot (sum_grad, sum_hess, count) — the zero-bin
    reconstruction anchor, accumulated over ALL rows of each slot.
    Dtype follows gh8: f32 for real-valued grads, int32 for the
    quantized lanes (where the residual must stay an exact integer)."""
    tot = jnp.zeros((K + 1, 3), gh8.dtype)
    return tot.at[srow].add(gh8[:3].T)[:K]


def _apply_zero_bin(hist: jax.Array, tot: jax.Array,
                    zero_bin: jax.Array) -> jax.Array:
    """Reconstruct the implicit-zero bin row of every store column:
    `leaf totals - sum(stored-entry bins)` added at the column's zero
    bin.  hist [K, C, 3, B] (stored entries only), tot [K, 3],
    zero_bin [C] (-1 marks padded columns, which must stay all-zero).
    Exact for counts (integers < 2^24) and within one f32 rounding of
    the dense accumulation for grad/hess — the same property the dense
    paths accept from parent-histogram subtraction.  In the int32
    quantized lanes the subtraction is exact, period."""
    colsum = jnp.sum(hist, axis=3)                       # [K, C, 3]
    resid = jnp.where((zero_bin >= 0)[None, :, None],
                      tot[:, None, :] - colsum,
                      jnp.zeros_like(colsum))
    zb = jnp.clip(zero_bin, 0, hist.shape[3] - 1)
    C = hist.shape[1]
    # advanced-index add: the (arange, zb) pair broadcasts to [C], and
    # with the interleaved slices the advanced axes move first → the
    # update operand is [C, K, 3]
    return hist.at[:, jnp.arange(C), :, zb].add(resid.transpose(1, 0, 2))


def _sparse_quant_ok(input_dtype: str, num_rows: int) -> bool:
    """Trace-time int8 eligibility for the sparse kernels: the same
    int32-exactness bound the dense masked kernel enforces (127·rows
    < 2^31 and per-cell counts < 2^24), keyed on the ROW count — every
    (column, bin) cell accumulates at most one entry per row."""
    if input_dtype != "int8":
        return False
    if num_rows > 16_000_000:
        from .. import log
        # graftlint: allow(retrace-hazard) — deliberate ONE-shot warning at trace time (shape is static, fires once per compile)
        log.warning("histogram_dtype=int8 disabled for this sparse pass: "
                    f"{num_rows} rows exceeds the int32-exactness bound "
                    "(16M rows per device); using float32")
        return False
    return True


@functools.partial(jax.jit, static_argnames=("num_columns_padded",
                                             "num_bins_padded",
                                             "input_dtype"))
def hist_sparse_xla(cols: jax.Array, binsv: jax.Array, zero_bin: jax.Array,
                    lid: jax.Array, gh8: jax.Array, sl: jax.Array, *,
                    num_columns_padded: int,
                    num_bins_padded: int,
                    input_dtype: str = "float32") -> jax.Array:
    """Nonzero-iterating multi-leaf histogram, XLA scatter-add path.

    cols/binsv : [N, R] ELL entries (col >= num_columns_padded marks an
        empty slot); zero_bin [Cp] int32 (-1 = padded column);
    lid [N] int32 leaf ids; gh8 [8, N] f32 (grad·rm, hess·rm, rm, …);
    sl [K] int32 leaf ids to histogram (-1 = empty slot).
    Returns [K, Cp, 3, B] f32 — hist_multileaf_masked's contract over
    the sparse store.

    input_dtype "int8" selects per-pass symmetric gradient quantization
    (quantize_gh — the dense masked kernel's discipline) with the whole
    accumulation held in INTEGER lanes: int32 scatter-add of the
    quantized entries, int32 slot totals, int32 zero-bin residual, ONE
    dequantizing scale at the end.  That makes the XLA path
    bitwise-identical to the pallas sparse int8 kernel for any
    gradients (both are exact integer sums of the same addends), and
    keeps `totals − Σstored` exact in the integer domain.
    """
    N, R = cols.shape
    K = sl.shape[0]
    Cp, B = num_columns_padded, num_bins_padded
    quant = _sparse_quant_ok(input_dtype, N)
    if quant:
        gh_acc, sg, sh = quantize_gh(gh8)               # [8, N] int32
    else:
        gh_acc = gh8
    srow = _slot_of_rows(lid, sl)                        # [N]
    tot = _slot_totals(srow, gh_acc, K)
    valid_e = cols < Cp                                  # [N, R]
    # entries of unslotted rows and empty ELL slots both route to the
    # K scratch slot (sliced off); column/bin ids stay in range
    s_e = jnp.where(valid_e, srow[:, None], K).reshape(-1)
    c_e = jnp.minimum(cols, Cp - 1).reshape(-1)
    b_e = jnp.minimum(binsv, B - 1).reshape(-1)
    v3 = jnp.stack([gh_acc[0], gh_acc[1], gh_acc[2]], axis=1)   # [N, 3]
    v_e = jnp.broadcast_to(v3[:, None, :], (N, R, 3)).reshape(-1, 3)
    hist = jnp.zeros((K + 1, Cp, B, 3), gh_acc.dtype)
    hist = hist.at[s_e, c_e, b_e].add(v_e)[:K]           # [K, Cp, B, 3]
    hist = hist.transpose(0, 1, 3, 2)                    # [K, Cp, 3, B]
    hist = _apply_zero_bin(hist, tot, zero_bin)
    if quant:
        scale = jnp.stack([sg, sh, jnp.float32(1.0)])
        hist = hist.astype(jnp.float32) * scale[None, None, :, None]
    return hist


def sparse_window_streams(cols: np.ndarray, binsv: np.ndarray,
                          num_columns: int, *, num_bins_padded: int,
                          window: int = FEATURE_GROUP,
                          chunk: int = SPARSE_CHUNK):
    """Slot-segmented entry streams for the pallas sparse kernel, built
    ONCE per dataset on the host (the store is static; only leaf ids
    and gradients change per pass).

    Entries sort by store column and split into SLOTS of at most
    `chunk` entries — a hot column simply occupies several slots (its
    partial histograms are summed back at unscatter time), so the
    layout is load-balanced by construction: real CTR column
    distributions are power-law, and padding windows to the hottest
    window's length would blow stream memory up by the skew factor
    (~90x at the acceptance shape).  Here memory is
    O(nnz + chunk * nonempty columns) regardless of skew.

    `window` slots share one kernel grid cell; slot s occupies the
    fixed segment [s*chunk, (s+1)*chunk) of its window's stream, so
    every block is one slot's entries — a fully regular
    (windows, window) grid, no scalar prefetch.

    Returns (e_row [nwin, window*chunk] int32 local row ids,
    e_flat [...] int32 flat local bin ids `lane * B + bin` with
    sentinel window*B for padding, e_valid [...] f32 0/1,
    slot_col [nwin*window] int32 store column per slot — sentinel
    num_columns for padding slots; `unscatter_slot_hist` folds the
    kernel output back to columns).
    """
    N, R = cols.shape
    B = num_bins_padded
    W = window
    keep = (cols < num_columns).ravel()
    r_e = np.repeat(np.arange(N, dtype=np.int64), R)[keep]
    c_e = cols.ravel()[keep].astype(np.int64)
    b_e = binsv.ravel()[keep].astype(np.int64)
    order = np.argsort(c_e, kind="stable")
    r_e, c_e, b_e = r_e[order], c_e[order], b_e[order]
    cnt = np.bincount(c_e, minlength=int(num_columns))
    nslot_c = -(-cnt // chunk)                     # 0 for empty columns
    nslots = int(nslot_c.sum())
    nsp = W * max(1, -(-max(nslots, 1) // W))      # pad to a window mult
    slot_col = np.full(nsp, int(num_columns), np.int32)
    slot_col[:nslots] = np.repeat(np.arange(num_columns), nslot_c)
    # entry -> (slot, position): entries are column-sorted, so an
    # entry's slot is its column's first slot + rank-in-column // chunk
    col_off = np.concatenate([[0], np.cumsum(cnt)])
    slot_base = np.concatenate([[0], np.cumsum(nslot_c)])
    rank = np.arange(r_e.size, dtype=np.int64) - col_off[c_e]
    s_e = slot_base[c_e] + rank // chunk
    p_e = rank % chunk
    nwin = nsp // W
    Ew = W * chunk
    e_row = np.zeros((nwin, Ew), np.int32)
    e_flat = np.full((nwin, Ew), W * B, np.int32)
    e_valid = np.zeros((nwin, Ew), np.float32)
    w_e = s_e // W
    pos = (s_e % W) * chunk + p_e
    e_row[w_e, pos] = r_e
    e_flat[w_e, pos] = (s_e % W) * B + b_e
    e_valid[w_e, pos] = 1.0
    return e_row, e_flat, e_valid, slot_col


def unscatter_slot_hist(h_slots: jax.Array, slot_col: jax.Array,
                        num_columns: int) -> jax.Array:
    """[nslots, Mp, B] per-slot partial histograms -> [Cp, Mp, B] by
    summing each column's slots (histograms are additive, so splitting
    a hot column across slots is exact).  Sentinel slots drop."""
    Cp = num_columns
    out = jnp.zeros((Cp + 1,) + h_slots.shape[1:], h_slots.dtype)
    return out.at[slot_col].add(h_slots)[:Cp]


def _hist_kernel_sparse(sl_ref, fb_ref, lid_ref, gh_ref, out_ref, *,
                        WB: int, K: int, input_dtype):
    """One (window, entry-chunk) grid cell of the sparse histogram.

    sl_ref : [Kp, 128] int32 slot leaf ids (replicated across lanes)
    fb_ref : [1, 1, Eblk] int32 flat local bin ids (sentinel WB matches
             no lane)
    lid_ref: [1, 1, Eblk] int32 leaf id of each entry's row
    gh_ref : [1, 8, Eblk] f32 (g·valid, h·valid, valid, pads)
    out_ref: [1, Mp, WB] f32 accumulated across the chunk grid axis

    Identical inner shape to _hist_kernel_masked (leaf masks in VMEM,
    one [Mp, Eblk] @ [Eblk, WB] MXU contraction) — only the one-hot
    axis is the window's flat (local column, bin) product.  The compare
    runs in int32, like every compare on this chip.
    """
    from jax.experimental import pallas as pl

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    lid = lid_ref[0, 0, :]                               # [Eblk]
    sl = sl_ref[:K, 0:1]                                 # [K, 1]
    m = (lid[None, :] == sl).astype(input_dtype)         # [K, Eblk]
    g = gh_ref[0, 0:1, :].astype(input_dtype)
    h = gh_ref[0, 1:2, :].astype(input_dtype)
    rm = gh_ref[0, 2:3, :].astype(input_dtype)
    vals = jnp.concatenate([m * g, m * h, m * rm], axis=0)   # [3K, Eblk]
    Mp = out_ref.shape[1]
    if Mp > 3 * K:
        vals = jnp.concatenate(
            [vals, jnp.zeros((Mp - 3 * K, vals.shape[1]), input_dtype)],
            axis=0)
    prec = (jax.lax.Precision.HIGHEST if input_dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    fb = fb_ref[0, 0, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, WB), 1)
    oh = (fb[:, None] == iota).astype(input_dtype)       # [Eblk, WB]
    out_ref[0, :, :] += jnp.dot(vals, oh,
                                preferred_element_type=jnp.float32,
                                precision=prec)


def _hist_kernel_sparse_q(sl_ref, fb_ref, lid_ref, gh_ref, out_ref, *,
                          WB: int, K: int):
    """Quantized variant of _hist_kernel_sparse: gh_ref carries
    int8-ranged int32 quantized entries, the MXU contraction runs
    int8 x int8 -> int32 and the [1, Mp, WB] output accumulates EXACT
    int32 partial histograms (dequantized once, outside, after the
    slot unscatter and integer zero-bin reconstruction).

    As in _hist_kernel_masked_q, elementwise mask work stays in i32
    (Mosaic has no int8 'arith.muli' on this target) — only the matmul
    OPERANDS are int8, which is where the throughput lives, and the
    i32->i8 truncation is a supported cast (values are int8-ranged by
    construction)."""
    from jax.experimental import pallas as pl

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    lid = lid_ref[0, 0, :]                               # [Eblk]
    sl = sl_ref[:K, 0:1]                                 # [K, 1]
    m = (lid[None, :] == sl).astype(jnp.int32)           # [K, Eblk]
    vals32 = jnp.concatenate([m * gh_ref[0, 0:1, :], m * gh_ref[0, 1:2, :],
                              m * gh_ref[0, 2:3, :]], axis=0)   # [3K, Eblk]
    Mp = out_ref.shape[1]
    if Mp > 3 * K:
        vals32 = jnp.concatenate(
            [vals32, jnp.zeros((Mp - 3 * K, vals32.shape[1]), jnp.int32)],
            axis=0)
    vals = vals32.astype(jnp.int8)
    fb = fb_ref[0, 0, :]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, WB), 1)
    # flat ids reach W*B = 1024, so the compare runs in int32; only the
    # RESULT narrows to int8 (0/1 — exact)
    oh = (fb[:, None] == iota).astype(jnp.int8)          # [Eblk, WB]
    out_ref[0, :, :] += jnp.dot(vals, oh,
                                preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_columns_padded",
                                             "num_bins_padded",
                                             "input_dtype", "interpret"))
def hist_sparse_pallas(e_row: jax.Array, e_flat: jax.Array,
                       e_valid: jax.Array, slot_col: jax.Array,
                       zero_bin: jax.Array,
                       lid: jax.Array, gh8: jax.Array, sl: jax.Array, *,
                       num_columns_padded: int, num_bins_padded: int,
                       input_dtype: str = "float32",
                       interpret: bool = False) -> jax.Array:
    """Pallas sparse histogram over slot-segmented entry streams
    (sparse_window_streams).  Per-pass state (leaf ids, gradients) is
    looked up per entry OUTSIDE the kernel — nnz-sized XLA gathers —
    then the grid runs (windows, entry-chunks) and the per-slot
    partial histograms fold back to columns (unscatter_slot_hist).
    Returns [K, Cp, 3, B] f32 with the zero bin reconstructed.

    input_dtype "int8" routes to _hist_kernel_sparse_q: quantized
    entries ride int8 MXU operands into an exact int32 accumulator, the
    slot unscatter and zero-bin residual stay integer, and ONE scale
    dequantizes at the end — bitwise-identical to hist_sparse_xla's
    int8 branch (same integer addends, exact sums in any order)."""
    quant = _sparse_quant_ok(input_dtype, lid.shape[0])
    if not quant:
        input_dtype = _coerce_dtype(input_dtype)
    from jax.experimental import pallas as pl

    nwin, Ew = e_row.shape
    K = sl.shape[0]
    Cp, B = num_columns_padded, num_bins_padded
    W = FEATURE_GROUP
    WB = W * B
    Eblk = min(Ew, SPARSE_CHUNK)
    if quant:
        gh_src, sg, sh = quantize_gh(gh8)               # [8, N] int32
        acc_dt = jnp.int32
        kern = functools.partial(_hist_kernel_sparse_q, WB=WB, K=K)
    else:
        gh_src = gh8
        acc_dt = jnp.float32
        kern = functools.partial(_hist_kernel_sparse, WB=WB, K=K,
                                 input_dtype=jnp.dtype(input_dtype))
    srow = _slot_of_rows(lid, sl)
    tot = _slot_totals(srow, gh_src, K)
    lid_e = jnp.take(lid, e_row.reshape(-1)).reshape(nwin, Ew)
    ghm = (jnp.take(gh_src[:3], e_row.reshape(-1), axis=1)
           .reshape(3, nwin, Ew).transpose(1, 0, 2))     # [nwin, 3, Ew]
    ghm = ghm * e_valid[:, None, :].astype(acc_dt)
    ghm = jnp.concatenate(
        [ghm, jnp.zeros((nwin, 5, Ew), acc_dt)], axis=1)
    Mp = 8 * ((3 * K + 7) // 8)
    Kp = 8 * ((K + 7) // 8)
    sl2 = jnp.broadcast_to(jnp.pad(sl, (0, Kp - K),
                                   constant_values=-1)[:, None], (Kp, 128))
    grid = (nwin, Ew // Eblk)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((nwin, Mp, WB), acc_dt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Kp, 128), lambda w, k: (0, 0)),
            # a singleton second-minor axis: Mosaic wants the last two
            # block dims (8, 128)-divisible or equal to the array's own
            pl.BlockSpec((1, 1, Eblk), lambda w, k: (w, 0, k)),
            pl.BlockSpec((1, 1, Eblk), lambda w, k: (w, 0, k)),
            pl.BlockSpec((1, 8, Eblk), lambda w, k: (w, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, Mp, WB), lambda w, k: (w, 0, 0)),
        interpret=interpret,
    )(sl2, e_flat[:, None, :], lid_e[:, None, :], ghm)
    # [nwin, Mp, W, B] → [nslots, Mp, B] → columns → [K, Cp, 3, B]
    h_slots = (out.reshape(nwin, Mp, W, B).transpose(0, 2, 1, 3)
               .reshape(nwin * W, Mp, B))
    h = unscatter_slot_hist(h_slots, slot_col, Cp)
    hist = jnp.stack([h[:, :K], h[:, K:2 * K], h[:, 2 * K:3 * K]],
                     axis=2).transpose(1, 0, 2, 3)       # [K, Cp, 3, B]
    hist = _apply_zero_bin(hist, tot, zero_bin)
    if quant:
        scale = jnp.stack([sg, sh, jnp.float32(1.0)])
        hist = hist.astype(jnp.float32) * scale[None, None, :, None]
    return hist


def hist_sparse_multileaf(sp, lid: jax.Array, gh8: jax.Array,
                          sl: jax.Array, *, num_columns_padded: int,
                          num_bins_padded: int, backend: str = "xla",
                          input_dtype: str = "float32",
                          interpret: bool = False) -> jax.Array:
    """Dispatch over the sparse store pytree (cols, binsv, zero_bin,
    e_row, e_flat, e_valid, slot_col): the slot-stream pallas kernel on
    TPU, the scatter-add XLA path elsewhere (stream arrays are then
    empty placeholders).  Same [K, F, 3, B] contract as
    hist_multileaf_masked."""
    cols, binsv, zero_bin, e_row, e_flat, e_valid, slot_col = sp
    if backend == "pallas":
        return hist_sparse_pallas(
            e_row, e_flat, e_valid, slot_col, zero_bin, lid, gh8, sl,
            num_columns_padded=num_columns_padded,
            num_bins_padded=num_bins_padded, input_dtype=input_dtype,
            interpret=interpret)
    return hist_sparse_xla(cols, binsv, zero_bin, lid, gh8, sl,
                           num_columns_padded=num_columns_padded,
                           num_bins_padded=num_bins_padded,
                           input_dtype=input_dtype)


def histogram_full_masked(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                          mask: jax.Array, *, num_bins_padded: int,
                          input_dtype: str = "float32") -> jax.Array:
    """Full-scan masked histogram over ALL rows (no gather) — used by
    the fused leaf-wise learner, whose one-leaf-at-a-time passes keep
    mask construction cheaper than maintaining a row partition.

    bins: [F, N] (no sentinel), mask: [N] float32 0/1 row weights.
    Returns [F, 3, B] float32.
    """
    vals = jnp.stack([grad * mask, hess * mask, mask])   # [3, N]
    return hist_xla(bins.T.astype(jnp.int32), vals,
                    num_bins_padded=num_bins_padded, input_dtype=input_dtype)


def histogram_full_sparse(cols: jax.Array, binsv: jax.Array,
                          zero_bin: jax.Array, grad: jax.Array,
                          hess: jax.Array, mask: jax.Array, *,
                          num_columns_padded: int, num_bins_padded: int,
                          input_dtype: str = "float32") -> jax.Array:
    """histogram_full_masked's contract over a per-shard ELL window —
    the fused (feature-sharded / voting) learners' sparse feed.

    cols/binsv: [N, R] ELL entries in the shard's LOCAL column space
    (col >= num_columns_padded marks an empty slot); zero_bin [Cp] int32
    (-1 = padded column); grad/hess [N] f32; mask [N] f32 0/1 row
    weights.  Returns [Cp, 3, B] f32 — masked rows contribute zero to
    both the stored entries and the totals, so the zero-bin residual is
    exact for any mask (the K=1 specialization of hist_sparse_xla).

    int8 coerces like the dense fused feed does (_coerce_dtype): the
    fused learners' quantized story is the rounds learner's — keeping
    both feeds f32 preserves the sparse-vs-dense dyadic-bitwise parity
    contract per learner.
    """
    N = grad.shape[0]
    gh8 = jnp.concatenate(
        [jnp.stack([grad * mask, hess * mask, mask]),
         jnp.zeros((5, N), jnp.float32)], axis=0)
    lid = jnp.zeros((N,), jnp.int32)
    sl = jnp.zeros((1,), jnp.int32)
    h = hist_sparse_xla(cols, binsv, zero_bin, lid, gh8, sl,
                        num_columns_padded=num_columns_padded,
                        num_bins_padded=num_bins_padded,
                        input_dtype=_coerce_dtype(input_dtype))
    return h[0]
