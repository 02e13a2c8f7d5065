"""Small-table row lookups as one-hot matmuls.

XLA:TPU lowers `table[ids]` for a [N]-sized `ids` to a serialized gather
that runs at well under 1 GB/s — measured 65 ms for a 256-entry lookup at
N=4M, which made the two per-round partition lookups cost MORE than the
histogram matmul itself (the reference does these as random-access loads,
dense_bin.hpp:67-120; TPU has no fast vector gather).  A one-hot matmul
(`one_hot(ids) @ table`) runs the same lookup on the MXU in ~5 ms and is
EXACT: each output row sums exactly one non-zero product, so any f32 table
value round-trips bit-for-bit under HIGHEST precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_CHUNK = 1 << 17
_PALLAS_CHUNK = 8192


def _lookup_kernel(tbl_ref, ids_ref, out_ref, *, S: int):
    ids = ids_ref[0, :]                                      # [Ck] i32
    oh = (ids[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (S, 1), 0)).astype(jnp.float32)           # [S, Ck]
    out_ref[:, :] = jnp.dot(tbl_ref[:, :], oh,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lookup_pallas(tables: jax.Array, ids: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """Fused lookup: the [S, Ck] one-hot lives only in VMEM, so HBM
    traffic is ids in + [T, N] out (the XLA scan formulation writes the
    one-hot through HBM — ~13 ms per 10.5M-row lookup at S=256)."""
    from jax.experimental import pallas as pl

    T, S = tables.shape
    N = ids.shape[0]
    if T < 8:                       # sublane-align the table rows
        tables = jnp.pad(tables, ((0, 8 - T), (0, 0)))
    # VMEM: S*Ck*4 one-hot + blocks; keep ~8 MB => Ck 8192 at S<=256
    Ck = min(N, max(512, (int(8e6) // (4 * S)) // 128 * 128))
    if N % Ck:
        ids = jnp.pad(ids, (0, Ck - N % Ck), constant_values=-1)
    C = ids.shape[0]
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, S=S),
        out_shape=jax.ShapeDtypeStruct((8, C), jnp.float32),
        grid=(C // Ck,),
        in_specs=[pl.BlockSpec((8, S), lambda k: (0, 0)),
                  pl.BlockSpec((1, Ck), lambda k: (0, k))],
        out_specs=pl.BlockSpec((8, Ck), lambda k: (0, k)),
        interpret=interpret,
    )(tables, ids[None, :])
    return out[:T, :N]


@functools.partial(jax.jit, static_argnames=("num_slots", "spmd"))
def table_lookup(tables: jax.Array, ids: jax.Array, *,
                 num_slots: int, spmd: bool = False) -> jax.Array:
    """tables [T, S] f32, ids [N] int32 in [0, num_slots) → [T, N] f32.

    S must be >= num_slots; slots >= num_slots are never selected (ids
    outside [0, S) select nothing and yield 0.0).  Exact for any f32
    table values (see module docstring).  On TPU the fused pallas path
    keeps the one-hot in VMEM; the XLA scan serves huge tables and
    other backends.

    spmd=True says that `ids` may be sharded over several devices under
    plain jit (outside any shard_map): XLA partitions the scan itself,
    a Mosaic kernel it cannot ("Mosaic kernels cannot be automatically
    partitioned"), so that caller gets the XLA formulation on TPU too.
    """
    T, S = tables.shape
    N = ids.shape[0]
    if (jax.default_backend() == "tpu" and not spmd and S <= 2048
            and T <= 8 and N >= _PALLAS_CHUNK):
        return _lookup_pallas(tables, ids)
    C = min(_CHUNK, N)
    nch = (N + C - 1) // C
    idp = jnp.pad(ids, (0, nch * C - N)) if nch * C > N else ids

    def body(_, idc):
        oh = (idc[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (S, 1), 0)).astype(jnp.float32)        # [S, C]
        r = jax.lax.dot(tables, oh,
                        precision=jax.lax.Precision.HIGHEST)  # [T, C]
        return None, r

    _, out = jax.lax.scan(body, None, idp.reshape(nch, C))
    return out.transpose(1, 0, 2).reshape(T, nch * C)[:, :N]


def select_bin_by_feature(bins_fn: jax.Array, fi: jax.Array) -> jax.Array:
    """Per-row bin of that row's feature: bins_fn [F, N] int, fi [N] int32
    → [N] int32 (rows whose fi matches no feature yield 0).

    A single fused compare/select/reduce pass over the feature axis — the
    alternative, a minor-axis 2-D gather `bins[fi, rows]`, serializes on
    TPU just like the table gathers above.
    """
    F = bins_fn.shape[0]
    return jnp.sum(jnp.where(fi[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (F, 1), 0), bins_fn.astype(jnp.int32), 0), axis=0)
