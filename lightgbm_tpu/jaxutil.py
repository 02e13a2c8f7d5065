"""Shared jitted micro-helpers that keep the hot path transfer-guard
clean.

Eager slicing/indexing/padding with Python scalars lowers to
dynamic_slice / scatter / pad whose start-index or fill operand is
uploaded host→device on EVERY call — one implicit transfer per boosting
iteration per site, flagged by the sanitizer
(diagnostics/sanitize.py) and measured as a dispatch stall on remote
TPUs.  Jitting with static bounds turns those scalars into trace
constants.  One home for the pattern, so the learners, the score
updater, and the metrics cannot drift apart (the same reason
learner/common.py exists for the split-search setup).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Where JAX_COMPILATION_CACHE_DIR is set, JAX
    itself reads it as the default of `jax_compilation_cache_dir`, so
    nothing is set here: the cache is placed from outside.  Otherwise
    it goes to `<checkout>/.jax_cache` — a fixed path, because the path
    is part of what a later process must repeat to find the entries."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def require_accelerator() -> dict:
    """The device a measurement runs on, as JAX reports it — or
    SystemExit when JAX found only the CPU.  Every script that writes a
    time under a device metric's name calls this before it times
    anything: a CPU number under such a name is worse than no number."""
    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        raise SystemExit(
            "no accelerator: JAX reports platform 'cpu'; this script "
            "measures the chip and does not fall back")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


@functools.partial(jax.jit, static_argnames=("pad",))
def pad_rows_dev(x: jax.Array, *, pad: int) -> jax.Array:
    """Zero-pad the trailing row axis on device (the eager jnp.pad
    uploads its fill scalar per call)."""
    return jnp.pad(x, (0, pad))


@functools.partial(jax.jit, static_argnames=("n",))
def slice_rows_dev(x: jax.Array, *, n: int) -> jax.Array:
    """The first n of the trailing row axis, with a trace-constant bound
    (the eager slice lowers to dynamic_slice and uploads its start index
    per call)."""
    return x[..., :n]


class RowLayout(NamedTuple):
    """Where a learner keeps its rows: `num_rows` of them, the dataset's
    `num_data` first and zero padding at the tail of the global order,
    and on a mesh the sharding of a `[num_rows]` array over it (None on
    one device).  Whatever is elementwise in the row — score, label,
    weights, gradients, leaf ids — lives in this layout through a
    boosting iteration, so that no row crosses a chip outside the tree
    build; every other reader takes `view`, the `num_data` real rows."""
    num_data: int
    num_rows: int
    sharding: Optional[jax.sharding.NamedSharding]

    def sharding_of(self, ndim: int):
        """The sharding of an `ndim`-D array whose LAST axis is the
        rows (None on one device)."""
        if self.sharding is None or ndim == 1:
            return self.sharding
        P = jax.sharding.PartitionSpec
        return jax.sharding.NamedSharding(
            self.sharding.mesh, P(*(None,) * (ndim - 1), *self.sharding.spec))

    def place(self, x, fill=0) -> jax.Array:
        """`x` with `num_data` trailing rows, padded with `fill` and
        placed in the layout.  A host array goes a shard to each device
        (through jnp.asarray all of it would first land on one); a
        device array is padded by one program.  Paths that write rows
        through the `[.., num_data]` view come through here, outside an
        iteration's hot path; an array already `num_rows` long is
        returned as it is."""
        pad = self.num_rows - x.shape[-1]
        if isinstance(x, np.ndarray):
            if pad:
                x = np.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                           constant_values=fill)
            return jax.device_put(x, self.sharding_of(x.ndim))
        if not pad:
            return x
        return _pad_rows_placed(pad, fill, self.sharding_of(x.ndim))(x)

    def view(self, x: jax.Array) -> jax.Array:
        """The `num_data` real rows of an array in the layout."""
        if x.shape[-1] == self.num_data:
            return x
        return slice_rows_dev(x, n=self.num_data)


@functools.lru_cache(maxsize=None)
def _pad_rows_placed(pad: int, fill, sharding):
    def pad_rows(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                       constant_values=fill)
    return jax.jit(pad_rows, out_shardings=sharding)


@jax.jit
def bag_mask_dev(bag_idx: jax.Array, base_mask: jax.Array) -> jax.Array:
    """Bag membership mask on device (sentinel indices drop): jitted so
    the 1.0 fill is a trace constant, not a per-redraw scalar upload."""
    return (jnp.zeros_like(base_mask).at[bag_idx].set(1.0, mode="drop")
            * base_mask)


@functools.lru_cache(maxsize=None)
def unstack_scalars(n: int):
    """Jitted [n] vector → n lazy 0-d device scalars in ONE program
    (eager v[i] uploads a dynamic_slice start index per element).
    Returns the compiled callable; cached per n."""
    def unstack_scalars(v):
        return tuple(v[i] for i in range(n))
    return jax.jit(unstack_scalars)
