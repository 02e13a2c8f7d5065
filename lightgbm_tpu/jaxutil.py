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

import jax
import jax.numpy as jnp


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
    """x[:n] with a trace-constant bound (the eager slice lowers to
    dynamic_slice and uploads its start index per call)."""
    return x[:n]


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
