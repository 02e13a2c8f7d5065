"""Sharded-primitive layer: every mesh/axis/shard_map decision in ONE
module.

The two mesh learners (learner/rounds.py, learner/fused.py) used to
carry their own copies of the mesh-axis resolution, the shard_map
compatibility shim, the column padding + scatter-divisibility guards,
the psum/psum_scatter selection, and the multi-host row-block assembly
— the exact duplication ROADMAP #1 named as the refactor blocking
multi-host work.  This module is that single layer; learner/common.py
re-exports the names so existing imports keep working.

Axis convention: a learner mesh always names its axes ("data",
"feature").  Rows shard over EVERY axis with size > 1 (the row axes);
under the psum_scatter exchange, the reduced histogram's store-column
axis is scattered over ONE of them — the data axis on a 1-D mesh, the
feature axis on a 2-D (data x feature) mesh, where the exchange
becomes "psum over data, reduce-scatter over feature"
(docs/Distributed-Data.md)."""
from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

import numpy as np

from ..config import Config


def mesh_axes(mesh) -> Dict[str, int]:
    """{axis name: size} of a mesh (empty when mesh is None)."""
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def row_shard_axes(dd: int, df: int) -> Optional[Tuple[str, ...]]:
    """The mesh axes a learner's row dimension shards over: every axis
    with more than one device.  None on a single-device mesh."""
    axes = tuple(name for name, size in (("data", dd), ("feature", df))
                 if size > 1)
    return axes or None


class MultiHostRows:
    """Row-block layout + assembly for multi-process data-parallel
    training: the mesh "data" axis spans processes, each process owns one
    contiguous row block (the loader's pre-partition contract,
    dataset.py pre_partition; reference dataset_loader.cpp:554-659).

    Every process pads its block to the same per-process length so the
    global [Np] row axis tiles evenly over the axis devices; global
    arrays are assembled with `jax.make_array_from_process_local_data`
    (the multi-controller analog of the reference's implicit "my rows
    are mine" layout — no data ever crosses hosts, only collectives).
    """

    def __init__(self, mesh, n_local: int):
        import jax
        from jax.experimental import multihost_utils
        axes = mesh_axes(mesh)
        dd = int(axes.get("data", 1))
        self.world = jax.process_count()
        if dd % self.world:
            raise ValueError(
                f"data axis ({dd}) must be divisible by the process count "
                f"({self.world}) for multi-host training")
        if int(axes.get("feature", 1)) > 1:
            raise NotImplementedError(
                "multi-host feature-parallel training is not supported; "
                "use tree_learner=data")
        self.local_dd = dd // self.world
        ns = np.asarray(multihost_utils.process_allgather(
            np.asarray([n_local], np.int64))).reshape(-1)
        self.n_local = int(n_local)
        per = int(ns.max())
        self.per_proc = self.local_dd * int(math.ceil(
            per / self.local_dd)) if per else self.local_dd
        self.np_global = self.per_proc * self.world
        self.n_global = int(ns.sum())
        self.mesh = mesh

    def pad_local(self, x: np.ndarray) -> np.ndarray:
        """Zero-pad the last (row) axis of a LOCAL block to per_proc."""
        pad = self.per_proc - x.shape[-1]
        if pad == 0:
            return x
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        return np.pad(x, widths)

    def put_rows(self, x_local: np.ndarray, spec):
        """Assemble the global row-sharded array from this process's
        padded local block (shape [..., per_proc])."""
        import jax
        from jax.sharding import NamedSharding
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, spec), np.ascontiguousarray(x_local))

    def local_rows(self, arr) -> np.ndarray:
        """Extract this process's rows from a global row-sharded array
        (last axis = rows), trimmed back to the unpadded local length."""
        shards = sorted(
            ((s.index[-1].start or 0, np.asarray(s.data))
             for s in arr.addressable_shards), key=lambda t: t[0])
        return np.concatenate([d for _, d in shards],
                              axis=-1)[..., : self.n_local]


def pad_cols_to_ndev(n_cols: int, ndev: int, align: int = 1) -> int:
    """Smallest column count >= `n_cols` that tiles the mesh axis the
    psum_scatter histogram exchange scatters over: a multiple of
    lcm(ndev, align) (`align` carries a kernel layout constraint, e.g.
    the int8 store's 32-sublane grouping; pass ndev = data*feature for
    a 2-D mesh, where the per-feature-shard slice must itself tile the
    data axis).  Raises a clear ValueError on degenerate mesh sizes
    instead of letting lax.psum_scatter fail with a raw XLA tiling
    error downstream."""
    if ndev < 1 or align < 1:
        raise ValueError(
            f"pad_cols_to_ndev: mesh axis size ({ndev}) and alignment "
            f"({align}) must be >= 1; a zero-sized mesh axis cannot be "
            "tiled by any column padding")
    unit = math.lcm(int(ndev), int(align))
    return unit * int(math.ceil(max(int(n_cols), 1) / unit))


def check_scatter_divisible(axis: str, size: int, ndev: int) -> None:
    """Trace-time guard in front of `lax.psum_scatter`: raise a clear
    ValueError naming the axis, its size, and the mesh axis size when
    the scattered axis cannot tile the mesh.  The learners pad their
    stores with pad_cols_to_ndev so this never fires on the built-in
    paths; a caller wiring build_tree* directly without padding used to
    get a bare `assert` (gone under `python -O`, leaving the raw XLA
    shape error at the psum_scatter dispatch)."""
    if ndev > 1 and size % ndev:
        raise ValueError(
            f"psum_scatter needs the scattered axis '{axis}' (size "
            f"{size}) to be a multiple of the mesh axis size "
            f"({ndev}); pad the store columns with "
            f"sharded.mesh.pad_cols_to_ndev "
            f"({pad_cols_to_ndev(size, ndev)} would tile)")


def check_tree_divergence(name: str, arrs, packed=None) -> None:
    """BENCH_SANITIZE divergence gate shared by every mesh learner
    (diagnostics/sanitize.py): the tree a build returned is replicated
    state — every device must hold the bitwise-identical copy, or a
    shard-local value leaked into the growth loop's control flow.
    Fingerprints one pytree shape for all learners (the packed tree
    vector plus leaf counts) so their divergence reports stay
    comparable across tree_growth modes.  No-op (one env read) unless
    the sanitizer is enabled; `packed` is computed only then when the
    caller has not already paid for it."""
    from ..diagnostics import sanitize
    if not sanitize.sanitize_enabled():
        return
    if packed is None:
        from ..learner.fused import pack_tree_arrays
        packed = pack_tree_arrays(arrs)
    sanitize.maybe_check_divergence(name, {"packed_tree": packed,
                                           "leaf_count": arrs.leaf_count})


# `hist_exchange=auto` switches to psum_scatter only when the per-pass
# histogram payload is at least this many bytes: below it the full psum
# is cheaper than reduce-scatter + the per-leaf record allgather
# (mirroring the reference's allgather-vs-Recursive-Halving switch on
# small payloads, network.cpp ReduceScatter dispatch / SURVEY.md §2.8).
# The measured crossover on chip is captured by
# scripts/profile_hotpath.py (hist_exchange_ab_measured.json); the
# validated Config key `hist_exchange_min_bytes` pins it per run, and
# LGBT_HIST_EXCHANGE_MIN_BYTES remains the ad-hoc env override for
# on-chip tuning when the key is unset.
HIST_EXCHANGE_MIN_SCATTER_BYTES = 1 << 20


def _hist_exchange_threshold(cfg: Optional[Config] = None) -> int:
    cfg_v = int(getattr(cfg, "hist_exchange_min_bytes", -1)) \
        if cfg is not None else -1
    if cfg_v >= 0:
        return cfg_v
    raw = os.environ.get("LGBT_HIST_EXCHANGE_MIN_BYTES", "")
    if not raw:
        return HIST_EXCHANGE_MIN_SCATTER_BYTES
    try:
        return max(0, int(raw))
    except ValueError:
        from .. import log
        log.warning(f"ignoring malformed LGBT_HIST_EXCHANGE_MIN_BYTES="
                    f"{raw!r}")
        return HIST_EXCHANGE_MIN_SCATTER_BYTES


def resolve_hist_exchange(cfg: Config, *, ndev: int,
                          payload_bytes: float) -> str:
    """Resolve `hist_exchange` to the collective a data-parallel learner
    runs per histogram pass.  `payload_bytes` is the full reduced
    histogram size of one pass (K * F * 3 * B * 4); with a single device
    there is no exchange and the answer is always "psum" (a no-op).
    `ndev` is the total device count of the mesh's row axes."""
    if ndev <= 1:
        return "psum"
    mode = getattr(cfg, "hist_exchange", "auto")
    if mode == "auto":
        return ("psum_scatter"
                if payload_bytes >= _hist_exchange_threshold(cfg)
                else "psum")
    return mode


def make_mesh(tree_learner: str, num_machines: int = 0):
    """Mesh for a distributed learner type.  `data` shards rows,
    `feature` shards the split search (reference tree_learner types,
    config.h:233; the topology/linker machinery of src/network is
    replaced by the mesh itself)."""
    import jax
    devs = jax.devices()
    if jax.process_count() > 1:
        # num_machines counts HOSTS (reference config.h:246); the mesh
        # always spans every device of the multi-process world
        n = len(devs)
    else:
        n = num_machines if num_machines and num_machines > 1 else len(devs)
        n = min(n, len(devs))
    if n <= 1:
        return None
    devs = np.asarray(devs[:n])
    if tree_learner in ("data", "voting"):
        return jax.sharding.Mesh(devs.reshape(n, 1), ("data", "feature"))
    if tree_learner == "feature":
        return jax.sharding.Mesh(devs.reshape(1, n), ("data", "feature"))
    # hybrid "data2d": balanced 2-D factorization
    df = 1
    for f in range(int(math.isqrt(n)), 0, -1):
        if n % f == 0:
            df = f
            break
    return jax.sharding.Mesh(devs.reshape(n // df, df), ("data", "feature"))
