"""Setup shared by the serial and fused tree learners — kept in one place
so the two learners (which must grow identical trees,
tests/test_parallel.py) cannot silently diverge.

The mesh/axis/shard_map wiring that used to live here moved to the
sharded-primitive layer (lightgbm_tpu/sharded/mesh.py); the names are
re-exported so existing imports keep working."""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from ..config import Config
from ..sharded.mesh import (  # noqa: F401 — re-exports (moved to sharded)
    HIST_EXCHANGE_MIN_SCATTER_BYTES, MultiHostRows, check_scatter_divisible,
    check_tree_divergence, mesh_axes, pad_cols_to_ndev,
    resolve_hist_exchange, row_shard_axes)


def make_split_kw(cfg: Config) -> tuple:
    """Hashable (static-arg) split hyperparameters for ops.split.best_split
    (reference feature_histogram.hpp:281-300 gain math inputs)."""
    return tuple(sorted(dict(
        lambda_l1=float(cfg.lambda_l1), lambda_l2=float(cfg.lambda_l2),
        min_data_in_leaf=int(cfg.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
        min_gain_to_split=float(cfg.min_gain_to_split)).items()))


def padded_bin_count(max_num_bin: int) -> int:
    """Bin axis padded to a lane-friendly multiple of 128."""
    return max(128, int(128 * math.ceil(max_num_bin / 128)))


def sentinel_bins_t(dataset) -> np.ndarray:
    """[N+1, C] int32 transpose of the STORE (per-feature rows, or EFB
    bundle columns) with a sentinel row at index N (bin 0) so padded
    gathers are branch-free."""
    bins_np = dataset.dense_bins(site="bins_t").astype(np.int32)
    pad = np.zeros((bins_np.shape[0], 1), np.int32)
    return np.concatenate([bins_np, pad], axis=1).T.copy()


# what a device is taken to hold where the backend reports no
# memory_stats (the CPU tier the tests run on)
CPU_TIER_BYTES_LIMIT = 16e9


def device_bytes_limit() -> Optional[float]:
    """`bytes_limit` of the first local device, or None on a backend
    that reports no memory stats (CPU).  A TPU that reports none is an
    error: every memory gate below would otherwise size itself from a
    guess, and a wrong guess shows up only as a slow bounded-pool run
    or an out-of-memory failure minutes later."""
    import jax
    dev = jax.local_devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit:
        return float(limit)
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']; the "
            "histogram pool and the bin storage layout are sized from "
            "it")
    return None


def _default_pool_budget() -> float:
    """Unset histogram_pool_size defaults to a quarter of the device's
    memory when the backend reports it (16 GB v5e -> 4 GB: Epsilon-scale
    [255, 2000, 3, 256] caches fit and keep the 2x-cheaper subtraction
    path); backends without memory stats keep the conservative 1.5 GB."""
    limit = device_bytes_limit()
    return 1.5e9 if limit is None else max(1.5e9, 0.25 * limit)


def use_parent_hist_cache(cfg: Config, num_features: int,
                          num_bins_padded: int) -> bool:
    """Keep the [num_leaves, F, 3, B] per-leaf histogram cache for the
    parent-subtraction trick only while it fits the pool budget
    (reference HistogramPool cap, feature_histogram.hpp:313-475);
    otherwise learners histogram both children directly."""
    hist_cache_bytes = 4 * cfg.num_leaves * num_features * 3 * num_bins_padded
    budget = (cfg.histogram_pool_size * 1e6
              if cfg.histogram_pool_size > 0 else _default_pool_budget())
    return hist_cache_bytes <= budget
