"""Serial (single-device) leaf-wise tree learner.

TPU re-design of the reference SerialTreeLearner + GPUTreeLearner
(/root/reference/src/treelearner/serial_tree_learner.cpp:168-574,
gpu_tree_learner.cpp): the leaf-wise policy, smaller/larger-child
subtraction trick (serial_tree_learner.cpp:344-422) and gain math are kept;
the mechanisms are replaced:

- DataPartition's index shuffling (data_partition.hpp:94-146) becomes a
  per-row `leaf_id` vector updated by a masked predicate — no data movement.
- Row sets for histogramming are compacted with `jnp.nonzero(size=cap)`
  where `cap` is the leaf count rounded up to a power of two.  Each cap is
  a separate jit specialization — the analog of the reference GPU learner
  compiling kernels for 11 workgroup powers (gpu_tree_learner.cpp:557-626):
  ~log2(N) variants total, cached across trees and iterations.
- Histograms run as one-hot matmuls on the MXU (ops/histogram.py); best
  splits as [F, B] cumsum scans (ops/split.py).

The split loop itself stays on the host (like the reference), but each step
is a single fused device program + one small device->host transfer of the
two children's packed split records.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset
from .common import make_split_kw, padded_bin_count, sentinel_bins_t
from ..ops.histogram import histogram_from_indices
from ..ops.split import (best_split, bundle_predicate_params,
                         identity_feat_table, maybe_unbundle, store_go_left,
                         SplitResult)
from ..tree import Tree, NUMERICAL_DECISION, CATEGORICAL_DECISION
from ..binning import CATEGORICAL


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


@functools.partial(jax.jit, static_argnames=("cap", "num_bins_padded",
                                             "backend", "split_kw"))
def _root_step(bins_t, grad_pad, hess_pad, idx, num_bins, is_cat, fmask,
               unb, *, cap, num_bins_padded, backend, split_kw):
    hist = histogram_from_indices(bins_t, grad_pad, hess_pad, idx,
                                  num_bins_padded=num_bins_padded,
                                  backend=backend)
    sum_g = jnp.sum(hist[0, 0, :])
    sum_h = jnp.sum(hist[0, 1, :])
    cnt = jnp.sum(hist[0, 2, :])
    sums = jnp.stack([sum_g, sum_h, cnt])
    h = maybe_unbundle(hist, unb, sums)
    rec = best_split(h, num_bins, is_cat, fmask, sum_g, sum_h, cnt,
                     **dict(split_kw))
    return hist, rec.packed(), sums


def _store_partition(bins, leaf_id, parent_leaf, new_leaf, feat, thr,
                     is_cat_split, ftbl):
    """Move the parent's right-going rows to new_leaf, evaluating the
    ORIGINAL-space split (feat, thr) on the bundled store via the
    store-space predicate (ops/split.bundle_predicate_params)."""
    N = leaf_id.shape[0]
    col, T, lo, hi1, dl = bundle_predicate_params(ftbl, feat, thr,
                                                  is_cat_split)
    featrow = jax.lax.dynamic_index_in_dim(bins, col, axis=0,
                                           keepdims=False)[:N]
    featrow = featrow.astype(jnp.int32)
    pred = store_go_left(featrow, T, lo, hi1, dl, is_cat_split)
    in_parent = leaf_id == parent_leaf
    return jnp.where(in_parent & ~pred, new_leaf, leaf_id)


@functools.partial(jax.jit, static_argnames=("cap", "num_bins_padded",
                                             "backend", "split_kw",
                                             "with_subtract"))
def _split_step(bins, bins_t, grad_pad, hess_pad, leaf_id, parent_leaf,
                new_leaf, feat, thr, is_cat_split, smaller_leaf, parent_hist,
                num_bins, is_cat, fmask, small_sums, large_sums, ftbl, unb,
                *, cap, num_bins_padded, backend, split_kw, with_subtract):
    """Partition parent rows, histogram the smaller child (gathered, cap
    static), obtain the larger by subtraction, best-split both.  The
    cached/returned histograms stay in STORE space; split search runs on
    the unbundled per-feature view."""
    N = leaf_id.shape[0]
    leaf_id = _store_partition(bins, leaf_id, parent_leaf, new_leaf, feat,
                               thr, is_cat_split, ftbl)

    small_mask = leaf_id == smaller_leaf
    idx = jnp.nonzero(small_mask, size=cap, fill_value=N)[0].astype(jnp.int32)
    hist_small = histogram_from_indices(bins_t, grad_pad, hess_pad, idx,
                                        num_bins_padded=num_bins_padded,
                                        backend=backend)
    if with_subtract:
        hist_large = parent_hist - hist_small
    else:
        hist_large = parent_hist  # unused placeholder
    kw = dict(split_kw)
    rec_small = best_split(maybe_unbundle(hist_small, unb, small_sums),
                           num_bins, is_cat, fmask,
                           small_sums[0], small_sums[1], small_sums[2], **kw)
    rec_large = best_split(maybe_unbundle(hist_large, unb, large_sums),
                           num_bins, is_cat, fmask,
                           large_sums[0], large_sums[1], large_sums[2], **kw)
    return (leaf_id, hist_small, hist_large,
            jnp.stack([rec_small.packed(), rec_large.packed()]))


@jax.jit
def _partition_only(bins, leaf_id, parent_leaf, new_leaf, feat, thr,
                    is_cat_split, ftbl):
    return _store_partition(bins, leaf_id, parent_leaf, new_leaf, feat,
                            thr, is_cat_split, ftbl)


class _LeafInfo:
    __slots__ = ("sum_grad", "sum_hess", "count", "depth", "hist", "best")

    def __init__(self, sum_grad, sum_hess, count, depth, hist, best):
        self.sum_grad = sum_grad
        self.sum_hess = sum_hess
        self.count = count
        self.depth = depth
        self.hist = hist      # device [F, 3, B] or None
        self.best = best      # numpy packed record or None


class SerialTreeLearner:
    def __init__(self, dataset: Dataset, config: Config):
        self.dataset = dataset
        self.config = config
        self.N = dataset.num_data
        self.F = dataset.num_features              # ORIGINAL feature count
        # bin axis sized by the STORE (bundled columns hold >= any
        # member's bins, so one padded count serves histogram and the
        # unbundled split search alike)
        self.B = padded_bin_count(dataset.max_num_bin)
        bt = sentinel_bins_t(dataset)              # store layout [N+1, C]
        self.bins = jnp.asarray(bt.T.copy())   # [C, N+1]
        self.bins_t = jnp.asarray(bt)          # [N+1, C]
        self.num_bins_dev = jnp.asarray(dataset.split_num_bins)
        self.is_cat_dev = jnp.asarray(dataset.is_categorical)
        ft = dataset.bundle_feat_table()
        self.ftbl = (identity_feat_table(dataset.num_bins) if ft is None
                     else jnp.asarray(ft))
        unb = dataset.unbundle_tables(self.B)
        self.unb = (None if unb is None
                    else (jnp.asarray(unb[0]), jnp.asarray(unb[1])))
        self.backend = ("pallas" if config.device_type == "tpu" and
                        jax.default_backend() == "tpu" else "xla")
        cfg = config
        self.split_kw = make_split_kw(cfg)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)
        # memory guard: keep per-leaf histograms only if the full set fits
        # (cached histograms live in STORE space — bundling shrinks them)
        hist_bytes = dataset.num_store_columns * 3 * self.B * 4
        pool_budget = (cfg.histogram_pool_size * 1e6
                       if cfg.histogram_pool_size > 0 else 1.5e9)
        self.keep_hists = hist_bytes * cfg.num_leaves <= pool_budget
        self.leaf_id: Optional[jax.Array] = None

    # -- helpers -----------------------------------------------------------

    def _feature_mask(self) -> jax.Array:
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return jnp.ones(self.F, dtype=bool)
        k = max(1, int(round(self.F * frac)))
        sel = self._feat_rng.choice(self.F, size=k, replace=False)
        m = np.zeros(self.F, dtype=bool)
        m[sel] = True
        return jnp.asarray(m)

    def _cap(self, count: int) -> int:
        return min(_next_pow2(max(int(count), 1)), self.N)

    def _can_split(self, info: _LeafInfo) -> bool:
        cfg = self.config
        if info.count < 2 * cfg.min_data_in_leaf:
            return False
        if info.sum_hess < 2 * cfg.min_sum_hessian_in_leaf:
            return False
        if cfg.max_depth > 0 and info.depth >= cfg.max_depth:
            return False
        return True

    def _direct_hist_best(self, leaf: int, info: _LeafInfo):
        """Histogram a leaf directly (no subtraction) — root and pool-miss
        path (reference HistogramPool miss → recompute)."""
        cap = self._cap(info.count)
        idx = jnp.nonzero(self.leaf_id == leaf, size=cap,
                          fill_value=self.N)[0].astype(jnp.int32)
        hist, packed, sums = _root_step(
            self.bins_t, self._grad_pad, self._hess_pad, idx,
            self.num_bins_dev, self.is_cat_dev, self._fmask, self.unb,
            cap=cap, num_bins_padded=self.B, backend=self.backend,
            split_kw=self.split_kw)
        return hist, np.asarray(packed)

    # -- main --------------------------------------------------------------

    def train(self, grad: jax.Array, hess: jax.Array,
              bag_idx: Optional[jax.Array] = None,
              bag_count: Optional[int] = None) -> Tuple[Tree, jax.Array]:
        """Grow one tree.  grad/hess: [N] f32 device arrays.

        Returns (tree, leaf_id) where leaf_id[i] is the leaf index of row i
        (-1 for out-of-bag rows) — used for the fast train-score update
        (reference serial_tree_learner.h:52-64 AddPredictionToScore).
        """
        cfg = self.config
        N = self.N
        zero = jnp.zeros((1,), grad.dtype)
        self._grad_pad = jnp.concatenate([grad, zero])
        self._hess_pad = jnp.concatenate([hess, zero])
        self._fmask = self._feature_mask()

        if bag_idx is None:
            self.leaf_id = jnp.zeros(N, jnp.int32)
            root_count = N
            idx = jnp.arange(N, dtype=jnp.int32)
        else:
            root_count = int(bag_count)
            # out-of-bag rows get leaf -1; the sentinel pad index N in
            # bag_idx is out of bounds and dropped by the scatter
            self.leaf_id = jnp.full(N, -1, jnp.int32).at[bag_idx].set(0)
            idx = bag_idx.astype(jnp.int32)

        hist, packed, sums = _root_step(
            self.bins_t, self._grad_pad, self._hess_pad, idx,
            self.num_bins_dev, self.is_cat_dev, self._fmask, self.unb,
            cap=int(idx.shape[0]), num_bins_padded=self.B,
            backend=self.backend, split_kw=self.split_kw)
        sums = np.asarray(sums, dtype=np.float64)

        tree = Tree(cfg.num_leaves)
        leaves: Dict[int, _LeafInfo] = {
            0: _LeafInfo(sums[0], sums[1], root_count, 0, hist,
                         np.asarray(packed))}

        for _ in range(cfg.num_leaves - 1):
            # pick best leaf (global greedy, serial_tree_learner.cpp:203-210)
            best_leaf, best_gain = -1, 0.0
            for lf, info in leaves.items():
                if info.best is None:
                    continue
                g = float(info.best[0])
                if np.isfinite(g) and g > best_gain:
                    best_leaf, best_gain = lf, g
            if best_leaf < 0:
                break
            info = leaves[best_leaf]
            rec = info.best
            feat = int(rec[1]); thr = int(rec[2])
            l_sum = (float(rec[3]), float(rec[4]), int(round(float(rec[5]))))
            r_sum = (float(rec[6]), float(rec[7]), int(round(float(rec[8]))))
            l_out, r_out = float(rec[9]), float(rec[10])
            real_feat = self.dataset.inner_to_real(feat)
            mapper = self.dataset.mappers[real_feat]
            bin_type = (CATEGORICAL_DECISION
                        if mapper.bin_type == CATEGORICAL else NUMERICAL_DECISION)
            new_leaf = tree.split(
                best_leaf, feat, bin_type, thr, real_feat,
                mapper.bin_to_value(thr), l_out, r_out, l_sum[2], r_sum[2],
                best_gain)

            child_depth = info.depth + 1
            left = _LeafInfo(l_sum[0], l_sum[1], l_sum[2], child_depth,
                             None, None)
            right = _LeafInfo(r_sum[0], r_sum[1], r_sum[2], child_depth,
                              None, None)
            need_l, need_r = self._can_split(left), self._can_split(right)
            is_cat_split = jnp.asarray(bin_type == CATEGORICAL_DECISION)

            if need_l or need_r:
                # smaller child is histogrammed; larger by subtraction
                # (serial_tree_learner.cpp:344-422 smaller/larger trick)
                small_is_left = l_sum[2] <= r_sum[2]
                small_leaf = best_leaf if small_is_left else new_leaf
                small = left if small_is_left else right
                large = right if small_is_left else left
                need_small = need_l if small_is_left else need_r
                need_large = need_r if small_is_left else need_l
                cap = self._cap(small.count)
                with_subtract = info.hist is not None
                parent_hist = (info.hist if with_subtract else
                               jnp.zeros((self.dataset.num_store_columns,
                                          3, self.B), jnp.float32))
                (self.leaf_id, hist_small, hist_large, recs) = _split_step(
                    self.bins, self.bins_t, self._grad_pad, self._hess_pad,
                    self.leaf_id, best_leaf, new_leaf, feat, thr,
                    is_cat_split, small_leaf, parent_hist,
                    self.num_bins_dev, self.is_cat_dev, self._fmask,
                    jnp.asarray([small.sum_grad, small.sum_hess,
                                 float(small.count)], jnp.float32),
                    jnp.asarray([large.sum_grad, large.sum_hess,
                                 float(large.count)], jnp.float32),
                    self.ftbl, self.unb,
                    cap=cap, num_bins_padded=self.B, backend=self.backend,
                    split_kw=self.split_kw, with_subtract=with_subtract)
                recs = np.asarray(recs)
                if need_small:
                    small.hist, small.best = hist_small, recs[0]
                if need_large:
                    if with_subtract:
                        large.hist, large.best = hist_large, recs[1]
                    else:
                        # pool-dropped parent (HistogramPool miss analog):
                        # recompute the larger child directly
                        lg_leaf = new_leaf if small_is_left else best_leaf
                        large.hist, large.best = self._direct_hist_best(
                            lg_leaf, large)
                if not self.keep_hists:
                    small.hist = None
                    large.hist = None
            else:
                self.leaf_id = _partition_only(
                    self.bins, self.leaf_id, best_leaf, new_leaf, feat, thr,
                    is_cat_split, self.ftbl)

            leaves[best_leaf] = left
            leaves[new_leaf] = right
            info.hist = None

        return tree, self.leaf_id
