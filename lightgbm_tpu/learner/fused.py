"""Fused SPMD leaf-wise tree builder — the distributed tree learner.

One fully-jitted device program grows a whole tree with `lax.fori_loop`,
replacing the reference's three network-parallel learners
(/root/reference/src/treelearner/{data,feature,voting}_parallel_tree_learner.cpp)
with a single SPMD formulation over a 2-D `(data, feature)` mesh:

- rows sharded on the `data` axis: local masked histograms are summed with
  `lax.psum` — the TPU analog of the reference's histogram ReduceScatter
  (data_parallel_tree_learner.cpp:148-161) with the byte-level reducer
  replaced by a typed collective (SURVEY.md §2.8 "TPU mapping").
- features sharded on the `feature` axis: each shard scans only its block
  of the histogram, then the per-shard best splits are `all_gather`ed and
  argmax-reduced — the analog of FeatureParallel's 2×SplitInfo Allreduce
  with MaxReducer (feature_parallel_tree_learner.cpp:53-75).
- both axes compose; pure data-parallel is `feature`-axis size 1 and
  vice versa.  The reference's per-machine row/feature ownership tables
  (dataset_loader.cpp:554-659, feature sharding at
  feature_parallel_tree_learner.cpp:31-50) become mesh shardings.

Unlike the host-loop SerialTreeLearner (learner/serial.py) — which gathers
each leaf's rows so per-split cost shrinks with the leaf — this builder is
mask-based with static shapes everywhere, so the entire tree (and the whole
boosting step) compiles to one XLA program: the design SURVEY.md §3.3 calls
for ("the whole split loop becomes a jitted/pallas program").
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset, nnz_capacity_tier
from ..sharded.mesh import (check_scatter_divisible, check_tree_divergence,
                            make_mesh, mesh_axes, pad_cols_to_ndev,
                            resolve_hist_exchange)
from .common import (make_split_kw, padded_bin_count, sentinel_bins_t,
                     use_parent_hist_cache)
from ..jaxutil import bag_mask_dev, pad_rows_dev, slice_rows_dev
from ..ops.histogram import histogram_full_masked, histogram_full_sparse
from ..ops.predict import sparse_bin_lookup
from ..ops.split import (best_split, best_split_in_store,
                         bundle_predicate_params, combine_sharded_records,
                         identity_feat_table, leaf_output, maybe_unbundle,
                         sharded_slice_search, store_go_left,
                         store_search_operands)
from ..tree import Tree, NUMERICAL_DECISION, CATEGORICAL_DECISION
from ..binning import CATEGORICAL, StoreCells

NEG_INF = -jnp.inf


class TreeArrays(NamedTuple):
    """Device tree in the reference's flat-node layout (tree.h:161-196):
    internal nodes 0..n-2, leaves as ~leaf in child arrays."""
    split_feature: jax.Array    # [L-1] int32 inner (used-feature) index
    threshold_bin: jax.Array    # [L-1] int32
    is_cat: jax.Array           # [L-1] bool
    left_child: jax.Array       # [L-1] int32
    right_child: jax.Array      # [L-1] int32
    split_gain: jax.Array       # [L-1] f32
    internal_value: jax.Array   # [L-1] f32 (parent output pre-split)
    internal_count: jax.Array   # [L-1] f32
    leaf_value: jax.Array       # [L] f32
    leaf_count: jax.Array       # [L] f32
    leaf_depth: jax.Array       # [L] int32
    num_leaves: jax.Array       # scalar int32


def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def build_tree(bins, grad, hess, row_mask, num_bins, is_cat, fmask, ftbl,
               unb=None, *,
               num_leaves: int, num_bins_padded: int, split_kw: tuple,
               max_depth: int, min_data_in_leaf: int,
               min_sum_hessian_in_leaf: float,
               data_axis: Optional[str] = None,
               feature_axis: Optional[str] = None,
               feature_shard_size: int = 0,
               input_dtype: str = "float32",
               voting_k: int = 0,
               num_machines: int = 1,
               hist_exchange: str = "psum",
               cache_parent_hist: bool = True):
    """Grow one tree; runs per-shard inside `shard_map` (or standalone when
    both axes are None).

    bins     : [Floc, Nloc] int  — this shard's STORE columns (= original
               per-feature bins, or bundled columns under EFB); OR a
               sparse ELL triple (cols [1, Nloc, R], binsv [1, Nloc, R],
               zero_bin [1, Floc]) — the shard's column window of the
               sparse store with a leading feature-shard axis that is 1
               per shard_map block (and kept at 1 on the unsharded path
               so both squeeze uniformly)
    grad/hess/row_mask : [Nloc] f32 (row_mask is 0 for padding / out-of-bag)
    num_bins/is_cat/fmask : per-ORIGINAL-feature metadata for this shard
    ftbl     : [5, F] feature→(col, offset, default, nslots, packed) table
               (identity when the store is unbundled)
    unb      : None, or what split search takes a BUNDLED store's
               histogram through (single feature shard only): a
               binning.StoreCells — searched in the store's own cells —
               or the (src, dmask) gather tables of a plan that packs a
               categorical feature, unbundled before split search
    Returns (TreeArrays, leaf_id [Nloc] int32).
    """
    sparse = isinstance(bins, (tuple, list))
    if sparse:
        sp_cols, sp_bins, sp_zb = bins[0][0], bins[1][0], bins[2][0]
        Floc = sp_zb.shape[0]
        Nloc = sp_cols.shape[0]
    else:
        Floc, Nloc = bins.shape
    L = num_leaves
    B = num_bins_padded
    skw = dict(split_kw)
    l1, l2 = skw["lambda_l1"], skw["lambda_l2"]
    f_off = (jax.lax.axis_index(feature_axis) * feature_shard_size
             if feature_axis is not None else jnp.int32(0))

    voting = voting_k > 0 and data_axis is not None
    # psum_scatter exchange (hist_exchange knob; the reference's
    # Network::ReduceScatter ownership, data_parallel_tree_learner.cpp:
    # 118-160): each device reduces and keeps only its Floc/nd slice of
    # the histogram's column axis, split-searches the slice, and the
    # per-leaf records are all_gathered + argmaxed in find_best.  The
    # voting learner routes its selected-subset exchange through the
    # same switch inside find_best_voting.
    hx = (hist_exchange == "psum_scatter" and data_axis is not None
          and not voting)
    hx_vote = hist_exchange == "psum_scatter" and voting
    nd = num_machines if data_axis is not None else 1
    if hx:
        # trace-time guard with a named ValueError (the learner pads the
        # store, so only direct build_tree callers can trip it)
        check_scatter_divisible("store columns", Floc, nd)
    Fs = Floc // nd if hx else Floc

    def make_local_hist(mask):
        if sparse:
            return histogram_full_sparse(sp_cols, sp_bins, sp_zb,
                                         grad, hess, mask,
                                         num_columns_padded=Floc,
                                         num_bins_padded=B,
                                         input_dtype=input_dtype)
        return histogram_full_masked(bins, grad, hess, mask,
                                     num_bins_padded=B,
                                     input_dtype=input_dtype)

    def make_hist(mask):
        h = make_local_hist(mask)
        # voting keeps histograms LOCAL: only the voted feature subset is
        # reduced, inside find_best (PV-Tree,
        # voting_parallel_tree_learner.cpp:314-350)
        if voting:
            return h
        if hx:
            return jax.lax.psum_scatter(h, data_axis, scatter_dimension=0,
                                        tiled=True)
        return _psum(h, data_axis)

    def can_gate(p, sums):
        # can-this-child-be-split-again gate (serial_tree_learner.cpp
        # _can_split checks; depth gate applied by caller via leaf_best)
        can = ((sums[2] >= 2 * min_data_in_leaf)
               & (sums[1] >= 2 * min_sum_hessian_in_leaf))
        gain = jnp.where(can & jnp.isfinite(p[0]) & (p[0] > 0), p[0], NEG_INF)
        return p.at[0].set(gain)

    # a bundled store searched in its own cells: the feature mask in
    # cell space once a tree, a device's own columns under psum_scatter
    in_store = isinstance(unb, StoreCells)
    if in_store:
        search = store_search_operands(
            unb, fmask, jax.lax.axis_index(data_axis) * Fs if hx else 0, Fs)

    def find_best(hist, sums):
        """Global best split record given this shard's histogram block
        (the reduce-scattered column slice under psum_scatter) and the
        leaf's GLOBAL (sum_grad, sum_hess, count)."""
        if voting:
            return find_best_voting(hist, sums)
        if hx:
            off = jax.lax.axis_index(data_axis) * Fs
            if unb is None:
                nb_s = jax.lax.dynamic_slice_in_dim(num_bins, off, Fs)
                ic_s = jax.lax.dynamic_slice_in_dim(is_cat, off, Fs)
                fm_s = jax.lax.dynamic_slice_in_dim(fmask, off, Fs)
                # fold the FEATURE-shard base into the slice offset so
                # the shared search emits global feature ids directly
                off = off + f_off
            else:
                nb_s = ic_s = fm_s = None
            p = sharded_slice_search(
                hist, sums, off=off, nb_s=nb_s, ic_s=ic_s, fm_s=fm_s,
                num_bins=num_bins, is_cat=is_cat, fmask=fmask,
                unb=search if in_store else unb, skw=skw)
            p = combine_sharded_records(p, data_axis)
        else:
            if in_store:
                rec = best_split_in_store(hist, search, sums[0],
                                          sums[1], sums[2], **skw)
            else:
                rec = best_split(maybe_unbundle(hist, unb, sums),
                                 num_bins, is_cat, fmask,
                                 sums[0], sums[1], sums[2], **skw)
            p = rec.packed()
            p = p.at[1].add(f_off.astype(jnp.float32))
        if feature_axis is not None:
            allp = jax.lax.all_gather(p, feature_axis)     # [k, 11]
            # argmax picks the first max → smallest shard → smallest
            # feature id among ties (split_info.hpp:100-105 determinism)
            p = allp[jnp.argmax(allp[:, 0])]
        return can_gate(p, sums)

    def find_best_voting(hist_local, sums):
        """PV-Tree split search (voting_parallel_tree_learner.cpp:163-251):
        local per-feature bests with relaxed constraints → local top-k →
        vote all_gather → global top-2k feature subset → psum only those
        features' histograms → exact best split on the subset."""
        from ..ops.split import split_gain_matrix
        local_sums = jnp.stack([jnp.sum(hist_local[0, 0, :]),
                                jnp.sum(hist_local[0, 1, :]),
                                jnp.sum(hist_local[0, 2, :])])
        relaxed = dict(skw)
        relaxed["min_data_in_leaf"] = max(
            1, skw["min_data_in_leaf"] // max(num_machines, 1))
        relaxed["min_sum_hessian_in_leaf"] = (
            skw["min_sum_hessian_in_leaf"] / max(num_machines, 1))
        gains, _, _, _ = split_gain_matrix(
            hist_local, num_bins, is_cat, fmask,
            local_sums[0], local_sums[1], local_sums[2], **relaxed)
        per_feat = jnp.max(gains, axis=1)                  # [F]
        k = min(voting_k, per_feat.shape[0])
        _, topk = jax.lax.top_k(per_feat, k)               # [k] local vote
        allv = jax.lax.all_gather(topk, data_axis).reshape(-1)
        votes = jnp.zeros(per_feat.shape[0], jnp.int32).at[allv].add(1)
        k2 = min(2 * k, per_feat.shape[0])
        _, sel = jax.lax.top_k(votes, k2)                  # [2k] selected
        if hx_vote:
            # same comms layer as the data-parallel learner: reduce-
            # scatter the voted subset over its slot axis (padded to a
            # data-axis multiple by repeating slot 0 — duplicates yield
            # identical records, which the argmax collapses), search this
            # shard's slots only, then allgather + argmax the records
            k2p = pad_cols_to_ndev(k2, nd)
            selp = jnp.concatenate(
                [sel, jnp.broadcast_to(sel[:1], (k2p - k2,))]) \
                if k2p > k2 else sel
            hs = jax.lax.psum_scatter(hist_local[selp], data_axis,
                                      scatter_dimension=0, tiled=True)
            ks = k2p // nd
            sel_s = jax.lax.dynamic_slice_in_dim(
                selp, jax.lax.axis_index(data_axis) * ks, ks)
            rec = best_split(hs, num_bins[sel_s], is_cat[sel_s],
                             fmask[sel_s], sums[0], sums[1], sums[2],
                             **skw)
            p = rec.packed()
            # combine on the GLOBAL slot id so gain ties break by vote
            # rank exactly like the psum path's flat argmax over the
            # [2k, B] selected block (a padded duplicate slot has a
            # larger id and so loses ties to its original); the slot
            # maps back to its feature after the combine
            gslot = jax.lax.axis_index(data_axis) * ks + rec.feature
            p = p.at[1].set(gslot.astype(jnp.float32))
            p = combine_sharded_records(p, data_axis)
            p = p.at[1].set(selp[p[1].astype(jnp.int32)]
                            .astype(jnp.float32))
            return can_gate(p, sums)
        hist_sel = _psum(hist_local[sel], data_axis)       # [2k, 3, B]
        rec = best_split(hist_sel, num_bins[sel], is_cat[sel], fmask[sel],
                         sums[0], sums[1], sums[2], **skw)
        p = rec.packed()
        p = p.at[1].set(sel[rec.feature].astype(jnp.float32))
        return can_gate(p, sums)

    def go_left_row(feat, thr, catf):
        """[Nloc] bool: does each local row go left under the ORIGINAL-
        space split (feat, thr)?  The owning store-column shard evaluates
        the store-space predicate; others contribute zeros."""
        col, T, lo, hi1, dl = bundle_predicate_params(ftbl, feat, thr, catf)
        lf = col - f_off
        owned = (lf >= 0) & (lf < Floc)
        lc = jnp.clip(lf, 0, Floc - 1)
        if sparse:
            featrow = sparse_bin_lookup(sp_cols, sp_bins, sp_zb,
                                        jnp.broadcast_to(lc, (Nloc,)))
        else:
            featrow = jnp.take(bins, lc, axis=0).astype(jnp.int32)
        gl = store_go_left(featrow, T, lo, hi1, dl, catf)
        gl = jnp.where(owned, gl, False)
        if feature_axis is not None:
            gl = jax.lax.psum(gl.astype(jnp.int32), feature_axis) > 0
        return gl

    # ---- root ---------------------------------------------------------------
    if hx:
        # leaf totals must be bitwise REPLICATED across data shards (they
        # gate control flow): partial sums of the LOCAL pass reduced with
        # one tiny psum — the scattered slice's column order differs per
        # shard, so summing it directly would diverge in f32 ulps
        h0_loc = make_local_hist(row_mask)
        root_sums = jax.lax.psum(
            jnp.stack([jnp.sum(h0_loc[0, 0, :]), jnp.sum(h0_loc[0, 1, :]),
                       jnp.sum(h0_loc[0, 2, :])]), data_axis)
        sum_g, sum_h, cnt = root_sums[0], root_sums[1], root_sums[2]
        hist0 = jax.lax.psum_scatter(h0_loc, data_axis,
                                     scatter_dimension=0, tiled=True)
    else:
        hist0 = make_hist(row_mask)
        # every row lands in exactly one bin of each feature, so any
        # single feature's bin sums give the leaf totals; feature blocks
        # are sharded, so reduce a local feature and max over shards
        # (only shards with >=1 real feature agree; all shards see
        # identical rows)
        sum_g = jnp.sum(hist0[0, 0, :])
        sum_h = jnp.sum(hist0[0, 1, :])
        cnt = jnp.sum(hist0[0, 2, :])
        root_sums = jnp.stack([sum_g, sum_h, cnt])
    if voting:
        # hist0 is local in voting mode; root totals are global
        root_sums = _psum(root_sums, data_axis)
        sum_g, sum_h, cnt = root_sums[0], root_sums[1], root_sums[2]
    if feature_axis is not None:
        # shard 0 always holds real features (padding only at the tail)
        root_sums = jax.lax.all_gather(root_sums, feature_axis)[0]
        sum_g, sum_h, cnt = root_sums[0], root_sums[1], root_sums[2]

    leaf_id = jnp.zeros(Nloc, jnp.int32)
    leaf_best = jnp.full((L, 11), NEG_INF, jnp.float32).at[0].set(
        find_best(hist0, root_sums))
    leaf_depth = jnp.zeros(L, jnp.int32)
    leaf_parent = jnp.full(L, -1, jnp.int32)
    leaf_side = jnp.zeros(L, jnp.int32)
    # leaf-hist cache for the parent-subtraction trick; dropped when the
    # pool budget binds (reference HistogramPool, feature_histogram.hpp:
    # 313-475) — both children are then histogrammed directly.  Under
    # psum_scatter the cache holds this shard's column SLICES (nd x less
    # memory per device)
    leaf_hist = (jnp.zeros((L,) + hist0.shape, jnp.float32).at[0].set(hist0)
                 if cache_parent_hist
                 else jnp.zeros((1, 1, 1, 1), jnp.float32))

    arrs = TreeArrays(
        split_feature=jnp.zeros(L - 1, jnp.int32),
        threshold_bin=jnp.zeros(L - 1, jnp.int32),
        is_cat=jnp.zeros(L - 1, bool),
        left_child=jnp.zeros(L - 1, jnp.int32),
        right_child=jnp.zeros(L - 1, jnp.int32),
        split_gain=jnp.zeros(L - 1, jnp.float32),
        internal_value=jnp.zeros(L - 1, jnp.float32),
        internal_count=jnp.zeros(L - 1, jnp.float32),
        leaf_value=jnp.zeros(L, jnp.float32).at[0].set(
            leaf_output(sum_g, sum_h, l1, l2)),
        leaf_count=jnp.zeros(L, jnp.float32).at[0].set(cnt),
        leaf_depth=jnp.zeros(L, jnp.int32),
        num_leaves=jnp.int32(1),
    )

    def body(i, st):
        (leaf_id, leaf_best, leaf_depth, leaf_parent, leaf_side,
         leaf_hist, arrs) = st
        gated = jnp.where(
            (max_depth <= 0) | (leaf_depth < max_depth),
            leaf_best[:, 0], NEG_INF)
        best_leaf = jnp.argmax(gated).astype(jnp.int32)
        rec = leaf_best[best_leaf]
        do = gated[best_leaf] > 0
        feat = rec[1].astype(jnp.int32)
        thr = rec[2].astype(jnp.int32)
        new_leaf = jnp.int32(i + 1)
        node = jnp.int32(i)

        # decision type lives with the owning shard's metadata (sized by
        # the ORIGINAL feature count, which equals Floc except under EFB)
        Fm = is_cat.shape[0]
        lf = feat - f_off
        owned = (lf >= 0) & (lf < Fm)
        catf = jnp.where(owned, is_cat[jnp.clip(lf, 0, Fm - 1)], False)
        if feature_axis is not None:
            catf = jax.lax.psum(catf.astype(jnp.int32), feature_axis) > 0

        # ---- partition (DataPartition::Split analog, mask-based) ----------
        gl = go_left_row(feat, thr, catf)
        split_mask = do & (leaf_id == best_leaf) & ~gl
        leaf_id2 = jnp.where(split_mask, new_leaf, leaf_id)

        l_sums = rec[3:6]
        r_sums = rec[6:9]
        small_is_left = l_sums[2] <= r_sums[2]
        small_leaf = jnp.where(small_is_left, best_leaf, new_leaf)

        # ---- smaller child histogram + larger by subtraction --------------
        # (serial_tree_learner.cpp smaller/larger trick; do=False → zero
        # mask → zero hist, state select below keeps everything unchanged)
        large_leaf = jnp.where(small_is_left, new_leaf, best_leaf)
        msk = row_mask * (leaf_id2 == small_leaf) * do
        hist_small = make_hist(msk)
        if cache_parent_hist:
            hist_large = leaf_hist[best_leaf] - hist_small
        else:
            hist_large = make_hist(row_mask * (leaf_id2 == large_leaf) * do)

        child_depth = leaf_depth[best_leaf] + 1
        small_sums = jnp.where(small_is_left, l_sums, r_sums)
        large_sums = jnp.where(small_is_left, r_sums, l_sums)
        rec_small = find_best(hist_small, small_sums)
        rec_large = find_best(hist_large, large_sums)
        rec_left = jnp.where(small_is_left, rec_small, rec_large)
        rec_right = jnp.where(small_is_left, rec_large, rec_small)
        if cache_parent_hist:
            hist_left = jnp.where(small_is_left, hist_small, hist_large)
            hist_right = jnp.where(small_is_left, hist_large, hist_small)
            leaf_hist_new = leaf_hist.at[best_leaf].set(hist_left).at[
                new_leaf].set(hist_right)
        else:
            leaf_hist_new = leaf_hist

        # ---- tree arrays (Tree::Split, tree.cpp:52-97) --------------------
        pn = leaf_parent[best_leaf]
        side = leaf_side[best_leaf]
        # out-of-bounds index (L-1) + mode="drop" when no parent / no-op
        lidx = jnp.where((pn >= 0) & (side == 0), pn, L - 1)
        ridx = jnp.where((pn >= 0) & (side == 1), pn, L - 1)
        arrs2 = arrs._replace(
            split_feature=arrs.split_feature.at[node].set(feat),
            threshold_bin=arrs.threshold_bin.at[node].set(thr),
            is_cat=arrs.is_cat.at[node].set(catf),
            split_gain=arrs.split_gain.at[node].set(rec[0]),
            internal_value=arrs.internal_value.at[node].set(
                arrs.leaf_value[best_leaf]),
            internal_count=arrs.internal_count.at[node].set(
                l_sums[2] + r_sums[2]),
            left_child=arrs.left_child.at[lidx].set(
                node, mode="drop").at[node].set(~best_leaf),
            right_child=arrs.right_child.at[ridx].set(
                node, mode="drop").at[node].set(~new_leaf),
            leaf_value=arrs.leaf_value.at[best_leaf].set(
                rec[9]).at[new_leaf].set(rec[10]),
            leaf_count=arrs.leaf_count.at[best_leaf].set(
                l_sums[2]).at[new_leaf].set(r_sums[2]),
            leaf_depth=arrs.leaf_depth.at[best_leaf].set(
                child_depth).at[new_leaf].set(child_depth),
            num_leaves=arrs.num_leaves + 1,
        )
        new_st = (
            leaf_id2,
            leaf_best.at[best_leaf].set(rec_left).at[new_leaf].set(rec_right),
            leaf_depth.at[best_leaf].set(child_depth).at[new_leaf].set(
                child_depth),
            leaf_parent.at[best_leaf].set(node).at[new_leaf].set(node),
            leaf_side.at[best_leaf].set(0).at[new_leaf].set(1),
            leaf_hist_new,
            arrs2,
        )
        old_st = (leaf_id, leaf_best, leaf_depth, leaf_parent,
                  leaf_side, leaf_hist, arrs)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(do, a, b), new_st, old_st)

    st = (leaf_id, leaf_best, leaf_depth, leaf_parent, leaf_side,
          leaf_hist, arrs)
    st = jax.lax.fori_loop(0, L - 1, body, st)
    return st[-1], st[0]


@jax.jit
def pack_tree_arrays(arrs: TreeArrays) -> jax.Array:
    """Flatten TreeArrays into ONE f32 vector so the host fetches a single
    transfer (per-array fetches cost a device round-trip each — ruinous on
    remote-attached TPUs).  All int fields fit f32 exactly (< 2^24)."""
    return jnp.concatenate(
        [jnp.ravel(x).astype(jnp.float32) for x in arrs]
        + [jnp.zeros(1, jnp.float32)])


def unpack_tree_arrays(vec: np.ndarray, L: int) -> TreeArrays:
    sizes = [L - 1] * 8 + [L] * 3 + [1]
    dts = ([np.int32, np.int32, bool, np.int32, np.int32, np.float32,
            np.float32, np.float32, np.float32, np.float32, np.int32,
            np.int32])
    out, off = [], 0
    for sz, dt in zip(sizes, dts):
        part = vec[off:off + sz]
        out.append(part.astype(dt) if dt != bool else part > 0.5)
        off += sz
    out[-1] = out[-1][0]
    return TreeArrays(*out)


def tree_arrays_to_host(arrs, dataset: Dataset, max_leaves: int) -> Tree:
    """Rehydrate the host Tree model (real feature ids + real-valued
    thresholds via the BinMappers) from device TreeArrays.  Accepts either
    a TreeArrays of device arrays or an already-unpacked numpy TreeArrays."""
    if isinstance(arrs.num_leaves, jax.Array):
        # pack to ONE vector, then ONE explicit fetch (jax.device_get):
        # per-array fetches cost a round-trip each, and np.asarray here
        # would be an implicit transfer under the sanitizer's guard
        a = unpack_tree_arrays(jax.device_get(pack_tree_arrays(arrs)),
                               max_leaves)
    else:
        a = arrs
    n = int(a.num_leaves)
    t = Tree(max_leaves)
    t.num_leaves = n
    k = max(n - 1, 0)
    from .. import profiling
    profiling.count(profiling.CATEGORICAL_SPLITS,
                    float(np.count_nonzero(a.is_cat[:k])))
    if n < 2:
        t.leaf_value[0] = float(a.leaf_value[0])
        return t
    t.split_feature_inner[:k] = a.split_feature[:k]
    t.threshold_in_bin[:k] = a.threshold_bin[:k]
    t.decision_type[:k] = np.where(a.is_cat[:k], CATEGORICAL_DECISION,
                                   NUMERICAL_DECISION)
    t.has_categorical = bool(a.is_cat[:k].any())
    t.left_child[:k] = a.left_child[:k]
    t.right_child[:k] = a.right_child[:k]
    t.split_gain[:k] = a.split_gain[:k]
    t.internal_value[:k] = a.internal_value[:k]
    t.internal_count[:k] = np.round(a.internal_count[:k]).astype(np.int64)
    t.leaf_value[:n] = a.leaf_value[:n]
    t.leaf_count[:n] = np.round(a.leaf_count[:n]).astype(np.int64)
    t.leaf_depth[:n] = a.leaf_depth[:n]
    for node in range(k):
        real = dataset.inner_to_real(int(t.split_feature_inner[node]))
        t.split_feature[node] = real
        t.threshold[node] = dataset.mappers[real].bin_to_value(
            int(t.threshold_in_bin[node]))
    return t


class FusedTreeLearner:
    """Mesh-parallel tree learner: `tree_learner=data|feature|serial2d`.

    Pads rows to a multiple of the data-axis size (mask 0) and features to
    a multiple of the feature-axis size (fmask False), then runs
    `build_tree` under `jax.shard_map`.
    """

    def __init__(self, dataset: Dataset, config: Config,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.dataset = dataset
        self.config = config
        self.mesh = mesh
        self.full_leaf_id = True   # leaf_id valid for out-of-bag rows too
        self.N = dataset.num_data
        self.F = dataset.num_features
        self.B = padded_bin_count(dataset.max_num_bin)

        if mesh is not None:
            axes = mesh_axes(mesh)
        else:
            axes = {}
        self.dd = int(axes.get("data", 1))
        self.df = int(axes.get("feature", 1))
        # multi-process world: this process holds only its row block;
        # the global row axis is assembled per-process (MultiHostRows)
        self.mh = None
        if mesh is not None and jax.process_count() > 1:
            from ..sharded.mesh import MultiHostRows
            self.mh = MultiHostRows(mesh, self.N)
            self.Np = self.mh.np_global
            self._local_np = self.mh.per_proc
        else:
            self.Np = int(self.dd * math.ceil(self.N / self.dd))
            self._local_np = self.Np
        self.Fp = int(self.df * math.ceil(self.F / self.df))

        cfg = config
        voting = (getattr(cfg, "tree_learner", "") == "voting"
                  and self.dd > 1)
        self._voting = voting
        # EFB: histogram over the narrower bundled store.  Feature
        # sharding and voting need per-ORIGINAL-feature store rows (the
        # vote / shard ownership is per feature), so they fall back to
        # the unbundled view of the same plan
        plan = dataset.bundle_plan
        self.use_bundle = plan is not None and self.df == 1 and not voting
        # data-parallel histogram exchange: resolve the collective from
        # the per-pass payload (the voted subset for PV-Tree), then size
        # the store so the histogram's column axis tiles the data axis
        # under psum_scatter
        pay_cols = (dataset.num_store_columns if self.use_bundle
                    else max(1, self.Fp // self.df))
        if voting:
            pay_cols = max(1, min(2 * int(cfg.top_k), self.F))
        self.hist_exchange = resolve_hist_exchange(
            cfg, ndev=self.dd, payload_bytes=4.0 * pay_cols * 3 * self.B)
        hx_pad = (self.hist_exchange == "psum_scatter" and self.dd > 1
                  and not voting)
        if hx_pad and not self.use_bundle:
            # each feature shard's Fp/df column slice must itself tile
            # the data axis, so the unit is the full df*dd product
            self.Fp = pad_cols_to_ndev(self.F, self.df * self.dd)
        # sparse datasets feed the fused builders directly (per-shard ELL
        # windows of the store — no densification); the multi-process
        # row exchange still ships dense blocks, so mh keeps the counted
        # dense fallback (ROADMAP: multi-host sparse ingest)
        self._sparse_feed = dataset.sparse is not None and self.mh is None
        bins_np = None
        if self.use_bundle:
            self.Cstore = dataset.num_store_columns
            cp = 0
            if hx_pad and self.Cstore % self.dd:
                # trivial zero columns so the bundled store tiles the
                # data axis (the unbundle sentinel must sit past them)
                cp = pad_cols_to_ndev(self.Cstore, self.dd) - self.Cstore
                self.Cstore += cp
            if not self._sparse_feed:
                store = dataset.dense_bins(site="fused_feed")
                bins_np = store.astype(np.int32)
                if self._local_np > self.N:
                    bins_np = np.pad(bins_np,
                                     ((0, 0), (0, self._local_np - self.N)))
                if cp:
                    bins_np = np.pad(bins_np, ((0, cp), (0, 0)))
        else:
            self.Cstore = self.Fp
            if not self._sparse_feed:
                base = (dataset.dense_bins(site="fused_feed")
                        if plan is None else dataset.unbundled_bins())
                bins_np = base.astype(np.int32)
                if self.Fp > self.F or self._local_np > self.N:
                    bins_np = np.pad(bins_np,
                                     ((0, self.Fp - self.F),
                                      (0, self._local_np - self.N)))
        nb = np.pad(dataset.split_num_bins.astype(np.int32),
                    (0, self.Fp - self.F), constant_values=1)
        ic = np.pad(dataset.is_categorical, (0, self.Fp - self.F))
        self._base_fmask = np.pad(np.ones(self.F, bool),
                                  (0, self.Fp - self.F))
        self._row_mask = np.pad(np.ones(self.N, np.float32),
                                (0, self._local_np - self.N))
        # host-numpy tables close over the traced builders as constants
        # (shard_map-safe; a few hundred KB at worst)
        if self.use_bundle:
            ftbl = plan.feat_table()
            unb = dataset.search_tables(self.B, self.Cstore)
            if unb is None:         # the plan packs a categorical feature
                unb = dataset.unbundle_tables(self.B, self.Cstore)
        else:
            ftbl = np.asarray(identity_feat_table(nb))
            unb = None

        self.split_kw = make_split_kw(cfg)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)

        # histogram-memory bound (reference HistogramPool analog); the
        # column count is this shard's local share of the STORE — under
        # psum_scatter each device caches only its column slice
        cache_cols = self.Cstore // self.df
        if hx_pad:
            cache_cols = max(1, cache_cols // self.dd)
        self.cache_parent_hist = use_parent_hist_cache(
            cfg, cache_cols, self.B)
        kw = dict(num_leaves=cfg.num_leaves, num_bins_padded=self.B,
                  split_kw=self.split_kw, max_depth=int(cfg.max_depth),
                  min_data_in_leaf=int(cfg.min_data_in_leaf),
                  min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
                  voting_k=int(cfg.top_k) if voting else 0,
                  num_machines=self.dd,
                  hist_exchange=self.hist_exchange,
                  cache_parent_hist=self.cache_parent_hist,
                  input_dtype=getattr(cfg, "histogram_dtype", "float32"))
        sp_feed = self._assemble_sparse_feed() if self._sparse_feed \
            else None
        if mesh is None:
            fn = functools.partial(build_tree, ftbl=ftbl, unb=unb, **kw)
            self._build = jax.jit(fn)
            if sp_feed is not None:
                self.bins_dev = tuple(jnp.asarray(x) for x in sp_feed)
            else:
                self.bins_dev = jnp.asarray(bins_np)
        else:
            from jax.sharding import PartitionSpec as P, NamedSharding
            fn = functools.partial(
                build_tree, ftbl=ftbl, unb=unb, **kw,
                data_axis="data" if self.dd > 1 else None,
                feature_axis="feature" if self.df > 1 else None,
                feature_shard_size=self.Fp // self.df)
            da = "data" if self.dd > 1 else None
            fa = "feature" if self.df > 1 else None
            bins_spec = ((P(fa, da, None), P(fa, da, None), P(fa, None))
                         if sp_feed is not None else P(fa, da))
            in_specs = (bins_spec, P(da), P(da), P(da), P(fa), P(fa), P(fa))
            out_specs = (jax.tree_util.tree_map(lambda _: P(), TreeArrays(
                *[0] * len(TreeArrays._fields))), P(da))
            self._build = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False))
            if self.mh is not None:
                self.bins_dev = self.mh.put_rows(bins_np, P(fa, da))
            elif sp_feed is not None:
                self.bins_dev = (
                    jax.device_put(jnp.asarray(sp_feed[0]),
                                   NamedSharding(mesh, P(fa, da, None))),
                    jax.device_put(jnp.asarray(sp_feed[1]),
                                   NamedSharding(mesh, P(fa, da, None))),
                    jax.device_put(jnp.asarray(sp_feed[2]),
                                   NamedSharding(mesh, P(fa, None))))
            else:
                self.bins_dev = jax.device_put(
                    jnp.asarray(bins_np), NamedSharding(mesh, P(fa, da)))
        # replicated metadata stays HOST-side numpy in multi-process mode
        # (jit replicates identical host values across processes; a
        # committed single-device array would be rejected)
        self.num_bins_dev = nb if self.mh is not None else jnp.asarray(nb)
        self.is_cat_dev = ic if self.mh is not None else jnp.asarray(ic)

    def _assemble_sparse_feed(self):
        """Host [df, Np, R] ELL column windows of the sparse store plus
        the [df, Fsh] zero-bin rows — the fused builders' sparse feed.
        Shard j holds its window's entries in LOCAL column ids with
        sentinel Fsh (= the shard's num_columns_padded); padded columns
        carry zero_bin -1.  The leading feature axis stays 1 when
        unsharded so build_tree squeezes both paths uniformly.  Rows are
        padded to the data tile with no entries — every column reads
        its zero bin there, and the zero row_mask keeps padding out of
        the histograms either way."""
        ds = self.dataset
        if self.use_bundle:
            ri, ci, bi, zb = ds.sparse_entries()
            ncols = self.Cstore
        else:
            ri, ci, bi, zb = ds.unbundled_sparse_entries()
            ncols = self.Fp
        zb = np.pad(zb, (0, ncols - zb.size), constant_values=-1)
        df = self.df
        Fsh = ncols // df
        Np = self._local_np
        w = ci // Fsh
        key = w.astype(np.int64) * Np + ri
        cnt = np.bincount(key, minlength=df * Np) if key.size else \
            np.zeros(df * Np, np.int64)
        R = nnz_capacity_tier(int(cnt.max(initial=1)))
        cols_np = np.full((df, Np, R), Fsh, np.int32)
        ell_np = np.zeros((df, Np, R), np.int32)
        if key.size:
            order = np.argsort(key, kind="stable")
            ks = key[order]
            offs = np.concatenate([[0], np.cumsum(cnt)])
            pos = np.arange(ks.size, dtype=np.int64) - offs[ks]
            cols_np[ks // Np, ks % Np, pos] = (ci - w * Fsh)[order]
            ell_np[ks // Np, ks % Np, pos] = bi[order]
        return cols_np, ell_np, zb.reshape(df, Fsh).astype(np.int32)

    @property
    def bins_t(self):
        """Store view for the ScoreUpdater's binned tree traversal:
        [N+1, F] sentinel-padded transpose (same layout as
        SerialTreeLearner.bins_t), or the sparse ELL triple when the
        dataset is sparse — replay then probes the row segments and the
        store never densifies for scoring."""
        if getattr(self, "_bins_t", None) is None:
            if self.dataset.sparse is not None:
                self._bins_t = self.dataset.sparse_triple()
            else:
                self._bins_t = jnp.asarray(sentinel_bins_t(self.dataset))
        return self._bins_t

    def _feature_mask(self):
        frac = self.config.feature_fraction
        if frac >= 1.0:
            # no sampling: cached device copy — re-uploading the constant
            # mask was one implicit transfer per boosting iteration
            if self.mh is not None:
                return self._base_fmask
            if getattr(self, "_fmask_dev", None) is None:
                self._fmask_dev = jax.device_put(self._base_fmask)
            return self._fmask_dev
        m = self._base_fmask.copy()
        k = max(1, int(round(self.F * frac)))
        sel = self._feat_rng.choice(self.F, size=k, replace=False)
        mm = np.zeros(self.Fp, bool)
        mm[sel] = True
        m &= mm
        # per-iteration host draw is the design (reference rng parity);
        # the upload is deliberate, so it is explicit
        return m if self.mh is not None else jax.device_put(m)

    def _pad_rows(self, x: jax.Array):
        if self.mh is not None:
            from jax.sharding import PartitionSpec as P
            return self.mh.put_rows(
                self.mh.pad_local(np.asarray(x, np.float32)), P("data"))
        if self.Np == self.N:
            return x
        return pad_rows_dev(x, pad=self.Np - self.N)

    def _record_comm_stats(self) -> None:
        """Per-tree comms accounting for the data-parallel exchange.
        The fused builder's fori_loop always runs num_leaves-1 bodies
        (no-op splits still execute their collectives), so the per-tree
        byte totals are STATIC — recorded host-side, no device scalar
        needed (unlike the rounds learner's cond-skipped chunks)."""
        if self.dd <= 1:
            return
        from .. import profiling
        L = self.config.num_leaves
        hxs = self.hist_exchange == "psum_scatter"
        calls = 1 + 2 * (L - 1)               # find_best invocations
        if self._voting:
            k2 = max(1, min(2 * int(self.config.top_k), self.F))
            k2p = self.dd * ((k2 + self.dd - 1) // self.dd) if hxs else k2
            per = 4.0 * (k2p // self.dd if hxs else k2) * 3 * self.B
            hx_bytes = per * calls
        else:
            cols = self.Cstore // self.df
            per = 4.0 * (cols // self.dd if hxs else cols) * 3 * self.B
            passes = 1 + (L - 1) * (1 if self.cache_parent_hist else 2)
            hx_bytes = per * passes
        profiling.count(profiling.HIST_EXCHANGE_BYTES, hx_bytes)
        profiling.count(profiling.SPLIT_RECORDS_BYTES,
                        4.0 * self.dd * 11 * calls if hxs else 0.0)

    def train(self, grad: jax.Array, hess: jax.Array,
              bag_idx: Optional[jax.Array] = None,
              bag_count: Optional[int] = None) -> Tuple[Tree, jax.Array]:
        if self.mh is not None:
            mask = self._row_mask
            if bag_idx is not None:
                m2 = np.zeros(self._local_np, np.float32)
                bi = np.asarray(bag_idx)
                m2[bi[bi < self.N]] = 1.0
                mask = m2 * mask
            from jax.sharding import PartitionSpec as P
            mask = self.mh.put_rows(mask, P("data"))
        else:
            if getattr(self, "_row_mask_dev", None) is None:
                self._row_mask_dev = jax.device_put(self._row_mask)
            mask = self._row_mask_dev
            if bag_idx is not None:
                # bag_idx is padded with sentinel N, which IS in bounds
                # when rows are padded (Np > N) — multiply by the base
                # row mask so padding rows can never count
                mask = bag_mask_dev(bag_idx, mask)
        arrs, leaf_id = self._build(
            self.bins_dev, self._pad_rows(grad), self._pad_rows(hess), mask,
            self.num_bins_dev, self.is_cat_dev, self._feature_mask())
        self._record_comm_stats()
        check_tree_divergence("fused/tree", arrs)
        tree = tree_arrays_to_host(arrs, self.dataset,
                                   self.config.num_leaves)
        if self.mh is not None:
            return tree, jnp.asarray(self.mh.local_rows(leaf_id))
        return tree, slice_rows_dev(leaf_id, n=self.N)


def create_tree_learner(dataset: Dataset, config: Config):
    """Factory (reference tree_learner.cpp:9-33).

    tree_learner picks the PARALLELISM (serial / data / feature / voting /
    data2d → mesh axes); tree_growth picks the SCHEDULE:
    - "exact": strict one-split-at-a-time leaf-wise.  On CPU this is the
      host-loop gather learner (learner/serial.py); on TPU it is the fused
      single-split builder (no per-split host syncs).
    - "rounds": batched rounds (learner/rounds.py) — the MXU-efficient
      schedule; equals leaf-wise whenever the num_leaves cap doesn't bind.
    - "auto": rounds on TPU, exact elsewhere (the masked multi-leaf
      formulation is matmul-heavy — right for the MXU, wasteful on CPU,
      where the gather-based exact learner is work-optimal).
    """
    lt = getattr(config, "tree_learner", "serial")
    growth0 = getattr(config, "tree_growth", "auto")
    growth = growth0
    on_tpu = jax.default_backend() == "tpu"
    if growth == "auto":
        growth = "rounds" if on_tpu else "exact"
    if getattr(dataset, "sparse", None) is not None and growth0 == "auto" \
            and growth != "rounds" and lt not in ("feature", "voting"):
        # the nonzero-iterating kernels live in the rounds learner; an
        # exact-growth build over a sparse store on the host-loop serial
        # learner would densify it, so `auto` resolves rounds wherever
        # the store is sparse.  The fused feature-sharded / voting
        # learners consume per-shard ELL windows directly
        # (FusedTreeLearner._assemble_sparse_feed) and keep the fused
        # builder; an EXPLICITLY pinned exact growth takes the counted
        # dense fallback instead.
        from .. import log
        log.info("sparse store: tree_growth=auto resolves to rounds "
                 "(the nonzero-iterating histogram path)")
        growth = "rounds"

    mesh = None
    if lt in ("data", "feature", "voting", "data2d"):
        mesh = make_mesh(lt, getattr(config, "num_machines", 0))
        if mesh is None:
            import warnings
            warnings.warn(f"tree_learner={lt} requested but only one device "
                          "is visible; running single-device")

    feature_sharded = (mesh is not None
                       and mesh_axes(mesh).get("feature", 1) > 1)
    if lt == "voting" and mesh is not None:
        # PV-Tree needs the per-split vote exchange of the fused builder
        return FusedTreeLearner(dataset, config, mesh)
    if growth == "rounds" and (not feature_sharded or lt == "data2d"):
        # data2d + rounds runs the 2-D (data x feature) mesh inside the
        # rounds builder itself: rows shard over both axes, histograms
        # psum over data and reduce-scatter over feature
        # (docs/Distributed-Data.md).  tree_learner=feature keeps the
        # fused exact builder (its feature sharding splits the search
        # over replicated rows, a different decomposition).
        from .rounds import RoundsTreeLearner
        return RoundsTreeLearner(dataset, config, mesh)
    if mesh is not None:
        return FusedTreeLearner(dataset, config, mesh)
    if on_tpu:
        return FusedTreeLearner(dataset, config, None)
    from .serial import SerialTreeLearner
    return SerialTreeLearner(dataset, config)
