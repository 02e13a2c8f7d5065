"""Batched-rounds tree learner — the TPU throughput path.

The reference grows leaf-wise, one split at a time
(/root/reference/src/treelearner/serial_tree_learner.cpp:168-224), which on
TPU leaves the MXU nearly idle: a single leaf's histogram matmul has only
M=8 value rows (~6% utilization) and each split costs a full pass over the
rows.  This learner restructures the SAME split math into rounds:

- every round splits ALL currently-splittable leaves at once (when the
  `num_leaves` cap binds, the top-gain leaves win — the greedy criterion
  applied per round instead of per split);
- the smaller children of all K splits in a round are histogrammed in ONE
  multi-leaf pass (`ops/histogram.hist_multileaf_masked`): vals rows are
  (grad·mask_k, hess·mask_k, mask_k) for K leaves → an [M=3K, C] @ [C, B]
  MXU matmul at M≈128, with the one-hot generation amortized over the
  whole round; larger children come from parent-histogram subtraction
  (serial_tree_learner.cpp smaller/larger trick, unchanged);
- the whole tree builds inside one `lax.while_loop` — zero host syncs
  (the reference's per-split host loop costs a device round-trip per
  split, which on remote-attached TPUs dominates everything).

When the cap never binds, a round-batched tree equals the leaf-wise tree:
splits of distinct leaves are independent, and every positive-gain leaf is
split in both policies.  They differ only in WHICH splits are kept once
`num_leaves` runs out (greedy-per-split vs greedy-per-round).

Data-parallel: rows sharded on the mesh "data" axis; histograms are
exchanged per pass either by full `lax.psum` or — the default at real
shapes — by `lax.psum_scatter` over the store-column axis, where each
device reduces and keeps only its F/ndev feature slice, split-searches
it, and all_gathers the per-leaf best-split records (the reference's
Network::ReduceScatter ownership model, data_parallel_tree_learner.cpp:
118-160; `hist_exchange` knob).

Every histogram pass streams the whole store with the leaf mask built in
the kernel, at the slot tier (8 / 32 / K) that holds the round's leaves.
A feed that copied out only the rounds' rows through a row permutation cost
about 125 ns a row and pass beyond its kernel and lost to the stream in
every benchmark cell, by 13x, 23 % and 4 % (PERF.md section 6, PR 32).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset
from ..sharded.mesh import (check_scatter_divisible, check_tree_divergence,
                            mesh_axes, pad_cols_to_ndev,
                            resolve_hist_exchange, row_shard_axes)
from .common import (CPU_TIER_BYTES_LIMIT, device_bytes_limit,
                     make_split_kw, padded_bin_count, sentinel_bins_t,
                     use_parent_hist_cache)
from .fused import TreeArrays, tree_arrays_specs, tree_arrays_to_host
from .. import profiling
from ..jaxutil import RowLayout, bag_mask_dev
from ..ops.histogram import (hist_multileaf_masked, hist_sparse_multileaf,
                             kernel_launch_slots, masked_hist_mxu_ops,
                             masked_pad_columns, masked_store_copy_rows,
                             int8_operands,
                             sparse_window_streams, store_alignment)
from ..ops.partition import (partition_rows, partition_rows_sparse,
                             partition_store_copy_rows)
from ..binning import StoreCells
from ..ops.split import (best_split, best_split_in_store,
                         bundle_predicate_params, combine_sharded_records,
                         record_width,
                         identity_feat_table, leaf_output, maybe_unbundle,
                         sharded_slice_search, store_search_operands)
from ..tree import Tree

NEG_INF = -jnp.inf

# The counters that build_tree_rounds' stats vector feeds, in the
# vector's order (profiling.py says what each counts).
STATS_COUNTERS = (
    profiling.HIST_ROWS_TOUCHED, profiling.HIST_EXCHANGE_BYTES,
    profiling.SPLIT_RECORDS_BYTES, profiling.SPARSE_NNZ_TOUCHED,
    profiling.TREE_ROUNDS, profiling.HIST_PASSES, profiling.HIST_SLOTS,
    profiling.HIST_LIVE_SLOTS, profiling.HIST_MXU_OPS,
    profiling.PARTITION_ROWS, profiling.STORE_COPY_ROWS,
    profiling.EXCHANGE_COLLECTIVES)
(S_ROWS, S_EXCHANGE, S_RECORDS, S_NNZ, S_ROUNDS, S_PASSES, S_SLOTS, S_LIVE,
 S_OPS, S_PARTITION, S_COPY, S_COLLECTIVES) = range(len(STATS_COUNTERS))

# Every phase of build_tree_rounds runs under a jax.named_scope
# "lgbt.<phase>", so that an operation in a profiler trace says which
# phase it belongs to (its op_name carries ".../lgbt.<phase>/..."; where
# scopes nest, as under lgbt.root, the innermost is the phase).  Scopes
# are metadata: they add no operation.

# Leaves histogrammed per multi-leaf pass.  3·K is the M dimension of the
# hist matmul, and a LARGER K means FEWER full-row passes per round.  The
# ISOLATED kernel's per-pass cost is nearly flat in K on the int8 path
# (207 ms at K=1 vs 214 ms at K=128 on the north-star shape,
# profile_hotpath_measured.json), which predicts K=128 — one chunk per
# round — should win.  The in-learner A/B on chip says otherwise: at the
# north-star shape, end-to-end s/iter with K=128 was NOT faster than
# K=84 (rounds rarely split a full 128 leaves, and the masked kernel's
# work scales with the padded M, so late narrow rounds pay for leaves
# that aren't there).  84 (M=256) stays the measured default for every
# precision; bf16/f32 additionally slow down outright at M=384 (258 ms
# → 404 ms per pass).  Grown trees agree across K up to f32
# summation-order ulps (tests/test_rounds.py::
# test_leaves_per_batch_k_independent) and LGBT_LEAVES_PER_BATCH
# overrides the default for on-chip tuning.
import os as _os


def _clamp_k(v: int) -> int:
    """Clamp to [1, 336]: 3K is the matmul M dim and the masked kernel's
    VMEM vals block is [3K, chunk].  Past K=84 the row chunk shrinks in
    proportion (ops/histogram._masked_chunk); the TPU compiler accepts
    every K up to 336 with int32 bins, and none past 84 with int8-stored
    bins, whose [32, 3K, 128] output block outgrows the VMEM scope."""
    c = max(1, min(v, 336))
    if c != v:
        from .. import log
        log.warning(f"LGBT_LEAVES_PER_BATCH={v} clamped to {c}")
    return c


def _leaves_per_batch_from_env() -> Optional[int]:
    """Defensive parse (a malformed value must not break every import);
    None when unset — the module default (84) then applies."""
    raw = _os.environ.get("LGBT_LEAVES_PER_BATCH", "")
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        from .. import log
        log.warning(f"ignoring malformed LGBT_LEAVES_PER_BATCH={raw!r}; "
                    "using the default (84)")
        return None
    return _clamp_k(v)


# K for one masked histogram pass: env override, else the chip-measured
# 84 (see the block comment above — the kernel-level case for K=128 on
# int8 did not survive the end-to-end A/B).  Read at call time by
# build_tree_rounds so tests can monkeypatch it.
LEAVES_PER_BATCH = _leaves_per_batch_from_env() or 84


def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def build_tree_rounds(bins, grad, hess, row_mask, num_bins, is_cat, fmask,
                      ftbl=None, unb=None, *,
                      num_leaves: int, num_bins_padded: int, split_kw: tuple,
                      max_num_bin: int = 0,
                      max_depth: int, min_data_in_leaf: int,
                      min_sum_hessian_in_leaf: float,
                      data_axis: Optional[str] = None,
                      feature_axis: Optional[str] = None,
                      backend: str = "xla",
                      input_dtype: str = "float32",
                      max_rounds: int = 0,
                      cache_parent_hist: bool = True,
                      hist_exchange: str = "psum",
                      num_devices: int = 1,
                      num_feature_shards: int = 1,
                      leaves_per_batch: int = 0,
                      sparse: bool = False,
                      real_columns: int = 0,
                      missing_bins=None):
    """Grow one tree in batched rounds.  Shapes as learner/fused.build_tree.
    Returns (TreeArrays, leaf_id, stats) — stats is a [12] f32 vector in
    the order of STATS_COUNTERS: rows processed by histogram kernels
    (global across shards); per-device histogram-exchange payload
    bytes; per-device best-split-record allgather bytes; stored sparse
    entries processed (global, 0 on the dense path); rounds of the
    loop; histogram kernel launches, the root's included; the slots
    those launches were made for (each launch's K) and the slots among
    them that held a leaf; the operations their contractions perform
    (ops/histogram.masked_hist_mxu_ops per dense launch, global across
    shards; the sparse kernels add 0); the rows whose leaf id the
    rounds rewrite (all Nloc in every round, global across shards); the
    rows of the store that the partitions and the launches copy
    into a padded form (0 for a store laid out to the kernels' tiles,
    as RoundsTreeLearner lays it out; rounds x Nloc + launches x Nloc
    where neither wrapper can tile `bins` as it stands); and the
    collectives launched across the mesh (per device: the exchange's
    legs, the record all_gathers and the root's psum of the leaf
    totals; 0 without a mesh).
    Every one is a scalar add where the launch or the round is made,
    on values the build already has.

    Every pass streams all Nloc rows of the store, at the slot tier
    (8 / 32 / K) that holds the round's leaves; bagged or GOSS-dropped
    rows, and the rows a learner pads the store with, carry a zero
    row_mask.  With int8 operands the gradients are quantised once a
    tree, by the largest of all rows, and every pass reads that.

    hist_exchange="psum_scatter" (static; with data_axis set and
    num_devices the data-axis size) replaces the full [K, F, 3, B]
    histogram psum with a reduce-scatter over the store-column axis:
    each device reduces and keeps only its F/num_devices column slice
    (the reference's ReduceScatter ownership model,
    data_parallel_tree_learner.cpp:118-160), runs best-split search on
    that slice only (bundle-aware: the slice's own cells through its
    rows of the StoreCells tables, or unbundled per shard via
    ops/split.unbundle_hist_local), then all_gathers the per-leaf
    packed records and combines them (max gain, ties to the smallest
    feature id — ops/split.combine_sharded_records).  Per-device comms
    drop ~num_devices x always; split-search work drops too (but where
    a bundled store falls back to the gather, which re-scans the full
    original-feature layout per shard).  The
    parent-histogram cache holds column SLICES in this mode
    (num_devices x less memory).  F must then divide evenly by
    num_devices (callers pad the store).

    feature_axis adds the 2-D (data x feature) mesh topology
    (docs/Distributed-Data.md): rows shard over BOTH axes (every device
    holds all store columns of its row block); the exchange
    reduce-scatters over the FEATURE axis first and then psums only
    the resulting F/num_feature_shards slice over the DATA axis — the
    axis meant to span hosts moves the slice, not the full store —
    leaving each device its column slice fully reduced across all
    num_devices * num_feature_shards row shards.  Split records combine over the
    feature axis; leaf totals, control flow, and the grown tree stay
    bitwise replicated across the whole mesh, so 2-D trees are
    IDENTICAL to the 1-D psum and psum_scatter trees (the MULTICHIP
    dryrun gate).  F must divide evenly by num_feature_shards.

    `bins` holds STORE columns (bundled under EFB); num_bins/is_cat/fmask
    are per-ORIGINAL-feature.  `ftbl` is the [5, F] feature→column table
    (identity when unbundled) and `unb` what split search takes a
    bundled histogram through: a binning.StoreCells — the search runs
    over the store histogram's own cells (ops/split.best_split_in_store)
    — or, for a plan that packs a categorical feature, the (src, dmask)
    gather tables that unbundle every histogram to [F, 3, B] first.
    Either way split records, TreeArrays, and leaf partitioning all
    speak original (feature, threshold) space; only partition_rows sees
    store columns, through the translated store-space predicate.

    cache_parent_hist=False bounds tree-state memory (the analog of the
    reference HistogramPool cap, feature_histogram.hpp:313-475): instead
    of keeping every leaf's [F, 3, B] histogram for the parent-subtraction
    trick, BOTH children are histogrammed directly — 2x histogram passes
    per round, O(1) leaf-hist memory.  The learner picks this mode when
    L*F*3*B*4 bytes exceeds the histogram_pool_size budget.

    sparse=True switches the row feed to the nonzero-iterating kernels
    (docs/Sparse.md): `bins` is then the sparse-store pytree
    (cols [Nloc, R], bins [Nloc, R], zero_bin [F], e_row, e_flat,
    e_valid window streams — stream leaves carry a leading stacked-shard
    axis under shard_map) and every histogram/partition touches only
    stored entries, with the zero bin reconstructed from per-leaf
    totals.  The reduced histogram keeps the dense [K, F, 3, B] layout,
    so hist_exchange (psum / psum_scatter slice ownership) and the
    round logic compose unchanged.  The stats vector's S_NNZ element
    counts the stored entries touched by histogram kernels (global
    across shards — the tree/sparse_nnz_touched counter).

    real_columns (static; 0 = all): the leading store columns that hold
    data.  The dense launches histogram only those
    (ops/histogram.hist_multileaf_masked) and hand back exact zeros for
    the columns a learner pads the store with, which split search never
    reads; tree/hist_mxu_ops counts the contraction as executed.

    missing_bins ([F] int32, a feature's NaN bin or -1; None where no
    feature has one — nothing of it is then traced): split search tries
    the NaN bin on both sides (ops/split.best_split), the records gain a
    twelfth field, `default_left`, which the partition's window and the
    TreeArrays carry."""
    if sparse:
        sp_cols, sp_bins, sp_zb = bins[0], bins[1], bins[2]
        # stream leaves arrive stacked with a leading shard axis (one
        # block per shard under shard_map); squeeze it
        sp_streams = tuple((a[0] if a.ndim == 3 else a) for a in bins[3:6])
        sp_slots = bins[6][0] if bins[6].ndim == 2 else bins[6]
        spt = (sp_cols, sp_bins, sp_zb) + sp_streams + (sp_slots,)
        Nloc = sp_cols.shape[0]
        F = sp_zb.shape[0]
        # stored entries per masked pass (static shape, traced value)
        nnz_pass = jnp.sum((sp_cols < F).astype(jnp.float32))
    else:
        F, Nloc = bins.shape
    L = num_leaves
    B = num_bins_padded
    K = leaves_per_batch or LEAVES_PER_BATCH
    n_chunks = (L + K - 1) // K
    # rows shard over every mesh axis present; under psum_scatter the
    # store-column axis scatters over ONE of them — the feature axis on
    # a 2-D (data x feature) mesh, else the data axis (1-D)
    row_axes = tuple(a for a in (data_axis, feature_axis)
                     if a is not None) or None
    sc_axis = feature_axis if feature_axis is not None else data_axis
    hx = hist_exchange == "psum_scatter" and sc_axis is not None
    nd = (num_feature_shards if feature_axis is not None
          else (num_devices if data_axis is not None else 1))
    if hx:
        # trace-time guard with a named ValueError (the learner pads the
        # store, so only direct build_tree_rounds callers can trip it)
        check_scatter_divisible("store columns", F, nd)
    Fs = F // nd if hx else F

    def exchange(h):
        """Reduce a LOCAL histogram [..., F, 3, B] across the row axes:
        full psum, or reduce(-scatter) keeping this shard's [Fs, 3, B]
        store-column slice.  On the 2-D mesh the reduction decomposes
        as reduce-scatter over the FEATURE axis first (dropping to the
        F/df slice while still inside the intra-host axis) and then a
        psum of only that slice over the DATA axis — the axis that
        spans hosts moves F/df columns, not F (one-step psum_scatter
        on a 1-D mesh)."""
        if row_axes is None:
            return h
        if hx:
            h = jax.lax.psum_scatter(h, sc_axis,
                                     scatter_dimension=h.ndim - 3,
                                     tiled=True)
            if data_axis is not None and feature_axis is not None:
                h = jax.lax.psum(h, data_axis)
            return h
        return jax.lax.psum(h, row_axes)

    # per-device reduced payload per collective leg: the scatter leg
    # keeps the F/nd slice; the 2-D mesh adds the data-axis psum of
    # that same slice as a second leg
    hx_legs = 2 if (hx and data_axis is not None
                    and feature_axis is not None) else 1

    def _exchange_bytes(k2: int) -> float:
        """Per-device reduced-histogram payload of one k2-leaf pass:
        the full tensor under psum, the F/nd slice (times the collective
        legs of the 2-D decomposition) under psum_scatter."""
        if row_axes is None:
            return 0.0
        if hx:
            return 4.0 * k2 * Fs * 3 * B * hx_legs
        return 4.0 * k2 * F * 3 * B

    rw = record_width(missing_bins)

    def _records_bytes(k2: int) -> float:
        """Per-device payload of the best-split-record allgather (only
        the psum_scatter path exchanges records)."""
        return 4.0 * nd * k2 * rw if hx else 0.0

    def count_collectives(stv, exchanges: int, searches: int, more: int = 0):
        """`stv` with the collectives added that one device launches
        for `exchanges` histogram exchanges and `searches` batched split
        searches (+ `more`): an exchange is one psum or psum_scatter
        (two legs on a 2-D mesh's scatter); a search all_gathers its
        records under psum_scatter only.  Without a mesh the slot
        stays 0 and the program gains no operation."""
        if row_axes is None:
            return stv
        return stv.at[S_COLLECTIVES].add(float(
            exchanges * hx_legs + (searches if hx else 0) + more))

    if ftbl is None:
        ftbl = identity_feat_table(num_bins)
    # Termination is governed by the while_loop predicate (no positive gain
    # or num_leaves reached); R is only a provably non-binding safety bound:
    # any round that runs splits >=1 leaf, so L-1 rounds suffice even for a
    # chain-shaped tree (serial_tree_learner.cpp:203-224 stopping rule).
    R = max_rounds if max_rounds > 0 else L - 1
    skw = dict(split_kw)
    l1, l2 = skw["lambda_l1"], skw["lambda_l2"]
    # int8-stored bins (value-128, see ops/histogram bin_offset) stay
    # narrow: a [F, N] int32 copy would be 4x the HBM (12.8 GB for a
    # [32, 100M] store); every consumer widens in fused ops / kernel VMEM
    if sparse:
        binsf = None
    elif bins.dtype == jnp.int8:
        binsf = bins
    else:
        with jax.named_scope("lgbt.feed"):
            binsf = bins.astype(jnp.int32)

    if not sparse:
        # what the counters of a dense launch are computed from
        # (ops/histogram: the launch's layout at these static shapes)
        lay_kw = dict(bins_itemsize=binsf.dtype.itemsize, num_bins_padded=B,
                      backend=backend, input_dtype=input_dtype,
                      max_num_bin=max_num_bin)
        cols_kw = dict(lay_kw, real_columns=real_columns)
    # rows of the store one round's partition copies before its kernel
    partition_copy = 0 if sparse else partition_store_copy_rows(
        F, Nloc, bins_itemsize=binsf.dtype.itemsize, num_slots=L + 1,
        backend=backend, num_bins_padded=B)

    def launch_stats(k: int, live):
        """The stats vector of one histogram kernel launch over all
        Nloc rows for k slots, `live` of which hold a leaf."""
        v = [0.0] * len(STATS_COUNTERS)
        v[S_ROWS], v[S_PASSES] = Nloc, 1.0
        v[S_SLOTS], v[S_LIVE] = k, live
        if sparse:
            v[S_NNZ] = nnz_pass
        else:
            v[S_OPS] = masked_hist_mxu_ops(F, Nloc, k, **cols_kw)
            v[S_COPY] = masked_store_copy_rows(F, Nloc, k, **lay_kw)
        return jnp.stack([jnp.float32(x) for x in v])

    def hist_masked(lid_, sl_):
        """One masked multi-leaf pass over the full store — dense
        streaming or nonzero-iterating per the static `sparse` flag;
        both return [K, F, 3, B]."""
        if sparse:
            return hist_sparse_multileaf(
                spt, lid_, gh8, sl_, num_columns_padded=F,
                num_bins_padded=B, backend=backend,
                input_dtype=input_dtype)
        return hist_multileaf_masked(
            binsf, lid_, gh8, sl_, num_bins_padded=B, backend=backend,
            input_dtype=input_dtype, max_num_bin=max_num_bin, ghq=ghq,
            real_columns=real_columns)

    # a bundled store searched in its own cells: the tree's feature
    # mask reaches cell space here, once, not once a searched slot; a
    # device under psum_scatter takes its own columns' rows of the tables
    in_store = isinstance(unb, StoreCells)
    if in_store:
        with jax.named_scope("lgbt.root"):
            search = store_search_operands(
                unb, fmask, jax.lax.axis_index(sc_axis) * Fs if hx else 0,
                Fs)

    def find_best_batch(hists, sums):
        """hists [K2, C, 3, B] reduced STORE histograms (C = F, or this
        shard's Fs slice under psum_scatter), sums [K2, 3] → packed recs
        [K2, 11] in ORIGINAL feature space (a bundled store's through
        its cell tables, or unbundled per leaf), with the can-split
        gate applied (depth gate at selection time).

        psum_scatter: each shard split-searches only its column slice
        (ops/split.sharded_slice_search — in the slice's own cells,
        unbundled per shard, or the identity store's metadata
        dynamic-slice), then the [nd, K2, 11] record allgather picks each leaf's max gain with ties broken by
        smallest feature id (ops/split.combine_sharded_records — the
        full search's flat-argmax tie-break, shard-order independent)."""
        if hx:
            off = jax.lax.axis_index(sc_axis) * Fs
            if unb is None:
                nb_s = jax.lax.dynamic_slice_in_dim(num_bins, off, Fs)
                ic_s = jax.lax.dynamic_slice_in_dim(is_cat, off, Fs)
                fm_s = jax.lax.dynamic_slice_in_dim(fmask, off, Fs)
                mb_s = (None if missing_bins is None else
                        jax.lax.dynamic_slice_in_dim(
                            jnp.asarray(missing_bins), off, Fs))
            else:
                nb_s = ic_s = fm_s = mb_s = None

        def one(h, s):
            if hx:
                p = sharded_slice_search(
                    h, s, off=off, nb_s=nb_s, ic_s=ic_s, fm_s=fm_s,
                    num_bins=num_bins, is_cat=is_cat, fmask=fmask,
                    unb=search if in_store else unb, skw=skw,
                    mb_s=mb_s, missing_bins=missing_bins)
            elif in_store:
                p = best_split_in_store(h, search, s[0], s[1], s[2],
                                        **skw).packed()
            else:
                rec = best_split(maybe_unbundle(h, unb, s),
                                 num_bins, is_cat, fmask,
                                 s[0], s[1], s[2], missing_bins, **skw)
                p = rec.packed()
            can = ((s[2] >= 2 * min_data_in_leaf)
                   & (s[1] >= 2 * min_sum_hessian_in_leaf))
            gain = jnp.where(can & jnp.isfinite(p[0]) & (p[0] > 0),
                             p[0], NEG_INF)
            return p.at[0].set(gain)

        recs = jax.vmap(one)(hists, sums)
        if hx:
            recs = combine_sharded_records(recs, sc_axis)
        return recs

    # ---- root ---------------------------------------------------------------
    with jax.named_scope("lgbt.feed"):
        gh8 = jnp.zeros((8, Nloc), jnp.float32)
        gh8 = gh8.at[0].set(grad * row_mask).at[1].set(hess * row_mask)
        gh8 = gh8.at[2].set(row_mask)
        # every launch of the tree is over these rows: quantise once
        ghq = (int8_operands(gh8) if input_dtype == "int8" and not sparse
               else None)
    with jax.named_scope("lgbt.root"):
        lid0 = jnp.zeros(Nloc, jnp.int32)
        h0 = hist_masked(lid0, jnp.zeros(1, jnp.int32))
        if hx:
            # leaf totals from the LOCAL pass (any single store column's bin
            # sums give them; store column 0 is always real) + one tiny
            # psum — the scattered histogram no longer holds column 0 on
            # every shard
            ls = jnp.stack([jnp.sum(h0[0, 0, 0, :]), jnp.sum(h0[0, 0, 1, :]),
                            jnp.sum(h0[0, 0, 2, :])])
            root_sums = jax.lax.psum(ls, row_axes)
            cnt = root_sums[2]
            hist0 = exchange(h0[0])                         # [Fs, 3, B]
        else:
            hist0 = _psum(h0[0], row_axes)                  # [F, 3, B]
            sum_g = jnp.sum(hist0[0, 0, :])
            sum_h = jnp.sum(hist0[0, 1, :])
            cnt = jnp.sum(hist0[0, 2, :])
            root_sums = jnp.stack([sum_g, sum_h, cnt])

        leaf_id = jnp.zeros(Nloc, jnp.int32)
        # the root contributes one full-stream launch for one slot + one
        # exchange; under psum_scatter also the psum of the leaf totals
        stats = count_collectives(
            launch_stats(1, 1).at[S_EXCHANGE].set(_exchange_bytes(1))
            .at[S_RECORDS].set(_records_bytes(1)), 1, 1, more=int(hx))
        leaf_best = jnp.full((L, rw), NEG_INF, jnp.float32).at[0].set(
            find_best_batch(hist0[None], root_sums[None])[0])
        leaf_depth = jnp.zeros(L, jnp.int32)
        leaf_parent = jnp.full(L, -1, jnp.int32)
        leaf_side = jnp.zeros(L, jnp.int32)
        # under psum_scatter the cache holds this shard's column SLICES
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
            # leaf 0 stays 0.0 until a split assigns it: a tree that never
            # splits must contribute zero score (the sync path discards such
            # trees; the pipelined path applies leaf values before it can know)
            leaf_value=jnp.zeros(L, jnp.float32),
            leaf_count=jnp.zeros(L, jnp.float32).at[0].set(cnt),
            leaf_depth=jnp.zeros(L, jnp.int32),
            num_leaves=jnp.int32(1),
            default_left=(None if missing_bins is None
                          else jnp.zeros(L - 1, bool)),
        )

    def round_body(st):
        (rnd, leaf_id, leaf_best, leaf_depth, leaf_parent, leaf_side,
         leaf_hist, stats, arrs) = st
        n_leaves = arrs.num_leaves

        # ---- select this round's splits (top-gain within the cap) ---------
        with jax.named_scope("lgbt.select"):
            gated = jnp.where((max_depth <= 0) | (leaf_depth < max_depth),
                              leaf_best[:, 0], NEG_INF)
            order = jnp.argsort(-gated).astype(jnp.int32)       # [L]
            sgain = gated[order]
            remaining = L - n_leaves
            slot = jax.lax.broadcasted_iota(jnp.int32, (L,), 0)
            do = (sgain > 0) & (slot < remaining)               # [L] sorted slots
            prefix = jnp.cumsum(do.astype(jnp.int32)) - do.astype(jnp.int32)
            m = jnp.sum(do.astype(jnp.int32))

            pl_ = order                                          # parent leaf/slot
            rec = leaf_best[pl_]                                 # [L, rw]
            feat = rec[:, 1].astype(jnp.int32)
            thr = rec[:, 2].astype(jnp.int32)
            catf = is_cat[feat]
            dlf = None if missing_bins is None else rec[:, 11] > 0.5
            new_leaf = n_leaves + prefix                         # [L]
            node = (n_leaves - 1) + prefix                       # [L]
            l_sums = rec[:, 3:6]
            r_sums = rec[:, 6:9]

        # ---- partition all rows in one pass -------------------------------
        with jax.named_scope("lgbt.partition"):
            # per-LEAF lookup of (split column, threshold, is-cat, new leaf)
            # then the per-row bin read and move — fused in one pallas pass
            # (ops/partition.py; XLA fallback composes the one-hot matmuls of
            # ops/lookup.py there).  XLA's [Nloc] table gather runs at
            # <1 GB/s on TPU and cost more than the histogram kernel
            # (65 ms/table at N=4M); new_leaf > 0 ⟺ leaf splits, leaf 0
            # is never a NEW leaf, so 0 table rows mean "stay".  The split
            # (feat, thr) is ORIGINAL space; the table carries the translated
            # STORE-space predicate (ops/split.bundle_predicate_params), so
            # bundled columns partition without ever materializing original
            # bins
            colv, Tv, lov, hi1v, dlv = bundle_predicate_params(
                ftbl, feat, thr, catf, missing_bins, dlf)
            tbl_idx = jnp.where(do, pl_, L)                      # drop-slot L
            zeros = jnp.zeros(L + 1, jnp.float32)

            def srow(v):
                return zeros.at[tbl_idx].set(v.astype(jnp.float32), mode="drop")

            tbl = jnp.stack([srow(colv), srow(Tv), srow(catf), srow(new_leaf),
                             srow(lov), srow(hi1v), srow(dlv)])
            if sparse:
                leaf_id2 = partition_rows_sparse(sp_cols, sp_bins, sp_zb,
                                                 leaf_id, tbl,
                                                 num_slots=L + 1)
            else:
                leaf_id2 = partition_rows(binsf, leaf_id, tbl,
                                          num_slots=L + 1, backend=backend,
                                          num_bins_padded=B)

        # ---- tree arrays (batched Tree::Split) ----------------------------
        with jax.named_scope("lgbt.tree_arrays"):
            nodei = jnp.where(do, node, L - 1)                   # drop idx
            lvali = jnp.where(do, pl_, L)
            nvali = jnp.where(do, new_leaf, L)
            pn = leaf_parent[pl_]
            side = leaf_side[pl_]
            lpar = jnp.where(do & (pn >= 0) & (side == 0), pn, L - 1)
            rpar = jnp.where(do & (pn >= 0) & (side == 1), pn, L - 1)
            child_depth = leaf_depth[pl_] + 1
            arrs2 = arrs._replace(
                split_feature=arrs.split_feature.at[nodei].set(
                    feat, mode="drop"),
                threshold_bin=arrs.threshold_bin.at[nodei].set(thr, mode="drop"),
                is_cat=arrs.is_cat.at[nodei].set(catf, mode="drop"),
                split_gain=arrs.split_gain.at[nodei].set(rec[:, 0], mode="drop"),
                internal_value=arrs.internal_value.at[nodei].set(
                    arrs.leaf_value[pl_], mode="drop"),
                internal_count=arrs.internal_count.at[nodei].set(
                    l_sums[:, 2] + r_sums[:, 2], mode="drop"),
                left_child=arrs.left_child.at[lpar].set(
                    node, mode="drop").at[nodei].set(~pl_, mode="drop"),
                right_child=arrs.right_child.at[rpar].set(
                    node, mode="drop").at[nodei].set(~new_leaf, mode="drop"),
                leaf_value=arrs.leaf_value.at[lvali].set(
                    rec[:, 9], mode="drop").at[nvali].set(rec[:, 10],
                                                          mode="drop"),
                leaf_count=arrs.leaf_count.at[lvali].set(
                    l_sums[:, 2], mode="drop").at[nvali].set(r_sums[:, 2],
                                                             mode="drop"),
                leaf_depth=arrs.leaf_depth.at[lvali].set(
                    child_depth, mode="drop").at[nvali].set(child_depth,
                                                            mode="drop"),
                num_leaves=n_leaves + m,
            )
            if dlf is not None:
                arrs2 = arrs2._replace(default_left=arrs.default_left.at[
                    nodei].set(dlf, mode="drop"))
            leaf_depth2 = leaf_depth.at[lvali].set(
                child_depth, mode="drop").at[nvali].set(child_depth, mode="drop")
            leaf_parent2 = leaf_parent.at[lvali].set(
                node, mode="drop").at[nvali].set(node, mode="drop")
            leaf_side2 = leaf_side.at[lvali].set(0, mode="drop").at[nvali].set(
                1, mode="drop")

        # ---- batched smaller-child histograms -----------------------------
        with jax.named_scope("lgbt.select"):
            small_is_left = l_sums[:, 2] <= r_sums[:, 2]
            small_leaf = jnp.where(small_is_left, pl_, new_leaf)
            large_leaf = jnp.where(small_is_left, new_leaf, pl_)
            small_sums = jnp.where(small_is_left[:, None], l_sums, r_sums)
            large_sums = jnp.where(small_is_left[:, None], r_sums, l_sums)

        # early rounds have few splittable leaves (1, 2, 4, ... for a
        # balanced tree): a chunk runs at the narrowest of its slot tiers
        # (chunk_tiers: 8, 32, Kc) that holds its active slots — a chunk
        # with <= 8 active slots runs rounds 1-4 of a balanced tree at 8,
        # <= 32 rounds 5-6 at 32 — and the WHOLE chunk body runs at that
        # width: the launch (its matmul has 3·Kt value rows), the
        # exchange, the parents' gather and subtraction, both searches
        # and the updates of leaf_best and the cache.  A slot past the
        # tier holds no leaf, so nothing of it is computed.
        def chunk_at(Kt, args, s):
            """The chunk's slots s..s+Kt, every active one among them:
            histogrammed, exchanged, searched and cached at width Kt."""
            leaf_best2, leaf_hist2, stv = args
            with jax.named_scope("lgbt.select"):
                dt = do[s:s + Kt]                        # [Kt]
                slv = jnp.where(dt, small_leaf[s:s + Kt], -1)  # -1 = empty
                llv = jnp.where(dt, large_leaf[s:s + Kt], -1)
                sil = small_is_left[s:s + Kt, None]
                li = jnp.where(dt, pl_[s:s + Kt], L)
                ni = jnp.where(dt, new_leaf[s:s + Kt], L)

            def hist_pass(slv, stv):
                """One launch for the slots `slv` over all rows, then its
                exchange: ([Kt, F|Fs, 3, B] reduced hists, stats)."""
                with jax.named_scope("lgbt.hist"):
                    h = hist_masked(leaf_id2, slv)
                    launch = launch_stats(Kt, jnp.sum(dt.astype(jnp.float32)))
                with jax.named_scope("lgbt.exchange"):
                    # whole before anything reads it: left free, the
                    # compiler overlaps the kernel output's unpack with
                    # the search's temporaries, 146 MB more at Epsilon's
                    # shape (PERF.md section 6, PR 42)
                    h = jax.lax.optimization_barrier(exchange(h))
                    return h, (stv + launch).at[S_EXCHANGE].add(
                        _exchange_bytes(Kt))

            h_small, stv = hist_pass(slv, stv)
            if cache_parent_hist:
                with jax.named_scope("lgbt.subtract"):
                    h_large = leaf_hist2[pl_[s:s + Kt]] - h_small
            else:
                h_large, stv = hist_pass(llv, stv)
            with jax.named_scope("lgbt.split"):
                rec_s = find_best_batch(h_small, small_sums[s:s + Kt])
                rec_l = find_best_batch(h_large, large_sums[s:s + Kt])
                stv = count_collectives(
                    stv.at[S_RECORDS].add(2 * _records_bytes(Kt)),
                    1 if cache_parent_hist else 2, 2)
                recL = jnp.where(sil, rec_s, rec_l)
                recR = jnp.where(sil, rec_l, rec_s)
                lb = leaf_best2.at[li].set(recL, mode="drop").at[ni].set(
                    recR, mode="drop")
            if cache_parent_hist:
                with jax.named_scope("lgbt.subtract"):
                    hL = jnp.where(sil[:, :, None, None], h_small, h_large)
                    hR = jnp.where(sil[:, :, None, None], h_large, h_small)
                    lh = leaf_hist2.at[li].set(hL, mode="drop").at[
                        ni].set(hR, mode="drop")
            else:
                lh = leaf_hist2
            return lb, lh, stv

        def skip_chunk(args):
            return args

        leaf_best2 = leaf_best
        leaf_hist2 = leaf_hist
        with jax.named_scope("lgbt.select"):
            rnd2 = rnd + 1
            stats2 = (stats.at[S_ROUNDS].add(1.0)
                      .at[S_PARTITION].add(float(Nloc))
                      .at[S_COPY].add(float(partition_copy)))
        for c in range(n_chunks):
            s = c * K
            Kc = min(K, L - s)                               # last chunk short
            dk = do[s:s + Kc]                                # [Kc]
            tiers = chunk_tiers(Kc)
            with jax.named_scope("lgbt.select"):
                # the index in `tiers` of the first whose window holds
                # every active slot, -1 for a chunk with none.  Gated on
                # the REAL precondition (no active slot past the tier),
                # not on the count — robust even if the sorted-prefix
                # layout of `do` ever changes
                tier = jnp.where(jnp.any(dk), sum(
                    jnp.any(dk[t:]).astype(jnp.int32) for t in tiers[:-1]),
                    -1)
            # one two-way cond a tier, not one switch over them: a branch
            # that updates the cache in place beside another that does
            # too makes the compiler copy the whole [L, F, 3, B] cache
            # in each (PERF.md section 6, PR 42)
            args = (leaf_best2, leaf_hist2, stats2)
            for i, Kt in enumerate(tiers):
                # graftlint: allow(divergent-collective) — dk slices `do`, derived from the replicated leaf_best records (psum/combine_sharded_records outputs carried through the while_loop), so every shard computes the identical predicate and takes the same branch; the DivergenceSanitizer checks the products at run time
                args = jax.lax.cond(tier == i, functools.partial(
                    chunk_at, Kt, s=s), skip_chunk, args)
            leaf_best2, leaf_hist2, stats2 = args

        return (rnd2, leaf_id2, leaf_best2, leaf_depth2, leaf_parent2,
                leaf_side2, leaf_hist2, stats2, arrs2)

    def round_cond(st):
        rnd, leaf_best, leaf_depth, arrs = st[0], st[2], st[3], st[-1]
        with jax.named_scope("lgbt.select"):
            gated = jnp.where((max_depth <= 0) | (leaf_depth < max_depth),
                              leaf_best[:, 0], NEG_INF)
            return ((rnd < R) & (arrs.num_leaves < L)
                    & jnp.any(gated > 0))

    st = (jnp.int32(0), leaf_id, leaf_best, leaf_depth, leaf_parent,
          leaf_side, leaf_hist, stats, arrs)
    st = jax.lax.while_loop(round_cond, round_body, st)
    # rows (histogrammed, partitioned, copied), sparse entries and
    # contraction operations are summed across shards (global traffic);
    # the byte counters and the round, launch and slot counts stay
    # per-device (passes are uniform, so every shard agrees)
    with jax.named_scope("lgbt.pack"):
        stv = st[-2]
        glob = jnp.asarray([S_ROWS, S_NNZ, S_OPS, S_PARTITION, S_COPY])
        stv = stv.at[glob].set(_psum(stv[glob], row_axes))
    return st[-1], st[1], stv


def _kernel_backend() -> str:
    """The kernels the learner builds its program from: Pallas on the
    chip, XLA elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _jit_build(fn):
    """jit the build step `fn` as `build_tree_rounds`.  jax.jit names a
    program after the function it is given, and a functools.partial or a
    shard_map closure has no name: the step would run as `jit__unknown`,
    which is no handle for a trace."""
    def step(*args):
        return fn(*args)
    step.__name__ = step.__qualname__ = build_tree_rounds.__name__
    return jax.jit(step)


def search_counters(totals, trees, *, cells, cached, unbundled,
                    pad_columns=0.0):
    """tree/split_cells, tree/unbundle_gather_elems and
    tree/hist_pad_columns of `trees` builds whose stats vectors sum to
    `totals`, on the host (count_deferred's `fold`): each is static per
    searched slot or per launch, and the vector already counts the
    slots and the launches.  A tree searches its root's one slot and,
    per executed chunk of a round, the slots of the tier the chunk ran
    at twice (smaller and larger children): with the parent cache
    (`cached`) one launch of that tier, both searched, else two, each
    searched once.  `cells` is one slot's [features, B] as the search
    scans it; an unbundle in front of it (`unbundled`) gathers that
    three times over (grad, hess, count).  `pad_columns` are the padded
    store columns that every launch leaves out, summed over shards
    (ops/histogram.masked_pad_columns)."""
    launched = float(totals[S_SLOTS])
    slots = trees + 2.0 * (launched - trees) if cached else launched
    return ((profiling.SPLIT_CELLS, cells * slots),
            (profiling.UNBUNDLE_GATHER_ELEMS,
             3.0 * cells * slots if unbundled else 0.0),
            (profiling.HIST_PAD_COLUMNS,
             pad_columns * float(totals[S_PASSES])))


class RoundsTreeLearner:
    """Single- or data-parallel learner using batched-rounds growth."""

    def __init__(self, dataset: Dataset, config: Config,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.dataset = dataset
        self.config = config
        self.mesh = mesh
        self.full_leaf_id = True
        self.N = dataset.num_data
        self.F = dataset.num_features
        self.B = padded_bin_count(dataset.max_num_bin)
        if mesh is not None:
            axes = mesh_axes(mesh)
        else:
            axes = {}
        self.dd = int(axes.get("data", 1))
        # 2-D (data x feature) mesh: rows shard over BOTH axes and the
        # psum_scatter exchange scatters store columns over the feature
        # axis (docs/Distributed-Data.md); nsh is the total row-shard
        # count, nd_sc the scatter world the column padding must tile
        self.df = int(axes.get("feature", 1))
        nsh = self.dd * self.df
        self._nd_sc = self.df if self.df > 1 else self.dd
        self.mh = None
        if mesh is not None and jax.process_count() > 1:
            from ..sharded.mesh import MultiHostRows
            self.mh = MultiHostRows(mesh, self.N)
            self.Np = self.mh.np_global
            self._local_np = self.mh.per_proc
        else:
            self.Np = int(nsh * math.ceil(self.N / max(nsh, 1)))
            self._local_np = self.Np

        backend = _kernel_backend()
        input_dtype = getattr(config, "histogram_dtype", "float32")
        nbv = dataset.split_num_bins.astype(np.int32)  # ORIGINAL [F]
        icv = np.asarray(dataset.is_categorical)     # ORIGINAL [F]
        mbv = dataset.missing_bins                   # ORIGINAL [F] or None
        plan = dataset.bundle_plan
        # nonzero-iterating sparse path (docs/Sparse.md): single-process
        # only for now — per-host stream assembly is the multi-host
        # follow-on; the dense fallback below is counted by the
        # dataset's bins property
        self.sparse = dataset.sparse is not None and self.mh is None
        if dataset.sparse is not None and not self.sparse:
            from .. import log
            log.warning("sparse store is not wired for multi-host runs "
                        "yet; materializing the dense store")
        if self.sparse:
            bins_np = None
            self.Cstore = dataset.sparse.num_columns
            self.Fpad = self.Cstore
            col_mult = 1
        else:
            store = dataset.dense_bins(
                site="rounds_feed")                  # [C, N] (bundled: C<F)
            self.Cstore = store.shape[0]
            # int8 HBM layout (value - 128): 4x less device memory and
            # bandwidth than int32, for a store whose int32 layout would
            # crowd the chip (over a quarter of its memory: 100M rows of
            # 13 columns, say).  Memory-gated: the G=32 block layout it
            # forces measured ~60% slower than the int32 G=8 layout on
            # wide 255-bin data (Epsilon shape), so narrow storage is
            # chosen only then (see _want_int8_bins).
            bins_dtype = (np.int8 if backend == "pallas"
                          and dataset.max_num_bin <= 256
                          and self._want_int8_bins() else np.int32)
            # The store is laid out ONCE, here on the host, to the tiles
            # of the two kernels that read it in every round — columns
            # to the feature group (8; 32 for int8 bins), each shard's
            # rows to the histogram kernel's row chunk, which the
            # partition's chunk divides — so that neither wrapper pads
            # it, or lid / gh8 beside it, in any round or launch: a pad
            # on the device is a whole-store copy an iteration cannot
            # change, and a second store's worth of temporaries.  Padded
            # columns are trivial (1 bin, fmask False) and can never be
            # selected; padded rows carry row_mask 0 and add exact zeros
            # to every sum.  A shard of under one row chunk is one block
            # as it stands; the XLA kernels (off the chip) tile nothing;
            # multi-host rows keep MultiHostRows' own size and the
            # wrappers' pads.
            col_mult, row_mult = (
                store_alignment(np.dtype(bins_dtype).itemsize, self.B,
                                input_dtype, int(dataset.max_num_bin))
                if backend == "pallas" else (1, 1))
            self.Fpad = col_mult * int(math.ceil(self.Cstore / col_mult))
            per_shard = self.Np // max(nsh, 1)
            if self.mh is None and per_shard > row_mult:
                self.Np = self._local_np = int(
                    nsh * row_mult * math.ceil(per_shard / row_mult))
        # data-parallel histogram exchange: resolve the collective from
        # the per-pass payload, then (for psum_scatter) align the store
        # columns so the [K, F, 3, B] histogram tiles the data axis —
        # each device owns an F/ndev store-column slice (the sparse
        # path's REDUCED histogram keeps the dense column layout, so
        # the same alignment applies).  Alignment keeps the kernels'
        # feature group.
        K_pass = min(LEAVES_PER_BATCH, int(config.num_leaves))
        self.hist_exchange = resolve_hist_exchange(
            config, ndev=nsh,
            payload_bytes=4.0 * K_pass * self.Fpad * 3 * self.B)
        if self.hist_exchange == "psum_scatter" and nsh > 1:
            self.Fpad = pad_cols_to_ndev(
                self.Fpad, self._nd_sc, align=col_mult)
        # the dense launches histogram the Cstore real columns only: the
        # padded ones each launch leaves out, on all shards together
        self._pad_columns = 0 if self.sparse else nsh * masked_pad_columns(
            self.Fpad, real_columns=self.Cstore,
            bins_itemsize=np.dtype(bins_dtype).itemsize,
            num_bins_padded=self.B, backend=backend,
            input_dtype=input_dtype, max_num_bin=int(dataset.max_num_bin))
        if self.sparse:
            sps = dataset.sparse
            cols_np = sps.cols.astype(np.int32)
            ell_np = sps.bins.astype(np.int32)
            # the empty-slot sentinel must sit PAST the padded columns,
            # or scatter-aligned padding columns would accumulate
            cols_np = np.where(cols_np >= self.Cstore,
                               np.int32(self.Fpad), cols_np)
            zb_np = np.full(self.Fpad, -1, np.int32)
            zb_np[: self.Cstore] = sps.zero_bin
            if self._local_np > self.N:
                rp = self._local_np - self.N
                cols_np = np.pad(cols_np, ((0, rp), (0, 0)),
                                 constant_values=self.Fpad)
                ell_np = np.pad(ell_np, ((0, rp), (0, 0)))
            self._nnz = int(sps.nnz)
            streams = self._build_sparse_streams(cols_np, ell_np, nsh,
                                                 backend)
        else:
            # one pass over the store: convert into the padded array.
            # The pad value must be an in-range bin (bin 0; -128 in the
            # int8 layout); padded rows/features carry zero mask so
            # their bin never matters
            bins_np = np.zeros((self.Fpad, self._local_np), bins_dtype)
            real = bins_np[: self.Cstore, : self.N]
            if bins_dtype == np.int8:
                bins_np.fill(-128)
                real[...] = store.astype(np.int16) - 128
            else:
                real[...] = store
        if plan is None:
            # unbundled: split metadata mirrors the (padded) store columns
            fp = self.Fpad - self.F
            nbv = np.pad(nbv, (0, fp), constant_values=1)
            icv = np.pad(icv, (0, fp))
            self._base_fmask = np.pad(np.ones(self.F, bool), (0, fp))
            if mbv is not None:
                mbv = np.pad(mbv, (0, fp), constant_values=-1)
            ftbl = None
            unb = None
        else:
            # bundled: split search names ORIGINAL features, so split
            # metadata keeps original size.  It runs over the store
            # histogram's own cells through per-cell tables; a plan that
            # packs a categorical feature has none and unbundles every
            # histogram to the [F] layout first.  Either table covers
            # the PADDED columns (histograms come back [K, Fpad, 3, B]):
            # a plan-sized gather sentinel would read a padded column's
            # bin-0 totals instead of zero
            self._base_fmask = np.ones(self.F, bool)
            ftbl = plan.feat_table()
            unb = dataset.search_tables(self.B, self.Fpad)
            if unb is None:         # the plan packs a categorical feature
                unb = dataset.unbundle_tables(self.B, self.Fpad)
        self._row_mask = np.pad(np.ones(self.N, np.float32),
                                (0, self._local_np - self.N))
        # what train / train_device take grad and hess in and hand the
        # leaf ids back in: Np rows, sharded like the store's on a mesh.
        # Multi-process rows are sized and assembled on the host
        # (MultiHostRows): no layout to report, callers keep N rows
        self.row_layout = None if self.mh is not None else RowLayout(
            self.N, self.Np, None if mesh is None else
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
                row_shard_axes(self.dd, self.df))))
        self._row_mask_dev = None     # lazy device cache (no bagging path)
        self._fmask_dev = None        # lazy device cache (no sampling path)
        cfg = config
        self.split_kw = make_split_kw(cfg)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)

        # histogram-memory bound (reference HistogramPool analog); the
        # column count is this shard's local share of the STORE — under
        # psum_scatter each device caches only its F/ndev column slice
        cache_cols = (self.Fpad // self._nd_sc
                      if self.hist_exchange == "psum_scatter" and nsh > 1
                      else self.Fpad)
        self.cache_parent_hist = use_parent_hist_cache(cfg, cache_cols,
                                                       self.B)
        kw = dict(num_leaves=cfg.num_leaves, num_bins_padded=self.B,
                  max_num_bin=int(dataset.max_num_bin),
                  split_kw=self.split_kw, max_depth=int(cfg.max_depth),
                  min_data_in_leaf=int(cfg.min_data_in_leaf),
                  min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
                  backend=backend,
                  cache_parent_hist=self.cache_parent_hist,
                  hist_exchange=self.hist_exchange,
                  num_devices=self.dd,
                  num_feature_shards=self.df,
                  ftbl=ftbl, unb=unb, sparse=self.sparse,
                  input_dtype=input_dtype,
                  real_columns=0 if self.sparse else self.Cstore,
                  missing_bins=mbv)
        self._fold_stats = self._search_counters(
            unb is not None and not isinstance(unb, StoreCells))
        if mesh is None:
            self._build = _jit_build(
                functools.partial(build_tree_rounds, **kw))
            if self.sparse:
                self.bins_dev = ((jnp.asarray(cols_np),
                                  jnp.asarray(ell_np), jnp.asarray(zb_np))
                                 + tuple(jnp.asarray(s) for s in streams))
            else:
                self.bins_dev = jnp.asarray(bins_np)
        else:
            from jax.sharding import PartitionSpec as P, NamedSharding
            fn = functools.partial(
                build_tree_rounds, **kw,
                data_axis="data" if self.dd > 1 else None,
                feature_axis="feature" if self.df > 1 else None)
            # rows shard over every mesh axis present (the 2-D mesh
            # splits the row axis dd*df ways; store columns replicate).
            # Sparse: ELL rows and the stacked stream blocks shard by
            # rows; zero_bin replicates like the split metadata.
            da = row_shard_axes(self.dd, self.df)
            bins_spec = ((P(da), P(da), P(), P(da), P(da), P(da), P(da))
                         if self.sparse else P(None, da))
            in_specs = (bins_spec, P(da), P(da), P(da), P(), P(), P())
            out_specs = (tree_arrays_specs(P(), mbv is not None), P(da), P())
            self._build = _jit_build(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False))
            if self.mh is not None:
                self.bins_dev = self.mh.put_rows(bins_np, P(None, da))
            elif self.sparse:
                def put(a, spec):
                    return jax.device_put(jnp.asarray(a),
                                          NamedSharding(mesh, spec))
                self.bins_dev = ((put(cols_np, P(da)), put(ell_np, P(da)),
                                  put(zb_np, P()))
                                 + tuple(put(s, P(da)) for s in streams))
            else:
                # straight from the host array, a shard to each device:
                # through jnp.asarray the whole store would first land
                # on one of them (15.5 GB at 54M rows by 72 columns)
                self.bins_dev = jax.device_put(
                    bins_np, NamedSharding(mesh, P(None, da)))
        # replicated metadata stays host numpy in multi-process mode
        # (nbv/icv already carry the int8 feature padding)
        self.num_bins_dev = nbv if self.mh is not None else jnp.asarray(nbv)
        self.is_cat_dev = icv if self.mh is not None else jnp.asarray(icv)
        self._publish_build()

    def _search_counters(self, unbundled: bool):
        """profiling.count_deferred's `fold` for this learner's build:
        search_counters over its static shapes.  The search runs over
        [features, B]: every original feature after an unbundle (a
        bundle plan that packs a categorical feature), else the
        store's padded columns, bundled or not, a device's slice of
        them under psum_scatter."""
        if unbundled:
            feats = self.F
        elif self.hist_exchange == "psum_scatter" and self.dd * self.df > 1:
            feats = self.Fpad // self._nd_sc
        else:
            feats = self.Fpad
        return functools.partial(
            search_counters, cells=float(feats * self.B),
            cached=self.cache_parent_hist, unbundled=unbundled,
            pad_columns=float(self._pad_columns))

    def _build_sparse_streams(self, cols_np: np.ndarray,
                              ell_np: np.ndarray, nsh: int, backend: str):
        """Stacked per-shard window entry streams for the pallas sparse
        kernel ([nsh, nwin, Ew], every shard padded to the common Ew so
        the stacked leaves shard cleanly).  Off-TPU the XLA path
        iterates the ELL arrays directly, so empty placeholders keep
        the pytree structure without the host sort."""
        from ..ops.histogram import FEATURE_GROUP
        if backend != "pallas":
            z = np.zeros((nsh, 0, 0), np.int32)
            return (z, z.copy(), np.zeros((nsh, 0, 0), np.float32),
                    np.zeros((nsh, 0), np.int32))
        blocks = np.split(np.arange(cols_np.shape[0]), nsh)
        parts = [sparse_window_streams(cols_np[b], ell_np[b], self.Fpad,
                                       num_bins_padded=self.B)
                 for b in blocks]
        # pad every shard to the common window count (padding windows
        # hold sentinel slots/entries and accumulate nothing)
        nwin = max(p[0].shape[0] for p in parts)
        sent = FEATURE_GROUP * self.B
        out_r, out_f, out_v, out_s = [], [], [], []
        for er, ef, ev, sc in parts:
            pad = ((0, nwin - er.shape[0]), (0, 0))
            out_r.append(np.pad(er, pad))
            out_f.append(np.pad(ef, pad, constant_values=sent))
            out_v.append(np.pad(ev, pad))
            out_s.append(np.pad(sc, (0, nwin * FEATURE_GROUP - sc.size),
                                constant_values=self.Fpad))
        return (np.stack(out_r), np.stack(out_f), np.stack(out_v),
                np.stack(out_s))

    def _want_int8_bins(self) -> bool:
        """Narrow bin storage only under memory pressure: a device's
        share of the int32 store beyond 25% of its memory switches to
        the int8 value-128 layout; a smaller store keeps the faster
        int32 G=8 kernel layout.  LGBT_BINS_INT8=0/1 overrides for
        on-chip experiments."""
        import os
        ov = os.environ.get("LGBT_BINS_INT8", "")
        if ov in ("0", "1"):
            return ov == "1"
        # bins shard along the data axis: the pressure that matters is
        # the PER-DEVICE share of the int32 STORE layout
        int32_bytes = 4.0 * self.Cstore * self.Np / max(self.dd * self.df, 1)
        limit = device_bytes_limit() or CPU_TIER_BYTES_LIMIT
        return int32_bytes > 0.25 * limit

    @property
    def bins_t(self):
        """Store view for the ScoreUpdater's binned traversal: the
        sparse ELL triple when the dataset is sparse (the training-set
        replay probes row segments, zero densification), else the
        [N+1, C] sentinel-padded dense transpose."""
        if getattr(self, "_bins_t", None) is None:
            if self.dataset.sparse is not None:
                self._bins_t = self.dataset.sparse_triple()
            else:
                self._bins_t = jnp.asarray(sentinel_bins_t(self.dataset))
        return self._bins_t

    def _feature_mask(self):
        frac = self.config.feature_fraction
        m = self._base_fmask.copy()
        if frac < 1.0:
            # sampling draws from the REAL features; int8-alignment
            # padding features stay masked out
            k = max(1, int(round(self.F * frac)))
            sel = self._feat_rng.choice(self.F, size=k, replace=False)
            mm = np.zeros(len(self._base_fmask), bool)
            mm[sel] = True
            m &= mm
        # per-iteration host draw is the design; the upload is explicit
        return m if self.mh is not None else jax.device_put(m)

    def _rows_in(self, x: jax.Array):
        """grad / hess as the build takes them.  The boosting loop hands
        them in `row_layout` already and this is a length check; N rows
        (a caller outside that loop) are padded with zeros and placed."""
        if self.mh is not None:
            from jax.sharding import PartitionSpec as P
            return self.mh.put_rows(
                self.mh.pad_local(np.asarray(x, np.float32)), P("data"))
        return self.row_layout.place(x)

    def _masks(self, bag_idx):
        if self.mh is not None:
            from jax.sharding import PartitionSpec as P
            mask = self._row_mask
            if bag_idx is not None:
                m2 = np.zeros(self._local_np, np.float32)
                bi = np.asarray(bag_idx)
                m2[bi[bi < self.N]] = 1.0
                mask = m2 * mask
            mask = self.mh.put_rows(mask, P("data"))
            fmask = (self._feature_mask()
                     if self.config.feature_fraction < 1.0
                     else self._base_fmask)
            return mask, fmask
        if self._row_mask_dev is None:
            self._row_mask_dev = self.row_layout.place(self._row_mask)
        mask = self._row_mask_dev
        if bag_idx is not None:
            mask = bag_mask_dev(bag_idx, mask)
        if self.config.feature_fraction < 1.0:
            fmask = self._feature_mask()
        else:
            if self._fmask_dev is None:
                self._fmask_dev = jax.device_put(self._base_fmask)
            fmask = self._fmask_dev
        return mask, fmask

    def train_device(self, grad: jax.Array, hess: jax.Array,
                     bag_idx: Optional[jax.Array] = None,
                     bag_count: Optional[int] = None):
        """Device-only train: (packed tree vector, leaf_id, TreeArrays)
        with NO device→host sync — callers pipeline the tree fetch and can
        score valid sets straight from the device TreeArrays.  `leaf_id`
        comes back in `row_layout`: Np rows, the padded ones at the tail."""
        from .fused import pack_tree_arrays
        mask, fmask = self._masks(bag_idx)
        arrs, leaf_id, stats = self._build(
            self.bins_dev, self._rows_in(grad), self._rows_in(hess), mask,
            self.num_bins_dev, self.is_cat_dev, fmask)
        # device scalars, folded into the counters at the next metrics
        # read — no sync on the pipelined path
        self._record_stats(stats)
        packed = pack_tree_arrays(arrs)
        check_tree_divergence("rounds/tree", arrs, packed)
        return packed, leaf_id, arrs

    def _record_stats(self, stats) -> None:
        # the whole vector against its counters: one device add per
        # iteration and one fetch at the drain, no sync here; the two
        # search counters are folded on the host at that drain
        profiling.count_deferred(STATS_COUNTERS, stats, self._fold_stats)

    def train(self, grad: jax.Array, hess: jax.Array,
              bag_idx: Optional[jax.Array] = None,
              bag_count: Optional[int] = None) -> Tuple[Tree, jax.Array]:
        mask, fmask = self._masks(bag_idx)
        arrs, leaf_id, stats = self._build(
            self.bins_dev, self._rows_in(grad), self._rows_in(hess), mask,
            self.num_bins_dev, self.is_cat_dev, fmask)
        self._record_stats(stats)
        check_tree_divergence("rounds/tree", arrs)
        tree = tree_arrays_to_host(arrs, self.dataset, self.config.num_leaves)
        if self.mh is not None:
            return tree, jnp.asarray(self.mh.local_rows(leaf_id))
        return tree, leaf_id

    def _publish_build(self) -> None:
        """Hand profiling.publish_program the recipe of this learner's
        build program: the jitted step and the abstract arguments of
        its call in train / train_device — shapes, dtypes and, where
        the call's array is committed to one, the sharding; no buffer,
        so the recipe outlives the learner and holds none of its
        memory.  Nothing is lowered here (profiling.phase_tables does
        that when asked).  A multi-process learner assembles its rows
        per process and publishes nothing."""
        if self.mh is not None:
            return

        def abstract(a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if a.committed else None)
        rows = jax.ShapeDtypeStruct((self.Np,), jnp.float32,
                                    sharding=self.row_layout.sharding)
        profiling.publish_program(
            "jit_" + build_tree_rounds.__name__, self._build,
            (jax.tree_util.tree_map(abstract, self.bins_dev), rows, rows,
             rows, abstract(self.num_bins_dev), abstract(self.is_cat_dev),
             jax.ShapeDtypeStruct(self._base_fmask.shape, jnp.bool_)),
            functools.partial(
                kernel_launch_slots,
                tiers=launch_slot_tiers(int(self.config.num_leaves))))


def chunk_tiers(chunk: int) -> Tuple[int, ...]:
    """The widths, narrowest first, at which build_tree_rounds can run a
    chunk of `chunk` leaf slots: min(8, chunk), min(32, chunk) and the
    chunk.  A round runs each chunk at the first that holds its active
    slots, launch, exchange, search and cache update alike."""
    return tuple(sorted({min(8, chunk), min(32, chunk), chunk}))


def launch_slot_tiers(num_leaves: int) -> Tuple[int, ...]:
    """The slot counts K at which build_tree_rounds launches the
    histogram kernel for a tree of `num_leaves`: 1 at the root and, for
    each chunk of a round (LEAVES_PER_BATCH slots, the last one what is
    left of num_leaves), its chunk_tiers.  The short last chunk is
    compiled and never runs: a round splits at most half the leaf
    slots."""
    tiers = {1}
    for s in range(0, num_leaves, LEAVES_PER_BATCH):
        tiers |= set(chunk_tiers(min(LEAVES_PER_BATCH, num_leaves - s)))
    return tuple(sorted(tiers))
