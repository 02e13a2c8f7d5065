"""Score updaters: raw model scores kept as [K, N] device arrays.

Reference: /root/reference/src/boosting/score_updater.hpp (three AddScore
paths: whole-data tree predict, leaf-partition fast path for train, and
constant adds).  Tree traversal over the BINNED matrix is a vectorized
node-walk instead of the reference's per-row pointer chase
(tree.cpp:99-192): all rows advance one tree level per step, with each
level's per-node fields fetched by ONE one-hot matmul (ops/lookup.py) and
the row's split-feature bin by a fused masked sum — no gathers, which
serialize on TPU.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiling
from ..jaxutil import RowLayout
from ..ops.lookup import select_bin_by_feature, table_lookup
from ..ops.predict import sparse_bin_lookup


def _bins_rows(bins_t):
    """(per-row store view, N).  Dense [N+1, C] stores carry a sentinel
    row that slices off; the sparse ELL triple (cols [N, R], binsv
    [N, R], zero_bin [C]) has no sentinel — its probe answers every
    column for every row by construction."""
    if isinstance(bins_t, (tuple, list)):
        return tuple(bins_t), bins_t[0].shape[0]
    N = bins_t.shape[0] - 1
    return bins_t[:N], N


def _walk_step(node, bins_nt, split_feature, threshold, decision,
               left_child, right_child, num_nodes, feat_tbl=None):
    """One tree level for every row at once.  All per-node lookups go
    through the one-hot matmul (ops/lookup.py) — XLA's [N] table gathers
    and 2-D `bins[rows, feat]` gathers serialize on TPU and cost more than
    the whole histogram pass; child ids are exact in f32 (|v| < 2^24).

    bins_nt may be the sparse ELL triple (cols, binsv, zero_bin): the
    bin lookup then probes the row's stored entries directly
    (ops/predict.sparse_bin_lookup — compare + masked sum, also
    gather-free) and the store never densifies.  Decision logic is
    identical either way.

    feat_tbl (optional [5, F]: col, offset, default, nslots, packed) maps
    the node's ORIGINAL inner feature onto a bundled store column and
    recovers the original bin from the packed slot — trees always speak
    original (feature, threshold-bin) space, so an EFB store needs this
    second lookup; unbundled stores skip it entirely."""
    if isinstance(bins_nt, tuple):
        def bin_of(c):
            return sparse_bin_lookup(*bins_nt, c)
    else:
        def bin_of(c):
            return select_bin_by_feature(bins_nt.T, c)
    nd = jnp.maximum(node, 0)
    tbl = jnp.stack([split_feature.astype(jnp.float32),
                     threshold.astype(jnp.float32),
                     decision.astype(jnp.float32),
                     left_child.astype(jnp.float32),
                     right_child.astype(jnp.float32)])
    r = table_lookup(tbl, nd, num_slots=num_nodes)
    feat = r[0].astype(jnp.int32)
    t = r[1].astype(jnp.int32)
    d = r[2]
    if feat_tbl is None:
        bv = bin_of(feat)
    else:
        fr = table_lookup(jnp.asarray(feat_tbl), feat,
                          num_slots=feat_tbl.shape[1])
        col = fr[0].astype(jnp.int32)
        off = fr[1].astype(jnp.int32)
        dflt = fr[2].astype(jnp.int32)
        ns = fr[3].astype(jnp.int32)
        pk = fr[4] > 0
        bv_store = bin_of(col)
        s = bv_store - off
        in_r = (s >= 0) & (s < ns)
        orig = jnp.where(in_r, s + (s >= dflt).astype(jnp.int32), dflt)
        bv = jnp.where(pk, orig, bv_store)
    # a decision lane of -1 - b sends the NaN bin b left (tree.binned_lanes)
    di = d.astype(jnp.int32)
    go_left = jnp.where(d == 1, bv == t,
                        (bv <= t) | ((di < 0) & (bv == -1 - di)))
    nxt = jnp.where(go_left, r[3], r[4]).astype(jnp.int32)
    return jnp.where(node < 0, node, nxt)


@functools.partial(jax.jit, static_argnames=("depth",))
def predict_binned_leaf(bins_t: jax.Array, split_feature_inner: jax.Array,
                        threshold_in_bin: jax.Array, decision_type: jax.Array,
                        left_child: jax.Array, right_child: jax.Array,
                        feat_tbl=None, *, depth: int) -> jax.Array:
    """Leaf index per row by walking the tree `depth` levels.

    bins_t: [N+1, C] int STORE bins (C = original features, or bundled
    columns with `feat_tbl` given), or the sparse ELL triple
    (cols, binsv, zero_bin) — see _bins_rows.  Tree arrays are padded
    to fixed length so the jit cache keys only on `depth`.
    """
    bins_nt, N = _bins_rows(bins_t)
    node = jnp.zeros(N, jnp.int32)
    nn = split_feature_inner.shape[0]

    def step(_, node):
        return _walk_step(node, bins_nt, split_feature_inner,
                          threshold_in_bin, decision_type, left_child,
                          right_child, nn, feat_tbl)

    node = jax.lax.fori_loop(0, max(depth, 1), step, node)
    return ~node


@jax.jit
def traverse_tree_device(bins_t, split_feature, threshold_bin, is_cat,
                         left_child, right_child, num_leaves,
                         feat_tbl=None, default_left=None,
                         missing_bins=None) -> jax.Array:
    """Leaf index per row from DEVICE tree arrays (learner TreeArrays) —
    no host tree needed, so the pipelined training path can score valid
    sets without waiting for the tree fetch.  A `while_loop` walks until
    every row parked at a leaf (negative node), so cost tracks the actual
    tree depth instead of a static worst-case bound.  With the tree's
    `default_left` and the store's `missing_bins` (Dataset.missing_bins),
    a split on a feature with a NaN bin sends that bin to its default
    side."""
    bins_nt, N = _bins_rows(bins_t)
    decision = is_cat
    if default_left is not None:
        mb = missing_bins[split_feature]
        decision = jnp.where(is_cat, 1, jnp.where(
            default_left & (mb >= 0), -1 - mb, 0))
    # stump: everything is leaf 0 (node -1 == ~0) from the start
    n0 = jnp.where(num_leaves < 2, jnp.int32(-1), jnp.int32(0))
    node = jnp.full(N, n0, jnp.int32)
    max_steps = split_feature.shape[0] + 1

    def cond(st):
        i, node = st
        return (i < max_steps) & jnp.any(node >= 0)

    nn = split_feature.shape[0]

    def body(st):
        i, node = st
        node = _walk_step(node, bins_nt, split_feature, threshold_bin,
                          decision, left_child, right_child, nn, feat_tbl)
        return i + 1, node

    _, node = jax.lax.while_loop(cond, body, (jnp.int32(0), node))
    return ~node


@jax.jit
def shrink_clip_leaves(leaf_value: jax.Array, num_leaves: jax.Array,
                       shrink: jax.Array) -> jax.Array:
    """Shrinkage + kMaxTreeOutput clamp (tree.h: ±100) + stump zeroing,
    fused in ONE device program.  The eager formulation uploaded the
    shrinkage scalar and both clamp constants host→device on every
    boosting iteration (three implicit transfers per iteration on the
    pipelined path — the sanitizer's `sanitize/implicit_transfers`
    counter flags them); here they are trace constants / an explicit
    device-resident scalar (GBDT._shrink_dev)."""
    lv = jnp.clip(leaf_value * shrink, -100.0, 100.0)
    # a no-split tree must contribute zero score: the rounds learner
    # guarantees leaf_value[0]==0 for stumps, but enforce it so every
    # train_device implementation is safe (the stump is popped next
    # iteration with no score rollback)
    return lv * (num_leaves >= 2)


@jax.jit
def _add_raw(score, raw):
    """score += raw, one program (whole-model replay — add_trees)."""
    return score + raw


def _leaf_values_by_row(leaf_id, leaf_values, *, spmd: bool = False):
    # one-hot matmul, not table gather: XLA's [N] gather from a leaf-sized
    # table runs at <1 GB/s on TPU (see ops/lookup.py) and cost ~65 ms per
    # iteration at N=4M; the matmul is exact for f32 leaf values
    lv = leaf_values.astype(jnp.float32)
    return table_lookup(lv[None], leaf_id, num_slots=lv.shape[0],
                        spmd=spmd)[0]


@functools.partial(jax.jit, static_argnames=("tree_id", "spmd"))
def _add_leaf_to_row_jit(score, leaf_id, leaf_values, *, tree_id: int,
                         spmd: bool):
    # spmd: the leaf ids come sharded over a mesh and the score is not
    # laid out like them (a learner that reports no row layout), so XLA
    # gathers them to every device in front of the lookup; the scope
    # names that gather and the lookup behind it in a trace (metadata
    # only, and only there)
    with (jax.named_scope("lgbt.score_gather") if spmd
          else contextlib.nullcontext()):
        val = _leaf_values_by_row(leaf_id, leaf_values, spmd=spmd)
    return score.at[tree_id].set(score[tree_id] + val)


@functools.lru_cache(maxsize=None)
def _add_leaf_to_row_sharded(sharding, tree_id: int):
    """The same update with score and leaf ids in one row layout over a
    mesh: under shard_map each device looks its own rows up (the Mosaic
    lookup on the chip, which XLA cannot partition under plain jit) and
    adds them to its own shard of the score — no collective, no row
    leaves its chip.  jax.jit names a program after its function and a
    shard_map closure has none, hence the names: `jit_` and not
    `jit_build_tree`, so a trace counts it under the boosting layer."""
    P = jax.sharding.PartitionSpec
    rows = sharding.spec

    def update(score, leaf_id, leaf_values):
        val = _leaf_values_by_row(leaf_id, leaf_values)
        return score.at[tree_id].set(score[tree_id] + val)

    step = jax.shard_map(update, mesh=sharding.mesh,
                         in_specs=(P(None, *rows), rows, P()),
                         out_specs=P(None, *rows), check_vma=False)

    def add_leaf_to_row_sharded(score, leaf_id, leaf_values):
        return step(score, leaf_id, leaf_values)
    return jax.jit(add_leaf_to_row_sharded)


def _add_leaf_to_row(score, leaf_id, leaf_values, *, tree_id: int,
                     sharding=None):
    """score[tree_id] += leaf_values[leaf_id], all inside ONE program.
    Eager `score[tree_id]` / `score.at[tree_id].set(...)` lower to
    dynamic_slice/scatter whose start index is uploaded host→device on
    every call — one implicit transfer per boosting iteration under the
    sanitizer's guard; a STATIC tree_id is a trace constant (the jit
    cache holds K entries, K = trees per iteration).

    `sharding` is the row sharding that score and leaf ids share on a
    mesh (RowLayout): the per-shard program above.  Without one, a
    learner that reports no layout may still hand back a `leaf_id`
    sharded over its mesh; that program runs under plain jit, so the
    lookup must then be one XLA can partition (table_lookup spmd=True),
    and `tree/score_gather_rows` counts the rows whose ids it fetches
    from another device's memory (host arithmetic on the two
    shardings, no sync; 0 registers the key)."""
    spmd = sharding is None and len(leaf_id.sharding.device_set) > 1
    profiling.count(profiling.SCORE_GATHER_ROWS,
                    leaf_id.shape[0] if spmd else 0)
    if sharding is not None:
        return _add_leaf_to_row_sharded(sharding, tree_id)(
            score, leaf_id, leaf_values)
    return _add_leaf_to_row_jit(score, leaf_id, leaf_values,
                                tree_id=tree_id, spmd=spmd)


@functools.partial(jax.jit, static_argnames=("tree_id",))
def _add_const_to_row(score, val, *, tree_id: int):
    return score.at[tree_id].add(val)


@functools.partial(jax.jit, static_argnames=("k",))
def select_class_row(x, *, k: int):
    """x[k] with a trace-constant index (the eager integer index lowers
    to dynamic_slice and uploads its start scalar host→device on every
    boosting iteration)."""
    return x[k]


class ScoreUpdater:
    """Holds [K, N] float32 raw scores for one dataset.

    The train set's updater is given its learner's row layout, if the
    learner reports one, and keeps `rows`, a [K, Np] array in that
    layout (the real rows first, padding at the tail, sharded like the
    store's rows on a mesh): gradients and the leaf-id update read and
    write it where it lies.  `score` is the [K, N] view every other
    reader takes; the rows past N hold anything finite and nothing
    reads them."""

    def __init__(self, bins_t, num_data: int, K: int,
                 init_score: Optional[np.ndarray] = None, feat_tbl=None,
                 layout: Optional[RowLayout] = None, missing_bins=None):
        # bins_t: [N+1, C] array, the sparse ELL triple (cols, binsv,
        # zero_bin), None, or a ZERO-ARG CALLABLE resolved on first
        # traversal.  Sparse stores hand the triple so every traversal
        # consumer (replay, valid scoring, refit routing) probes the ELL
        # segments directly and the store NEVER densifies
        # (tree/sparse_fallbacks stays 0 — docs/Sparse.md)
        self._bins_src = bins_t
        # [5, F] bundle walk table when bins_t is an EFB store (see
        # _walk_step), None for the plain per-feature layout
        self.feat_tbl = None if feat_tbl is None else jnp.asarray(feat_tbl)
        # [F] NaN bin per feature of a store that has one (None without)
        self.missing_bins = (None if missing_bins is None
                             else jnp.asarray(missing_bins))
        self.num_data = num_data
        self.K = K
        self.has_init_score = init_score is not None
        score = np.zeros((K, num_data), np.float32)
        if init_score is not None:
            init_score = np.asarray(init_score, np.float64).reshape(-1)
            if init_score.size == num_data * K:
                score = init_score.reshape(K, num_data).astype(np.float32)
            elif init_score.size == num_data:
                score[:] = init_score[None, :].astype(np.float32)
            else:
                raise ValueError("init score size mismatch")
        self.layout = layout or RowLayout(num_data, num_data, None)
        self.rows = self.layout.place(score)

    @property
    def score(self) -> jax.Array:
        return self.layout.view(self.rows)

    @property
    def bins_t(self):
        src = self._bins_src
        if callable(src):
            src = self._bins_src = src()
        return src

    def add_constant(self, val: float, tree_id: int) -> None:
        self.rows = _add_const_to_row(
            self.rows, jax.device_put(np.float32(val)), tree_id=tree_id)

    def _tree_leaf_idx(self, tree) -> jax.Array:
        d = tree.as_device_arrays()
        # pad tree arrays to the tree's max capacity for stable jit shapes
        return predict_binned_leaf(
            self.bins_t, d["split_feature_inner"], d["threshold_in_bin"],
            d["decision_type"], d["left_child"], d["right_child"],
            self.feat_tbl, depth=d["depth"])

    def add_tree(self, tree, tree_id: int, scale: float = 1.0) -> None:
        """Whole-data tree predict path (score_updater.hpp AddScore(tree))."""
        if tree.num_leaves <= 1:
            self.add_constant(float(tree.leaf_value[0]) * scale, tree_id)
            return
        leaf_idx = self._tree_leaf_idx(tree)
        # scale on HOST (f32*f32 is IEEE-identical either side), then ONE
        # explicit upload — the eager jnp.asarray + np-scalar multiply
        # was two implicit transfers per call
        lv = jax.device_put(
            tree.leaf_value[: tree.max_leaves].astype(np.float32)
            * np.float32(scale))
        self._add_by_leaf_id(leaf_idx, lv, tree_id)

    def _add_by_leaf_id(self, leaf_id, leaf_values, tree_id: int) -> None:
        # ids over the N real rows (a walk's) are padded with -1, which
        # matches no leaf and adds 0.0, and placed; a learner's own come
        # in the layout already
        self.rows = _add_leaf_to_row(
            self.rows, self.layout.place(leaf_id, fill=-1), leaf_values,
            tree_id=tree_id, sharding=self.layout.sharding)

    def add_trees(self, trees, K: int, kernel: str = "auto") -> None:
        """Replay a WHOLE model onto the scores (add_valid / continued-
        training replay).  With ``predict_kernel=tensorized`` the replay
        is ONE binned ensemble traversal — `depth` fused gather/select
        passes over the store with integer bin compares (ops/predict.py
        predict_ensemble_binned, EFB packed-slot remap included) —
        instead of ``len(trees)`` sequential per-tree walk programs.
        Stump constants ride in the stack (leaf 0), so the result matches
        the sequential add_tree/add_constant loop to f32 addition
        reassociation (exact on dyadic leaf values).  A sparse store
        replays through `predict_ensemble_binned_sparse` — same walk,
        ELL probes instead of dense gathers, zero densification."""
        from ..ops.predict import (build_ensemble, predict_ensemble_binned,
                                   predict_ensemble_binned_sparse,
                                   resolve_predict_kernel)
        if (resolve_predict_kernel(kernel) != "tensorized"
                or len(trees) < 2 or self._bins_src is None):
            for i, t in enumerate(trees):
                self.add_tree(t, i % K)
            return
        trees_by_class = [[t for i, t in enumerate(trees) if i % K == k]
                          for k in range(K)]
        stack, meta = build_ensemble(trees_by_class, binned=True,
                                     layout="soa")
        stack = jax.device_put(stack)
        bt = self.bins_t
        if isinstance(bt, (tuple, list)):
            raw = predict_ensemble_binned_sparse(
                stack, *bt, self.feat_tbl, meta=meta)           # [K, N]
        else:
            raw = predict_ensemble_binned(stack, bt, self.feat_tbl,
                                          meta=meta)            # [K, N]
        self.rows = _add_raw(self.rows, self.layout.place(raw))

    def add_tree_arrays_dev(self, arrs, leaf_values: jax.Array,
                            tree_id: int) -> None:
        """Whole-data score update from DEVICE TreeArrays (pipelined path
        for datasets that don't have the training leaf_id — valid sets).
        `leaf_values` carries shrinkage/clamp pre-applied."""
        dl = arrs.default_left
        leaf_idx = traverse_tree_device(
            self.bins_t, arrs.split_feature, arrs.threshold_bin,
            arrs.is_cat, arrs.left_child, arrs.right_child, arrs.num_leaves,
            self.feat_tbl, dl, None if dl is None else self.missing_bins)
        self._add_by_leaf_id(leaf_idx, leaf_values, tree_id)

    def add_tree_by_leaf_id_dev(self, leaf_id: jax.Array,
                                leaf_values: jax.Array, tree_id: int
                                ) -> None:
        """Leaf-partition score update with DEVICE leaf values (shrinkage
        pre-applied) — no host tree needed; used by the pipelined
        training path."""
        self._add_by_leaf_id(leaf_id, leaf_values, tree_id)

    def add_tree_by_leaf_id(self, tree, leaf_id: jax.Array, tree_id: int
                            ) -> None:
        """Leaf-partition fast path for the training set
        (serial_tree_learner.h:52-64): leaf_id -1 rows (out-of-bag) match
        no one-hot slot and contribute exactly 0.0 — callers follow with
        add_tree for OOB when bagging."""
        lv = jax.device_put(
            tree.leaf_value[: tree.max_leaves].astype(np.float32))
        self._add_by_leaf_id(leaf_id, lv, tree_id)

    def get(self) -> np.ndarray:
        """Fetch the whole [K, N] score to host — the ONE deliberate
        bulk sync of the host-metric fallback path (explicit, so the
        sanitizer's guard distinguishes it from accidental syncs)."""
        return jax.device_get(self.score).astype(np.float64)
