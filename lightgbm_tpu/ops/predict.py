"""On-device ensemble prediction.

The reference predicts row-by-row with a pointer-chasing node walk
(/root/reference/include/LightGBM/tree.h:217-241, gbdt.cpp:874-923).  On
TPU that becomes a vectorized breadth-parallel walk: all rows advance one
level per step (`lax.fori_loop` over the tree depth), with gathers instead
of pointer dereferences, vmapped over the stacked trees of the ensemble.

Two kernels implement that walk, selected by ``predict_kernel``:

- ``walk`` — the original shape: one `_walk_one_tree` per tree, vmapped
  over each class's TreeStack, one program per class
  (`predict_trees` / `ensemble_raw`).
- ``tensorized`` — the Booster-accelerator shape (arXiv:2011.02022):
  EVERY tree of EVERY class flattened into ONE padded ``[T, nodes]``
  SoA whose per-node record (feature, threshold, decision, children,
  default-left) is packed into a single trailing lane axis, so each
  depth level costs ONE batched record gather + ONE feature gather +
  selects for all N rows x T trees at once — `depth` loop iterations
  total for the whole ensemble, and per-class sums fall out of one
  sorted segment-sum.  A binned-input variant
  (`predict_ensemble_binned`) walks the int bin store directly with
  in-bin thresholds (integer compares, no float thresholding), including
  the EFB packed-slot remap, so whole-model replay onto a ScoreUpdater
  is `depth` passes instead of `len(trees)` sequential tree walks.  The
  serving request path runs the same walk on ingress-quantized uint8
  buffers (`predict_ensemble_quantized`, serve_quantize=binned): the
  fixed-point traversal of the Booster accelerator applied end-to-end,
  bitwise-identical to the raw kernel by construction of the quantizer
  (lightgbm_tpu/quantize.py).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import COSTACK_KERNELS, PREDICT_KERNELS


def resolve_predict_kernel(kernel: str = "auto") -> str:
    """Resolve the ``predict_kernel`` dial to a concrete kernel.

    ``auto`` picks ``tensorized``: it traverses the whole ensemble in
    `depth` fused steps on every backend, strictly fewer dispatches and
    gathers than the per-class walk (which it matches bitwise on fp32
    dyadic leaf values — tests/test_predict_kernel.py).  ``walk`` stays
    reachable as the A/B baseline and conservative fallback.
    """
    if kernel not in PREDICT_KERNELS:
        raise ValueError(f"unknown predict_kernel: {kernel!r}; "
                         f"use one of {PREDICT_KERNELS}")
    return "tensorized" if kernel == "auto" else kernel


# above this total stacked tree count, even launch-bound accelerators
# go compute-bound on the walk-all grouped traversal: the per-level
# record gather over all T_total trees dwarfs the one launch that
# co-stacking saves, so `auto` switches to the segment-gathered walk.
# Default for the validated `costack_segment_trees` Config key; direct
# resolve_costack_kernel callers inherit it when they pass no override.
COSTACK_SEGMENT_TREES = 4096


def resolve_costack_kernel(kernel: str = "auto", *,
                           total_trees: int = 0,
                           segment_trees: int = 0) -> str:
    """Resolve the ``costack_kernel`` dial to a concrete grouped
    traversal (config.COSTACK_KERNELS).

    ``auto`` picks ``segment`` on compute-bound backends (CPU: node
    math scales with the trees walked, so walking all T_total stacked
    trees costs ~G x a solo tenant per row) and on accelerators once
    the group's total stacked tree count crosses the switch point;
    ``stacked`` stays the pick where launch overhead dominates (the TPU
    premise — surplus trees ride a gather-bound depth loop for free).
    Both variants are bitwise-identical to per-tenant dispatch
    (tests/test_costack.py), so the dial is purely a cost model.

    ``segment_trees`` (<= 0 = COSTACK_SEGMENT_TREES) is the Config key
    ``costack_segment_trees``; the LIGHTGBM_TPU_COSTACK_SEGMENT_TREES
    environment override — read here, at resolve time — wins over both
    for fleet-wide retunes without a config rollout.
    """
    if kernel not in COSTACK_KERNELS:
        raise ValueError(f"unknown costack_kernel: {kernel!r}; "
                         f"use one of {COSTACK_KERNELS}")
    if kernel != "auto":
        return kernel
    thresh = int(segment_trees) if segment_trees and segment_trees > 0 \
        else COSTACK_SEGMENT_TREES
    env = os.environ.get("LIGHTGBM_TPU_COSTACK_SEGMENT_TREES")
    if env:
        try:
            thresh = max(1, int(env))
        except ValueError:
            raise ValueError(
                "LIGHTGBM_TPU_COSTACK_SEGMENT_TREES must be an integer, "
                f"got {env!r}")
    if jax.default_backend() not in ("tpu", "gpu"):
        return "segment"
    return "segment" if total_trees >= thresh else "stacked"


class TreeStack(NamedTuple):
    """Ensemble as stacked flat-node arrays, padded to the widest tree.
    Child convention matches tree.h: internal >= 0, leaves as ~leaf."""
    split_feature: jax.Array   # [T, M-1] int32 (inner feature index)
    threshold: jax.Array       # [T, M-1] f32 — bin id for binned input,
                               #               raw value for raw input
    decision_type: jax.Array   # [T, M-1] int32 (0 numerical, 1 categorical)
    left_child: jax.Array      # [T, M-1] int32
    right_child: jax.Array     # [T, M-1] int32
    leaf_value: jax.Array      # [T, M] f32
    num_leaves: jax.Array      # [T] int32


def threshold_f32(th) -> np.ndarray:
    """Raw f64 thresholds as the largest f32 NOT ABOVE them.  The device
    walk compares f32 features with f32 thresholds; with this rounding
    `x <= t` decides exactly as the f64 host walk (tree.py) does for
    every f32-representable x.  Round-to-nearest can land above t, and
    a row with x == f32(t) > t then goes left on the device and right
    on the host — about one row in a few million at Higgs width."""
    th = np.asarray(th, np.float64)
    t32 = th.astype(np.float32)
    above = t32.astype(np.float64) > th
    return np.where(above, np.nextafter(t32, np.float32(-np.inf)), t32)


def stack_trees(trees, binned: bool) -> TreeStack:
    """Stack host Tree objects into one padded TreeStack (device)."""
    m = max(max(t.max_leaves for t in trees), 2)
    T = len(trees)
    sf = np.zeros((T, m - 1), np.int32)
    th = np.zeros((T, m - 1), np.float32)
    dc = np.zeros((T, m - 1), np.int32)
    lc = np.full((T, m - 1), -1, np.int32)
    rc = np.full((T, m - 1), -1, np.int32)
    lv = np.zeros((T, m), np.float32)
    nl = np.zeros(T, np.int32)
    for i, t in enumerate(trees):
        n = t.num_leaves
        nl[i] = n
        lv[i, :n] = t.leaf_value[:n]
        if n < 2:
            continue
        k = n - 1
        sf[i, :k] = (t.split_feature_inner[:k] if binned
                     else t.split_feature[:k])
        th[i, :k] = (t.threshold_in_bin[:k].astype(np.float32) if binned
                     else threshold_f32(t.threshold[:k]))
        dc[i, :k] = t.decision_type[:k]
        lc[i, :k] = t.left_child[:k]
        rc[i, :k] = t.right_child[:k]
    return TreeStack(*map(jnp.asarray, (sf, th, dc, lc, rc, lv, nl)))


def _walk_one_tree(sf, th, dc, lc, rc, lv, nl, Xf, depth: int) -> jax.Array:
    """Leaf values for every row of one tree ([N] f32): all rows advance
    one level per step, gathers instead of pointer dereferences."""
    n0 = jnp.where(nl < 2, jnp.int32(-1), jnp.int32(0))  # stumps: leaf 0
    node = jnp.full(Xf.shape[0], n0, jnp.int32)

    def step(_, node):
        safe = jnp.maximum(node, 0)
        f = sf[safe]
        v = jnp.take_along_axis(Xf, f[:, None], axis=1)[:, 0]
        t = th[safe]
        cat = dc[safe] == 1
        # categorical: int truncation compare with the host walk's
        # explicit finite mask (tree.py predict_leaf_index) — a bare
        # int cast of NaN is backend-defined and could match category 0
        finite = jnp.isfinite(v)
        vi = jnp.where(finite, v, -1.0).astype(jnp.int32)
        gl = jnp.where(cat, finite & (vi == t.astype(jnp.int32)), v <= t)
        nxt = jnp.where(gl, lc[safe], rc[safe])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.fori_loop(0, depth, step, node)
    leaf = jnp.where(node < 0, ~node, 0)
    return lv[leaf]


@functools.partial(jax.jit, static_argnames=("depth",))
def predict_trees(stack: TreeStack, X: jax.Array, *, depth: int) -> jax.Array:
    """Sum of tree outputs for every row.

    X : [N, F] — binned ids (f32-comparable) or raw feature values,
        matching how the stack was built.
    depth : static upper bound on tree depth (#levels to walk).
    Returns [N] f32.
    """
    Xf = X.astype(jnp.float32)

    def one_tree(sf, th, dc, lc, rc, lv, nl):
        return _walk_one_tree(sf, th, dc, lc, rc, lv, nl, Xf, depth)

    vals = jax.vmap(one_tree)(*stack)          # [T, N]
    return _sum_trees(vals)


def ensemble_raw(stacks, X: jax.Array, *, depths) -> jax.Array:
    """Raw per-class scores for a multi-class ensemble ([K, N] f32).

    `stacks` is one TreeStack (or None for an untrained class — its row
    stays zero, matching GBDT._predict_raw_device) per class; `depths`
    the matching static walk depths.  Traceable: the serving runtime
    AOT-compiles this once per (generation, row bucket, output kind).
    """
    Xf = X.astype(jnp.float32)
    outs = []
    for stack, depth in zip(stacks, depths):
        if stack is None:
            outs.append(jnp.zeros(Xf.shape[0], jnp.float32))
            continue

        def one_tree(sf, th, dc, lc, rc, lv, nl, _d=depth):
            return _walk_one_tree(sf, th, dc, lc, rc, lv, nl, Xf, _d)

        outs.append(_sum_trees(jax.vmap(one_tree)(*stack)))
    return jnp.stack(outs)


# Leaf values are summed in two float32 parts: the multiple of 2^-12
# nearest each, whose sums are exact in any order while they stay below
# 2^12 in magnitude, and the remainder, at most 2^-13, whose sums are
# small and so round far finer than the margin.  Adding the two sums
# rounds once, by at most half an ulp of the margin.  A plain float32
# sum rounds once a tree: over ten trees at margins of a few units it
# strays past 1e-6 of the float64 sum.
_HI_STEP = 2.0 ** -12


def _split(vals: jax.Array) -> tuple:
    """(multiples of _HI_STEP nearest `vals`, the exact remainders)."""
    hi = jnp.round(vals * (1.0 / _HI_STEP)) * _HI_STEP
    return hi, vals - hi


def _sum_trees(vals: jax.Array) -> jax.Array:
    """[N] sums over the tree axis of [T, N] float32 leaf values."""
    hi, lo = _split(vals)
    return jnp.sum(hi, axis=0) + jnp.sum(lo, axis=0)


def _class_sums(vals: jax.Array, class_id: jax.Array,
                num_class: int) -> jax.Array:
    """[K, N] per-class sums over the tree axis of [T, N] float32 leaf
    values.  class_id is sorted (class-major flatten), so the segment-sum
    reduces each class's trees in stack order."""
    if num_class == 1:
        return _sum_trees(vals)[None]
    hi, lo = _split(vals)

    def seg(v):
        return jax.ops.segment_sum(v, class_id, num_segments=num_class,
                                   indices_are_sorted=True)
    return seg(hi) + seg(lo)


# ----------------------------------------------------------------------
# tensorized ensemble traversal (predict_kernel=tensorized)
# ----------------------------------------------------------------------

# packed node-record lane order of EnsembleStack.nodes (one trailing lane
# axis so each depth level fetches ALL per-node fields with ONE gather of
# a contiguous record, instead of five scattered gathers):
#   raw stacks    (f32):       feat, threshold, is_cat, left, right
#   binned stacks (i16/i32):   feat, threshold_bin, decision, left, right
# child ids / feature ids are exact in f32 (|v| < 2^24, num_leaves caps
# far below that), so the raw record can stay one dtype.  Binned stacks
# narrow the whole record to int16 whenever every lane fits — half the
# per-level record-gather bytes on the serving request path.  NaN/missing
# routing needs no lane: raw kernels send NaN right (v <= t is False,
# categorical finite mask matches nothing) and the binned request path
# encodes missing as the quantizer's sentinel bin, which routes right
# through the same integer compares (lightgbm_tpu/quantize.py) — the
# never-populated default_left lane PR 7 reserved is gone.
_LANES = 5


class EnsembleStack(NamedTuple):
    """Every tree of every class as ONE padded [T, nodes] SoA.

    Trees are flattened class-major (class 0's trees in boosting order,
    then class 1's, ...), so ``class_id`` is sorted ascending and the
    per-class reduction is a sorted segment-sum.
    """
    nodes: jax.Array       # [T, M-1, _LANES] packed node records
    leaf_value: jax.Array  # [T, M] f32
    root: jax.Array        # [T] int32 — 0, or -1 for stumps (leaf 0)
    class_id: jax.Array    # [T] int32, sorted ascending


class PerfectEnsemble(NamedTuple):
    """Shallow numerical ensembles re-laid out as PERFECT binary trees of
    the ensemble depth: navigation is pure arithmetic (``2*node + 1 +
    go_right``), so the walk needs NO child gathers and no parked-row
    select — the Booster accelerator layout (arXiv:2011.02022 §3).

    A leaf grown at depth d < D acts as a filler subtree: every
    last-level record it covers carries the leaf's value in BOTH value
    lanes, so the routing through filler slots is irrelevant (any path
    lands on the same value).  The LAST level's records fuse the two
    child leaf values in, saving the separate leaf-value gather.

    BINNED perfect stacks (the serving request path under
    serve_quantize=binned) carry the INNER feature id and the in-bin
    threshold in the same f32 lanes: bin ids are < 2^24, so the f32
    compare against a quantized buffer is exactly the integer compare
    — one layout, both compare domains.
    """
    inner: jax.Array       # [T, 2^(D-1)-1, 2] f32: (feature, threshold)
    last: jax.Array        # [T, 2^(D-1), 4] f32: (feat, thr, lval, rval)
    class_id: jax.Array    # [T] int32, sorted ascending


class EnsembleMeta(NamedTuple):
    """Static (hashable) companions of an ensemble stack — jit cache keys."""
    depth: int             # levels to walk (max grown depth, >= 1)
    num_class: int         # K — rows of the [K, N] output
    any_cat: bool          # ensemble has categorical splits


class GroupMeta(NamedTuple):
    """Static companions of a cross-model SUPER-STACK: N tenants'
    ensembles concatenated along the tree axis (tenant-major, each
    tenant's trees class-major like its solo stack), scored for a mixed
    batch in ONE launch.  ``segments[g] = (start, stop)`` bounds tenant
    g's trees in the stack — static at trace time, so the per-tenant
    reductions slice and reduce exactly the tree set (same shape, same
    op) the tenant's SOLO stack would, which is what makes grouped
    scoring bitwise-identical to per-tenant dispatch."""
    depth: int             # levels to walk (max over every tenant)
    num_class: int         # K — shared by every tenant in the group
    any_cat: bool          # any tenant has categorical splits
    segments: tuple        # ((start, stop), ...) tree bounds per tenant


# perfect relayout budget: total value-slab slots (T * 2^depth) above
# which the padded-SoA traversal takes over — 2^22 slots is ~50 MB of
# node records at the default, far above the north-star 500-tree
# depth-8 shape (128k slots) and far below a pathological leaf-wise
# chain (depth 30+ would want 2^31 slots per tree).
PERFECT_SLOT_BUDGET = 1 << 22


def _ensemble_shape(flat, binned: bool):
    """(max-capacity leaves, walk depth, any_cat) over a class-major
    [(class, tree)] flatten — the ONE scan shared by `build_ensemble`'s
    layout choice and `stack_ensemble`'s meta, so the two can't
    desynchronize.  Binned stacks compare on `binned_decision_type`
    (trivial-feature categorical splits rebin to numerical
    sentinels)."""
    m = max(max(t.max_leaves for _, t in flat), 2)
    depth = 1
    any_cat = False
    for _, t in flat:
        if t.num_leaves < 2:
            continue
        depth = max(depth, t.max_depth_grown)
        k = t.num_leaves - 1
        dec = (getattr(t, "binned_decision_type", t.decision_type)
               if binned else t.decision_type)
        any_cat = any_cat or bool(np.any(dec[:k] == 1))
    return m, max(int(depth), 1), any_cat


def build_ensemble(trees_by_class, *, binned: bool = False,
                   layout: str = "auto"):
    """Build the tensorized-traversal stack for a whole model.

    Returns ``(stack, meta)`` where stack is a PerfectEnsemble (shallow,
    purely numerical raw ensembles within PERFECT_SLOT_BUDGET) or the
    general EnsembleStack SoA — both host numpy pytrees; callers
    `jax.device_put` them (per replica for the serving fleet).
    `predict_ensemble_any` dispatches on the type.
    """
    num_class = len(trees_by_class)
    flat = [(k, t) for k, trees in enumerate(trees_by_class) for t in trees]
    if not flat:
        raise ValueError("build_ensemble needs at least one tree")
    shape = _ensemble_shape(flat, binned)
    m, depth, any_cat = shape
    meta = EnsembleMeta(depth=depth, num_class=num_class, any_cat=any_cat)
    if layout not in ("auto", "perfect", "soa"):
        raise ValueError(f"unknown ensemble layout: {layout!r}")
    if layout == "auto":
        fits = len(flat) << depth <= PERFECT_SLOT_BUDGET
        layout = "perfect" if fits and not any_cat else "soa"
    if layout == "perfect":
        if any_cat:
            raise ValueError("perfect layout supports numerical "
                             "ensembles only")
        return _build_perfect(flat, meta, binned=binned)
    return stack_ensemble(trees_by_class, binned=binned, _shape=shape)


def _build_perfect(flat, meta: EnsembleMeta, binned: bool = False
                   ) -> tuple[PerfectEnsemble, EnsembleMeta]:
    D = meta.depth
    T = len(flat)
    half = 1 << (D - 1)
    inner = np.zeros((T, max(half - 1, 1), 2), np.float32)
    last = np.zeros((T, half, 4), np.float32)
    cls = np.zeros(T, np.int32)
    for i, (k, t) in enumerate(flat):
        cls[i] = k
        # binned stacks speak (inner feature, in-bin threshold) — both
        # < 2^24, exact in the f32 lanes
        sf = t.split_feature_inner if binned else t.split_feature
        th = t.threshold_in_bin if binned else threshold_f32(t.threshold)
        if t.num_leaves < 2:                 # stump: one giant filler
            last[i, :, 2] = last[i, :, 3] = np.float32(t.leaf_value[0])
            continue
        # iterative heap-order fill; a leaf met above the last level
        # replicates its value across every last-level slot it covers
        stack = [(0, 0, 0)]                  # (tree node, heap slot, level)
        while stack:
            node, slot, lvl = stack.pop()
            if lvl == D - 1:                 # last level: fuse child values
                local = slot - (half - 1)
                if node < 0:                 # leaf: value in both lanes
                    v = np.float32(t.leaf_value[~node])
                    last[i, local, 2] = last[i, local, 3] = v
                else:
                    lc = int(t.left_child[node])
                    rc = int(t.right_child[node])
                    # children at depth D of a depth-D tree are leaves
                    last[i, local, 0] = sf[node]
                    last[i, local, 1] = np.float32(th[node])
                    last[i, local, 2] = np.float32(t.leaf_value[~lc])
                    last[i, local, 3] = np.float32(t.leaf_value[~rc])
                continue
            if node < 0:                     # early leaf: filler subtree
                lo = (slot - ((1 << lvl) - 1)) << (D - 1 - lvl)
                hi = lo + (1 << (D - 1 - lvl))
                v = np.float32(t.leaf_value[~node])
                last[i, lo:hi, 2] = last[i, lo:hi, 3] = v
                continue
            inner[i, slot, 0] = sf[node]
            inner[i, slot, 1] = np.float32(th[node])
            stack.append((int(t.left_child[node]), 2 * slot + 1, lvl + 1))
            stack.append((int(t.right_child[node]), 2 * slot + 2, lvl + 1))
    return PerfectEnsemble(inner=inner, last=last, class_id=cls), meta


def stack_ensemble(trees_by_class, *, binned: bool, _shape=None
                   ) -> tuple[EnsembleStack, EnsembleMeta]:
    """Flatten per-class host Tree lists into one EnsembleStack (host
    numpy — callers `jax.device_put` the pytree, per replica for the
    serving fleet).  A class with no trees contributes no stack rows and
    its output row stays zero (segment-sum over an absent segment),
    matching `ensemble_raw`'s None handling.  Stumps ride along as
    root=-1 rows whose leaf 0 carries the constant.
    """
    num_class = len(trees_by_class)
    flat = [(k, t) for k, trees in enumerate(trees_by_class) for t in trees]
    if not flat:
        raise ValueError("stack_ensemble needs at least one tree")
    m, depth, any_cat = _shape or _ensemble_shape(flat, binned)
    meta = EnsembleMeta(depth=depth, num_class=num_class, any_cat=any_cat)
    nodes, lv, root, cls = _fill_stack(flat, m, binned)
    stack = EnsembleStack(nodes=_maybe_narrow(nodes, binned),
                          leaf_value=lv, root=root, class_id=cls)
    return stack, meta


def _fill_stack(flat, m: int, binned: bool):
    """The node/leaf fill over a class-major ``[(class, tree)]`` flatten
    — ONE loop shared by `stack_ensemble` and `stack_ensemble_group`, so
    a solo stack and a super-stack can never encode the same tree
    differently."""
    T = len(flat)
    dtype = np.int32 if binned else np.float32
    nodes = np.zeros((T, m - 1, _LANES), dtype)
    lv = np.zeros((T, m), np.float32)
    root = np.zeros(T, np.int32)
    cls = np.zeros(T, np.int32)
    for i, (k, t) in enumerate(flat):
        n = t.num_leaves
        cls[i] = k
        lv[i, :n] = t.leaf_value[:n]
        if n < 2:
            root[i] = -1                     # stump: every row is leaf 0
            continue
        knodes = n - 1
        if binned:
            dec = getattr(t, "binned_decision_type", t.decision_type)
            nodes[i, :knodes, 0] = t.split_feature_inner[:knodes]
            nodes[i, :knodes, 1] = t.threshold_in_bin[:knodes]
            nodes[i, :knodes, 2] = dec[:knodes]
        else:
            nodes[i, :knodes, 0] = t.split_feature[:knodes]
            nodes[i, :knodes, 1] = threshold_f32(t.threshold[:knodes])
            nodes[i, :knodes, 2] = t.decision_type[:knodes]
        nodes[i, :knodes, 3] = t.left_child[:knodes]
        nodes[i, :knodes, 4] = t.right_child[:knodes]
    return nodes, lv, root, cls


def _maybe_narrow(nodes: np.ndarray, binned: bool) -> np.ndarray:
    """The integer record narrows to int16 whenever every lane fits
    (bins < 2^15, children/features < 2^15 — always, outside the
    trivial-feature rebin sentinels): half the record-gather bytes per
    depth level on the binned serving request path.  TPU only — CPU
    XLA's int16 gathers de-vectorize (measured 1.5x slower than the
    int32 record at the north-star shape)."""
    if binned and nodes.size and jax.default_backend() == "tpu" and \
            -0x8000 <= int(nodes.min()) and int(nodes.max()) < 0x8000:
        return nodes.astype(np.int16)
    return nodes


def stack_ensemble_group(members, *, binned: bool = False
                         ) -> tuple[EnsembleStack, GroupMeta]:
    """Co-stack N tenants' ensembles into ONE super-stack.

    ``members`` is a list of per-tenant ``trees_by_class`` lists (the
    same shape `stack_ensemble` takes), all with the SAME class count.
    Trees flatten tenant-major (each tenant's trees class-major, i.e.
    exactly its solo stack order) into one padded [T_total, nodes] SoA;
    ``meta.segments`` records each tenant's static tree bounds so
    `_grouped_sums` can reduce per tenant with the solo reduction.
    Node records pad to the WIDEST tree across the group and the walk
    runs to the DEEPEST tenant's depth — a parked row no-ops through
    surplus levels, so padding changes no routing decision, only the
    launch's node-record footprint (the grouping policy in
    serving/catalog.py bounds that waste by leaf-budget tier).
    """
    if not members:
        raise ValueError("stack_ensemble_group needs at least one member")
    ks = {len(tbc) for tbc in members}
    if len(ks) != 1:
        raise ValueError("co-stacked members must share num_class "
                         f"(got {sorted(ks)})")
    num_class = ks.pop()
    flat = []
    segments = []
    for tbc in members:
        start = len(flat)
        flat.extend((k, t) for k, trees in enumerate(tbc) for t in trees)
        if len(flat) == start:
            raise ValueError("every co-stacked member needs at least "
                             "one tree")
        segments.append((start, len(flat)))
    m, depth, any_cat = _ensemble_shape(flat, binned)
    meta = GroupMeta(depth=depth, num_class=num_class, any_cat=any_cat,
                     segments=tuple(segments))
    nodes, lv, root, cls = _fill_stack(flat, m, binned)
    stack = EnsembleStack(nodes=_maybe_narrow(nodes, binned),
                          leaf_value=lv, root=root, class_id=cls)
    return stack, meta


def _leaf_sums(stack: EnsembleStack, node: jax.Array, num_class: int
               ) -> jax.Array:
    """[K, N] per-class sums of the leaf values the [T, N] walk parked
    on (`_class_sums`) — exact for fp32 dyadic leaf values in any order,
    and the same trees the walk kernel sums."""
    leaf = jnp.where(node < 0, ~node, 0)
    vals = jnp.take_along_axis(stack.leaf_value, leaf, axis=1)   # [T, N]
    return _class_sums(vals, stack.class_id, num_class)


def _raw_decide(rec: jax.Array, v: jax.Array, any_cat: bool) -> jax.Array:
    """Go-left mask from packed raw node records and gathered feature
    values — THE numerical/categorical routing decision, shared by the
    full-stack walk (`_walk_raw_nodes`) and the segment-gathered walk
    (`_walk_raw_segment`) so the two can never disagree: numerical
    ``v <= t`` (NaN falls right), categorical int-truncation compare
    behind a finite mask."""
    t = rec[..., 1]
    gl = v <= t
    if any_cat:
        finite = jnp.isfinite(v)
        vi = jnp.where(finite, v, -1.0).astype(jnp.int32)
        gl = jnp.where(rec[..., 2] > 0,
                       finite & (vi == t.astype(jnp.int32)), gl)
    return gl


def _binned_decide(rec: jax.Array, bv: jax.Array,
                   any_cat: bool) -> jax.Array:
    """Go-left mask from packed BINNED node records and gathered bin
    ids — integer compares end to end, shared by `_walk_binned_nodes`
    and `_walk_binned_segment` (same contract as `_raw_decide`)."""
    t = rec[..., 1].astype(jnp.int32)
    if any_cat:
        return jnp.where(rec[..., 2] == 1, bv == t, bv <= t)
    return bv <= t


def _walk_raw_nodes(stack: EnsembleStack, Xf: jax.Array, meta
                    ) -> jax.Array:
    """The raw-feature ensemble walk itself: parked node per (tree, row)
    — [T, N] int32, leaves encoded as ~leaf.  Shared by the value kernel
    (`predict_ensemble`), the leaf-index kernel
    (`predict_ensemble_leaf`), and the grouped super-stack kernel
    (`predict_ensemble_grouped`) so they can never disagree on a routing
    decision.  Decision parity with `_walk_one_tree` is bitwise:
    numerical ``v <= t`` (NaN falls right), categorical int-truncation
    compare behind the host walk's finite mask (tree.py
    predict_leaf_index — non-finite never matches; a bare int cast of
    NaN is backend-defined)."""
    T = stack.nodes.shape[0]
    N = Xf.shape[0]
    rows = jnp.arange(N)[None, :]
    node = jnp.broadcast_to(stack.root[:, None], (T, N))

    def step(_, node):
        safe = jnp.maximum(node, 0)
        rec = jnp.take_along_axis(stack.nodes, safe[:, :, None], axis=1)
        f = rec[..., 0].astype(jnp.int32)
        v = Xf[rows, f]                                  # [T, N]
        gl = _raw_decide(rec, v, meta.any_cat)
        nxt = jnp.where(gl, rec[..., 3], rec[..., 4]).astype(jnp.int32)
        return jnp.where(node >= 0, nxt, node)

    return jax.lax.fori_loop(0, meta.depth, step, node)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble(stack: EnsembleStack, X: jax.Array, *,
                     meta: EnsembleMeta) -> jax.Array:
    """Raw per-class scores over raw feature values — [K, N] f32.

    All N rows x T trees advance one depth level per step: one batched
    record gather, one feature gather, one select.  `meta.depth` loop
    iterations total for the whole ensemble (the walk kernel runs a
    depth-loop per class and five gathers per level).
    """
    node = _walk_raw_nodes(stack, X.astype(jnp.float32), meta)
    return _leaf_sums(stack, node, meta.num_class)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_perfect(stack: PerfectEnsemble, X: jax.Array, *,
                             meta: EnsembleMeta) -> jax.Array:
    """Raw per-class scores via perfect-layout traversal — [K, N] f32.

    Per level: ONE 8-byte record gather + ONE feature gather + a
    compare; the next node is arithmetic (no child gathers, no
    parked-row select).  The root level is peeled into a broadcast
    (every row reads record 0), and the last level's records carry both
    child leaf values, so the separate leaf-value gather disappears.
    Bitwise-identical routing to `_walk_one_tree` (same ``v <= t`` f32
    compare on the same thresholds).
    """
    Xf = X.astype(jnp.float32)
    T = stack.last.shape[0]
    N = Xf.shape[0]
    rows = jnp.arange(N)[None, :]
    depth = meta.depth

    def level(rec_slab, node):
        r = jnp.take_along_axis(rec_slab, node[:, :, None], axis=1)
        f = r[..., 0].astype(jnp.int32)
        gl = Xf[rows, f] <= r[..., 1]
        return r, gl

    if depth == 1:
        local = jnp.zeros((T, N), jnp.int32)
    else:
        # level 0: every row is at the root — broadcast, no gather
        f0 = stack.inner[:, 0, 0].astype(jnp.int32)
        gl0 = jnp.take(Xf, f0, axis=1).T <= stack.inner[:, 0, 1][:, None]
        node = 2 - gl0.astype(jnp.int32)

        def step(_, node):
            _, gl = level(stack.inner, node)
            return 2 * node + 2 - gl.astype(jnp.int32)

        node = jax.lax.fori_loop(1, depth - 1, step, node)
        local = node - ((1 << (depth - 1)) - 1)
    r, gl = level(stack.last, local)
    vals = jnp.where(gl, r[..., 2], r[..., 3])              # [T, N]
    return _class_sums(vals, stack.class_id, meta.num_class)


def predict_ensemble_any(stack, X: jax.Array, *,
                         meta: EnsembleMeta) -> jax.Array:
    """Layout dispatch (trace-time): PerfectEnsemble or EnsembleStack."""
    if isinstance(stack, PerfectEnsemble):
        return predict_ensemble_perfect(stack, X, meta=meta)
    return predict_ensemble(stack, X, meta=meta)


def sparse_bin_lookup(cols: jax.Array, binsv: jax.Array,
                      zero_bin: jax.Array, col: jax.Array) -> jax.Array:
    """Store bin id per requested column, straight off the ELL row
    segments — the traversal-side analog of the sparse partition probe
    (ops/partition.partition_rows_sparse): a stored (column, bin) entry
    answers directly, everything else answers the column's zero bin.

    cols/binsv: [N, R] ELL entries (col >= num_columns marks an empty
    slot — never matches a real request); zero_bin: [C] int32 (-1 only
    for padded columns no tree names); col: [..., N] int32 requested
    store columns.  Returns [..., N] int32 bin ids.
    """
    hit = cols == col[..., None]                         # [..., N, R]
    bv = jnp.sum(jnp.where(hit, binsv.astype(jnp.int32), 0), axis=-1)
    C = zero_bin.shape[0]
    zb = jnp.maximum(jnp.take(zero_bin, jnp.clip(col, 0, C - 1)), 0)
    return jnp.where(jnp.any(hit, axis=-1), bv, zb)


def _walk_binned_nodes(stack: EnsembleStack, bins_nt,
                       feat_tbl: Optional[jax.Array], meta: EnsembleMeta
                       ) -> jax.Array:
    """The binned ensemble walk itself: parked node per (tree, row) —
    [T, N] int32, leaves encoded as ~leaf, over [N, C] integer bins.
    Shared by the score replay (`predict_ensemble_binned`), the
    leaf-index router (`predict_ensemble_leaf_binned`), and the serving
    request path (`predict_ensemble_quantized`) so the three can never
    disagree on a routing decision — the online refit subsystem depends
    on routing rows to exactly the leaves whose values the replay sums,
    and serving depends on integer compares reproducing the raw f32
    kernel bit-for-bit (lightgbm_tpu/quantize.py).

    bins_nt may instead be the sparse store triple (cols [N, R],
    binsv [N, R], zero_bin [C]) — then every per-level bin gather runs
    `sparse_bin_lookup` over the ELL row segments and the store never
    densifies; the decision logic (`_binned_decide`, the EFB remap) is
    byte-for-byte the same code, so the sparse walk cannot diverge from
    the dense one (tests/test_sparse.py pins the bitwise parity)."""
    sparse = isinstance(bins_nt, (tuple, list))
    if sparse:
        cols, binsv, zero_bin = bins_nt
        cols = cols.astype(jnp.int32)
        zero_bin = zero_bin.astype(jnp.int32)
        N = cols.shape[0]
    else:
        N = bins_nt.shape[0]
        bins_nt = bins_nt.astype(jnp.int32)
    T = stack.nodes.shape[0]
    rows = jnp.arange(N)[None, :]
    node = jnp.broadcast_to(stack.root[:, None], (T, N))
    ft = None if feat_tbl is None else feat_tbl.astype(jnp.int32)

    def bin_at(c):
        if sparse:
            return sparse_bin_lookup(cols, binsv, zero_bin, c)
        return bins_nt[rows, c]

    def step(_, node):
        safe = jnp.maximum(node, 0)
        rec = jnp.take_along_axis(stack.nodes, safe[:, :, None], axis=1)
        f = rec[..., 0].astype(jnp.int32)
        if ft is None:
            bv = bin_at(f)
        else:
            col = ft[0, f]
            off = ft[1, f]
            dflt = ft[2, f]
            ns = ft[3, f]
            pk = ft[4, f] > 0
            bv_store = bin_at(col)
            s = bv_store - off
            in_r = (s >= 0) & (s < ns)
            orig = jnp.where(in_r, s + (s >= dflt).astype(jnp.int32), dflt)
            bv = jnp.where(pk, orig, bv_store)
        gl = _binned_decide(rec, bv, meta.any_cat)
        nxt = jnp.where(gl, rec[..., 3], rec[..., 4]).astype(jnp.int32)
        return jnp.where(node >= 0, nxt, node)

    return jax.lax.fori_loop(0, meta.depth, step, node)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_binned(stack: EnsembleStack, bins_t: jax.Array,
                            feat_tbl: Optional[jax.Array] = None, *,
                            meta: EnsembleMeta) -> jax.Array:
    """Raw per-class scores over the BINNED store — [K, N] f32.

    bins_t: [N+1, C] int store bins (the ScoreUpdater layout — C is
    original features, or bundled columns with `feat_tbl`).  Compares
    stay integer end to end (bin codes vs in-bin thresholds), so replay
    skips float thresholding entirely.  `feat_tbl` ([5, F]: col, offset,
    default, nslots, packed) is the EFB packed-slot remap of
    score_updater._walk_step: trees speak original (feature, bin) space,
    the store speaks bundle space.
    """
    node = _walk_binned_nodes(stack, bins_t[: bins_t.shape[0] - 1],
                              feat_tbl, meta)
    return _leaf_sums(stack, node, meta.num_class)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_binned_sparse(stack: EnsembleStack, cols: jax.Array,
                                   binsv: jax.Array, zero_bin: jax.Array,
                                   feat_tbl: Optional[jax.Array] = None, *,
                                   meta: EnsembleMeta) -> jax.Array:
    """Raw per-class scores over the SPARSE binned store — [K, N] f32,
    without densifying: the score replay for `sparse_store=csr` runs.

    cols/binsv: [N, R] ELL row segments (col >= num_columns = empty
    slot); zero_bin [C] int32.  Per level the walk probes the row's ELL
    segment for the split column (`sparse_bin_lookup`) instead of
    gathering from a dense [N, C] store; the routing decisions are the
    SAME `_walk_binned_nodes` / `_binned_decide` code as the dense
    replay, so scores are bitwise `predict_ensemble_binned` over
    `SparseStore.densify()` on every input.  `feat_tbl` composes: the
    probe answers store-space bins, the EFB remap runs on top.
    """
    node = _walk_binned_nodes(stack, (cols, binsv, zero_bin),
                              feat_tbl, meta)
    return _leaf_sums(stack, node, meta.num_class)


def predict_ensemble_quantized(stack, Xb: jax.Array, *,
                               meta: EnsembleMeta) -> jax.Array:
    """Raw per-class scores over an ingress-quantized request buffer —
    [K, N] f32 from [N, F] uint8/uint16 ORIGINAL per-feature bin ids
    (quantize.FeatureQuantizer) — the binned serving request path.

    Layout dispatch mirrors the raw path: shallow numerical ensembles
    traverse the PERFECT layout (arithmetic navigation; the f32 lanes
    carry bin ids < 2^24, so the compare is exactly the integer
    compare), everything else runs the SoA walk shared with the
    replay/router (`_walk_binned_nodes`) with integer compares end to
    end.  Either way the per-request buffer ships to the device 4x
    smaller than f32, and the quantizer's MISSING sentinel exceeds
    every threshold bin and matches no category bin, so
    NaN/unseen-category rows route exactly like the raw kernel (always
    right); scores are bitwise the raw-feature kernel's on every
    input.  No ``feat_tbl``: trees speak original (feature, bin) space
    and the ingress buffer is built in it — EFB remaps are a
    training-store concern.
    """
    if isinstance(stack, PerfectEnsemble):
        return predict_ensemble_perfect(stack, Xb, meta=meta)
    return _predict_ensemble_quantized_soa(stack, Xb, meta=meta)


@functools.partial(jax.jit, static_argnames=("meta",))
def _predict_ensemble_quantized_soa(stack: EnsembleStack, Xb: jax.Array,
                                    *, meta: EnsembleMeta) -> jax.Array:
    node = _walk_binned_nodes(stack, Xb, None, meta)
    return _leaf_sums(stack, node, meta.num_class)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_leaf_binned(stack: EnsembleStack, bins_t: jax.Array,
                                 feat_tbl: Optional[jax.Array] = None, *,
                                 meta: EnsembleMeta) -> jax.Array:
    """Per-tree leaf index over the BINNED store — [T, N] int32.

    The online-refit router: exactly the walk `predict_ensemble_binned`
    sums values over, returning the parked leaf instead (stumps park at
    leaf 0).  Integer bin compares end to end, so routing is exact on
    any store the trees were rebinned to.
    """
    node = _walk_binned_nodes(stack, bins_t[: bins_t.shape[0] - 1],
                              feat_tbl, meta)
    return jnp.where(node < 0, ~node, 0)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_leaf(stack: EnsembleStack, X: jax.Array, *,
                          meta: EnsembleMeta) -> jax.Array:
    """Per-tree leaf index over RAW feature values — [T, N] int32.

    The tensorized `pred_leaf` kernel: exactly the walk
    `predict_ensemble` sums values over (`_walk_raw_nodes`), returning
    the parked leaf instead — the divergence the walk/tensorized parity
    test pins down cannot reappear while the walk is shared.
    """
    node = _walk_raw_nodes(stack, X.astype(jnp.float32), meta)
    return jnp.where(node < 0, ~node, 0)


# ----------------------------------------------------------------------
# grouped (cross-model) traversal — N co-stacked tenants, ONE launch
# ----------------------------------------------------------------------

def _grouped_sums(stack: EnsembleStack, node: jax.Array,
                  tids: jax.Array, meta: GroupMeta) -> jax.Array:
    """[K, N] per-class sums where row n sums ONLY the trees of its own
    tenant ``tids[n]``.

    The walk above parked every row in every tree (rows do visit
    wrong-tenant trees — those trees gather whichever of the row's
    features their splits name, park somewhere, and are discarded
    here).  Each tenant's reduction is a STATIC slice of the [T, N]
    leaf values (`meta.segments` — trace-time bounds) fed to the SAME
    reduction and shape `_leaf_sums` uses on the tenant's solo stack
    (`_class_sums`).  Same addends in the same reduction ⇒
    bitwise-identical to per-tenant dispatch — which is why this is G
    static slices and NOT one masked segment-sum over the concatenated
    stack (a different accumulation order/shape XLA may reassociate
    differently).
    The final per-row select is a gather over the [G, K, N] stack of
    per-tenant answers; an out-of-range tid clamps (JAX gather
    semantics) rather than reading garbage.
    """
    leaf = jnp.where(node < 0, ~node, 0)
    vals = jnp.take_along_axis(stack.leaf_value, leaf, axis=1)   # [T, N]
    sums = jnp.stack([_class_sums(vals[a:b], stack.class_id[a:b],
                                  meta.num_class)
                      for a, b in meta.segments])          # [G, K, N]
    idx = jnp.broadcast_to(tids.astype(jnp.int32)[None, None, :],
                           (1,) + sums.shape[1:])
    return jnp.take_along_axis(sums, idx, axis=0)[0]       # [K, N]


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_grouped(stack: EnsembleStack, X: jax.Array,
                             tids: jax.Array, *,
                             meta: GroupMeta) -> jax.Array:
    """Mixed-tenant raw scores over raw features — [K, N] f32.

    One walk of the whole super-stack (every row through every tenant's
    trees — the walk is gather-bound, so surplus trees ride the same
    depth loop), then per-tenant reductions and a per-row tenant
    select.  ``tids``: [N] int — row n's segment index into
    ``meta.segments``.  Bitwise-identical to scoring each row through
    its tenant's solo stack (`_grouped_sums`).
    """
    node = _walk_raw_nodes(stack, X.astype(jnp.float32), meta)
    return _grouped_sums(stack, node, tids, meta)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_grouped_binned(stack: EnsembleStack, Xb: jax.Array,
                                    tids: jax.Array, *,
                                    meta: GroupMeta) -> jax.Array:
    """Mixed-tenant raw scores over ingress-quantized bin ids — [K, N]
    f32 from [N, F] uint8/uint16 ORIGINAL per-feature bin ids.  The
    serving request path under serve_quantize=binned for co-stacked
    tenants: the shared binned walk (`_walk_binned_nodes`, integer
    compares end to end) over the super-stack, then the same per-tenant
    demuxed reduction as the raw grouped kernel.  Every tenant's buffer
    columns must be padded to the group-wide max feature count (the
    group runtime pads; surplus columns are never gathered by that
    tenant's trees, and wrong-tenant trees' gathers are discarded).
    """
    node = _walk_binned_nodes(stack, Xb, None, meta)
    return _grouped_sums(stack, node, tids, meta)


# ----------------------------------------------------------------------
# segment-gathered grouped traversal (costack_kernel=segment) — each
# row walks ONLY its own tenant's tree segment.  The walk-all kernels
# above are gather-bound where launch overhead dominates (the TPU
# premise), but cost ~G x a solo tenant's node math per row on
# compute-bound tiers; here per-depth-level record/feature gathers
# index ``seg_start[tid] + local_tree`` over L = max segment length
# slots, so node math returns to ~1x while the group still compiles
# ONE executable per (bucket, kind).
# ----------------------------------------------------------------------

def _segment_slots(stack: EnsembleStack, tids: jax.Array,
                   meta: GroupMeta) -> tuple:
    """Per-(slot, row) tree indices for the segment-gathered walk:
    ``tree[j, n] = seg_start[tids[n]] + j`` over L = max segment
    length slots, plus the ``valid`` mask (``j < len(segment)``).
    ``meta.segments`` is static, so the offset tables are trace-time
    constants; slots past a short tenant's segment clamp to a real
    tree (walked and discarded — `_segment_sums` zeroes them), and an
    out-of-range tid clamps exactly like `_grouped_sums`' final
    gather."""
    starts = np.fromiter((a for a, _b in meta.segments), np.int32,
                         len(meta.segments))
    stops = np.fromiter((b for _a, b in meta.segments), np.int32,
                        len(meta.segments))
    L = int((stops - starts).max())
    T = stack.nodes.shape[0]
    tids = tids.astype(jnp.int32)
    start = jnp.asarray(starts)[tids]                      # [N]
    length = jnp.asarray(stops - starts)[tids]             # [N]
    j = jnp.arange(L, dtype=jnp.int32)[:, None]            # [L, 1]
    valid = j < length[None, :]                            # [L, N]
    tree = jnp.minimum(start[None, :] + j, T - 1)          # [L, N]
    return tree, valid


def _walk_raw_segment(stack: EnsembleStack, Xf: jax.Array,
                      tree: jax.Array, meta: GroupMeta) -> jax.Array:
    """Raw-feature walk over per-row gathered tree slots: parked node
    per (slot, row) — [L, N] int32, leaves as ~leaf.  Identical
    per-level structure to `_walk_raw_nodes` (one record gather, one
    feature gather, one select) with the tree axis indexed per row
    instead of broadcast; routing decisions go through the SAME
    `_raw_decide`, so a row's own trees park on exactly the leaves the
    walk-all kernel parks them on."""
    rows = jnp.arange(Xf.shape[0])[None, :]

    def step(_, node):
        safe = jnp.maximum(node, 0)
        rec = stack.nodes[tree, safe]                      # [L, N, lanes]
        f = rec[..., 0].astype(jnp.int32)
        v = Xf[rows, f]                                    # [L, N]
        gl = _raw_decide(rec, v, meta.any_cat)
        nxt = jnp.where(gl, rec[..., 3], rec[..., 4]).astype(jnp.int32)
        return jnp.where(node >= 0, nxt, node)

    return jax.lax.fori_loop(0, meta.depth, step, stack.root[tree])


def _walk_binned_segment(stack: EnsembleStack, bins_nt: jax.Array,
                         tree: jax.Array, meta: GroupMeta) -> jax.Array:
    """Binned walk over per-row gathered tree slots — `_walk_raw_segment`
    with integer compares through the shared `_binned_decide` (the
    serving request path under serve_quantize=binned; no ``feat_tbl``:
    request buffers speak original (feature, bin) space)."""
    bins_nt = bins_nt.astype(jnp.int32)
    rows = jnp.arange(bins_nt.shape[0])[None, :]

    def step(_, node):
        safe = jnp.maximum(node, 0)
        rec = stack.nodes[tree, safe]                      # [L, N, lanes]
        f = rec[..., 0].astype(jnp.int32)
        bv = bins_nt[rows, f]                              # [L, N]
        gl = _binned_decide(rec, bv, meta.any_cat)
        nxt = jnp.where(gl, rec[..., 3], rec[..., 4]).astype(jnp.int32)
        return jnp.where(node >= 0, nxt, node)

    return jax.lax.fori_loop(0, meta.depth, step, stack.root[tree])


def _segment_sums(stack: EnsembleStack, node: jax.Array, tree: jax.Array,
                  valid: jax.Array, meta: GroupMeta) -> jax.Array:
    """[K, N] per-class sums of the [L, N] segment walk's parked leaf
    values — the demux of the segment kernels.

    Row n's slots hold ITS tenant's trees in stack order (class-major —
    exactly the solo stack order), padded slots gather a clamped tree
    and mask to an exact +0.0 addend.  The reduction therefore adds the
    same fp32 dyadic leaf values in the same order as the solo
    reduction (`_leaf_sums`) with exact-zero padding interleaved —
    exact for the dyadic leaf-value domain every grouped/solo parity
    in this module already stands on, and pinned bitwise against both
    `_grouped_sums` and per-tenant dispatch in tests/test_costack.py.
    K>1 demuxes by each slot's gathered class id (sorted within a
    segment, so each class's trees still add in stack order) with a
    sequential in-slot-order accumulation: `jax.ops.segment_sum` — the
    solo/`_grouped_sums` K>1 reduction — adds segment members
    sequentially in index order, and a masked `jnp.sum` over the slot
    axis reassociates (pairwise) and lands ~1 ulp off, so the loop is
    what keeps the multiclass demux bitwise."""
    leaf = jnp.where(node < 0, ~node, 0)
    vals = jnp.where(valid, stack.leaf_value[tree, leaf],
                     jnp.float32(0.0))                     # [L, N]
    if meta.num_class == 1:
        return _sum_trees(vals)[None]
    parts = jnp.stack(_split(vals), axis=1)                # [L, 2, N]
    cls = stack.class_id[tree]                             # [L, N]
    ks = jnp.arange(meta.num_class, dtype=cls.dtype)[None, :, None]

    def step(j, acc):
        return acc + jnp.where(cls[j][None, None, :] == ks,
                               parts[j][:, None, :], jnp.float32(0.0))

    acc = jax.lax.fori_loop(0, vals.shape[0], step,
                            jnp.zeros((2, meta.num_class, node.shape[1]),
                                      jnp.float32))
    return acc[0] + acc[1]


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_grouped_segment(stack: EnsembleStack, X: jax.Array,
                                     tids: jax.Array, *,
                                     meta: GroupMeta) -> jax.Array:
    """Mixed-tenant raw scores over raw features, segment-gathered —
    [K, N] f32, bitwise-identical to `predict_ensemble_grouped` and to
    per-tenant dispatch.  Row n walks the L = max-segment-length tree
    slots of its own tenant instead of all T_total stacked trees: same
    ONE launch per (bucket, kind), per-row node math back to ~1x."""
    tree, valid = _segment_slots(stack, tids, meta)
    node = _walk_raw_segment(stack, X.astype(jnp.float32), tree, meta)
    return _segment_sums(stack, node, tree, valid, meta)


@functools.partial(jax.jit, static_argnames=("meta",))
def predict_ensemble_grouped_segment_binned(stack: EnsembleStack,
                                            Xb: jax.Array,
                                            tids: jax.Array, *,
                                            meta: GroupMeta) -> jax.Array:
    """Mixed-tenant raw scores over ingress-quantized bin ids,
    segment-gathered — the binned twin of
    `predict_ensemble_grouped_segment` (integer compares end to end;
    buffers padded to the group-wide max feature count exactly like
    `predict_ensemble_grouped_binned`)."""
    tree, valid = _segment_slots(stack, tids, meta)
    node = _walk_binned_segment(stack, Xb, tree, meta)
    return _segment_sums(stack, node, tree, valid, meta)
