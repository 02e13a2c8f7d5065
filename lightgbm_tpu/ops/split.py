"""Vectorized best-split search over histograms.

Replaces the reference's per-feature sequential scans
(FeatureHistogram::FindBestThresholdNumerical/Categorical,
/root/reference/src/treelearner/feature_histogram.hpp:75-249) with one
cumulative-sum scan over ALL features' bins at once — `[F, B]` arrays on
the VPU instead of an OMP loop of scalar scans.

Math parity (feature_histogram.hpp:281-300):
  gain(G, H)   = max(0, |G| - l1)^2 / (H + l2)
  leaf_out(G,H)= -copysign(max(0, |G| - l1), G) / (H + l2)
  split gain reported = gain(GL,HL) + gain(GR,HR) - gain(G,H)
  valid iff both children satisfy min_data_in_leaf / min_sum_hessian_in_leaf
  and the total gain exceeds gain(G,H) + min_gain_to_split.

Numerical thresholds: rows with bin <= t go left (tree.h NumericalDecision).
Categorical: one-vs-rest, rows with bin == t go left (threshold is the bin).

Tie-break: flat argmax over [F, B] picks the smallest feature id then the
smallest threshold — matching the reference's deterministic tie-break
(split_info.hpp:100-105; its right-to-left scan with strict `>` also keeps
the smallest threshold).

Missing values (`missing_bins`, [F] int32: a feature's NaN bin, its last,
or -1): a numerical feature with a NaN bin is scanned twice over its value
bins, once with the NaN bin's sums on the right (thresholds 0 .. nb - 2,
the last of them "every value left, NaN right") and once with them on the
left (thresholds 0 .. nb - 3), as the reference's two scans for missing
type NaN.  A threshold keeps the better of its two candidates, NaN right
on a tie, and the record carries `default_left`.  Without `missing_bins`
nothing of this is traced: the record keeps its 11 fields.

A store that Exclusive Feature Bundling packed (binning.BundlePlan) is
searched in its own cells, `best_split_in_store`: a bundle's slots are its
members' bins, so the same candidates are scored with the same formulas
over [C, B] store cells instead of [F, B], and the same tie-break is taken
from the cells' (feature, threshold) tables.  `unbundle_hist` + `best_split`
stays for learner/serial.py and for a plan that packs a categorical feature.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..binning import StoreCells

K_MIN_SCORE = -jnp.inf
K_EPSILON = 1e-15  # reference meta.h kEpsilon


class SplitResult(NamedTuple):
    """Device split record (all [*] scalars).  `packed()` flattens to one
    f32 vector so the host fetches a single small transfer per split."""
    gain: jax.Array
    feature: jax.Array        # inner (used-feature) index, int32
    threshold_bin: jax.Array  # int32
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    left_output: jax.Array
    right_output: jax.Array
    # the NaN bin's side, bool (a search given missing_bins), else None
    default_left: Optional[jax.Array] = None

    def packed(self) -> jax.Array:
        """[11], or [12] with `default_left` (1.0 left) last."""
        v = [self.gain, self.feature.astype(jnp.float32),
             self.threshold_bin.astype(jnp.float32),
             self.left_sum_grad, self.left_sum_hess,
             self.left_count, self.right_sum_grad,
             self.right_sum_hess, self.right_count,
             self.left_output, self.right_output]
        if self.default_left is not None:
            v.append(self.default_left.astype(jnp.float32))
        return jnp.stack(v)


def record_width(missing_bins) -> int:
    """Fields of a packed record: 12 where the search is given
    missing_bins (SplitResult.default_left), else 11."""
    return 11 if missing_bins is None else 12


# ----------------------------------------------------------------------------
# Exclusive Feature Bundling support (binning.BundlePlan device side)
# ----------------------------------------------------------------------------

def identity_feat_table(num_bins) -> "jnp.ndarray":
    """[5, F] feat table for an UNBUNDLED store: every feature is its own
    column, packed=0, so bundle_predicate_params degenerates to the plain
    (feature, threshold) predicate.  Accepts host or traced num_bins."""
    F = num_bins.shape[0] if hasattr(num_bins, "shape") else len(num_bins)
    z = jnp.zeros(F, jnp.float32)
    return jnp.stack([jnp.arange(F, dtype=jnp.float32), z, z,
                      jnp.asarray(num_bins).astype(jnp.float32), z])


def bundle_predicate_params(feat_tbl, feat, thr, is_cat, missing_bins=None,
                            default_left=None):
    """Translate an ORIGINAL-space split (feature, threshold bin, is-cat)
    into STORE-space go-left parameters (col, T, lo, hi1, dl):

        in_range = lo <= store_bin <= hi1
        go_left  = in_range ? (is_cat ? store_bin == T : store_bin <= T)
                            : dl

    feat_tbl: [5, F] f32 rows (col, offset, default, nslots, packed) —
    binning.BundlePlan.feat_table() or identity_feat_table().  Works for
    scalar or vector `feat`/`thr`/`is_cat` (all traced).

    Slot packing keeps bin order with the default bin removed, so a
    numerical `orig_bin <= thr` is exactly the slot interval
    [offset, offset + thr - (thr >= default)]; rows outside the feature's
    slot range sit at the default bin, which goes left iff default <= thr
    (numerical) / default == thr (categorical).  For a categorical split
    ON the default bin, T = offset - 1 matches no in-range slot (offsets
    start at 1) and dl sends the default rows left.

    missing_bins ([F], as best_split's) with the split's `default_left`:
    a feature with a NaN bin is never packed, and its window ends below
    that bin, so its NaN rows go by dl = default_left."""
    feat = jnp.asarray(feat, jnp.int32)
    thr = jnp.asarray(thr, jnp.int32)
    feat_tbl = jnp.asarray(feat_tbl)   # may arrive as a host constant
    col = feat_tbl[0, feat].astype(jnp.int32)
    off = feat_tbl[1, feat].astype(jnp.int32)
    d = feat_tbl[2, feat].astype(jnp.int32)
    ns = feat_tbl[3, feat].astype(jnp.int32)
    pk = feat_tbl[4, feat] > 0
    t_num = off + thr - (thr >= d).astype(jnp.int32)
    t_cat = jnp.where(thr == d, off - 1,
                      off + thr - (thr > d).astype(jnp.int32))
    T = jnp.where(pk, jnp.where(is_cat, t_cat, t_num), thr)
    lo = jnp.where(pk, off, 0)
    hi1 = jnp.where(pk, off + ns - 1, jnp.int32(1 << 30))
    dl = pk & jnp.where(is_cat, thr == d, d <= thr)
    if missing_bins is not None:
        mb = jnp.asarray(missing_bins)[feat]
        nan = mb >= 0
        hi1 = jnp.where(nan, mb - 1, hi1)
        dl = jnp.where(nan, default_left, dl)
    return col, T, lo, hi1, dl


def store_go_left(store_bin, T, lo, hi1, dl, is_cat):
    """Evaluate the store-space predicate of bundle_predicate_params on a
    row vector of store bins."""
    in_r = (store_bin >= lo) & (store_bin <= hi1)
    gl = jnp.where(is_cat, store_bin == T, store_bin <= T)
    return jnp.where(in_r, gl, dl)


def unbundle_hist(hist: jax.Array, src: jax.Array, dmask: jax.Array,
                  totals: jax.Array) -> jax.Array:
    """Bundled histogram [C, 3, B] -> original-feature histogram [F, 3, B].

    src/dmask come from binning.BundlePlan.unbundle_tables: `src[f, b]`
    is a flat index into the [C*B] store histogram (C*B = zero sentinel
    for out-of-range bins and the default slot), and `dmask` marks each
    packed feature's default bin, reconstructed as
    `leaf_totals - sum(non-default bins)` — exact under zero conflicts
    (every row of the leaf lands in exactly one bin of each feature; the
    reference reconstructs sparse-bin zero entries the same way).
    `totals` is the leaf's [3] (sum_grad, sum_hess, count)."""
    C, _, B = hist.shape
    flat = hist.transpose(0, 2, 1).reshape(C * B, 3)
    flat = jnp.concatenate([flat, jnp.zeros((1, 3), flat.dtype)], axis=0)
    F, Bo = src.shape
    g = flat[src.reshape(-1)].reshape(F, Bo, 3).transpose(0, 2, 1)
    fill = totals[None, :, None] - jnp.sum(g, axis=2, keepdims=True)
    return jnp.where(dmask[:, None, :], fill, g)


def maybe_unbundle(hist: jax.Array, unb, totals: jax.Array) -> jax.Array:
    """unb is None (store is the original layout) or (src, dmask)."""
    if unb is None:
        return hist
    with jax.named_scope("lgbt.unbundle"):
        return unbundle_hist(hist, unb[0], unb[1], totals)


def unbundle_hist_local(hist: jax.Array, src: jax.Array, dmask: jax.Array,
                        totals: jax.Array, col_start) -> tuple:
    """Per-shard unbundle for the psum_scatter exchange: `hist` is a
    store-column SLICE [Cs, 3, B] holding global columns
    [col_start, col_start + Cs) of a reduce-scattered histogram;
    src/dmask are the GLOBAL tables of BundlePlan.unbundle_tables
    (flat indices into [C*B], sentinel C*B with C the padded column
    count — the store must be padded so the shard slices tile C exactly
    and the sentinel stays outside every slice's range).

    Returns ([F, 3, B] histogram, owned [F] bool).  An original feature
    lives entirely in ONE store column, so it is exact on the shard
    owning that column and garbage elsewhere (its default-bin fill
    reconstructs from zero sums); the split search must AND `owned`
    into its feature mask so only the owning shard's record for each
    feature survives the cross-shard argmax."""
    Cs, _, B = hist.shape
    src = jnp.asarray(src)
    col_start = jnp.asarray(col_start, jnp.int32)
    lo = col_start * B
    # the global sentinel C*B sits past the last shard's range, so
    # in_range is False for every invalid-bin entry on every shard
    in_range = (src >= lo) & (src < lo + Cs * B)
    owned = jnp.any(in_range, axis=1)
    src_l = jnp.where(in_range, src - lo, Cs * B)
    flat = hist.transpose(0, 2, 1).reshape(Cs * B, 3)
    flat = jnp.concatenate([flat, jnp.zeros((1, 3), flat.dtype)], axis=0)
    F, Bo = src_l.shape
    g = flat[src_l.reshape(-1)].reshape(F, Bo, 3).transpose(0, 2, 1)
    fill = totals[None, :, None] - jnp.sum(g, axis=2, keepdims=True)
    return jnp.where(jnp.asarray(dmask)[:, None, :], fill, g), owned


def sharded_slice_search(h, sums, *, off, nb_s, ic_s, fm_s,
                         num_bins, is_cat, fmask, unb, skw,
                         mb_s=None, missing_bins=None) -> jax.Array:
    """Per-shard best split of ONE leaf from its reduce-scattered
    store-column slice (the psum_scatter exchange of learner/rounds.py
    and learner/fused.py — shared so the two learners cannot diverge).

    h : [Cs, 3, B] this shard's reduced column slice; off: the shard's
    first global column.  Identity store (unb None): nb_s/ic_s/fm_s are
    the shard's dynamic metadata slices and the record's feature id gets
    `off` folded back in.  Bundled store searched in its own cells (unb
    the tree's StoreSearch for the slice's columns): a member lies
    whole in one column and the tables name original features, so the
    slice is searched as it is.  Bundled store with gather tables: the
    slice is unbundled to the full original-feature layout with
    non-owned features masked out of the search.  Returns the packed
    [11] record in ORIGINAL feature space; combine across shards with
    `combine_sharded_records`.  mb_s / missing_bins: the slice's and
    the whole store's NaN bins (best_split), or None."""
    if unb is None:
        rec = best_split(h, nb_s, ic_s, fm_s,
                         sums[0], sums[1], sums[2], mb_s, **skw)
        p = rec.packed()
        return p.at[1].add(jnp.asarray(off).astype(jnp.float32))
    if isinstance(unb, StoreSearch):
        return best_split_in_store(h, unb, sums[0], sums[1], sums[2],
                                   **skw).packed()
    with jax.named_scope("lgbt.unbundle"):
        hF, owned = unbundle_hist_local(h, unb[0], unb[1], sums, off)
    rec = best_split(hF, num_bins, is_cat, fmask & owned,
                     sums[0], sums[1], sums[2], missing_bins, **skw)
    return rec.packed()


def combine_sharded_records(recs: jax.Array, axis_name) -> jax.Array:
    """all_gather the per-shard packed records over `axis_name` and pick
    each leaf's winner: maximum gain, ties broken by the SMALLEST
    feature id — every feature is owned by exactly one shard, so this
    reproduces the full search's flat-argmax tie-break exactly even
    when feature→shard ownership is not monotone in feature id (EFB
    bundles order shards by store column, not original feature).

    recs: [..., 11] (a single record or a [K, 11] batch); returns the
    same shape, replicated across the axis.

    REPLICATION CONTRACT: every shard receives the identical winning
    record (all_gather is replicated and the argmin over it is
    deterministic), so results may legally gate replicated control
    flow.  shardlint's taint lattice (diagnostics/lint.py) encodes this
    by name — treat this function like psum when reasoning about
    divergence — and the DivergenceSanitizer checksums the downstream
    tree state at run time."""
    allr = jax.lax.all_gather(recs, axis_name)       # [nd, ..., 11]
    gains = allr[..., 0]
    mx = jnp.max(gains, axis=0, keepdims=True)
    cand = jnp.where(gains == mx, allr[..., 1], jnp.inf)
    best = jnp.argmin(cand, axis=0)
    return jnp.take_along_axis(allr, best[None, ..., None],
                               axis=0).squeeze(0)


def leaf_split_gain(G, H, l1, l2):
    reg = jnp.maximum(jnp.abs(G) - l1, 0.0)
    return reg * reg / (H + l2)


def leaf_output(G, H, l1, l2):
    reg = jnp.maximum(jnp.abs(G) - l1, 0.0)
    return -jnp.sign(G) * reg / (H + l2)


def split_gain_matrix(hist: jax.Array, num_bins: jax.Array, is_cat: jax.Array,
                      feature_mask: jax.Array, sum_grad: jax.Array,
                      sum_hess: jax.Array, num_data: jax.Array,
                      missing_bins=None, *,
                      lambda_l1: float = 0.0, lambda_l2: float = 0.0,
                      min_data_in_leaf: int = 20,
                      min_sum_hessian_in_leaf: float = 1e-3,
                      min_gain_to_split: float = 0.0):
    """[F, B] total gain per candidate threshold (K_MIN_SCORE where
    invalid), plus (GL, HL, CL) cumulatives for record assembly and the
    [F, B] bool NaN side of each candidate (None without
    missing_bins).  Exposed separately from `best_split` so the
    voting-parallel learner can rank features locally
    (voting_parallel_tree_learner.cpp local top-k)."""
    F, _, B = hist.shape
    l1, l2 = lambda_l1, lambda_l2
    g, h, c = hist[:, 0, :], hist[:, 1, :], hist[:, 2, :]

    bin_idx = jax.lax.broadcasted_iota(jnp.int32, (F, B), 1)
    nb = num_bins[:, None]

    # ---- numerical: left = bins <= t, valid t in [0, nb-2] ----------------
    GL = jnp.cumsum(g, axis=1)
    HL = jnp.cumsum(h, axis=1)
    CL = jnp.cumsum(c, axis=1)
    # ---- categorical: left = bin == t, valid t in [0, nb-1] ---------------
    GL = jnp.where(is_cat[:, None], g, GL)
    HL = jnp.where(is_cat[:, None], h, HL)
    CL = jnp.where(is_cat[:, None], c, CL)

    GR = sum_grad - GL
    HR = sum_hess - HL
    CR = num_data - CL

    t_valid = jnp.where(is_cat[:, None], bin_idx < nb, bin_idx < nb - 1)
    total_gain = _candidate_gains(
        t_valid & feature_mask[:, None], GL, HL, CL, GR, HR, CR,
        sum_grad, sum_hess, l1, l2, min_data_in_leaf,
        min_sum_hessian_in_leaf, min_gain_to_split)
    if missing_bins is None:
        return total_gain, GL, HL, CL, None

    # ---- the NaN bin on the left: value thresholds t in [0, nb-3] -------
    mb = jnp.asarray(missing_bins)
    nan_at = jnp.maximum(mb, 0)[:, None]
    GLn = GL + jnp.take_along_axis(g, nan_at, axis=1)
    HLn = HL + jnp.take_along_axis(h, nan_at, axis=1)
    CLn = CL + jnp.take_along_axis(c, nan_at, axis=1)
    left_valid = ((mb >= 0) & ~is_cat)[:, None] & (bin_idx < nb - 2)
    gain_n = _candidate_gains(
        left_valid & feature_mask[:, None], GLn, HLn, CLn, sum_grad - GLn,
        sum_hess - HLn, num_data - CLn, sum_grad, sum_hess, l1, l2,
        min_data_in_leaf, min_sum_hessian_in_leaf, min_gain_to_split)
    dl = gain_n > total_gain
    return (jnp.where(dl, gain_n, total_gain), jnp.where(dl, GLn, GL),
            jnp.where(dl, HLn, HL), jnp.where(dl, CLn, CL), dl)


def _candidate_gains(cand, GL, HL, CL, GR, HR, CR, sum_grad, sum_hess,
                     l1, l2, min_data_in_leaf, min_sum_hessian_in_leaf,
                     min_gain_to_split):
    """Total gain of every candidate with both children's sums given,
    K_MIN_SCORE where `cand` is False, a child is under
    min_data_in_leaf / min_sum_hessian_in_leaf or the gain does not
    exceed the leaf's own plus min_gain_to_split: the one arithmetic of
    the search over [F, B] and of the one over a bundled store's
    cells."""
    valid = (cand
             & (CL >= min_data_in_leaf) & (CR >= min_data_in_leaf)
             & (HL >= min_sum_hessian_in_leaf)
             & (HR >= min_sum_hessian_in_leaf))

    gain_shift = leaf_split_gain(sum_grad, sum_hess, l1, l2)
    min_gain_shift = gain_shift + min_gain_to_split
    total_gain = leaf_split_gain(GL, HL, l1, l2) + leaf_split_gain(GR, HR, l1, l2)
    return jnp.where(valid & (total_gain > min_gain_shift),
                     total_gain, K_MIN_SCORE)


def _split_record(bg, bf, bt, glb, hlb, clb, sum_grad, sum_hess, num_data,
                  gain_shift, l1, l2) -> SplitResult:
    """The record of the winning candidate: its total gain `bg` (the
    leaf's own, `gain_shift`, comes off), original feature and
    threshold, and its left child's sums."""
    grb, hrb, crb = sum_grad - glb, sum_hess - hlb, num_data - clb
    return SplitResult(
        gain=jnp.where(jnp.isfinite(bg), bg - gain_shift, K_MIN_SCORE),
        feature=bf, threshold_bin=bt,
        left_sum_grad=glb, left_sum_hess=hlb, left_count=clb,
        right_sum_grad=grb, right_sum_hess=hrb, right_count=crb,
        left_output=leaf_output(glb, hlb, l1, l2),
        right_output=leaf_output(grb, hrb, l1, l2))


@functools.partial(
    jax.jit,
    static_argnames=("lambda_l1", "lambda_l2", "min_data_in_leaf",
                     "min_sum_hessian_in_leaf", "min_gain_to_split"))
def best_split(hist: jax.Array, num_bins: jax.Array, is_cat: jax.Array,
               feature_mask: jax.Array, sum_grad: jax.Array,
               sum_hess: jax.Array, num_data: jax.Array,
               missing_bins=None, *,
               lambda_l1: float = 0.0, lambda_l2: float = 0.0,
               min_data_in_leaf: int = 20,
               min_sum_hessian_in_leaf: float = 1e-3,
               min_gain_to_split: float = 0.0) -> SplitResult:
    """Find the best split of one leaf from its histogram.

    hist : [F, 3, B] f32 (sum_grad, sum_hess, count)
    num_bins : [F] int32 actual bins per feature
    is_cat : [F] bool
    feature_mask : [F] bool (feature_fraction subset for this tree)
    sum_grad/sum_hess/num_data : leaf totals (host-accurate scalars)
    missing_bins : [F] int32 NaN bin per feature, -1 none; or None
    """
    F, _, B = hist.shape
    l1, l2 = lambda_l1, lambda_l2
    total_gain, GL, HL, CL, DL = split_gain_matrix(
        hist, num_bins, is_cat, feature_mask, sum_grad, sum_hess, num_data,
        missing_bins, lambda_l1=lambda_l1, lambda_l2=lambda_l2,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        min_gain_to_split=min_gain_to_split)
    gain_shift = leaf_split_gain(sum_grad, sum_hess, l1, l2)

    flat = total_gain.reshape(-1)
    best = jnp.argmax(flat)
    bf = (best // B).astype(jnp.int32)
    bt = (best % B).astype(jnp.int32)
    bg = flat[best]
    glb, hlb, clb = GL.reshape(-1)[best], HL.reshape(-1)[best], CL.reshape(-1)[best]
    rec = _split_record(bg, bf, bt, glb, hlb, clb, sum_grad, sum_hess,
                        num_data, gain_shift, l1, l2)
    if DL is not None:
        rec = rec._replace(default_left=DL.reshape(-1)[best])
    return rec


class StoreSearch(NamedTuple):
    """What best_split_in_store reads beside a histogram [C, 3, B]: the
    rows of a StoreCells for those C columns and, made once a tree
    (store_search_operands), the tree's feature mask in cell space and
    the cells each candidate's sum runs over."""
    feat: jax.Array      # [C, B] int32, -1: no candidate
    thr: jax.Array       # [C, B] int32
    suffix: jax.Array    # [C, B] bool
    mask: jax.Array      # [C, B] bool: a candidate, its feature in the tree
    member: jax.Array    # [C, B, B] f32 0/1: cell s' is in cell s's sum
    nan: Optional[jax.Array] = None   # [C] int32 StoreCells.nan, or None


def store_search_operands(cells: StoreCells, feature_mask: jax.Array,
                          start=0, size: int = 0) -> StoreSearch:
    """StoreSearch for store columns [start, start + size) (all of them
    by default; a shard's slice under psum_scatter, `start` traced — a
    member lies whole in one column) under the tree's feature_mask [F]
    over ORIGINAL features.  Once a tree, not once a searched slot."""
    size = size or cells.feat.shape[0]
    feat, thr, suffix, lo, hi, nan = (
        None if t is None else
        jax.lax.dynamic_slice_in_dim(jnp.asarray(t), start, size)
        for t in cells)
    mask = (feat >= 0) & jnp.asarray(feature_mask)[jnp.maximum(feat, 0)]
    B = feat.shape[1]
    sp = jax.lax.broadcasted_iota(jnp.int32, (1, B, 1), 1)
    member = (sp >= lo[:, None, :]) & (sp <= hi[:, None, :])
    return StoreSearch(feat, thr, suffix, mask,
                       member.astype(jnp.float32), nan)


def _member_sums(hist: jax.Array, member: jax.Array) -> jax.Array:
    """hist [C, 3, B] -> for every cell s the float32 sum of the cells
    of its own member that StoreSearch.member marks: one contraction of
    the bin axis against a 0/1 matrix, at float32 precision (HIGHEST:
    on the chip's bf16 matrix unit a value goes through as three pieces
    that add up to it exactly; the default, one piece, keeps 8 bits of
    a sum).  Sums stay inside a member — a column-wide cumsum less its
    base would cancel a rare level's slot against a prefix a thousand
    times its size — and a one-slot member's sum is the slot itself.
    Every output adds its terms in the one order of the contraction,
    zeros included, so thresholds over a run of empty bins read the
    same sum (and tie, to the smallest, as under a sequential
    cumsum)."""
    return jnp.einsum("cjs,cst->cjt", hist, member,
                      precision=jax.lax.Precision.HIGHEST)


def best_split_in_store(hist: jax.Array, search: StoreSearch,
                        sum_grad: jax.Array,
                        sum_hess: jax.Array, num_data: jax.Array, *,
                        lambda_l1: float = 0.0, lambda_l2: float = 0.0,
                        min_data_in_leaf: int = 20,
                        min_sum_hessian_in_leaf: float = 1e-3,
                        min_gain_to_split: float = 0.0) -> SplitResult:
    """best_split of one leaf over a BUNDLED store histogram's own cells.

    hist : [C, 3, B] f32 store histogram (C padded store columns, or a
        shard's slice of them)
    search : the tree's store_search_operands for those C columns

    Scores exactly the (feature, threshold) candidates that
    best_split(unbundle_hist(hist)) scores, with the same formulas, and
    returns the same record in ORIGINAL feature space.  The left sums
    are member-local sums of slots (for thresholds at or above the
    member's default bin: the leaf totals less such a sum), so a
    one-slot member's are the gathered search's bit for bit and any
    other's differ by the order of a float32 sum.  The winner is the
    maximum gain, then the smallest original feature, then the smallest
    threshold — the flat argmax over [F, B]; cells of a column are not
    in feature order, so it takes two reductions here.

    A column with a NaN bin (`search.nan`: one numerical feature, its
    prefix cells its value thresholds) scores each threshold again with
    that bin's sums on the left, as split_gain_matrix, and the record
    carries `default_left`; without `search.nan` nothing of it is
    traced."""
    B = hist.shape[-1]
    l1, l2 = lambda_l1, lambda_l2
    feat, thr, suffix = search.feat, search.thr, search.suffix
    part = _member_sums(hist, search.member)
    GL = jnp.where(suffix, sum_grad - part[:, 0, :], part[:, 0, :])
    HL = jnp.where(suffix, sum_hess - part[:, 1, :], part[:, 1, :])
    CL = jnp.where(suffix, num_data - part[:, 2, :], part[:, 2, :])
    GR = sum_grad - GL
    HR = sum_hess - HL
    CR = num_data - CL
    total_gain = _candidate_gains(
        search.mask, GL, HL, CL, GR, HR, CR, sum_grad, sum_hess, l1, l2,
        min_data_in_leaf, min_sum_hessian_in_leaf, min_gain_to_split)
    DL = None
    if search.nan is not None:
        # the NaN bin on the left: value thresholds t in [0, nb-3]
        nan = search.nan[:, None]
        at = jnp.maximum(nan, 0)[:, None, :]                # [C, 1, 1]
        nan_sums = jnp.take_along_axis(hist, jnp.broadcast_to(
            at, (hist.shape[0], 3, 1)), axis=2)             # [C, 3, 1]
        GLn = GL + nan_sums[:, 0, :]
        HLn = HL + nan_sums[:, 1, :]
        CLn = CL + nan_sums[:, 2, :]
        gain_n = _candidate_gains(
            search.mask & (nan >= 0) & (thr < nan - 1), GLn, HLn, CLn,
            sum_grad - GLn, sum_hess - HLn, num_data - CLn, sum_grad,
            sum_hess, l1, l2, min_data_in_leaf, min_sum_hessian_in_leaf,
            min_gain_to_split)
        DL = gain_n > total_gain
        total_gain = jnp.where(DL, gain_n, total_gain)
        GL, HL, CL = (jnp.where(DL, GLn, GL), jnp.where(DL, HLn, HL),
                      jnp.where(DL, CLn, CL))

    flat = total_gain.reshape(-1)
    bg = jnp.max(flat)
    order = jnp.where((flat == bg) & (feat.reshape(-1) >= 0),
                      (feat * B + thr).reshape(-1),
                      jnp.iinfo(jnp.int32).max)
    best = jnp.argmin(order)
    bf = jnp.maximum(feat.reshape(-1)[best], 0)
    bt = thr.reshape(-1)[best]
    glb, hlb, clb = GL.reshape(-1)[best], HL.reshape(-1)[best], CL.reshape(-1)[best]
    rec = _split_record(bg, bf, bt, glb, hlb, clb, sum_grad, sum_hess,
                        num_data, leaf_split_gain(sum_grad, sum_hess, l1, l2),
                        l1, l2)
    if DL is not None:
        rec = rec._replace(default_left=DL.reshape(-1)[best])
    return rec
