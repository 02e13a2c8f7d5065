"""What the cell `allstate.full` trains, against plain references, on the
chip (one TPU chip).

The benchmark's `correct` compares what the trained model *predicts* with a
plain walk.  This compares what the learner *builds* from the bundled store,
through the objects the timed path uses (the cell's binned training set,
`lgb.Booster`, its learner's store on the device, the program's own
`unbundle_hist`), with NumPy over the scipy CSR matrix: no bundle anywhere.

`--check root` (the cell's full size, 12,184,290 x 4,228; about four minutes
once the set is binned):

1. tree 1's root histogram in ORIGINAL feature space — the root launch of
   `hist_multileaf_masked` over the learner's store, gradients quantised as
   `build_tree_rounds` quantises them and its real store columns alone
   launched (the padded ones have to come back exact zeros), then `ops/split.unbundle_hist`
   through the learner's tables — against int64 sums of the same int8
   levels per (feature, bin) over the CSR matrix's stored entries
   (`np.bincount`; a feature's zero bin is the leaf's totals less its
   stored entries), bins from the mappers' thresholds by `np.searchsorted`.
   Every feature that has a store column to itself: equal cell for cell
   (the float32 image of the int64 sums).  A member of a bundle: equal
   where no row of it conflicted; else its non-default bins may fall short
   of the reference's, and only short, by the rows `Dataset` counted as
   conflicting (`max_conflict_rate=0` is judged on the planner's
   50,000-row sample; a conflicting row keeps the last member's bin) — the
   shortfalls of all members add up to `bundle_conflict_rows`, and that
   count, for that reason, is the whole tolerance.
2. the root split of tree 1 as the learner's build grew it (feature,
   threshold bin, original space; the build searches the store
   histogram's own cells, `ops/split.best_split_in_store`, and unbundles
   nothing) against the float64 best split, by the textbook gain, of the
   device's histogram as step 1 unbundled it: the same feature
   and bin, or the same feature with a float64 gain within four float32
   steps of the sum the search compares (what float32 cannot tell apart;
   both gains and the step are printed).

`--check bundle --rows N` (one size a call; the readings in PERF.md are at
50,000 rows, where the planner sees every row and no row conflicts, at
200,000, the most whose UNBUNDLED store, 4,228 columns wide, keeps the
cell's int32 layout, and at 1,000,000, in the int8-stored layout the learner
picks for that store by itself; at 2,000,000 the unbundled side does not fit
the chip's host):

3. `--trees` trees with `enable_bundle` on and off (both on a dense store)
   on the same rows.  Held to, at every size: the logloss on the test split
   within `LOGLOSS_LIMIT`, and every departure from "the same trees" with a
   counted or computed cause.  Where no row conflicts, besides: tree 1 node
   for node, and — if the logloss is not within 1e-6 — the FIRST node at
   which the two sides part, with the float64 gain of either side's split
   there from int64 sums over the CSR matrix (the tree's own gradients,
   quantised as the build quantises them).  The check passes only if those
   two gains lie within `TIE_STEPS` float32 steps of the sum the search
   compares (`GL^2/HL + GR^2/HR`, of the size of `G^2/H`): what float32
   cannot tell apart.  It also prints what can make two sides with the same
   histograms of a node see it differently: the node's totals reach it
   through its ancestors' split records, and an ancestor that split on a
   bundle member hands its default-bin side `totals less slots` in float32
   where the unbundled store hands on the kernel's own sum.

One JSON line per check, `{"ok": ...}` last; exit code 1 if any failed.
"""
import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

from higgs_reference_check import (load_cell, quantize,       # noqa: E402
                                   reference_split, say, tree_of)

CELL = "allstate.full"
EXACT_ROWS = 50_000   # the planner looks at no more rows: none conflicts below
# |bundled - plain| logloss after the trees: a guard against a store that
# is wrong, not against rounding.  Between two readings on the chip (PR 39):
# the largest departure of the two right stores, 9.0e-5 (50,000 rows, no
# conflict, near-ties turned from tree 2 on), and what the unbundled model
# reads with its last tree left out, 4.5e-2 at every size.
LOGLOSS_LIMIT = 5e-4
TIE_STEPS = 4.0


def root_pass(learner, grad, hess, dtype):
    """-> (the root launch as `build_tree_rounds` makes it, over the
    store's real columns, unbundled to [F, 3, B] float32 by the program's
    own `unbundle_hist`, (grad scale, hess scale), whether the launch
    handed back exact zeros for every padded store column)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked, quantize_gh
    from lightgbm_tpu.ops.split import unbundle_hist
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    mask, _ = learner._masks(None)
    n = learner.Np
    gh8 = (jnp.zeros((8, n), jnp.float32)
           .at[0].set(learner._rows_in(grad) * mask)
           .at[1].set(learner._rows_in(hess) * mask).at[2].set(mask))
    ghq = quantize_gh(gh8) if dtype == "int8" else None
    store = hist_multileaf_masked(
        learner.bins_dev, jnp.zeros(n, jnp.int32), gh8,
        jnp.zeros(1, jnp.int32), num_bins_padded=learner.B, backend=backend,
        input_dtype=dtype, max_num_bin=int(learner.dataset.max_num_bin),
        ghq=ghq, real_columns=learner.Cstore)[0]
    pad_zero = bool(not np.asarray(store[learner.Cstore:]).any())
    totals = jnp.sum(store[0], axis=1)           # any store column's bins
    src, dmask = learner.dataset.unbundle_tables(learner.B, learner.Fpad)
    feat = unbundle_hist(store, jnp.asarray(src), jnp.asarray(dmask), totals)
    scales = ((float(ghq[1]), float(ghq[2])) if ghq is not None
              else (1.0, 1.0))
    return np.asarray(feat), scales, pad_zero


def csr_histogram(X, uppers, used, gq, hq, B):
    """[F, 3, B] int64: per used feature the sums of (grad level, hess
    level, 1) over its stored entries by bin, the rows without an entry in
    the bin a raw 0.0 falls in."""
    csc = X.tocsc()
    totals = np.array([gq.sum(), hq.sum(), X.shape[0]], np.int64)
    g, h = gq.astype(np.float64), hq.astype(np.float64)   # exact below 2^53
    out = np.zeros((len(used), 3, B), np.int64)
    for k, j in enumerate(used):
        s, e = csc.indptr[j], csc.indptr[j + 1]
        rows = csc.indices[s:e]
        vals = np.nan_to_num(csc.data[s:e].astype(np.float64), nan=0.0)
        b = np.searchsorted(uppers[k], vals, side="left")
        out[k, 0] = np.bincount(b, g[rows], B).astype(np.int64)
        out[k, 1] = np.bincount(b, h[rows], B).astype(np.int64)
        out[k, 2] = np.bincount(b, minlength=B)
        zero = int(np.searchsorted(uppers[k], 0.0, side="left"))
        out[k, :, zero] += totals - out[k].sum(axis=1)
    return out, totals


def check_root(config, cell, params) -> list:
    import scipy.sparse as sps
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset
    t0 = time.perf_counter()
    train, facts = dataset.binned_train_set(config, params)
    bst = lgb.Booster(params, train)
    learner, ds = bst._gbdt.learner, train._inner
    plan = ds.bundle_plan
    if plan is None:
        raise SystemExit("the check is written for a bundled store")
    say(check="setup", dataset=facts, learner=type(learner).__name__,
        store=list(learner.bins_dev.shape),
        store_dtype=str(learner.bins_dev.dtype),
        features=ds.num_features, store_columns=ds.num_store_columns,
        bundles=plan.num_bundles, packed=plan.num_packed,
        conflict_rows=int(ds.bundle_conflict_rows),
        realised_conflict_rate=ds.realized_conflict_rate(),
        sampled_conflict_rate=plan.est_conflict_rate,
        seconds=time.perf_counter() - t0)
    failed = []
    grad, hess = (a.reshape(-1) for a in bst._gbdt.boosting_gradients())
    dtype = params["histogram_dtype"]
    feat, (sg, sh), pad_zero = root_pass(learner, grad, hess, dtype)
    N, B = learner.N, learner.B
    gq, sg_np = quantize(np.asarray(grad)[:N])
    hq, sh_np = quantize(np.asarray(hess)[:N])
    assert (float(sg_np), float(sh_np)) == (sg, sh), (sg_np, sh_np, sg, sh)
    scale = np.array([sg, sh, 1.0], np.float32)

    t0 = time.perf_counter()
    X, _ = dataset.generate(config, int(config["rows"]),
                            (int(config["data_seed"]), dataset.TRAIN))
    if not sps.issparse(X):               # a rehearsal's rows come dense
        X = sps.csr_matrix(X)
    used = ds.used_features
    ref, totals = csr_histogram(
        X, [ds.mappers[i].bin_upper_bound for i in used], used, gq, hq, B)
    del X
    image = ref.astype(np.float32) * scale[None, :, None]
    singles = ~plan.feat_packed
    singles_off = int((feat[singles] != image[singles]).sum())
    # a packed member's count per bin, back in units of rows
    short = ref[:, 2] - np.rint(feat[:, 2]).astype(np.int64)       # [F, B]
    nondefault = np.ones((len(used), B), bool)
    nondefault[np.arange(len(used)), plan.feat_default] = False
    nondefault &= np.arange(B)[None, :] < np.asarray(ds.num_bins)[:, None]
    short_nd = np.where(nondefault & plan.feat_packed[:, None], short, 0)
    surplus = int((short_nd < 0).sum())
    lost = int(short_nd.sum())
    # a member no row of which conflicted: its slots bit for bit; its
    # default bin is totals less slots in float32 on the device, so that
    # cell is held to float32 rounding at the size of the totals
    clean = plan.feat_packed & (short_nd.sum(axis=1) == 0)
    at_slots = nondefault[:, None, :] & clean[:, None, None]
    clean_off = int(((feat != image) & at_slots).sum())
    at_default = ~nondefault[:, None, :] & clean[:, None, None]
    size = np.abs(totals * scale.astype(np.float64))[None, :, None]
    default_err = float((np.abs(feat.astype(np.float64) - image)
                         / size)[np.broadcast_to(at_default, feat.shape)]
                        .max(initial=0.0))
    hist_ok = (singles_off == 0 and surplus == 0 and clean_off == 0
               and default_err <= 1e-6
               and lost == int(ds.bundle_conflict_rows) and pad_zero)
    say(check="root_histogram", ok=hist_ok, features=len(used),
        padded_store_columns=learner.Fpad - learner.Cstore,
        padded_store_columns_zero=pad_zero,
        cells=int(ref.size), singleton_features=int(singles.sum()),
        singleton_cells_off=singles_off, members=int(plan.num_packed),
        members_without_conflict=int(clean.sum()),
        slot_cells_off_in_members_without_conflict=clean_off,
        default_bin_max_err_over_totals=default_err,
        member_bins_above_the_reference=surplus,
        shortfall_rows_all_members=lost,
        dataset_conflict_rows=int(ds.bundle_conflict_rows),
        realised_conflict_rate=ds.realized_conflict_rate(),
        largest_sum=int(np.abs(ref).max()), scales=[sg, sh],
        reference_seconds=time.perf_counter() - t0)
    if not hist_ok:
        failed.append("root_histogram")

    tree = tree_of(learner, grad, hess)
    sc64 = scale.astype(np.float64)[:, None]
    seen = feat.astype(np.float64)       # what the device's search sees
    want = reference_split(seen, ds.num_bins,
                           int(params["min_data_in_leaf"]),
                           float(params["min_sum_hessian_in_leaf"]))
    got = (int(tree.split_feature[0]), int(tree.threshold_bin[0]))
    # the search compares GL^2/HL + GR^2/HR in float32, a number of the size
    # of G^2/H: thresholds whose sums lie within a few float32 steps of
    # that are one to it
    h = seen[got[0]]
    GL, HL = h[0, :got[1] + 1].sum(), h[1, :got[1] + 1].sum()
    G, H = (totals * sc64[:, 0])[:2]
    gain_path = GL * GL / HL + (G - GL) ** 2 / (H - HL) - G * G / H
    step = 2.0 ** -23 * G * G / H
    split_ok = bool(got == want[:2] or (
        got[0] == want[0] and 0 <= want[2] - gain_path <= 4 * step))
    say(check="root_split", ok=split_ok, path=got, reference=want[:2],
        same=got == want[:2], reference_gain=want[2],
        gain_of_path_in_float64=gain_path, gap=want[2] - gain_path,
        float32_step_of_the_compared_sum=step,
        packed=bool(plan.feat_packed[got[0]]),
        raw_column=int(used[got[0]]), leaves=int(tree.num_leaves))
    if not split_ok:
        failed.append("root_split")
    return failed


def train_both(config, cell, params, rows, trees):
    """`trees` trees with and without bundling on the same `rows` rows,
    handed over as a scipy CSR matrix.  -> {side: facts}, the matrix, and
    the binning both sides share."""
    import scipy.sparse as sps
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset, walk
    X, y = dataset.generate(config, rows, (int(config["data_seed"]),
                                           dataset.TRAIN))
    X = sps.csr_matrix(X)
    Xv, yv = dataset.test_split(config, min(int(cell["valid_rows"]), 50_000))
    got, binning = {}, None
    for side, bundle in (("bundled", True), ("plain", False)):
        t0 = time.perf_counter()
        # 4,228 sparse columns without a plan would resolve to the CSR
        # store (sparse_store=auto), which no chip has run: the comparison
        # is of one dense store with and without bundles
        p = dict(params, enable_bundle=bundle, sparse_store="dense")
        ds = lgb.Dataset(X, y).construct(p)
        bst = lgb.Booster(p, ds)
        gbdt, inner = bst._gbdt, ds._inner
        grads = []
        for _ in range(trees):
            # what the next tree is grown on: the first N rows of the
            # layout's [1, Np] (the pipeline's pending tree is the host's
            # copy; the score on the device is already updated)
            g, h = gbdt.boosting_gradients()
            grads.append((np.asarray(g).reshape(-1)[:rows].copy(),
                          np.asarray(h).reshape(-1)[:rows].copy()))
            bst.update()
        gbdt._flush_pending()
        lr = gbdt.learner
        used = list(inner.used_features)
        if binning is None:
            binning = dict(
                used=used, num_bins=np.asarray(inner.num_bins), B=lr.B,
                mappers=[inner.mappers[i] for i in used],
                packed=(np.asarray(inner.bundle_plan.feat_packed)
                        if inner.bundle_plan is not None
                        else np.zeros(len(used), bool)))
        elif used != binning["used"]:
            raise SystemExit("the two sides use different features")
        got[side] = dict(
            models=gbdt.models, grads=grads,
            logloss=walk.logloss(yv, bst.predict(Xv, raw_score=True), 1.0),
            one_tree_short=walk.logloss(yv, bst.predict(
                Xv, raw_score=True, num_iteration=trees - 1), 1.0),
            conflicts=int(inner.bundle_conflict_rows))
        say(check="trained", side=side, rows=rows,
            learner=type(lr).__name__, store=list(lr.bins_dev.shape),
            store_dtype=str(lr.bins_dev.dtype),
            conflict_rows=got[side]["conflicts"],
            leaves=[int(t.num_leaves) for t in gbdt.models],
            valid_logloss=got[side]["logloss"],
            seconds=time.perf_counter() - t0)
        del bst, ds, gbdt, inner, lr
    return got, X, binning


def first_departure(a, b):
    """Walk two trees from their roots, level by level, while they split
    alike: -> None, or (path to the first pair of nodes that differ, each
    step (inner feature, threshold bin, went left), side a's node, side
    b's node); a node is an index, or None for a leaf."""
    def node(t, i):
        return None if i < 0 or t.num_leaves < 2 else i

    level = [((), node(a, 0), node(b, 0))]
    while level:
        nxt = []
        for path, i, j in level:
            sa = None if i is None else (int(a.split_feature_inner[i]),
                                         int(a.threshold_in_bin[i]))
            sb = None if j is None else (int(b.split_feature_inner[j]),
                                         int(b.threshold_in_bin[j]))
            if sa != sb:
                return path, i, j
            if sa is None:
                continue
            for left, ca, cb in ((True, a.left_child[i], b.left_child[j]),
                                 (False, a.right_child[i], b.right_child[j])):
                nxt.append((path + ((sa[0], sa[1], left),),
                            node(a, int(ca)), node(b, int(cb))))
        level = nxt
    return None


def explain_departure(X, binning, got, params):
    """The first tree and node at which bundled and plain part, and the
    float64 gain of either side's split there from exact sums.  -> facts
    (with `tie`: the two gains lie within TIE_STEPS float32 steps of a
    default bin the size of the leaf's totals), or None if no tree parts."""
    A, P = got["bundled"], got["plain"]
    for t, (ta, tb) in enumerate(zip(A["models"], P["models"])):
        where = first_departure(ta, tb)
        if where is not None:
            break
    else:
        return None
    path, i, j = where
    (ga, ha), (gp, hp) = A["grads"][t], P["grads"][t]
    facts = dict(tree=t + 1, depth=len(path), path=[list(s) for s in path],
                 gradients_equal_on_both_sides=bool(
                     np.array_equal(ga, gp) and np.array_equal(ha, hp)))
    # the node's rows: down the path both sides share, by the features' bins
    csc = X.tocsc()
    used, mappers = binning["used"], binning["mappers"]
    inside = np.ones(X.shape[0], bool)
    for k, thr, left in path:
        s, e = csc.indptr[used[k]], csc.indptr[used[k] + 1]
        bins = np.full(X.shape[0], int(mappers[k].value_to_bin(
            np.zeros(1))[0]), np.int64)
        bins[csc.indices[s:e]] = mappers[k].value_to_bin(
            csc.data[s:e].astype(np.float64))
        inside &= (bins <= thr) == left
    gq, sg = quantize(ga)
    hq, sh = quantize(ha)
    ref, totals = csr_histogram(
        X[inside], [m.bin_upper_bound for m in mappers], used,
        gq[inside], hq[inside], binning["B"])
    scale = np.array([sg, sh, 1.0], np.float64)
    hist = ref.astype(np.float64) * scale[None, :, None]
    G, H, C = totals * scale
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])

    # the search compares GL^2/HL + GR^2/HR in float32, a number of the
    # size of G^2/H: gains closer than a few steps of that are one to it
    step = float(2.0 ** -23 * G * G / H)

    def split_of(tree, n):
        if n is None:
            return None
        f, thr = int(tree.split_feature_inner[n]), int(tree.threshold_in_bin[n])
        GL, HL, CL = (hist[f, k, :thr + 1].sum() for k in range(3))
        GR, HR = G - GL, H - HL
        return dict(feature=f, threshold_bin=thr,
                    gain_float64=float(GL * GL / HL + GR * GR / HR - G * G / H),
                    gain_the_side_recorded=float(tree.split_gain[n]),
                    node_value_the_side_recorded=float(tree.internal_value[n]),
                    left=[float(GL), float(HL), int(CL)],
                    packed=bool(binning["packed"][f]))

    sa, sb = split_of(A["models"][t], i), split_of(P["models"][t], j)
    # how crowded the top is: the best threshold of each feature, in float64
    per_feature = sorted(
        (reference_split(hist[f:f + 1], binning["num_bins"][f:f + 1],
                         min_data, min_hess)[1:] + (f,)
         for f in range(hist.shape[0])), key=lambda r: -r[1])[:5]
    # ancestors whose split feature is a bundle member: the side that took
    # the member's default bin got its totals as `totals less slots`
    zero_bin = [int(m.value_to_bin(np.zeros(1))[0]) for m in mappers]
    handed_down = [dict(depth=d, feature=k, threshold_bin=thr,
                        default_bin_side=bool((zero_bin[k] <= thr) == left))
                   for d, (k, thr, left) in enumerate(path)
                   if binning["packed"][k]]
    facts.update(rows_in_node=int(inside.sum()), totals=[float(G), float(H)],
                 bundled=sa, plain=sb,
                 float64_top5=[dict(feature=f, threshold_bin=t, gain_float64=g)
                               for t, g, f in per_feature],
                 ancestors_split_on_a_bundle_member=handed_down,
                 float32_step_of_the_compared_sum=step)
    if sa is None or sb is None:
        # one side stops here: the other's gain is what it did not see
        facts["gap"] = (sa or sb)["gain_float64"]
    else:
        facts["gap"] = abs(sa["gain_float64"] - sb["gain_float64"])
    facts["gap_in_steps"] = facts["gap"] / step
    facts["tie"] = bool(facts["gradients_equal_on_both_sides"]
                        and sa is not None and sb is not None
                        and facts["gap"] <= TIE_STEPS * step)
    return facts


def check_bundle(config, cell, params, rows: int, trees: int) -> list:
    got, X, binning = train_both(config, cell, params, rows, trees)
    a, b = got["bundled"], got["plain"]
    conflicts = a["conflicts"]
    diff = a["logloss"] - b["logloss"]
    same = first_departure(a["models"][0], b["models"][0]) is None
    cause, why = [], None
    if conflicts:
        cause.append(
            f"{conflicts} rows conflict inside a bundle and keep only the "
            "last member's bin: the two stores differ in them")
    elif abs(diff) > 1e-6:
        why = explain_departure(X, binning, got, params)
        if why is not None and why["tie"]:
            cause.append(
                f"tree {why['tree']} parts at depth {why['depth']} on two "
                f"splits whose float64 gains lie {why['gap']:.3g} apart, "
                f"{why['gap_in_steps']:.2f} float32 steps of the sum the "
                "search compares: a tie to float32, turned by the totals "
                "the two sides hand the node (the node values differ)")
    ok = bool(abs(diff) <= LOGLOSS_LIMIT and (
        conflicts > 0 or (same and (abs(diff) <= 1e-6 or bool(cause)))))
    say(check="bundled_against_plain", rows=rows, ok=ok,
        first_tree_node_for_node=same, trees=trees,
        bundled_logloss=a["logloss"], plain_logloss=b["logloss"], diff=diff,
        logloss_within_1e6=bool(abs(diff) <= 1e-6),
        logloss_limit=LOGLOSS_LIMIT,
        plain_one_tree_short_moves_it=b["one_tree_short"] - b["logloss"],
        conflict_rows=conflicts, first_departure=why, cause=cause)
    return [] if ok else [f"bundled_against_plain@{rows}"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", choices=("root", "bundle"), default="root")
    ap.add_argument("--rows", type=int, default=EXACT_ROWS)
    ap.add_argument("--trees", type=int, default=5)
    args = ap.parse_args(argv)
    from lightgbm_tpu.jaxutil import enable_compile_cache, require_accelerator
    dev = require_accelerator()
    enable_compile_cache()
    config, cell = load_cell(CELL)
    params = {**config["params"], **cell.get("params", {})}
    if args.check == "root":
        failed = check_root(config, cell, params)
    else:
        failed = check_bundle(config, cell, params, args.rows, args.trees)
    say(ok=not failed, failed=failed, device=dev)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
