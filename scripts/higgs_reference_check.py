"""What the timed path trains at Higgs 10.5M x 28, against a plain
reference, on the chip (needs a TPU; about six minutes cold).

The benchmark's `correct` compares what the trained model *predicts* with
a plain walk.  This compares what the cell `higgs.full` *trains*, at the
timed size and through the objects the timed path uses (the cell's binned
training set, `lgb.Booster`, its learner's own build program):

1. the root pass of tree 1 — `hist_multileaf_masked` over the learner's
   device store with the arguments `build_tree_rounds` gives it, the real
   column count included (the store's padded columns have to come back
   exact zeros) — against
   NumPy: the same int8-quantised gradient and hessian values
   (`ops/histogram.quantize_gh`, redone in NumPy float32), summed per
   (column, bin) in int64 over the binned store in blocks of a million
   rows.  The kernel sums exact products in int32, so every cell has to
   be equal to the unit;
2. the root split of tree 1 as the learner's build grew it (feature,
   threshold bin) against the best split of the reference histogram,
   found in float64 by the textbook gain.

With `--auc-trees N` it makes one other check instead, the one that needs
many trees, on the cell `--cell` names (`higgs.full` unless given):

3. the AUC on the cell's test split after `quality_iters` and after N
   trees.  From tree 2 on the int8 path quantises a pass by the largest
   gradient of all rows, so the sums carry rounding the first tree does
   not have; the model has to go on learning through it: the AUC after N
   trees is above the one after `quality_iters`.

One JSON line per check, `{"ok": ...}` last; exit code 1 if any failed.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "higgs.full"
BLOCK = 1_000_000


def say(**facts):
    print(json.dumps(facts, default=float), flush=True)


def quantize(v: np.ndarray):
    """`quantize_gh` for one row of values, in NumPy float32."""
    scale = np.maximum(np.max(np.abs(v)), np.float32(1e-30)) / np.float32(127)
    return np.round(v / scale).astype(np.int64), np.float32(scale)


def reference_hist(store: np.ndarray, gq, hq, num_bins_padded: int):
    """[F, 3, B] int64 sums of (grad, hess, count) per store column and
    bin, block by block."""
    F, N = store.shape
    out = np.zeros((F, 3, num_bins_padded), np.int64)
    for lo in range(0, N, BLOCK):
        hi = min(N, lo + BLOCK)
        w = (gq[lo:hi].astype(np.float64), hq[lo:hi].astype(np.float64))
        for f in range(F):
            b = store[f, lo:hi]
            # float64 weights hold these integers exactly (sums < 2^53)
            out[f, 0] += np.bincount(b, w[0], num_bins_padded).astype(np.int64)
            out[f, 1] += np.bincount(b, w[1], num_bins_padded).astype(np.int64)
            out[f, 2] += np.bincount(b, minlength=num_bins_padded)
    return out


def reference_split(hist, num_bins, min_data: int, min_hess: float):
    """Best (feature, threshold bin, gain) of a [F, 3, B] float64
    histogram: left = bins <= t, gain = GL^2/HL + GR^2/HR - G^2/H, over
    thresholds that leave both sides min_data rows and min_hess hessian;
    first maximum in (feature, bin) order, as a flat argmax takes it."""
    G, H, C = (hist[0, k].sum() for k in range(3))
    best = (-1, -1, -np.inf)
    for f in range(hist.shape[0]):
        nb = int(num_bins[f])
        if nb < 2:
            continue
        GL, HL, CL = (np.cumsum(hist[f, k, :nb - 1]) for k in range(3))
        GR, HR, CR = G - GL, H - HL, C - CL
        ok = ((CL >= min_data) & (CR >= min_data)
              & (HL >= min_hess) & (HR >= min_hess))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, GL * GL / HL + GR * GR / HR - G * G / H,
                            -np.inf)
        t = int(np.argmax(gain))
        if gain[t] > best[2]:
            best = (f, t, float(gain[t]))
    return best


def tree_of(learner, grad, hess):
    """Tree 1 as the learner's own build program grows it (TreeArrays
    on the host)."""
    import jax
    _, _, arrs = learner.train_device(grad, hess, None, None)
    return jax.device_get(arrs)


def load_cell(name=CELL):
    from benchmark.run import load_json
    cell = load_json("workloads", name + ".json")
    return load_json("configs", cell["config"] + ".json"), cell


def auc_after(params, cell, config, train, trees: int) -> bool:
    """Check 3: `trees` iterations, the AUC on the test split after
    `quality_iters` trees and after all of them."""
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset, walk
    Xv, yv = dataset.test_split(config, int(cell["valid_rows"]))
    early = int(cell["quality_iters"])

    def wait(bst):
        """One value fetch: the device has finished every update."""
        return float(bst._gbdt.train_score.score.sum())

    bst = lgb.Booster(params, train)
    bst.update()                    # compiles; timed from tree 2 on
    wait(bst)
    t0 = time.perf_counter()
    for _ in range(trees - 1):
        bst.update()
    wait(bst)
    s_per_iter = (time.perf_counter() - t0) / max(trees - 1, 1)
    aucs = [walk.auc(yv, bst.predict(Xv, raw_score=True, num_iteration=n))
            for n in (early, trees)]
    ok = bool(np.isfinite(aucs).all() and aucs[1] > aucs[0])
    say(check="auc_after_trees", ok=ok, trees=[early, trees], valid_auc=aucs,
        s_per_iter=s_per_iter)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--auc-trees", type=int, default=0,
                    help="run check 3 alone, over this many trees")
    ap.add_argument("--cell", default=CELL,
                    help="the cell whose job --auc-trees trains")
    args = ap.parse_args(argv)
    from benchmark.harness import dataset
    from lightgbm_tpu.jaxutil import enable_compile_cache, require_accelerator
    dev = require_accelerator()
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked

    config, cell = load_cell(args.cell if args.auc_trees else CELL)
    params = {**config["params"], **cell.get("params", {})}
    t0 = time.perf_counter()
    train, facts = dataset.binned_train_set(config, params)
    if args.auc_trees:
        ok = auc_after(params, cell, config, train, args.auc_trees)
        say(ok=ok, failed=[] if ok else ["auc_after_trees"], device=dev)
        return 0 if ok else 1
    bst = lgb.Booster(params, train)
    learner = bst._gbdt.learner
    say(check="setup", device=dev, dataset=facts["how"],
        learner=type(learner).__name__,
        store=list(learner.bins_dev.shape), seconds=time.perf_counter() - t0)
    failed = []

    # -- 1: the root histogram ----------------------------------------------
    grad, hess = (a.reshape(-1) for a in bst._gbdt.boosting_gradients())
    N, B, F = learner.N, learner.B, learner.Cstore
    if learner.mesh is not None:
        raise SystemExit("the check is written for one device")
    # as RoundsTreeLearner resolves it
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    # the learner's store is laid out to the kernel's tiles: its padded
    # rows carry row_mask 0, its padded columns are launched for nothing
    # and have to come back exact zeros
    rows = learner._rows_in
    gh8 = (jnp.zeros((8, learner.Np), jnp.float32).at[0].set(rows(grad))
           .at[1].set(rows(hess)).at[2].set(jnp.asarray(learner._row_mask)))
    out = np.asarray(hist_multileaf_masked(
        learner.bins_dev, jnp.zeros(learner.Np, jnp.int32), gh8,
        jnp.zeros(1, jnp.int32), num_bins_padded=B, backend=backend,
        input_dtype=params["histogram_dtype"],
        max_num_bin=int(learner.dataset.max_num_bin), real_columns=F))[0]
    path, pad_zero = out[:F], bool(not out[F:].any())      # [F, 3, B]
    store = np.asarray(learner.bins_dev)[:F, :N]
    g_np, h_np = np.asarray(grad)[:N], np.asarray(hess)[:N]
    gq, sg = quantize(g_np)
    hq, sh = quantize(h_np)
    t0 = time.perf_counter()
    ref = reference_hist(store, gq, hq, B)
    scale = np.array([sg, sh, np.float32(1)], np.float32)[None, :, None]
    # the kernel's own last step: int32 sums to float32, times the scale
    ref_f32 = ref.astype(np.float32) * scale
    # (bitwise equality of that is the check; back in units it reads 0
    # wherever a sum is under 2^24, which float32 holds exactly)
    units = np.abs(np.rint(path.astype(np.float64) / scale) - ref)
    hist_ok = bool(np.array_equal(path, ref_f32)) and pad_zero
    say(check="root_histogram", ok=hist_ok, cells=int(ref.size),
        padded_columns=int(out.shape[0] - F), padded_columns_zero=pad_zero,
        rows=int(ref[0, 2].sum()), largest_sum=int(np.abs(ref).max()),
        cells_off=int((path != ref_f32).sum()), max_off_units=float(units.max()),
        grad_levels=np.unique(gq).tolist(), hess_levels=np.unique(hq).tolist(),
        reference_seconds=time.perf_counter() - t0)
    if not hist_ok:
        failed.append("root_histogram")

    # -- 2: the root split --------------------------------------------------
    tree = tree_of(learner, grad, hess)
    want = reference_split(
        ref.astype(np.float64) * scale.astype(np.float64),
        np.asarray(learner.num_bins_dev)[:F], int(params["min_data_in_leaf"]),
        float(params["min_sum_hessian_in_leaf"]))
    got = (int(tree.split_feature[0]), int(tree.threshold_bin[0]))
    split_ok = got == want[:2]
    say(check="root_split", ok=split_ok, path=got, reference=want[:2],
        reference_gain=want[2], leaves=int(tree.num_leaves))
    if not split_ok:
        failed.append("root_split")

    say(ok=not failed, failed=failed)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
