"""What the four-chip cell `criteo_tb.data4` trains, against plain references,
on the chip (needs a four-chip TPU host).

The benchmark's `correct` compares what the trained model *predicts* with a
plain walk.  This compares what the data-parallel learner *builds*, through
the objects the timed path uses (the cell's binned training set,
`lgb.Booster`, its learner's store as placed on the mesh):

`--check root` (the cell's full size, 54M x 67 over four chips; about four
minutes once the set is binned):

1. the root pass of tree 1 on every shard — `hist_multileaf_masked` under
   `shard_map` over the learner's sharded store, gradients quantised on
   the shard as `build_tree_rounds` does — against NumPy over the
   *dataset's own* host store: the same int8 levels summed per (column,
   bin) in int64 over the shard's real rows.  Each shard's kernel result
   has to be the float32 image of those sums, bit for bit (the kernel sums
   exact products in int32), padded rows adding nothing and the padded
   columns, which the launch leaves out, exact zeros; the
   shards' row counts add up to the configuration's rows;
2. the root split of tree 1 as the learner's build grew it across the
   mesh (feature, threshold bin) against the best split of the summed
   reference histogram, found in float64 by the textbook gain.

`--check shards --rows N` (N rows that one chip holds, 13,500,000 by
default; about five minutes):

3. `--trees` trees by the one-device learner and by `tree_learner=data` on
   the same binned rows, in one process: tree 1 node for node (features,
   threshold bins and counts equal; leaf values to rounding, since four
   partial sums meet in another order), and the logloss on the test split
   after all trees within 1e-6.

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
                                   reference_hist, reference_split, say,
                                   tree_of)

CELL = "criteo_tb.data4"


def shard_root_pass(learner, grad, hess, params):
    """-> ([shards, Fpad, 3, B] float32, [shards, 2] float32 scales): the
    root launch of every shard, as `build_tree_rounds` makes it: over the
    store's real columns, exact zeros for the padded ones."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked, quantize_gh
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    dtype = params["histogram_dtype"]

    def one(bins, g, h, mask):
        n = bins.shape[1]
        gh8 = (jnp.zeros((8, n), jnp.float32).at[0].set(g * mask)
               .at[1].set(h * mask).at[2].set(mask))
        ghq = quantize_gh(gh8) if dtype == "int8" else None
        out = hist_multileaf_masked(
            bins, jnp.zeros(n, jnp.int32), gh8, jnp.zeros(1, jnp.int32),
            num_bins_padded=learner.B, backend=backend, input_dtype=dtype,
            max_num_bin=int(learner.dataset.max_num_bin), ghq=ghq,
            real_columns=learner.Cstore)
        scales = (jnp.stack([ghq[1], ghq[2]]) if ghq is not None
                  else jnp.ones(2, jnp.float32))
        return out, scales[None]

    fn = jax.jit(jax.shard_map(
        one, mesh=learner.mesh, check_vma=False,
        in_specs=(P(None, "data"), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"))))
    mask, _ = learner._masks(None)
    hist, scales = fn(learner.bins_dev, learner._rows_in(grad),
                      learner._rows_in(hess), mask)
    return np.asarray(hist), np.asarray(scales)


def check_root(config, cell, params) -> list:
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset
    t0 = time.perf_counter()
    train, facts = dataset.binned_train_set(config, params)
    bst = lgb.Booster(params, train)
    learner = bst._gbdt.learner
    nsh = learner.dd * learner.df
    say(check="setup", dataset=facts["how"], learner=type(learner).__name__,
        hist_exchange=learner.hist_exchange, shards=nsh,
        store=list(learner.bins_dev.shape),
        store_dtype=str(learner.bins_dev.dtype),
        seconds=time.perf_counter() - t0)
    if learner.mesh is None or learner.df != 1:
        raise SystemExit("the check is written for a data-parallel mesh")
    failed = []
    grad, hess = (a.reshape(-1) for a in bst._gbdt.boosting_gradients())
    N, B, F = learner.N, learner.B, learner.Cstore
    per = learner.Np // nsh                      # a shard's rows, padded
    path, scales = shard_root_pass(learner, grad, hess, params)
    store = learner.dataset.dense_bins()         # [F, N] on the host
    g_np, h_np = np.asarray(grad), np.asarray(hess)
    t0 = time.perf_counter()
    total = np.zeros((F, 3, B), np.int64)
    off, rows, worst = 0, 0, 0.0
    for s in range(nsh):
        lo, hi = min(s * per, N), min((s + 1) * per, N)
        gq, sg = quantize(g_np[lo:hi])
        hq, sh = quantize(h_np[lo:hi])
        ref = reference_hist(store[:, lo:hi], gq, hq, B)
        scale = np.array([sg, sh, np.float32(1)], np.float32)[None, :, None]
        assert (sg, sh) == tuple(scales[s]), (s, sg, sh, scales[s])
        mine = path[s, :F]
        off += int((mine != ref.astype(np.float32) * scale).sum())
        off += int(np.count_nonzero(path[s, F:]))   # padded columns: zeros
        worst = max(worst, float(np.abs(
            np.rint(mine.astype(np.float64) / scale) - ref).max()))
        rows += int(ref[0, 2].sum())
        # the shards' int8 levels share one scale at tree 1 (every shard
        # holds a click): the summed reference is in those units
        total += ref
    hist_ok = off == 0 and rows == N == int(config["rows"])
    say(check="root_histogram", ok=hist_ok, shards=nsh,
        cells=int(total.size) * nsh, rows=rows, cells_off=off,
        max_off_units=worst, largest_sum=int(np.abs(total).max()),
        scales=scales.tolist(), reference_seconds=time.perf_counter() - t0)
    if not hist_ok:
        failed.append("root_histogram")

    tree = tree_of(learner, grad, hess)
    scale = np.array([scales[0, 0], scales[0, 1], 1.0])[None, :, None]
    want = reference_split(
        total.astype(np.float64) * scale,
        np.asarray(learner.num_bins_dev)[:F], int(params["min_data_in_leaf"]),
        float(params["min_sum_hessian_in_leaf"]))
    got = (int(tree.split_feature[0]), int(tree.threshold_bin[0]))
    split_ok = got == want[:2] and len(set(map(tuple, scales))) == 1
    say(check="root_split", ok=split_ok, path=got, reference=want[:2],
        reference_gain=want[2], leaves=int(tree.num_leaves))
    if not split_ok:
        failed.append("root_split")
    return failed


def check_shards(config, cell, params, rows: int, trees: int) -> list:
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset, walk
    t0 = time.perf_counter()
    config = dict(config, rows=rows)
    train, facts = dataset.binned_train_set(config, params)
    Xv, yv = dataset.test_split(config, int(cell["valid_rows"]))
    say(check="setup", dataset=facts["how"], rows=rows,
        seconds=time.perf_counter() - t0)
    got = {}
    for name, extra in (("one", {"tree_learner": "serial"}),
                        ("four", {"tree_learner": "data"})):
        t0 = time.perf_counter()
        bst = lgb.Booster({**params, **extra}, train)
        for _ in range(trees):
            bst.update()
        bst._gbdt._flush_pending()
        lr = bst._gbdt.learner
        raw = bst.predict(Xv, raw_score=True)
        got[name] = (bst._gbdt.models, walk.logloss(yv, raw, 1.0), raw)
        say(check="trained", side=name, learner=type(lr).__name__,
            store=list(lr.bins_dev.shape),
            devices=len(lr.bins_dev.sharding.device_set),
            leaves=[int(t.num_leaves) for t in bst._gbdt.models],
            valid_logloss=got[name][1], seconds=time.perf_counter() - t0)
        del bst
    failed = []
    a, b = got["one"][0][0], got["four"][0][0]
    n = a.num_leaves
    same = bool(
        n == b.num_leaves
        and all(np.array_equal(np.asarray(getattr(a, f))[:k],
                               np.asarray(getattr(b, f))[:k])
                for f, k in (("split_feature_inner", n - 1),
                             ("threshold_in_bin", n - 1),
                             ("left_child", n - 1), ("right_child", n - 1),
                             ("leaf_count", n))))
    lv = float(np.abs(np.asarray(a.leaf_value)[:n]
                      - np.asarray(b.leaf_value)[:b.num_leaves][:n]).max()
               ) if same else float("nan")
    say(check="first_tree", ok=same, leaves=[int(n), int(b.num_leaves)],
        max_abs_leaf_value_diff=lv)
    if not same:
        failed.append("first_tree")
    la, lb = got["one"][1], got["four"][1]
    loss_ok = bool(abs(la - lb) <= 1e-6)
    say(check="logloss_after_trees", ok=loss_ok, trees=trees, one=la, four=lb,
        diff=la - lb,
        max_abs_margin_diff=float(np.abs(got["one"][2]
                                         - got["four"][2]).max()),
        leaves_equal=[int(x.num_leaves) == int(y.num_leaves)
                      for x, y in zip(got["one"][0], got["four"][0])])
    if not loss_ok:
        failed.append("logloss_after_trees")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", choices=("root", "shards"), default="root")
    ap.add_argument("--rows", type=int, default=13_500_000)
    ap.add_argument("--trees", type=int, default=5)
    args = ap.parse_args(argv)
    from lightgbm_tpu.jaxutil import enable_compile_cache, require_accelerator
    dev = require_accelerator()
    enable_compile_cache()
    config, cell = load_cell(CELL)
    params = {**config["params"], **cell.get("params", {})}
    if dev["count"] < int(cell["chips"]):
        raise SystemExit(f"the check needs {cell['chips']} chips")
    if args.check == "root":
        failed = check_root(config, cell, params)
    else:
        del params["tree_learner"]
        failed = check_shards(config, cell, params, args.rows, args.trees)
    say(ok=not failed, failed=failed, device=dev)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
