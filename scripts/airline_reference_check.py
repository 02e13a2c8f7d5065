"""What the cell `airline.full` trains, against the plain reference
(`benchmark/harness/split_reference.py`), on the chip (one TPU chip).

The benchmark's `correct` compares what the trained model *predicts* with a
plain walk, raw value against the model text's thresholds.  This compares
what the learner *builds* from its int8-stored bins, and what the training
score holds, through the objects the timed path uses (the cell's binned
training set, `lgb.Booster`, its learner's device store and build).

`--check root` (the cell's full size; the store has more than 2^31
elements on one device):

1. the device store: every stored bin is the dataset's bin less 128, every
   padded cell -128;
2. the root launch of `hist_multileaf_masked` over the whole store, with
   the operands and the real column count `build_tree_rounds` gives it
   (the store's 19 padded columns have to come back exact zeros).  Above `INT8_EXACT_ROWS` rows
   a device the kernel takes bfloat16 operands in place of int8 ones (an
   int32 sum could overflow: `ops/histogram.hist_multileaf_masked`), and
   its sums are float32 sums of bfloat16 values; they are held to the
   float64 sums of the same bfloat16 values within the rounding of a
   float32 sum over the kernel's row chunks (`F32_ROUNDING`), and their
   counts to the int64 counts exactly wherever those are below 2^24;
3. the same launch over the first `INT8_EXACT_ROWS` rows of the store
   (a slice of the learner's device array, the rows a chunk multiple), where
   the operands are int8: equal cell for cell to the float32 image of
   `split_reference.histogram`'s int64 sums;
4. the root split of tree 1 as the learner's build grew it (feature,
   threshold bin; a categorical feature's threshold is one category)
   against `split_reference.best_split` of the reference histogram of
   step 2, over numerical `<=` and categorical `==` candidates: the same
   feature and bin, or a float64 gain within four float32 steps.  The
   line also gives that build's launches and the padded store columns
   they left out (`tree/hist_passes`, `tree/hist_pad_columns`).

`--check layout --rows N`: `--trees` trees with the int8-stored layout and
with the int32 one (`LGBT_BINS_INT8=0`) on the same first N rows.  Where
the operands are int8 (N at most `INT8_EXACT_ROWS`) both layouts sum the
same integers exactly, so the trees have to agree node for node; above it
the two sum bfloat16 values in float32 in another order (row chunks of
2,048 against 8,192), and the check holds them to the logloss on the test
split within `LOGLOSS_LIMIT` and prints the first node at which they part.

`--check routing`: after `--trees` trees, the training score on the
device (`train_score`, built by leaf ids from bins) against
`Booster.predict(raw_score=True)` (raw values against the model text) on
the first 1,000,000 training rows and on every training row whose Origin
or Dest the binning did not keep (they share the `other_bin`): within
1e-6.  A category the binning dropped is a bin of its own that no split
takes as its threshold (`BinMapper.other_bin`), so both send its rows
right of every categorical split.

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

from higgs_reference_check import load_cell, say, tree_of   # noqa: E402
from lightgbm_tpu.ops.histogram import INT8_EXACT_ROWS      # noqa: E402

CELL = "airline.full"
# |float32 sum - float64 sum| of C values accumulated by the kernel: a
# row chunk's partial sum, then the partials one after another, each add
# rounding by at most 2^-24 of the running magnitude
F32_ROUNDING = 2.0 ** -24
LOGLOSS_LIMIT = 1e-4
TIE_STEPS = 4.0
ROUTING_TOL = 1e-6
ROUTING_ROWS = 1_000_000


def bf16(v: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 -> float32, round to nearest even."""
    u = np.ascontiguousarray(v, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def root_launch(learner, grad, hess, dtype, rows=None):
    """([F, 3, B] float32, padding zero): the root launch over the
    learner's store (its first `rows` rows, when given) with the operands
    and the real column count build_tree_rounds hands it — the dataset's
    store columns, and whether the launch handed back exact zeros for
    every padded column past them.  One program, as in the build: the
    gradient block is one [8, rows] buffer (3.7 GB at the cell's rows),
    and the program is dropped after the launch so that its temporaries
    stay reserved no longer."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked, int8_operands
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    n = learner.Np if rows is None else int(rows)

    def launch(bins, g, h, m):
        bins, g, h, m = bins[:, :n], g[:n], h[:n], m[:n]
        gh8 = (jnp.zeros((8, n), jnp.float32).at[0].set(g * m)
               .at[1].set(h * m).at[2].set(m))
        ghq = int8_operands(gh8) if dtype == "int8" else None
        return hist_multileaf_masked(
            bins, jnp.zeros(n, jnp.int32), gh8, jnp.zeros(1, jnp.int32),
            num_bins_padded=learner.B, backend=backend, input_dtype=dtype,
            max_num_bin=int(learner.dataset.max_num_bin), ghq=ghq,
            real_columns=learner.Cstore)[0]
    mask, _ = learner._masks(None)
    out = np.asarray(jax.jit(launch)(learner.bins_dev, learner._rows_in(grad),
                                     learner._rows_in(hess), mask))
    jax.clear_caches()
    return out[:learner.Cstore], bool(not out[learner.Cstore:].any())


def float_histogram(store, g, h, B):
    """[F, 3, B] float64 sums of g, h and 1, and [F, 3, B] sums of |g|,
    |h|, 1, per store column and bin."""
    from benchmark.harness.split_reference import BLOCK
    F, N = store.shape
    out = np.zeros((F, 3, B))
    mag = np.zeros((F, 3, B))
    for lo in range(0, N, BLOCK):
        hi = min(N, lo + BLOCK)
        w = (g[lo:hi].astype(np.float64), h[lo:hi].astype(np.float64))
        for f in range(F):
            b = store[f, lo:hi]
            for k in range(2):
                out[f, k] += np.bincount(b, w[k], B)
                mag[f, k] += np.bincount(b, np.abs(w[k]), B)
            out[f, 2] += np.bincount(b, minlength=B)
    mag[:, 2] = out[:, 2]
    return out, mag


def setup(config, params):
    import lightgbm_tpu as lgb
    from benchmark.harness import dataset
    t0 = time.perf_counter()
    train, facts = dataset.binned_train_set(config, params)
    bst = lgb.Booster(params, train)
    learner, ds = bst._gbdt.learner, train._inner
    say(check="setup", dataset=facts, learner=type(learner).__name__,
        store=list(learner.bins_dev.shape),
        store_dtype=str(learner.bins_dev.dtype),
        store_devices=len(learner.bins_dev.sharding.device_set),
        rows=int(ds.num_data), store_columns=int(ds.num_store_columns),
        categorical=[int(ds.used_features[k]) for k in
                     np.flatnonzero(ds.is_categorical)],
        other_bin=[int(i) for i in ds.used_features
                   if ds.mappers[i].other_bin],
        max_num_bin=int(ds.max_num_bin),
        seconds=time.perf_counter() - t0)
    return bst, learner, ds


def check_root(config, cell, params) -> list:
    import jax
    from benchmark.harness import split_reference as ref
    from lightgbm_tpu import profiling
    from lightgbm_tpu.ops.histogram import _masked_layout, store_alignment
    bst, learner, ds = setup(config, params)
    failed = []
    N, C, B = learner.N, learner.Cstore, learner.B
    want = ds.dense_bins()                                # [C, N] uint8

    # -- 1: the store --------------------------------------------------------
    dev = np.asarray(learner.bins_dev)
    int8 = dev.dtype == np.int8
    off = 128 if int8 else 0
    real = dev[:C, :N].astype(np.int16) + off
    pad_ok = bool((dev[C:] == -off).all() and (dev[:, N:] == -off).all())
    store_ok = bool(int8 and np.array_equal(real, want) and pad_ok)
    say(check="store", ok=store_ok, dtype=str(dev.dtype),
        shape=list(dev.shape), elements=int(dev.size),
        cells_off=int((real != want).sum()), padding_ok=pad_ok)
    if not store_ok:
        failed.append("store")
    del dev, real

    # -- 2: the root launch over every row -----------------------------------
    grad, hess = (a.reshape(-1) for a in bst._gbdt.boosting_gradients())
    g_np, h_np = np.asarray(grad)[:N], np.asarray(hess)[:N]
    dtype = params["histogram_dtype"]
    # tree 1 as the build grows it (step 4), before any other program
    # holds the chip's memory beside the build's
    tree = tree_of(learner, grad, hess)
    jax.clear_caches()
    path, pad_zero = root_launch(learner, grad, hess, dtype)
    full_int8 = dtype == "int8" and learner.Np <= INT8_EXACT_ROWS
    t0 = time.perf_counter()
    if full_int8:
        gq, sg = ref.quantize(g_np)
        hq, sh = ref.quantize(h_np)
        exact = ref.histogram(want, gq, hq, B)
        scale = np.array([sg, sh, 1.0], np.float32)[None, :, None]
        seen = exact.astype(np.float64) * scale.astype(np.float64)
        hist_ok = bool(np.array_equal(path, exact.astype(np.float32) * scale))
        facts = dict(operands="int8",
                     cells_off=int((path != exact.astype(np.float32)
                                    * scale).sum()))
    else:
        seen, mag = float_histogram(want, bf16(g_np), bf16(h_np), B)
        Ck = _masked_layout(learner.Fpad, learner.Np, 1, 1 if int8 else 4,
                            B, "bfloat16", int(ds.max_num_bin)).Ck
        bound = F32_ROUNDING * (learner.Np / Ck + Ck) * mag
        err = np.abs(path.astype(np.float64) - seen)
        small = seen[:, 2] < 2 ** 24
        counts_ok = bool(np.array_equal(path[:, 2][small], seen[:, 2][small]))
        hist_ok = bool((err <= bound).all() and counts_ok)
        rel = err / np.maximum(mag, 1e-300)
        facts = dict(operands="bfloat16 (above the int8 bound)",
                     row_chunk=int(Ck), cells_over_bound=int(
                         (err > bound).sum()),
                     counts_below_2_24_exact=counts_ok,
                     max_err_over_abs_sum=float(rel.max()),
                     bound_over_abs_sum=float(bound.max() / max(
                         mag.max(), 1e-300)))
    hist_ok = hist_ok and pad_zero
    say(check="root_histogram", ok=hist_ok, rows=int(seen[0, 2].sum()),
        cells=int(seen.size), reference_seconds=time.perf_counter() - t0,
        padded_columns=learner.Fpad - C, padded_columns_zero=pad_zero,
        **facts)
    if not hist_ok:
        failed.append("root_histogram")

    # -- 3: int8 operands over the first rows, exact -------------------------
    if not full_int8 and dtype == "int8":
        _, row = store_alignment(1 if int8 else 4, B, dtype,
                                 int(ds.max_num_bin))
        P = INT8_EXACT_ROWS // row * row
        part, part_pad_zero = root_launch(learner, grad, hess, dtype, rows=P)
        gq, sg = ref.quantize(g_np[:P])
        hq, sh = ref.quantize(h_np[:P])
        exact = ref.histogram(want[:, :P], gq, hq, B)
        image = exact.astype(np.float32) * np.array(
            [sg, sh, 1.0], np.float32)[None, :, None]
        prefix_ok = bool(np.array_equal(part, image)) and part_pad_zero
        say(check="root_histogram_int8_rows", ok=prefix_ok, rows=P,
            cells_off=int((part != image).sum()),
            padded_columns_zero=part_pad_zero,
            largest_sum=int(np.abs(exact).max()))
        if not prefix_ok:
            failed.append("root_histogram_int8_rows")

    # -- 4: the root split ---------------------------------------------------
    is_cat = np.asarray(ds.is_categorical)
    want_split = ref.best_split(seen, ds.split_num_bins, is_cat,
                                int(params["min_data_in_leaf"]),
                                float(params["min_sum_hessian_in_leaf"]))
    got = (int(tree.split_feature[0]), int(tree.threshold_bin[0]))
    G, H = float(seen[0, 0].sum()), float(seen[0, 1].sum())
    step = 2.0 ** -23 * G * G / H
    gain_path = ref.gain_of(seen, got[0], got[1], bool(is_cat[got[0]]))
    split_ok = bool(got == want_split[:2] or (
        0 <= want_split[2] - gain_path <= TIE_STEPS * step))
    say(check="root_split", ok=split_ok, path=got,
        reference=want_split[:2], same=got == want_split[:2],
        categorical=bool(is_cat[got[0]]),
        raw_column=int(ds.used_features[got[0]]),
        reference_gain=want_split[2], gain_of_path_in_float64=gain_path,
        float32_step_of_the_compared_sum=step, leaves=int(tree.num_leaves),
        hist_passes=profiling.counter_value(profiling.HIST_PASSES),
        hist_pad_columns=profiling.counter_value(profiling.HIST_PAD_COLUMNS))
    if not split_ok:
        failed.append("root_split")
    return failed


def train_trees(params, X, y, trees, int8_bins):
    """`trees` trees on (X, y) with the layout forced: -> (models, the
    store's dtype, logloss on the test split later)."""
    import lightgbm_tpu as lgb
    os.environ["LGBT_BINS_INT8"] = "1" if int8_bins else "0"
    try:
        ds = lgb.Dataset(X, y).construct(params)
        bst = lgb.Booster(params, ds)
        for _ in range(trees):
            bst.update()
        bst._gbdt._flush_pending()
        dtype = str(bst._gbdt.learner.bins_dev.dtype)
    finally:
        os.environ.pop("LGBT_BINS_INT8", None)
    return bst, dtype


def check_layout(config, cell, params, rows: int, trees: int) -> list:
    from benchmark.harness import dataset, walk
    from allstate_reference_check import first_departure
    X, y = dataset.generate(config, rows, (int(config["data_seed"]),
                                           dataset.TRAIN))
    Xv, yv = dataset.test_split(config, 200_000)
    got = {}
    for side, int8_bins in (("int8", True), ("int32", False)):
        t0 = time.perf_counter()
        bst, dtype = train_trees(params, X, y, trees, int8_bins)
        got[side] = dict(models=bst._gbdt.models, dtype=dtype,
                         rows_padded=int(bst._gbdt.learner.Np),
                         logloss=walk.logloss(
                             yv, bst.predict(Xv, raw_score=True), 1.0))
        say(check="trained", side=side, rows=rows, store_dtype=dtype,
            store=list(bst._gbdt.learner.bins_dev.shape),
            leaves=[int(t.num_leaves) for t in bst._gbdt.models],
            valid_logloss=got[side]["logloss"],
            seconds=time.perf_counter() - t0)
        del bst
        import gc
        gc.collect()
    a, b = got["int8"], got["int32"]
    parts = [(i, first_departure(ta, tb))
             for i, (ta, tb) in enumerate(zip(a["models"], b["models"]))]
    first = next(((i, w) for i, w in parts if w is not None), None)
    diff = a["logloss"] - b["logloss"]
    # int8 operands where each layout's padded rows stay within the bound
    exact = (params["histogram_dtype"] == "int8" and max(
        a["rows_padded"], b["rows_padded"]) <= INT8_EXACT_ROWS)
    ok = bool(a["dtype"] == "int8" and b["dtype"] == "int32" and (
        first is None if exact else abs(diff) <= LOGLOSS_LIMIT))
    say(check="int8_against_int32", ok=ok, rows=rows, trees=trees,
        operands="int8, exact sums" if exact else "bfloat16",
        rows_padded=[a["rows_padded"], b["rows_padded"]],
        node_for_node=first is None,
        first_departure=None if first is None else dict(
            tree=first[0] + 1, depth=len(first[1][0]),
            path=[list(s) for s in first[1][0]]),
        int8_logloss=a["logloss"], int32_logloss=b["logloss"], diff=diff)
    return [] if ok else [f"int8_against_int32@{rows}"]


def check_routing(config, cell, params, trees: int) -> list:
    from benchmark.harness import dataset
    bst, learner, ds = setup(config, params)
    for _ in range(trees):
        bst.update()
    gbdt = bst._gbdt
    gbdt._flush_pending()
    score = np.asarray(gbdt.train_score.score).reshape(-1)[:ds.num_data]
    X, _ = dataset.generate(config, int(config["rows"]),
                            (int(config["data_seed"]), dataset.TRAIN))
    names = ("Origin", "Dest")
    cols = [9, 10]
    dropped = np.zeros(len(X), bool)
    per = {}
    for name, j in zip(names, cols):
        m = ds.mappers[j]
        kept = np.isin(X[:, j].astype(np.int64), m.bin_2_categorical)
        per[name] = dict(other_bin=bool(m.other_bin), kept=len(
            m.bin_2_categorical), rows_dropped=int((~kept).sum()))
        dropped |= ~kept
    sel = np.flatnonzero(dropped)
    first = np.arange(min(ROUTING_ROWS, len(X)))
    t0 = time.perf_counter()
    err_first = np.abs(bst.predict(X[first], raw_score=True)
                       - score[first]).max()
    err_dropped = (np.abs(bst.predict(X[sel], raw_score=True)
                          - score[sel]).max() if sel.size else 0.0)
    cat_splits = []
    for t in gbdt.models:
        k = t.num_leaves - 1
        dt = np.asarray(t.decision_type[:k])
        sf = np.asarray(t.split_feature[:k])
        cat_splits.append(dict(categorical=int((dt == 1).sum()), airports=int(
            ((dt == 1) & np.isin(sf, cols)).sum())))
    ok = bool(err_first <= ROUTING_TOL and err_dropped <= ROUTING_TOL
              and sel.size > 0)
    say(check="routing", ok=ok, trees=trees, rows_first=int(first.size),
        max_err_first=float(err_first), rows_with_a_dropped_airport=int(
            sel.size), max_err_dropped=float(err_dropped), columns=per,
        splits=cat_splits, seconds=time.perf_counter() - t0)
    return [] if ok else ["routing"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", choices=("root", "layout", "routing"),
                    default="root")
    # the most rows that both layouts pad (to 8,192 and 2,048 rows) within
    # the int8 bound: 1,953 x 8,192
    ap.add_argument("--rows", type=int,
                    default=INT8_EXACT_ROWS // 8192 * 8192)
    ap.add_argument("--trees", type=int, default=5)
    args = ap.parse_args(argv)
    from lightgbm_tpu.jaxutil import enable_compile_cache, require_accelerator
    dev = require_accelerator()
    enable_compile_cache()
    config, cell = load_cell(CELL)
    params = {**config["params"], **cell.get("params", {})}
    if args.check == "root":
        failed = check_root(config, cell, params)
    elif args.check == "layout":
        failed = check_layout(config, cell, params, args.rows, args.trees)
    else:
        failed = check_routing(config, cell, params, args.trees)
    say(ok=not failed, failed=failed, device=dev)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
