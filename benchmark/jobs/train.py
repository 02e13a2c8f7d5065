"""The job `train`: boosting iterations of `lgb.Booster` on the cell's binned
training set, through the calls a user makes, with every selector the program
resolves itself left alone.

One run: the configuration's binned training set (bin, or load this
checkout's binary file) and `warmup_iters` updates — the set-up — then the
window: updates enqueued back to back with no fetch between them, closed by
one value fetch of the train score (`Booster.update()` stays pipelined one
tree ahead, as in `bench.py`).  The loop starts an update while the window has
time left, so the last ones end after it; the time per iteration is taken
over all of them, to the fetch.  With tracing on, the window is `trace_iters`
iterations under the profiler.  The device's memory is read as the window
closes.  Only then come the benchmark's own needs: the configuration's test
split and the quality on it of the model as it stood after exactly
`quality_iters` trees, the rows drawn from `--seed`, and the checks that
decide `correct` — so that neither their buffers nor their programs count as
the training job's.
"""
import glob
import os
import shutil
import time

import numpy as np

from benchmark.harness import dataset, device, trace, walk

# Device margins against the float64 numpy walk: the device sums float32
# leaf values (relative rounding 6e-8 per addition, margins of order 1; PR 24
# measured 2.7e-8 over five trees), and leaf values lie 1e-3 and more apart,
# so 1e-6 passes every rounding and fails every row sent to another leaf.
# It would also fail leaf values kept in bfloat16 (rounding 4e-3).
MARGIN_TOL = 1e-6


def _fetch(bst) -> float:
    """One value fetch of the train score: ends when the device has finished
    every update enqueued so far."""
    return float(bst._gbdt.train_score.score.sum())


def _learner_facts(bst) -> dict:
    lr = bst._gbdt.learner
    facts = {"learner": type(lr).__name__,
             "hist_rows": getattr(lr, "hist_rows", None),
             "hist_exchange": getattr(lr, "hist_exchange", None)}
    mesh = getattr(lr, "mesh", None)
    if mesh is not None:
        facts["mesh"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    bins = getattr(lr, "bins_dev", None)
    if bins is not None:
        facts.update(store_shape=list(bins.shape), store_dtype=str(bins.dtype),
                     store_devices=len(bins.sharding.device_set))
    facts["score_devices"] = len(
        bst._gbdt.train_score.score.sharding.device_set)
    return facts


def _counters() -> dict:
    from lightgbm_tpu import profiling
    return dict(profiling.counters("tree/"))


def _window(bst, ctx, trace_dir) -> dict:
    """The timed window.  Nothing in the loop waits for the device but
    `update()` itself, which collects the previous tree."""
    import jax
    tracing, limit = ctx["trace"], int(ctx["cell"]["trace_iters"])
    stamps, calls, raised = [], 0, 0
    if tracing:
        jax.profiler.start_trace(trace_dir)
    w0 = time.perf_counter()
    while (len(stamps) < limit if tracing
           else time.perf_counter() - w0 < ctx["seconds"]):
        calls += 1
        try:
            with jax.profiler.TraceAnnotation("bench.update",
                                              iter=len(stamps)):
                stop = bst.update()
        except Exception as e:          # counted, reported, and the run ends
            ctx["say"](phase="window", error=repr(e))
            raised += 1
            break
        stamps.append(time.perf_counter() - w0)
        if stop:                        # no leaf can split: nothing to time
            raised += 1
            break
    with jax.profiler.TraceAnnotation("bench.fetch"):
        total = _fetch(bst)
    window_s = time.perf_counter() - w0
    if tracing:
        jax.profiler.stop_trace()
    return {"stamps": stamps, "calls": calls, "raised": raised,
            "score_sum": total, "window_s": window_s}


def _checks(bst, cell, win, store, Xv, yv, raw_q, Xc) -> dict:
    """What decides `correct`, after the window: the losses on the test split
    `Xv`, and the device's predictions against the walk on it and on the
    run's own rows `Xc`."""
    text = bst.model_to_string()
    trees = walk.parse_model(text)
    sigmoid = float(text.split("sigmoid:")[1].split()[0]) \
        if "sigmoid:" in text else 1.0
    raw_end = bst.predict(Xv, raw_score=True)
    flips, err = walk.routing_flips(trees, Xv, raw_end, MARGIN_TOL)
    flips_c, err_c = walk.routing_flips(
        trees, Xc, bst.predict(Xc, raw_score=True), MARGIN_TOL)
    flips, err = flips + flips_c, max(err, err_c)
    loss_q = walk.logloss(yv, raw_q, sigmoid)
    loss_end = walk.logloss(yv, raw_end, sigmoid)
    iters = len(win["stamps"])
    grown = [t["num_leaves"] for t in trees[-iters:]] if iters else []
    checks = {
        "iterations_ran": bool(iters > 0 and win["raised"] == 0
                               and np.isfinite(win["score_sum"])),
        "logloss_fell": bool(np.isfinite(loss_end) and loss_end < loss_q),
        "predict_matches_walk": flips == 0 and err <= MARGIN_TOL,
        "trees_grew": bool(grown) and min(grown) > 1,
        "store_on_every_chip":
            store.get("store_devices", cell["chips"]) == cell["chips"],
    }
    facts = dict(valid_logloss_after_quality_iters=loss_q,
                 valid_logloss_after_window=loss_end, trees=len(trees),
                 leaves_in_window=grown, walk_max_abs_err=err,
                 walk_routing_flips=flips)
    return checks, facts


def run(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import profiling

    cell, config, say = ctx["cell"], ctx["config"], ctx["say"]
    compiles = ctx["compiles"]
    params = {**config["params"], **cell.get("params", {})}
    warmup, quality = int(cell["warmup_iters"]), int(cell["quality_iters"])
    if quality > warmup:
        raise SystemExit("benchmark: quality_iters exceeds warmup_iters")

    t0 = time.perf_counter()
    train, facts = dataset.binned_train_set(config, params)
    dataset_s = time.perf_counter() - t0
    say(phase="dataset", **facts)

    bst = lgb.Booster(params, train)
    say(phase="booster")
    for _ in range(warmup):
        bst.update()
    _fetch(bst)
    store = _learner_facts(bst)
    say(phase="warm", compile=compiles.snapshot(), **store)

    trace_dir = os.path.join(dataset.CACHE_DIR, "trace", ctx["name"])
    if ctx["trace"]:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    c0, m0 = _counters(), compiles.snapshot()
    # process start to here, less the runtime's attach to the chip (run.py)
    setup_s = time.perf_counter() - ctx["t_start"] - ctx["attach_s"]
    win = _window(bst, ctx, trace_dir)
    in_window, c1 = compiles.since(m0), _counters()
    peak = device.peak_bytes()
    iters = len(win["stamps"])
    say(phase="window", iters=iters, window_s=win["window_s"],
        update_returned_at_s=win["stamps"], train_score_sum=win["score_sum"],
        compile_in_window=in_window,
        memory={k: v for k, v in
                (jax.devices()[0].memory_stats() or {}).items()
                if isinstance(v, (int, float))})

    t0 = time.perf_counter()
    Xv, yv = dataset.test_split(config, int(cell["valid_rows"]))
    Xc, _ = dataset.check_rows(config, int(cell["check_rows"]), ctx["seed"])
    say(phase="held_out", test_rows=len(yv), check_rows=len(Xc),
        check_sum=float(Xc[:, 0].sum()), seconds=time.perf_counter() - t0)
    raw_q = bst.predict(Xv, raw_score=True, num_iteration=quality)
    valid_auc = walk.auc(yv, raw_q)
    checks, facts = _checks(bst, cell, win, store, Xv, yv, raw_q, Xc)
    say(phase="checks", **checks, **facts,
        hist_rows_downgrades=profiling.counter_value(
            profiling.HIST_ROWS_DOWNGRADES),
        sparse_fallbacks=profiling.counter_value(profiling.SPARSE_FALLBACKS))

    reduced = None
    if ctx["trace"]:
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if found:
            reduced = trace.load(found[0])
        shutil.rmtree(trace_dir, ignore_errors=True)

    finite = np.isfinite(win["score_sum"])
    return {
        "correct": all(checks.values()),
        "attempted": win["calls"],
        "failed": win["raised"] if finite else win["calls"],
        "iters": iters,
        "clock": {"setup_s": setup_s, "attach_s": ctx["attach_s"],
                  "dataset_s": dataset_s,
                  "window_s": win["window_s"],
                  "train_s_per_iter":
                      win["window_s"] / iters if iters else None},
        "counters": {k: c1.get(k, 0.0) - c0.get(k, 0.0) for k in c1},
        "compile": {"before_window": m0, "in_window": in_window},
        "memory": {"peak_bytes": peak},
        "quality": {"valid_auc": valid_auc},
        "store": {"columns": store.get("store_shape", [config["features"]])[0],
                  "bin_itemsize": np.dtype(
                      store.get("store_dtype", "int32")).itemsize,
                  "hist_itemsize": {"int8": 1, "bfloat16": 2}.get(
                      params.get("histogram_dtype", "float32"), 4)},
        "trace": reduced,
    }
