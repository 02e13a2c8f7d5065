"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the north-star model (BASELINE.md): synthetic Higgs
10.5M x 28 from `bench.synth_higgs(seed)`, 255 leaves, 255 bins,
`lgb.Dataset` -> `lgb.train` for a few iterations with the default
`tree_growth` (rounds learner, Pallas kernels), once with the stock
`histogram_dtype` and once with `int8`; then
`Booster.predict` on the device and an in-process `PredictionServer`
answering a few `POST /predict` requests, both compared with the host
walk predictor (numpy, no JAX) on a row sample.

It fails — another exit code than 0, and no result line — when JAX finds
no TPU, when a phase raises, when the lowered tree-build step holds no
`tpu_custom_call`, or when a fallback counter moved.  The times it prints
are a smoke's, not a benchmark's: one run, compilation beside them.

    python chip_smoke.py              one chip, the whole main path
    python chip_smoke.py --chips 4    four chips, and only what exists
                                      across chips: data-parallel training
                                      against the one-chip learner, and
                                      four serving replicas

The last line of standard output is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
import argparse
import http.client
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

PARAMS = {
    "objective": "binary", "metric": "binary_logloss", "verbose": -1,
    "num_leaves": 255, "max_bin": 255, "learning_rate": 0.1,
    "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
}
SAMPLE_ROWS = 1_000_000     # rows compared with the host walk
REQUEST_ROWS = 256          # rows per served request
REQUESTS = 4

COMPILE = {"hits": 0, "misses": 0, "backend_compile_s": 0.0}


def say(**kv):
    print(json.dumps(kv), flush=True)


def check(ok, why):
    """Not `assert`: the smoke must fail under `python -O` too."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {why}")


def watch_compiles():
    from jax import monitoring

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            COMPILE["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            COMPILE["misses"] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILE["backend_compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def memory(device):
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def train(ds, iters, **extra):
    """`lgb.train` with the train logloss read back every iteration (which
    also bounds the pipelined iteration, so the stamps are real)."""
    import lightgbm_tpu as lgb
    stamps, losses = [time.perf_counter()], []

    def stamp(env):
        stamps.append(time.perf_counter())
        losses.append(float(env.evaluation_result_list[0][2]))

    c0 = COMPILE["backend_compile_s"]
    bst = lgb.train(dict(PARAMS, **extra), ds, num_boost_round=iters,
                    valid_sets=[ds], verbose_eval=False, callbacks=[stamp])
    bst.model_to_string()       # materialises the last, pipelined tree
    per_iter = np.diff(stamps)
    facts = {
        "first_iter_s": round(float(per_iter[0]), 3),
        "smoke_s_per_iter_after_first": round(
            float(np.median(per_iter[1:])), 4),
        "backend_compile_s": round(COMPILE["backend_compile_s"] - c0, 1),
        "train_logloss": [round(v, 6) for v in losses],
        "leaves_per_tree": [int(t.num_leaves) for t in bst._gbdt.models],
    }
    check(all(np.isfinite(losses))
          and all(b < a for a, b in zip(losses, losses[1:])),
          f"train logloss did not fall every iteration: {losses}")
    check(max(facts["leaves_per_tree"]) == PARAMS["num_leaves"],
          f"no tree reached {PARAMS['num_leaves']} leaves: {facts}")
    return bst, facts


def lowered_has_kernel(learner) -> bool:
    """Lower (not compile) the learner's jitted build step at its real
    shapes: on the chip it must hold the Pallas kernels."""
    import jax
    import jax.numpy as jnp
    rows = jax.ShapeDtypeStruct((learner.Np,), jnp.float32)
    text = learner._build.lower(
        learner.bins_dev, rows, rows, rows, learner.num_bins_dev,
        learner.is_cat_dev,
        jax.ShapeDtypeStruct(learner._base_fmask.shape, jnp.bool_)).as_text()
    return "tpu_custom_call" in text


def host_walk(bst, X):
    """The plain reference: every tree walked in numpy on f64 rows.
    Returns (raw margins, probabilities)."""
    raw = np.zeros(X.shape[0], np.float64)
    for tree in bst._gbdt.models:
        raw += tree.predict_raw(X)
    return raw, 1.0 / (1.0 + np.exp(-bst._gbdt.objective.sigmoid * raw))


def post_predict(host, port, X, raw_score=False):
    conn = http.client.HTTPConnection(host, port, timeout=120)
    try:
        conn.request("POST", "/predict" + "?raw_score=1" * raw_score,
                     "\n".join(json.dumps([float(v) for v in row])
                               for row in X))
        r = conn.getresponse()
        text = r.read().decode()
        check(r.status == 200, f"HTTP {r.status}: {text[:300]}")
        return np.array([json.loads(line)
                         for line in text.strip().splitlines()])
    finally:
        conn.close()


def fallback_counters():
    from lightgbm_tpu import profiling
    names = (profiling.SPARSE_FALLBACKS, profiling.SERVE_CHUNK_RETRIES,
             profiling.SERVE_REPLICA_FAILURES, profiling.SERVE_REPLICA_BROKEN,
             profiling.REGISTRY_SWAP_FAILURES)
    return {n: profiling.counter_value(n) for n in names}


def check_learner(bst, dtype):
    lr = bst._gbdt.learner
    facts = {"learner": type(lr).__name__,
             "histogram_dtype": bst._gbdt.config.histogram_dtype,
             "bins_dtype": str(lr.bins_dev.dtype),
             "pallas_in_lowered_step": lowered_has_kernel(lr)}
    check(facts["learner"] == "RoundsTreeLearner"
          and facts["histogram_dtype"] == dtype,
          f"not the default chip path: {facts}")
    check(facts["pallas_in_lowered_step"],
          "the lowered build step holds no tpu_custom_call")
    return facts


def serve_model(bst):
    """Publish the model the way a deployment does and open a registry."""
    from lightgbm_tpu.serving import ModelRegistry
    path = os.path.join(tempfile.mkdtemp(prefix="lgbt_smoke_"), "model.txt")
    bst.save_model(path + ".tmp")
    os.replace(path + ".tmp", path)
    return ModelRegistry(path, params={"verbose": -1},
                         max_batch_rows=REQUEST_ROWS, replicas=0)


def one_chip(args, X, y):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.serving import PredictionServer

    t0 = time.perf_counter()
    ds = lgb.Dataset(X, y).construct(dict(PARAMS))
    say(phase="bin", seconds=round(time.perf_counter() - t0, 1),
        store=str(ds._inner.bins.dtype), shape=list(ds._inner.bins.shape))

    rng = np.random.RandomState(args.seed + 1)
    idx = np.sort(rng.choice(len(y), min(SAMPLE_ROWS, len(y)),
                             replace=False))
    Xs = X[idx]
    for dtype, extra in (("float32", {}),
                         ("int8", {"histogram_dtype": "int8"})):
        bst, facts = train(ds, args.iters, **extra)
        facts.update(check_learner(bst, dtype))
        say(phase=f"train/{dtype}", rows=len(y), **facts,
            memory=memory(jax.devices()[0]))

        # Booster.predict on the device against the numpy walk
        check(len(idx) * bst.num_trees() >= GBDT._DEVICE_PREDICT_MIN_WORK,
              "the sample is too small for Booster.predict's device path")
        t0 = time.perf_counter()
        dev = bst.predict(Xs)
        t_dev = time.perf_counter() - t0
        ref_raw, ref = host_walk(bst, Xs)
        err = float(np.abs(dev - ref).max())
        say(phase=f"predict/{dtype}", rows=len(idx),
            device_seconds_with_compile=round(t_dev, 2),
            max_abs_err_vs_host_walk=err)
        check(dev.shape == ref.shape and np.all(np.isfinite(dev))
              and err <= 1e-6,
              f"device predict differs from the host walk by {err}")

        # the serving plane: in-process server, real HTTP requests.
        # Margins are f32 tree sums: held to the walk like the device
        # predict.  Probabilities pass through the chip's f32 sigmoid,
        # which is ~1e-6 off the host's f64 one, so they are held
        # bitwise to the serving runtime called in process (HTTP,
        # batcher and demux add nothing) and to 5e-6 of Booster.predict.
        reg = serve_model(bst)
        with PredictionServer(reg, flush_deadline_ms=2,
                              model_poll_seconds=0) as srv:
            e_raw = e_val = 0.0
            for r in range(REQUESTS):
                part = slice(r * REQUEST_ROWS, (r + 1) * REQUEST_ROWS)
                got_raw = post_predict(srv.host, srv.port, Xs[part], True)
                got = post_predict(srv.host, srv.port, Xs[part])
                check(got.shape == got_raw.shape == ref[part].shape,
                      "served answer shape")
                check(np.array_equal(got, reg.current().predict(Xs[part])),
                      "HTTP answers differ from the in-process runtime")
                e_raw = max(e_raw, float(np.abs(got_raw - ref_raw[part]).max()))
                e_val = max(e_val, float(np.abs(got - bst.predict(Xs[part])
                                                ).max()))
            stats = srv.stats()
        say(phase=f"serve/{dtype}", requests=2 * REQUESTS,
            rows_per_request=REQUEST_ROWS,
            margin_max_abs_err_vs_host_walk=e_raw,
            value_max_abs_err_vs_booster_predict=e_val,
            bitwise_equal_to_in_process_runtime=True,
            replicas=stats["replicas"]["dispatches"],
            cache_misses=stats["cache_misses"])
        check(e_raw <= 1e-6 and e_val <= 5e-6,
              f"served answers differ: margins {e_raw}, values {e_val}")
        del bst


def four_chips(args, X, y):
    """Only what exists across chips: the data-parallel rounds learner
    against the one-chip learner in this process, and four replicas."""
    import lightgbm_tpu as lgb

    ds = lgb.Dataset(X, y).construct(dict(PARAMS))
    one, f_one = train(ds, args.iters)
    f_one.update(check_learner(one, "float32"))
    say(phase="train/one-chip", rows=len(y), **f_one)
    par, f_par = train(ds, args.iters, tree_learner="data")
    f_par.update(check_learner(par, "float32"))
    lr = par._gbdt.learner
    score = par._gbdt.train_score.rows     # [K, Np] in the row layout
    f_par.update(
        mesh=dict(zip(lr.mesh.axis_names, lr.mesh.devices.shape)),
        hist_exchange=lr.hist_exchange,
        store_devices=sorted(str(d) for d in lr.bins_dev.sharding.device_set),
        store_shard_shapes=sorted({str(s.data.shape)
                                   for s in lr.bins_dev.addressable_shards}),
        score_devices=sorted(str(d) for d in score.sharding.device_set),
        score_shard_shapes=sorted({str(s.data.shape)
                                   for s in score.addressable_shards}))
    say(phase="train/four-chip", rows=len(y), **f_par)
    check(lr.mesh.devices.size == 4
          and len(lr.bins_dev.sharding.device_set) == 4
          and len(score.sharding.device_set) == 4,
          "the bin store or the scores are not on four devices")

    a, b = one._gbdt.models[0], par._gbdt.models[0]
    n = a.num_leaves - 1
    check(a.num_leaves == b.num_leaves == PARAMS["num_leaves"],
          f"first trees have {a.num_leaves} and {b.num_leaves} leaves")
    for field in ("split_feature_inner", "threshold_in_bin", "left_child",
                  "right_child"):
        check(np.array_equal(getattr(a, field)[:n], getattr(b, field)[:n]),
              f"first tree differs in {field}")
    gap = abs(f_one["train_logloss"][-1] - f_par["train_logloss"][-1])
    say(phase="compare", first_tree_identical=True,
        final_logloss_gap=gap)
    check(gap <= 1e-4, f"final train logloss gap {gap}")

    # four serving replicas, one per chip
    reg = serve_model(par)
    rt = reg.current()
    check(rt.replica_count == 4, f"{rt.replica_count} replicas, not 4")
    rows = X[:REQUEST_ROWS]
    answers = [rt._run_compiled(REQUEST_ROWS, "value", rows, replica=rep)
               for rep in rt.replicas]
    check(np.all(np.isfinite(answers[0])), "replica 0 answers not finite")
    for i, ans in enumerate(answers[1:], 1):
        check(np.array_equal(ans, answers[0]),
              f"replica {i} differs from replica 0")
    got = rt.predict(X[:4 * REQUEST_ROWS])        # one request, whole fleet
    check(np.abs(got - host_walk(par, X[:4 * REQUEST_ROWS])[1]).max()
          <= 5e-6, "fleet answers differ from the host walk")
    dispatches = rt.replica_dispatches()
    say(phase="serve/four-replicas",
        replica_devices=[str(rep.device) for rep in rt.replicas],
        dispatches=dispatches, bitwise_equal_to_replica_0=True)
    check(len({str(rep.device) for rep in rt.replicas}) == 4
          and all(d > 0 for d in dispatches),
          f"replicas idle or sharing a device: {dispatches}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rows", type=int, default=10_500_000,
                    help="cut the rows (not below 1M) when a time limit "
                         "forces it; the cut is printed")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()
    if args.rows < 1_000_000:
        ap.error("--rows may not be cut below 1,000,000")

    import jax
    from bench import synth_higgs
    from lightgbm_tpu.jaxutil import enable_compile_cache, \
        require_accelerator

    device = require_accelerator()
    if device["platform"] != "tpu" or device["count"] < args.chips:
        raise SystemExit(f"need {args.chips} TPU chip(s), JAX reports "
                         f"{device}")
    watch_compiles()
    cache_dir = enable_compile_cache()
    say(phase="start", device=device, chips=args.chips, rows=args.rows,
        rows_cut_from=10_500_000 if args.rows != 10_500_000 else None,
        iters=args.iters, seed=args.seed, compile_cache=cache_dir,
        jax=jax.__version__)

    t0 = time.perf_counter()
    X, y = synth_higgs(args.rows, seed=args.seed)
    say(phase="data", seconds=round(time.perf_counter() - t0, 1),
        shape=list(X.shape))

    before = fallback_counters()
    (four_chips if args.chips == 4 else one_chip)(args, X, y)
    devs = jax.devices()
    moved = {k: v - before[k] for k, v in fallback_counters().items()
             if v != before[k]}
    say(phase="done", fallback_counters_moved=moved,
        persistent_cache={"dir": cache_dir, **COMPILE},
        memory=memory(devs[0]))
    check(not moved, f"a fallback counter moved: {moved}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
