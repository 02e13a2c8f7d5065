"""Chaos bench — the serve+online loop under deterministic injected
faults (diagnostics/faults.py), asserting the docs/Robustness.md
recovery contracts end-to-end with evidence.

Prints ONE JSON line (bench.py shape) and writes it, pretty-printed, to
``BENCH_CHAOS_OUT`` when set.

Scenario — one continuous drill over a live fleet:

1. **Healthy baseline**: train + publish a model, load it into a
   2-replica ModelRegistry fleet (warmed), capture the healthy outputs
   and the warm compile-cache size.
2. **Replica outage**: arm ``serve.dispatch.r0`` (replica 0 throws on
   EVERY dispatch) and keep driving traffic.  Every request must still
   answer with BITWISE the healthy outputs (failed chunks retry on the
   surviving replica), and replica 0 must circuit-break after
   ``replica_failure_threshold`` consecutive failures.
3. **Recovery**: disarm.  The half-open probe (count-based, no wall
   clock) must readmit replica 0 within one probe window, restoring the
   full fleet.
4. **Daemon crash mid-publish**: an online refresh is killed by
   ``online.after_publish`` BETWEEN the model rename and the state
   flush (the torn two-phase commit).  The restarted daemon must adopt
   the landed generation from its write-ahead intent — no re-processed
   rows — and the registry hot-swaps it with warm buckets.
5. **Torn model file**: the next publish is torn mid-write at the final
   path (``online.publish_model``).  The registry poll must reject it,
   keep serving the old generation, and record the failure; the redo
   publish then swaps cleanly.

Gates (asserted AFTER the JSON prints, so violations leave evidence):
every request answered, outage outputs bitwise the healthy outputs,
breaker opened + readmitted, swap failure recorded + recovered, and —
the PR 5 contract — ZERO request-path compiles after warmup across the
WHOLE drill, plus 0 retraces / 0 implicit transfers at steady state
under BENCH_SANITIZE=1.

Env knobs: BENCH_CHAOS_ROWS (20000 train rows), BENCH_CHAOS_ITERS (20
trees), BENCH_CHAOS_LEAVES (63), BENCH_CHAOS_REQS (24 requests per
phase), BENCH_CHAOS_OUT.  Shapes are modest by design — this bench
proves CONTRACTS, not throughput, and runs on the platform it is given
(the JSON names it).
"""
import json
import os
import sys
import time

# the failover drill needs a FLEET: make sure the CPU tier carves out
# enough host devices for 2 replicas (no-op for accelerator backends;
# must run before jax initializes)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ROWS = int(os.environ.get("BENCH_CHAOS_ROWS", 20_000))
ITERS = int(os.environ.get("BENCH_CHAOS_ITERS", 20))
LEAVES = int(os.environ.get("BENCH_CHAOS_LEAVES", 63))
REQS = int(os.environ.get("BENCH_CHAOS_REQS", 24))
FEATURES = 28
BATCH = 256


def synth(n: int, weights: np.ndarray, seed: int):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, FEATURES))
    y = (X @ weights + rng.logistic(size=n) * 0.5 > 0).astype(np.float64)
    return X, y


def main():
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.diagnostics import faults, locksan
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.online import OnlineTrainer, append_traffic
    from lightgbm_tpu.serving import ModelRegistry

    faults.reset()
    t_start = time.perf_counter()
    out = {
        "bench": "chaos",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "rows": ROWS, "iters": ITERS, "num_leaves": LEAVES,
        "requests_per_phase": REQS,
    }

    import tempfile
    workdir = tempfile.mkdtemp(prefix="lgbt_chaos_")
    pub = os.path.join(workdir, "model.txt")
    traffic = os.path.join(workdir, "traffic.jsonl")

    # -- 1. healthy baseline -------------------------------------------
    rng = np.random.default_rng(7)
    w_base = rng.standard_normal(FEATURES)
    X, y = synth(ROWS, w_base, seed=1)
    params = {"objective": "binary", "verbose": -1,
              "num_leaves": LEAVES, "learning_rate": 0.2,
              "min_data_in_leaf": 20, "online_trigger_rows": 2048,
              "refit_decay_rate": 0.0, "refit_min_rows": 1}
    bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=ITERS)
    init_model = os.path.join(workdir, "init.txt")
    bst.save_model(init_model)
    bst.save_model(pub + ".tmp")
    os.replace(pub + ".tmp", pub)

    threshold = 3
    reg = ModelRegistry(pub, params={"verbose": -1},
                        max_batch_rows=BATCH, replicas=2,
                        failure_threshold=threshold,
                        warmup_buckets=(BATCH,))
    rt = reg.current()
    Xq = X[:BATCH]
    healthy = rt.predict(Xq)                 # warm bucket, steady path
    warm_misses = rt.cache_misses
    out["replicas"] = rt.replica_count

    san = HotPathSanitizer(warmup=0, label="bench-chaos-serve")
    sanitize = sanitize_enabled()

    # -- 2. replica outage under traffic --------------------------------
    answered = mismatches = 0
    faults.arm("serve.dispatch.r0")
    with san if sanitize else _noop():
        for _ in range(REQS):
            if sanitize:
                with san.step():
                    got = rt.predict(Xq)
            else:
                got = rt.predict(Xq)
            answered += 1
            if not np.array_equal(got, healthy):
                mismatches += 1
    health = {h["index"]: h for h in rt.replica_health()}
    out["outage"] = {
        "answered": answered, "bitwise_mismatches": mismatches,
        "chunk_retries": rt.chunk_retries,
        "faults_fired_r0": faults.fired("serve.dispatch.r0"),
        "r0_state": health[0]["state"],
        "healthy_replicas": rt.healthy_count(),
    }
    broke = health[0]["state"] == "broken"

    # -- 3. recovery: half-open probe readmits --------------------------
    faults.disarm()
    for _ in range(REQS):
        got = rt.predict(Xq)
        answered += 1
        if not np.array_equal(got, healthy):
            mismatches += 1
        if rt.healthy_count() == rt.replica_count:
            break
    health = {h["index"]: h for h in rt.replica_health()}
    out["recovery"] = {
        "r0_state": health[0]["state"],
        "probes": health[0]["probes"],
        "healthy_replicas": rt.healthy_count(),
        # retries + probes + readmission never compile: the retry
        # replica's executable cache is as warm as the failed one's
        "request_path_compiles": rt.cache_misses - warm_misses,
    }
    readmitted = health[0]["state"] == "healthy"
    serve_compiles = rt.cache_misses - warm_misses

    # -- 4. daemon crash between publish and state flush ----------------
    w_drift = rng.standard_normal(FEATURES)
    Xd, yd = synth(4096, w_drift, seed=2)
    cfg = config_from_params(params)
    tr = OnlineTrainer(bst, traffic, pub, config=cfg)
    append_traffic(traffic, Xd[:2048], yd[:2048])
    faults.arm("online.after_publish:1")
    crashed = False
    try:
        tr.poll_once()
    except faults.InjectedFault:
        crashed = True                       # the daemon "process" died
    faults.disarm()
    del tr
    # cold restart: fresh booster, resume from the state sidecar
    bst2 = lgb.Booster(params={"verbose": -1}, model_file=init_model)
    tr2 = OnlineTrainer(bst2, traffic, pub, config=cfg)
    adopted = tr2.generation == 1            # write-ahead intent adopted
    # the landed generation hot-swaps with warm buckets; traffic keeps
    # being answered from the new generation with zero request-path
    # compiles (swap warmup covers the live buckets)
    swapped = reg.maybe_reload()
    rt = reg.current()
    misses_after_swap = rt.cache_misses
    p2 = rt.predict(Xq)
    out["crash_publish"] = {
        "crashed": crashed, "intent_adopted": adopted,
        "generation": tr2.generation, "hot_swapped": bool(swapped),
        "request_path_compiles": rt.cache_misses - misses_after_swap,
        "resumed_offset": tr2.traffic.offset,
    }

    # -- 5. torn model file at the publish path -------------------------
    append_traffic(traffic, Xd[2048:], yd[2048:])
    faults.arm("online.publish_model:1")
    torn_crash = False
    try:
        tr2.poll_once()
    except faults.InjectedFault:
        torn_crash = True
    faults.disarm()
    rejected = reg.maybe_reload(force=True) is False
    still_serving = np.array_equal(reg.current().predict(Xq), p2)
    del tr2
    bst3 = lgb.Booster(params={"verbose": -1}, model_file=init_model)
    tr3 = OnlineTrainer(bst3, traffic, pub, config=cfg)
    redo = tr3.poll_once()                   # the window redoes cleanly
    swapped2 = reg.maybe_reload()
    rt = reg.current()
    misses_final = rt.cache_misses
    rt.predict(Xq)
    out["torn_publish"] = {
        "crashed": torn_crash, "registry_rejected_torn": rejected,
        "old_generation_kept_serving": bool(still_serving),
        "swap_failures": reg.swap_failures,
        "last_swap_error_recorded": bool(reg.last_swap_error) or rejected,
        "redo_published": bool(redo), "clean_swap_landed": bool(swapped2),
        "request_path_compiles": rt.cache_misses - misses_final,
    }

    # -- verdicts -------------------------------------------------------
    out["faults"] = faults.snapshot()
    out["answered_total"] = answered
    out["bitwise_mismatches"] = mismatches
    out["request_path_compiles_total"] = (
        serve_compiles + out["crash_publish"]["request_path_compiles"]
        + out["torn_publish"]["request_path_compiles"])
    out["seconds_total"] = round(time.perf_counter() - t_start, 2)
    if sanitize:
        out["sanitize"] = san.report()
    if locksan.armed():
        out["locksan"] = locksan.report()
    print(json.dumps(out))
    dest = os.environ.get("BENCH_CHAOS_OUT")
    if dest:
        with open(dest, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {dest}", file=sys.stderr)

    # gates AFTER the evidence prints
    assert mismatches == 0, "fleet answered WRONG values under faults"
    assert broke, "replica 0 never circuit-broke under injected failures"
    assert readmitted, "half-open probe never readmitted replica 0"
    assert out["outage"]["chunk_retries"] > 0, (
        "no chunk ever retried (faults unwired?)")
    assert crashed and adopted, "publish-intent recovery did not adopt"
    assert swapped, "landed generation never hot-swapped"
    assert out["crash_publish"]["request_path_compiles"] == 0, (
        "post-swap request compiled on the request path")
    assert rejected and still_serving, "torn model was not survived"
    assert redo and swapped2, "torn window never redone/republished"
    assert out["request_path_compiles_total"] == 0, (
        "the drill compiled on the request path")
    if sanitize:
        assert san.retraces == 0, (
            f"serve loop retraced under faults: {san.compile_names}")
        assert san.implicit_transfers == 0, (
            "serve loop moved data implicitly under faults")
    if locksan.armed():
        locksan.check()  # 0 lock-order cycles across the whole drill


class _noop:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
