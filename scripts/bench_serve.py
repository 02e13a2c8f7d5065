"""Serving-fleet bench — sustained-QPS load + kernel and quantize A/Bs.

Prints ONE JSON line (same shape as bench.py) and optionally writes it
to ``SERVE_BENCH_OUT``.  Four sections:

1. **Kernel A/B** — `predict_kernel=walk` vs `tensorized` through the
   same PredictorRuntime at the north-star model shape (500 trees,
   depth <= 8 by default): interleaved calls, min-call-time rows/s per
   kernel (median alongside) and the speedup.
2. **Quantize A/B** — `serve_quantize=raw` vs `binned` through the
   same runtime class at the same shape: interleaved calls including
   the binned side's host ingress quantization, min-call-time rows/s,
   speedup, and the request-buffer byte ratio (f32 vs uint8 — the >=4x
   shrink the binned path ships to the device).  Answers are asserted
   BITWISE equal before timing.
3. **Sustained load** — the full serving stack (ModelRegistry →
   continuous MicroBatcher → replicated PredictorRuntime → HTTP) under
   `SERVE_BENCH_CLIENTS` concurrent clients for `SERVE_BENCH_SECONDS`
   per side (paced to `SERVE_BENCH_QPS` aggregate when set, closed-loop
   otherwise), run TWICE — serve_quantize=raw then =binned against the
   same published model + .refbin sidecar: p50/p95/p99 request latency,
   achieved QPS, sustained rows/s, replica dispatch balance per side.
4. **Sanitize** (`BENCH_SANITIZE=1`) — BOTH runtime variants probed
   directly under `HotPathSanitizer` (single-threaded — jax's transfer
   guard is thread-local, so the HTTP stack's flush threads can't be
   guarded from here) at steady state: ZERO retraces and ZERO implicit
   transfers per request after warmup, asserted AFTER the JSON line
   prints so the chip-queue log always has the counter evidence.

5. **Multi-tenant mode** (``SERVE_BENCH_TENANTS=M``, the
   `bench_serve_mt` chip-queue stage) — replaces sections 1-3: M
   catalog tenants on one fleet under MIXED per-tenant QPS (tenant 0
   heaviest, weights M..1), per-model p50/p95/p99 + achieved QPS from
   both the clients and the server's /stats `models` block, eviction
   churn under ``SERVE_BENCH_CACHE_MB`` (0 = no budget), and the
   BENCH_SANITIZE steady-state probe per tenant.

Env knobs: SERVE_BENCH_TREES (500), SERVE_BENCH_LEAVES (63),
SERVE_BENCH_DEPTH (8), SERVE_BENCH_ROWS (rows/request, 64),
SERVE_BENCH_CLIENTS (8), SERVE_BENCH_SECONDS (10, per sustained side),
SERVE_BENCH_QPS (0 = closed loop), SERVE_BENCH_REPLICAS (0 = auto),
SERVE_BENCH_AB_ROWS (2048), SERVE_BENCH_AB_REPS (15), SERVE_BENCH_OUT,
SERVE_BENCH_REQUIRE_SPEEDUP (kernel A/B gate),
SERVE_BENCH_REQUIRE_BINNED (fail if binned rows/s < raw * this),
SERVE_BENCH_TENANTS (0 = single-model sections 1-4),
SERVE_BENCH_CACHE_MB (multi-tenant executable budget, 0 = unlimited).
"""
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

TREES = int(os.environ.get("SERVE_BENCH_TREES", 500))
LEAVES = int(os.environ.get("SERVE_BENCH_LEAVES", 63))
DEPTH = int(os.environ.get("SERVE_BENCH_DEPTH", 8))
ROWS_PER_REQ = int(os.environ.get("SERVE_BENCH_ROWS", 64))
CLIENTS = int(os.environ.get("SERVE_BENCH_CLIENTS", 8))
SECONDS = float(os.environ.get("SERVE_BENCH_SECONDS", 10))
QPS = float(os.environ.get("SERVE_BENCH_QPS", 0))
REPLICAS = int(os.environ.get("SERVE_BENCH_REPLICAS", 0))
AB_ROWS = int(os.environ.get("SERVE_BENCH_AB_ROWS", 2048))
AB_REPS = int(os.environ.get("SERVE_BENCH_AB_REPS", 15))
TENANTS = int(os.environ.get("SERVE_BENCH_TENANTS", 0))
CACHE_MB = int(os.environ.get("SERVE_BENCH_CACHE_MB", 0))
FEATURES = 28


_PARAMS = {"objective": "binary", "verbose": -1, "num_leaves": 0,
           "max_depth": 0, "min_data_in_leaf": 20}


def _train_model():
    """Synthetic HIGGS-shaped binary model at the north-star serving
    shape, plus the frozen-mapper refbin dataset the binned serving
    path quantizes against.  ``SERVE_BENCH_MODEL=<path>`` caches the
    trained model text across runs (training 500 trees dwarfs the
    measured phases on the CPU tier); the feature matrix — and with it
    the deterministic bin mappers — is regenerated either way, so the
    refbin always matches the model's training quantization."""
    import lightgbm_tpu as lgb
    params = dict(_PARAMS, num_leaves=LEAVES, max_depth=DEPTH)
    rng = np.random.RandomState(0)
    X = rng.rand(20_000, FEATURES)
    z = X @ rng.randn(FEATURES)
    y = (z > np.median(z)).astype(float)
    ds = lgb.Dataset(X, y)
    cache = os.environ.get("SERVE_BENCH_MODEL", "")
    shape = {"trees": TREES, "leaves": LEAVES, "depth": DEPTH}
    if cache and os.path.exists(cache):
        # the sidecar records the EXACT requested shape at save time;
        # introspecting the model can't distinguish e.g. a 31-leaf run
        # from a 63-leaf run whose trees stayed small, and a mismatched
        # cache would silently mislabel the JSON's "model" block
        try:
            with open(cache + ".meta") as f:
                cached_shape = json.load(f)
        except (OSError, ValueError):
            cached_shape = None
        if cached_shape == shape:
            ds.construct(params)          # mappers only (deterministic)
            return lgb.Booster(model_file=cache), X, ds._inner
    bst = lgb.Booster(params, ds)
    for _ in range(TREES):
        bst.update()
    if cache:
        bst.save_model(cache)
        with open(cache + ".meta", "w") as f:
            json.dump(shape, f)
    return bst, X, ds.construct()._inner


def _kernel_ab(bst, X):
    """Walk-vs-tensorized predict throughput on ONE replica, same
    bucket, same rows.  The two kernels' calls are INTERLEAVED (walk,
    tensorized, walk, ...) so machine-speed drift on a shared host hits
    both equally, and the headline throughput/speedup comes from the
    per-kernel MIN call time: external interference is one-sided (it
    can only slow a call down), so the min is the noise-free estimate
    of kernel speed; the median rides along for the noise picture."""
    from lightgbm_tpu.serving import PredictorRuntime
    Xq = np.ascontiguousarray(X[:AB_ROWS], np.float64)
    kernels = ("walk", "tensorized")
    rts = {}
    for kernel in kernels:
        rts[kernel] = PredictorRuntime(bst, predict_kernel=kernel,
                                       replicas=1,
                                       max_batch_rows=AB_ROWS,
                                       min_bucket_rows=AB_ROWS)
        rts[kernel].predict(Xq)                         # compile + warm
    times = {k: [] for k in kernels}
    for _ in range(AB_REPS):
        for kernel in kernels:
            t0 = time.perf_counter()
            rts[kernel].predict(Xq)
            times[kernel].append(time.perf_counter() - t0)
    out = {"rows": AB_ROWS, "reps": AB_REPS}
    for kernel in kernels:
        best = min(times[kernel])
        med = sorted(times[kernel])[AB_REPS // 2]
        out[kernel] = {"ms_per_call": round(best * 1e3, 3),
                       "ms_per_call_median": round(med * 1e3, 3),
                       "rows_per_s": round(AB_ROWS / best, 1)}
    out["speedup"] = round(out["tensorized"]["rows_per_s"]
                           / out["walk"]["rows_per_s"], 3)
    return out


def _quantize_ab(bst, X, refbin):
    """serve_quantize=raw vs binned throughput through the runtime,
    same bucket, same rows, interleaved min-call-time (the kernel-A/B
    measurement discipline).  The binned side pays its real ingress
    cost (host quantization) inside the timed call.  Scores are
    asserted BITWISE equal before any timing — the acceptance bar of
    the binned path."""
    from lightgbm_tpu.serving import PredictorRuntime
    Xq = np.ascontiguousarray(X[:AB_ROWS], np.float64)
    rts = {
        "raw": PredictorRuntime(bst, replicas=1, max_batch_rows=AB_ROWS,
                                min_bucket_rows=AB_ROWS),
        "binned": PredictorRuntime(bst, replicas=1, quantize="binned",
                                   refbin=refbin, max_batch_rows=AB_ROWS,
                                   min_bucket_rows=AB_ROWS),
    }
    base = rts["raw"].predict(Xq)                   # compile + warm
    got = rts["binned"].predict(Xq)
    if not np.array_equal(base, got):
        raise SystemExit("raw-vs-binned parity FAILED at the bench shape")
    times = {k: [] for k in rts}
    for _ in range(AB_REPS):
        for variant, rt in rts.items():
            t0 = time.perf_counter()
            rt.predict(Xq)
            times[variant].append(time.perf_counter() - t0)
    rb = rts["binned"]
    out = {"rows": AB_ROWS, "reps": AB_REPS, "bitwise_equal": True,
           "buffer_bytes_raw": AB_ROWS * rb.num_features * 4,
           "buffer_bytes_binned": int(
               AB_ROWS * rb._buf_cols * np.dtype(rb._buf_dtype).itemsize)}
    out["buffer_shrink"] = round(out["buffer_bytes_raw"]
                                 / out["buffer_bytes_binned"], 2)
    for variant in rts:
        best = min(times[variant])
        med = sorted(times[variant])[AB_REPS // 2]
        out[variant] = {"ms_per_call": round(best * 1e3, 3),
                        "ms_per_call_median": round(med * 1e3, 3),
                        "rows_per_s": round(AB_ROWS / best, 1)}
    out["speedup"] = round(out["binned"]["rows_per_s"]
                           / out["raw"]["rows_per_s"], 3)
    return out


def _sustained_load(server, X, model=None, clients=None, seconds=None):
    """Concurrent HTTP clients for a fixed window; returns latency
    percentiles + achieved rates.  ``model`` routes every request to
    one catalog tenant (the multi-tenant mode runs one of these client
    pools per tenant, concurrently)."""
    import http.client
    clients = CLIENTS if clients is None else clients
    seconds = SECONDS if seconds is None else seconds
    path = "/predict" + (f"?model={model}" if model else "")
    latencies = []
    lat_lock = threading.Lock()
    errors = []
    t_end = time.monotonic() + seconds
    interval = clients / QPS if QPS > 0 else 0.0

    def client(idx):
        conn = http.client.HTTPConnection(server.host, server.port,
                                          timeout=120)
        k = 0
        start = time.monotonic() + (idx * interval / max(clients, 1))
        try:
            while time.monotonic() < t_end:
                if interval:
                    nxt = start + k * interval
                    delay = nxt - time.monotonic()
                    if delay > 0:
                        time.sleep(delay)
                k += 1
                lo = ((idx * 7919 + k * ROWS_PER_REQ) % 10_000)
                rows = X[lo:lo + ROWS_PER_REQ]
                body = "\n".join(
                    json.dumps([float(v) for v in r]) for r in rows)
                t0 = time.perf_counter()
                conn.request("POST", path, body)
                resp = conn.getresponse()
                resp.read()
                dt = time.perf_counter() - t0
                if resp.status != 200:
                    errors.append(resp.status)
                    return
                with lat_lock:
                    latencies.append(dt)
        except Exception as e:          # noqa: BLE001 — recorded, reported
            errors.append(repr(e))
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    lat = sorted(latencies)
    if errors or not lat:
        return {"error": str(errors[:3])}

    def q(p):
        # nearest-rank (ceil(p*n)-1), matching profiling.summary — the
        # old int(p*n) indexing overshot by one position (p99 of 100
        # samples reported the max)
        import math
        i = min(len(lat) - 1, max(0, math.ceil(p * len(lat)) - 1))
        return round(lat[i] * 1e3, 3)

    return {
        "seconds": round(wall, 2),
        "clients": clients,
        "rows_per_request": ROWS_PER_REQ,
        "target_qps": QPS or "closed-loop",
        "requests": len(lat),
        "achieved_qps": round(len(lat) / wall, 1),
        "rows_per_s": round(len(lat) * ROWS_PER_REQ / wall, 1),
        "p50_ms": q(0.50), "p95_ms": q(0.95), "p99_ms": q(0.99),
        "max_ms": round(lat[-1] * 1e3, 3),
    }


def _multi_tenant_main() -> None:
    """SERVE_BENCH_TENANTS=M: M catalog tenants (copies of the
    north-star model under distinct ids), mixed per-tenant QPS (tenant
    0 heaviest), per-model p99 from clients AND the /stats models
    block, eviction churn under SERVE_BENCH_CACHE_MB, per-tenant
    sanitize probe."""
    from lightgbm_tpu import profiling
    from lightgbm_tpu.diagnostics import locksan
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    from lightgbm_tpu.serving import ModelCatalog, PredictionServer

    t_train0 = time.monotonic()
    bst, X, _refbin = _train_model()
    train_s = time.monotonic() - t_train0
    tenant_ids = [f"t{i}" for i in range(TENANTS)]
    # mixed QPS: tenant 0 carries the most clients (weight M..1) — the
    # "one hot tenant" shape the per-tenant accounting must resolve
    weights = [TENANTS - i for i in range(TENANTS)]
    wsum = sum(weights)
    clients = {tid: max(1, round(CLIENTS * w / wsum))
               for tid, w in zip(tenant_ids, weights)}
    warm = []
    b = ROWS_PER_REQ
    while b <= min(max(clients.values()) * ROWS_PER_REQ, 4096):
        warm.append(b)
        b <<= 1
    san_rec = {}
    with tempfile.TemporaryDirectory() as tmp:
        models = {}
        for tid in tenant_ids:
            path = os.path.join(tmp, f"{tid}.txt")
            bst.save_model(path)
            models[tid] = path
        catalog = ModelCatalog(
            models, params={"verbose": -1}, max_batch_rows=4096,
            flush_deadline_ms=2.0, replicas=REPLICAS,
            cache_budget_mb=CACHE_MB,
            warmup_buckets=tuple(warm) or (ROWS_PER_REQ,))
        server = PredictionServer(catalog=catalog, model_poll_seconds=0)
        evict0 = profiling.counter_value(profiling.SERVE_CACHE_EVICTIONS)
        with server:
            pools = {}
            results = {}

            def run_pool(tid):
                results[tid] = _sustained_load(server, X, model=tid,
                                               clients=clients[tid])

            for tid in tenant_ids:
                pools[tid] = threading.Thread(target=run_pool,
                                              args=(tid,))
            t0 = time.monotonic()
            for t in pools.values():
                t.start()
            for t in pools.values():
                t.join()
            wall = time.monotonic() - t0
            stats = server.stats()
        evictions = (profiling.counter_value(
            profiling.SERVE_CACHE_EVICTIONS) - evict0)
        sans = []
        if sanitize_enabled():
            # steady-state probe per tenant, directly on its runtime
            # (the transfer guard is thread-local); one unguarded call
            # re-warms whatever the budget may have evicted.  Violations
            # fail AFTER the JSON prints, as everywhere in this script.
            Xq = np.ascontiguousarray(X[:ROWS_PER_REQ], np.float64)
            for tid in tenant_ids:
                rt = catalog.get(tid).registry.current()
                rt.predict(Xq)
                san = HotPathSanitizer(warmup=1, label=f"serve-mt-{tid}")
                with san:
                    for _ in range(6):
                        with san.step():
                            rt.predict(Xq)
                san_rec[tid] = san.report()
                sans.append(san)
        catalog.close()
    per_model = {}
    for tid in tenant_ids:
        load = results.get(tid, {})
        srv_side = stats["models"].get(tid, {})
        per_model[tid] = {
            "clients": clients[tid],
            "load": load,
            "server_requests": srv_side.get("requests"),
            "server_p99_ms": (srv_side.get("latency_ms") or {}).get("p99"),
            "evictions": srv_side.get("evictions"),
        }
    worst_p99 = max((r["load"].get("p99_ms") or 0.0)
                    for r in per_model.values())
    out = {
        "metric": f"multi-tenant serve fleet ({TENANTS} tenants, mixed "
                  f"QPS): worst per-model p99 under sustained load",
        "value": worst_p99,
        "unit": "ms",
        "train_s": round(train_s, 1),
        "model": {"trees": TREES, "num_leaves": LEAVES,
                  "max_depth": DEPTH},
        "tenants": per_model,
        "wall_s": round(wall, 2),
        "cache_budget_mb": CACHE_MB,
        "evictions": evictions,
        "default_model": stats["default_model"],
    }
    if san_rec:
        out["sanitize"] = san_rec
    if locksan.armed():
        out["locksan"] = locksan.report()
    line = json.dumps(out)
    print(line)
    dest = os.environ.get("SERVE_BENCH_OUT", "")
    if dest:
        with open(dest, "w") as f:
            f.write(line + "\n")
    for tid, rec in results.items():
        if "error" in rec:
            raise SystemExit(f"sustained load ({tid}) failed: "
                             f"{rec['error']}")
    for san in sans:
        san.check()     # fail AFTER the JSON so counters are recorded
    if locksan.armed():
        locksan.check()  # 0 lock-order cycles across the whole window


def main() -> None:
    from lightgbm_tpu import profiling
    from lightgbm_tpu.diagnostics import locksan
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    from lightgbm_tpu.serving import ModelRegistry, PredictionServer

    if TENANTS > 0:
        _multi_tenant_main()
        return

    t_train0 = time.monotonic()
    bst, X, refbin = _train_model()
    train_s = time.monotonic() - t_train0
    depth_grown = max((t.max_depth_grown
                       for t in bst._gbdt.models if t.num_leaves > 1),
                      default=0)
    ab = _kernel_ab(bst, X)
    qab = _quantize_ab(bst, X, refbin)

    sans = []
    san_rec = {}
    loads = {}
    stats = {}
    with tempfile.TemporaryDirectory() as tmp:
        model_path = os.path.join(tmp, "model.txt")
        bst.save_model(model_path)
        refbin.save_refbin(model_path + ".refbin")
        # warm every bucket a coalesced batch can land on (1 request up
        # to all clients' requests in one flush)
        warm = []
        b = ROWS_PER_REQ
        while b <= min(CLIENTS * ROWS_PER_REQ, 4096):
            warm.append(b)
            b <<= 1
        for variant in ("raw", "binned"):
            registry = ModelRegistry(
                model_path, params={"verbose": -1}, max_batch_rows=4096,
                warmup_buckets=tuple(warm) or (ROWS_PER_REQ,),
                replicas=REPLICAS, serve_quantize=variant)
            runtime = registry.current()
            assert runtime.variant == variant
            if sanitize_enabled():
                Xq = np.ascontiguousarray(X[:ROWS_PER_REQ], np.float64)
                san = HotPathSanitizer(warmup=1, label=f"serve-{variant}")
                with san:
                    for _ in range(8):
                        with san.step():
                            runtime.predict(Xq)
                san_rec[variant] = san.report()
                sans.append(san)
                # violations fail AFTER the JSON line below is printed,
                # so the chip-queue log always has the counter evidence
            server = PredictionServer(registry, flush_deadline_ms=2.0,
                                      model_poll_seconds=0)
            with server:
                # delta-snapshot the process-global counters around the
                # sustained window: the quantize A/B and warmup already
                # ran binned traffic in this process, and the committed
                # artifact must describe THIS phase only
                misses_before = profiling.counter_value("serve.cache_miss")
                qb_before = profiling.counter_value(
                    profiling.SERVE_QUANTIZE_BYTES_IN)
                br_before = profiling.counter_value(
                    profiling.SERVE_BINNED_REQUESTS)
                loads[variant] = _sustained_load(server, X)
                misses_after = profiling.counter_value("serve.cache_miss")
                stats[variant] = server.stats()
                loads[variant]["warm_cache_misses"] = (misses_after
                                                       - misses_before)
                loads[variant]["quantize_bytes_in"] = (
                    profiling.counter_value(
                        profiling.SERVE_QUANTIZE_BYTES_IN) - qb_before)
                loads[variant]["binned_requests"] = (
                    profiling.counter_value(
                        profiling.SERVE_BINNED_REQUESTS) - br_before)

    load = loads["binned"]
    out = {
        "metric": f"serve fleet {FEATURES}f {TREES} trees depth<={DEPTH}: "
                  "p99 request latency under sustained load "
                  "(serve_quantize=binned)",
        "value": load.get("p99_ms"),
        "unit": "ms",
        "train_s": round(train_s, 1),
        "model": {"trees": TREES, "num_leaves": LEAVES,
                  "max_depth": DEPTH, "depth_grown": int(depth_grown)},
        "kernel_ab": ab,
        "quantize_ab": qab,
        "sustained": loads,
        "replicas": stats["binned"]["replicas"],
        "batch_workers": stats["binned"]["batch_workers"],
        "quantize_bytes_in": loads["binned"]["quantize_bytes_in"],
        "binned_requests": loads["binned"]["binned_requests"],
        "generation": stats["binned"]["generation"],
    }
    if san_rec:
        out["sanitize"] = san_rec
    if locksan.armed():
        out["locksan"] = locksan.report()
    line = json.dumps(out)
    print(line)
    dest = os.environ.get("SERVE_BENCH_OUT", "")
    if dest:
        with open(dest, "w") as f:
            f.write(line + "\n")
    for variant, rec in loads.items():
        if "error" in rec:
            raise SystemExit(f"sustained load ({variant}) failed: "
                             f"{rec['error']}")
    for san in sans:
        san.check()     # fail AFTER the JSON so counters are recorded
    if locksan.armed():
        locksan.check()  # 0 lock-order cycles across the whole window
    if os.environ.get("SERVE_BENCH_REQUIRE_SPEEDUP", ""):
        need = float(os.environ["SERVE_BENCH_REQUIRE_SPEEDUP"])
        if ab["speedup"] < need:
            raise SystemExit(
                f"kernel A/B speedup {ab['speedup']} < required {need}")
    if os.environ.get("SERVE_BENCH_REQUIRE_BINNED", ""):
        need = float(os.environ["SERVE_BENCH_REQUIRE_BINNED"])
        ratio = (qab["binned"]["rows_per_s"] / qab["raw"]["rows_per_s"])
        if ratio < need:
            raise SystemExit(
                f"quantize A/B binned/raw throughput {ratio:.3f} < "
                f"required {need}")


if __name__ == "__main__":
    main()
