"""Cross-model co-stack bench — the `serve_costack` A/B at fleet scale.

The tenpole claim of the co-stacked catalog (serving/superstack.py) is
that N compatible tenants cost ONE compiled executable per (bucket,
output kind) instead of N — and that mixed batches demux bitwise
identically to per-tenant dispatch.  This harness measures exactly
that, twice per tenant count (default 10 and 100 tenants):

- **costack=off** — the PR-15 catalog: per-tenant registries, each
  warmed solo, per-tenant micro-batchers.
- **costack=on**  — the same models co-stacked: one GroupRuntime, one
  shared MicroBatcher, per-row tenant-id demux.

Per side it records the compiled-executable count (the process-global
``serve.cache_miss`` delta across catalog build + warmup + the load
window), closed-loop p50/p95/p99 request latency and sustained rows/s
under ``SERVE_MT_WORKERS`` concurrent submitters round-robining the
tenants, and the steady-state miss count (must be ZERO on both sides —
every compile belongs to warmup, never the request path).  Before the
load window every tenant scores one fixed slice through the live
catalog; the off-side answers are the parity reference the on-side
must match BITWISE.

With ``BENCH_SANITIZE=1`` both sides get a single-threaded
``HotPathSanitizer`` steady-state probe (jax's transfer guard is
thread-local): zero retraces and zero implicit transfers per request,
asserted AFTER the JSON line prints so the chip-queue log always has
the counter evidence.

Prints ONE JSON line (bench.py shape); ``SERVE_MT_OUT`` also writes it
to a file.  Gates (all fire after the JSON):

- compile ratio (off/on) >= ``SERVE_MT_REQUIRE_RATIO`` (default 5) at
  every tenant count >= 10 — the acceptance bar of the co-stack PR;
- on-side p99 <= off-side p99 * ``SERVE_MT_REQUIRE_P99`` (default
  1.15) at every tenant count >= 100 — the compute-bound bar of the
  segment-kernel PR: under ``costack_kernel=auto`` the CPU tier
  resolves to the segment-gathered walk, so the on side must no
  longer pay the walk-everyone G× node math that made large-fleet
  co-stacking a latency regression (0 disables; smaller counts stay
  report-only — closed-loop CPU p99 is noisy at low load);
- steady-state misses == 0 on both sides;
- per-tenant parity is always a hard gate.

Per on-side record the resolved kernel variant rides along with the
``serve/group_segment_rows`` / ``serve/group_stacked_rows`` /
``serve/group_quantize_shared`` counter deltas, so the JSON itself
proves WHICH traversal served the load window.

Env knobs: SERVE_MT_TENANTS ("10,100" — comma list),
SERVE_MT_DISTINCT (4 distinct fits cycled across tenant ids),
SERVE_MT_TREES (60), SERVE_MT_LEAVES (15), SERVE_MT_DEPTH (6),
SERVE_MT_ROWS (rows/request, 32), SERVE_MT_WORKERS (8),
SERVE_MT_SECONDS (6, per side), SERVE_MT_MAX_BATCH (256),
SERVE_MT_REPLICAS (0 = auto), SERVE_MT_OUT,
SERVE_MT_REQUIRE_RATIO (5.0; 0 disables), SERVE_MT_REQUIRE_P99
(p99 slack multiplier, default 1.15 at >= 100 tenants; 0 = report
only), SERVE_MT_KERNEL (costack_kernel for the on side; "auto").
"""
import json
import math
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

TENANT_COUNTS = [int(v) for v in
                 os.environ.get("SERVE_MT_TENANTS", "10,100").split(",")
                 if v.strip()]
DISTINCT = int(os.environ.get("SERVE_MT_DISTINCT", 4))
TREES = int(os.environ.get("SERVE_MT_TREES", 60))
LEAVES = int(os.environ.get("SERVE_MT_LEAVES", 15))
DEPTH = int(os.environ.get("SERVE_MT_DEPTH", 6))
ROWS_PER_REQ = int(os.environ.get("SERVE_MT_ROWS", 32))
WORKERS = int(os.environ.get("SERVE_MT_WORKERS", 8))
SECONDS = float(os.environ.get("SERVE_MT_SECONDS", 6))
MAX_BATCH = int(os.environ.get("SERVE_MT_MAX_BATCH", 256))
REPLICAS = int(os.environ.get("SERVE_MT_REPLICAS", 0))
REQUIRE_RATIO = float(os.environ.get("SERVE_MT_REQUIRE_RATIO", 5.0))
REQUIRE_P99 = float(os.environ.get("SERVE_MT_REQUIRE_P99", 1.15))
KERNEL = os.environ.get("SERVE_MT_KERNEL", "auto")
FEATURES = 16


def _train_fits():
    """DISTINCT binary fits at one shape (same num_class, same kernel
    variant, same leaf tier — the costack_key the grouping policy
    needs), different seeds: distinct trees/leaf values so the parity
    check exercises real demux, not N copies of one answer."""
    import lightgbm_tpu as lgb
    fits = []
    for seed in range(DISTINCT):
        rng = np.random.RandomState(seed)
        X = rng.rand(4000, FEATURES)
        z = X @ rng.randn(FEATURES)
        y = (z > np.median(z)).astype(float)
        params = {"objective": "binary", "verbose": -1,
                  "num_leaves": LEAVES, "max_depth": DEPTH,
                  "min_data_in_leaf": 20}
        bst = lgb.Booster(params, lgb.Dataset(X, y))
        for _ in range(TREES):
            bst.update()
        fits.append(bst)
    rng = np.random.RandomState(99)
    Xreq = rng.rand(10_000, FEATURES)
    return fits, Xreq


def _closed_loop(catalog, tenant_ids, X):
    """WORKERS threads round-robining the tenants for SECONDS: each
    request is ROWS_PER_REQ rows through catalog.submit (the real
    routing + batching + demux path, minus HTTP framing).  Returns
    latency percentiles + sustained rows/s."""
    latencies = []
    lock = threading.Lock()
    errors = []
    t_end = time.monotonic() + SECONDS

    def worker(idx):
        k = 0
        try:
            while time.monotonic() < t_end:
                tid = tenant_ids[(idx * 7919 + k) % len(tenant_ids)]
                lo = (idx * 131 + k * ROWS_PER_REQ) % (len(X)
                                                       - ROWS_PER_REQ)
                rows = X[lo:lo + ROWS_PER_REQ]
                k += 1
                t0 = time.perf_counter()
                _tenant, fut = catalog.submit(rows, kind="value",
                                              model_id=tid)
                fut.result()
                dt = time.perf_counter() - t0
                with lock:
                    latencies.append(dt)
        except Exception as e:      # noqa: BLE001 — recorded, reported
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(WORKERS)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    if errors or not latencies:
        return {"error": str(errors[:3])}
    lat = sorted(latencies)

    def q(p):
        i = min(len(lat) - 1, max(0, math.ceil(p * len(lat)) - 1))
        return round(lat[i] * 1e3, 3)

    return {
        "seconds": round(wall, 2),
        "workers": WORKERS,
        "rows_per_request": ROWS_PER_REQ,
        "requests": len(lat),
        "achieved_qps": round(len(lat) / wall, 1),
        "rows_per_s": round(len(lat) * ROWS_PER_REQ / wall, 1),
        "p50_ms": q(0.50), "p95_ms": q(0.95), "p99_ms": q(0.99),
        "max_ms": round(lat[-1] * 1e3, 3),
    }


def _run_side(models, tenant_ids, X, Xfix, costack, warm, san_label,
              sans, san_rec):
    """Build one catalog (co-stack on or off), score the parity slice
    per tenant, run the closed loop, probe the sanitizer.  Returns the
    side record + per-tenant parity answers."""
    from lightgbm_tpu import profiling
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    from lightgbm_tpu.serving import ModelCatalog

    miss0 = profiling.counter_value("serve.cache_miss")
    gc0 = profiling.counter_value(profiling.SERVE_GROUP_COMPILES)
    seg0 = profiling.counter_value(profiling.SERVE_GROUP_SEGMENT_ROWS)
    stk0 = profiling.counter_value(profiling.SERVE_GROUP_STACKED_ROWS)
    shq0 = profiling.counter_value(profiling.SERVE_GROUP_QUANTIZE_SHARED)
    t0 = time.monotonic()
    catalog = ModelCatalog(models, params={"verbose": -1},
                           max_batch_rows=MAX_BATCH,
                           flush_deadline_ms=2.0, replicas=REPLICAS,
                           warmup_buckets=warm, costack=costack,
                           costack_kernel=KERNEL)
    build_s = time.monotonic() - t0
    try:
        parity = {}
        for tid in tenant_ids:
            _t, fut = catalog.submit(Xfix, kind="value", model_id=tid)
            parity[tid] = np.asarray(fut.result())
        steady0 = profiling.counter_value("serve.cache_miss")
        load = _closed_loop(catalog, tenant_ids, X)
        steady_misses = (profiling.counter_value("serve.cache_miss")
                         - steady0)
        rec = {
            "costack": costack,
            "build_s": round(build_s, 2),
            "compiled_executables": (profiling.counter_value(
                "serve.cache_miss") - miss0),
            "steady_state_misses": steady_misses,
            "load": load,
        }
        if costack:
            rec["groups"] = len(catalog._groups)
            rec["group_compiles"] = (profiling.counter_value(
                profiling.SERVE_GROUP_COMPILES) - gc0)
            # which traversal actually served the window: the resolved
            # kernel per group plus the canonical row counters' deltas
            # (segment vs stacked are mutually exclusive per group)
            rec["costack_kernel"] = sorted({
                g.current().costack_kernel
                for g in catalog._groups.values()})
            rec["segment_rows"] = (profiling.counter_value(
                profiling.SERVE_GROUP_SEGMENT_ROWS) - seg0)
            rec["stacked_rows"] = (profiling.counter_value(
                profiling.SERVE_GROUP_STACKED_ROWS) - stk0)
            rec["quantize_shared_rows"] = (profiling.counter_value(
                profiling.SERVE_GROUP_QUANTIZE_SHARED) - shq0)
            rec["group_stats"] = catalog.group_stats()
        if sanitize_enabled():
            # single-threaded steady-state probe (the transfer guard is
            # thread-local, so the flusher threads can't be guarded):
            # one unguarded call settles state, then every step must
            # run retrace-free and transfer-free on the warm bucket
            half = ROWS_PER_REQ // 2
            Xa = np.ascontiguousarray(X[:half], np.float64)
            Xb = np.ascontiguousarray(X[half:2 * half], np.float64)
            san = HotPathSanitizer(warmup=1, label=san_label)
            if costack and catalog._groups:
                rt = next(iter(catalog._groups.values())).current()
                jobs = [(0, Xa), (1, Xb)]       # a REAL mixed batch
                rt.predict_mixed(jobs, "value")
                with san:
                    for _ in range(6):
                        with san.step():
                            rt.predict_mixed(jobs, "value")
            else:
                rt = catalog.get(tenant_ids[0]).registry.current()
                Xq = np.ascontiguousarray(X[:ROWS_PER_REQ], np.float64)
                rt.predict(Xq)
                with san:
                    for _ in range(6):
                        with san.step():
                            rt.predict(Xq)
            san_rec[san_label] = san.report()
            sans.append(san)
        return rec, parity
    finally:
        catalog.close()


def main() -> None:
    from lightgbm_tpu.diagnostics import locksan

    t_train0 = time.monotonic()
    fits, X = _train_fits()
    train_s = time.monotonic() - t_train0
    Xfix = np.ascontiguousarray(X[:ROWS_PER_REQ], np.float64)
    warm = []
    b = ROWS_PER_REQ
    while b <= MAX_BATCH:
        warm.append(b)
        b <<= 1
    warm = tuple(warm) or (ROWS_PER_REQ,)

    sans = []
    san_rec = {}
    scales = {}
    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        paths = {}
        for n in sorted(set(TENANT_COUNTS)):
            for i in range(n):
                tid = f"t{i}"
                if tid not in paths:
                    path = os.path.join(tmp, f"{tid}.txt")
                    fits[i % DISTINCT].save_model(path)
                    paths[tid] = path
        for n in TENANT_COUNTS:
            tenant_ids = [f"t{i}" for i in range(n)]
            models = {tid: paths[tid] for tid in tenant_ids}
            off, ref = _run_side(models, tenant_ids, X, Xfix, False,
                                 warm, f"mt{n}-solo", sans, san_rec)
            on, got = _run_side(models, tenant_ids, X, Xfix, True,
                                warm, f"mt{n}-costack", sans, san_rec)
            mismatch = [tid for tid in tenant_ids
                        if not np.array_equal(ref[tid], got[tid])]
            ratio = (off["compiled_executables"]
                     / max(on["compiled_executables"], 1))
            scales[str(n)] = {
                "tenants": n,
                "solo": off,
                "costack": on,
                "compile_ratio": round(ratio, 2),
                "parity": "bitwise" if not mismatch else
                          f"MISMATCH:{mismatch[:3]}",
            }
            if mismatch:
                failures.append(f"{n} tenants: co-stack answers diverge "
                                f"from solo dispatch for {mismatch[:3]}")
            if REQUIRE_RATIO and n >= 10 and ratio < REQUIRE_RATIO:
                failures.append(
                    f"{n} tenants: compile ratio {ratio:.2f} < required "
                    f"{REQUIRE_RATIO}")
            for side, rec in (("solo", off), ("costack", on)):
                if "error" in rec["load"]:
                    failures.append(f"{n} tenants ({side}): load failed "
                                    f"{rec['load']['error']}")
                elif rec["steady_state_misses"]:
                    failures.append(
                        f"{n} tenants ({side}): "
                        f"{rec['steady_state_misses']} request-path "
                        "compiles after warmup")
            if (REQUIRE_P99 and n >= 100 and "error" not in on["load"]
                    and "error" not in off["load"]
                    and on["load"]["p99_ms"]
                    > off["load"]["p99_ms"] * REQUIRE_P99):
                failures.append(
                    f"{n} tenants: co-stack p99 {on['load']['p99_ms']}ms "
                    f"> solo {off['load']['p99_ms']}ms * {REQUIRE_P99}")

    top = str(max(TENANT_COUNTS))
    out = {
        "metric": f"cross-model co-stack serving A/B "
                  f"({'+'.join(str(n) for n in TENANT_COUNTS)} tenants): "
                  f"compiled-executable ratio solo/costack at "
                  f"{top} tenants",
        "value": scales[top]["compile_ratio"],
        "unit": "x",
        "train_s": round(train_s, 1),
        "model": {"trees": TREES, "num_leaves": LEAVES,
                  "max_depth": DEPTH, "distinct_fits": DISTINCT},
        "scales": scales,
    }
    if san_rec:
        out["sanitize"] = san_rec
    if locksan.armed():
        out["locksan"] = locksan.report()
    line = json.dumps(out)
    print(line)
    dest = os.environ.get("SERVE_MT_OUT", "")
    if dest:
        with open(dest, "w") as f:
            f.write(line + "\n")
    for msg in failures:
        print(f"FAIL: {msg}", file=sys.stderr)
    if failures:
        raise SystemExit(1)
    for san in sans:
        san.check()     # fail AFTER the JSON so counters are recorded
    if locksan.armed():
        locksan.check()  # 0 lock-order cycles across the whole window


if __name__ == "__main__":
    main()
