"""Microbenchmark the training hot path on the live chip.

Times each device op of the rounds learner in isolation at the
north-star shape — the masked multi-leaf histogram kernel in every
supported precision, the partition ops — then one full Booster.update,
so the gap between "sum of parts" and the whole iteration (host
orchestration, dispatch latency, fusion losses) is visible.  Writes
profile_hotpath_measured.json at the repo root (the committed MFU
evidence behind BASELINE.md's "honest bar" analysis).  Usage:

    python scripts/profile_hotpath.py [N] [F] [max_bin]
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N = int(sys.argv[1]) if len(sys.argv) > 1 else 10_500_000
F = int(sys.argv[2]) if len(sys.argv) > 2 else 28
MB = int(sys.argv[3]) if len(sys.argv) > 3 else 255
from lightgbm_tpu.learner.rounds import LEAVES_PER_BATCH as K  # noqa: E402

# v5e peak matmul throughput per chip (public spec: 394 TOPS int8,
# 197 TFLOP/s bf16) — the denominators for MXU utilization
PEAK = {"int8": 394e12, "bfloat16": 197e12, "float32": 49e12}


def _force(r):
    """Wait for r by FETCHING a scalar reduction of it."""
    import jax.numpy as jnp
    return float(jnp.sum(jnp.asarray(r).astype(jnp.float32)))


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    _force(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    # the device stream is serial: fetching the LAST result bounds all n
    # executions; one fetch RTT is amortized over n
    _force(r)
    return (time.perf_counter() - t0) / n


def exchange_ab(F: int, B: int, K: int) -> dict:
    """Per-pass timing A/B of the data-parallel histogram exchange at
    the north-star [K, F, 3, B] payload: full psum vs psum_scatter over
    the feature axis + the [ndev, K, 11] record allgather the scattered
    path adds (learner/rounds.py hist_exchange).  Runs over every
    visible device of the default backend; a single-device host records
    the skip so the chip-queue artifact is always written."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    ndev = len(jax.devices())
    rec = {"backend": jax.default_backend(), "n_devices": ndev,
           "K": K, "F": F, "B": B,
           "payload_mb": round(4.0 * K * F * 3 * B / 1e6, 2)}
    if ndev < 2:
        rec["skipped"] = True
        rec["reason"] = "single device: no exchange to measure"
        return rec
    Fp = ndev * ((F + ndev - 1) // ndev)
    mesh = Mesh(np.asarray(jax.devices()).reshape(ndev), ("data",))

    def ab_psum(h):
        return jax.lax.psum(h, "data")

    def ab_scatter(h):
        s = jax.lax.psum_scatter(h, "data", scatter_dimension=1,
                                 tiled=True)
        # the record exchange the scattered path pays per pass
        recs = jnp.sum(s, axis=(1, 2, 3))[:, None] * jnp.ones(11)
        return s, jax.lax.all_gather(recs, "data")

    f_psum = jax.jit(jax.shard_map(
        ab_psum, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False))
    f_scat = jax.jit(jax.shard_map(
        ab_scatter, mesh=mesh, in_specs=P(),
        out_specs=(P(None, "data"), P()), check_vma=False))
    h = jnp.asarray(np.random.RandomState(0).rand(
        K, Fp, 3, B).astype(np.float32))
    t_psum = timeit(lambda: f_psum(h))
    t_scat = timeit(lambda: f_scat(h)[0])
    rec["psum_ms"] = round(t_psum * 1e3, 3)
    rec["psum_scatter_ms"] = round(t_scat * 1e3, 3)
    rec["speedup"] = round(t_psum / t_scat, 3)
    rec["bytes_per_device_psum"] = 4 * K * Fp * 3 * B
    rec["bytes_per_device_psum_scatter"] = 4 * K * (Fp // ndev) * 3 * B
    print(f"hist exchange A/B [{K},{Fp},3,{B}] over {ndev} devices: "
          f"psum {t_psum*1e3:.2f} ms vs psum_scatter {t_scat*1e3:.2f} ms "
          f"({t_psum/t_scat:.2f}x)")
    return rec


def main():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked
    from lightgbm_tpu.ops.lookup import select_bin_by_feature, table_lookup

    from lightgbm_tpu.learner.common import padded_bin_count
    from lightgbm_tpu.jaxutil import require_accelerator
    device = require_accelerator()
    B = padded_bin_count(MB + 1)
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    print(f"device={device} N={N} F={F} B={B} K={K}")
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, MB, size=(F, N), dtype=np.int32))
    lid = jnp.asarray(rng.randint(0, 255, size=N, dtype=np.int32))
    gh8 = jnp.asarray(rng.randn(8, N).astype(np.float32))
    sl = jnp.asarray(np.arange(K, dtype=np.int32))

    rec = {"backend": jax.default_backend(), "device": device, "N": N,
           "F": F, "B": B, "K": K, "kernels": {}}
    try:
        rec["measured_at_commit"] = subprocess.run(
            ["git", "describe", "--always", "--dirty"], cwd=ROOT,
            capture_output=True, text=True).stdout.strip() or "unknown"
    except OSError:
        rec["measured_at_commit"] = "unknown"

    Mp = 8 * ((3 * K + 7) // 8)
    macs = float(N) * F * Mp * B  # one-hot contraction MACs per pass
    for dt in ("int8", "bfloat16", "float32"):
        # max_num_bin=MB engages the same feature-packing layout the
        # learner uses (2 features/lane-block at <=64 bins)
        t = timeit(lambda dt=dt: hist_multileaf_masked(
            bins, lid, gh8, sl, num_bins_padded=B, backend=backend,
            input_dtype=dt, max_num_bin=MB))
        util = 2 * macs / t / PEAK[dt]
        rec["kernels"][f"hist_multileaf_masked_K{K}_{dt}"] = {
            "ms": round(t * 1e3, 2),
            "effective_tops": round(2 * macs / t / 1e12, 1),
            "mxu_utilization": round(util, 3)}
        print(f"hist_multileaf_masked K={K} {dt}: {t*1e3:.1f} ms  "
              f"({2 * macs / t / 1e12:.0f} TOPS = "
              f"{util:.0%} of {dt} peak)")

    t1 = timeit(lambda: hist_multileaf_masked(
        bins, lid, gh8, jnp.asarray(np.arange(1, dtype=np.int32)),
        num_bins_padded=B, backend=backend, input_dtype="int8",
        max_num_bin=MB))
    rec["kernels"]["hist_multileaf_masked_K1_root"] = {
        "ms": round(t1 * 1e3, 2)}
    print(f"hist_multileaf_masked K=1 (root): {t1*1e3:.1f} ms")

    t2 = timeit(lambda: select_bin_by_feature(bins, lid % F))
    rec["kernels"]["select_bin_by_feature"] = {"ms": round(t2 * 1e3, 2)}
    print(f"select_bin_by_feature: {t2*1e3:.1f} ms")

    tbl = jnp.asarray(rng.randn(4, 256).astype(np.float32))
    t3 = timeit(lambda: table_lookup(tbl, lid, num_slots=256))
    rec["kernels"]["table_lookup_4x256"] = {"ms": round(t3 * 1e3, 2)}
    print(f"table_lookup [4,256]: {t3*1e3:.1f} ms")

    # fused partition (replaces the two ops above + the move) — a
    # realistic round table: every even leaf splits
    from lightgbm_tpu.ops.partition import partition_rows
    L = 255
    ptbl = np.zeros((4, L + 1), np.float32)
    ptbl[0, 0:L:2] = rng.randint(0, F, size=len(range(0, L, 2)))
    ptbl[1, 0:L:2] = rng.randint(0, MB, size=len(range(0, L, 2)))
    ptbl[3, 0:L:2] = rng.randint(1, L, size=len(range(0, L, 2)))
    ptbl = jnp.asarray(ptbl)
    t4 = timeit(lambda: partition_rows(bins, lid, ptbl, num_slots=L + 1,
                                       backend=backend,
                                       num_bins_padded=B))
    rec["kernels"]["partition_rows_fused"] = {"ms": round(t4 * 1e3, 2)}
    print(f"partition_rows (fused): {t4*1e3:.1f} ms")

    # data-parallel exchange A/B at the same [F, 3, B] shape — written
    # to its own artifact so the chip window captures the comms win (or
    # the single-chip skip) for free alongside the kernel profile
    ab = exchange_ab(F, B, K)
    ab["measured_at_commit"] = rec["measured_at_commit"]
    with open(os.path.join(ROOT, "hist_exchange_ab_measured.json"),
              "w") as f:
        json.dump(ab, f, indent=1)
    print("wrote hist_exchange_ab_measured.json")

    # full iteration at the same shape, bench-default precision
    import lightgbm_tpu as lgb
    import bench
    X, y = bench.synth_higgs(N, f=F)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 255,
              "learning_rate": 0.1, "max_bin": MB, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 100.0, "histogram_dtype": "int8"}
    ds = lgb.Dataset(X, y)
    bst = lgb.Booster(params, ds)
    for _ in range(3):
        bst.update()
    _force(bst._gbdt.train_score.score)
    # BENCH_SANITIZE=1: run the timed window under the hot-path
    # sanitizer — the zero-retrace / zero-implicit-transfer contract is
    # asserted on the same loop the MFU profile times, and the result
    # rides along in the committed artifact
    from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                                   sanitize_enabled)
    # BENCH_TRACE=<logdir>: xprof device trace of the same timed loop,
    # artifact dir recorded in the committed JSON (chip-queue windows
    # capture the device profile beside the MFU numbers for free)
    import contextlib
    from lightgbm_tpu import profiling
    trace_dir = os.environ.get("BENCH_TRACE", "")
    trace_ctx = (profiling.device_trace(trace_dir) if trace_dir
                 else contextlib.nullcontext())
    san = None
    t0 = time.perf_counter()
    with trace_ctx:
        if sanitize_enabled():
            san = HotPathSanitizer(warmup=1, label="profile_hotpath")
            with san:
                for _ in range(10):
                    with san.step():
                        bst.update()
        else:
            for _ in range(10):
                bst.update()
    _force(bst._gbdt.train_score.score)
    if trace_dir:
        rec["device_trace_dir"] = trace_dir
    full = (time.perf_counter() - t0) / 10
    rec["full_update_ms"] = round(full * 1e3, 1)
    if san is not None:
        rec["sanitize"] = san.report()
        print(f"sanitize: {san.retraces} retraces, "
              f"{san.implicit_transfers} implicit transfers "
              f"(over {san.steps} steps, warmup 1)")
    print(f"full update(): {full*1e3:.1f} ms/iter")

    # non-default shapes get their own artifact: the north-star MFU
    # profile (10.5M x 28 x 255) must not be clobbered by e.g. the
    # Epsilon-shape decomposition run
    at_default = (N == 10_500_000 and F == 28 and MB == 255)
    name = ("profile_hotpath_measured.json" if at_default
            else f"profile_{N}x{F}b{MB}_measured.json")
    with open(os.path.join(ROOT, name), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {name}")
    if san is not None:
        san.check()     # fail AFTER the artifact is written


if __name__ == "__main__":
    main()
