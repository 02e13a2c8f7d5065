"""Ingestion benchmark: streamed (out-of-core) vs monolithic dataset
construction — prints ONE JSON line and writes the committed artifact
(`bench_ingest_measured.json` via BENCH_INGEST_OUT).

The claim under test (sharded/ingest.py, ROADMAP #1): peak host memory
of `Dataset.from_stream` is bounded by `stream_chunk_rows` plus the
~1 byte/cell binned store — NOT by the raw [N, F] float64 matrix the
monolithic path materializes.  Each configuration runs in its own
SUBPROCESS so `ru_maxrss` (a process-lifetime high-water mark) is the
configuration's own peak, and the matrix crosses two dataset lengths
with two chunk sizes (the BENCH_STREAM_CHUNK_ROWS A/B):

- monolithic @ N and @ 4N: peak RSS grows ~linearly with N;
- streamed @ N and @ 4N: peak RSS stays ~flat (chunk + binned store);
- streamed @ small vs large chunk at 4N: the chunk-size knob moves the
  peak, N does not.

Rows are generated COUNTER-BASED (row i is a pure function of i, no
sequential RNG), so every configuration sees bitwise-identical data at
any chunking and the streamed store is asserted sha1-equal to the
monolithic one.  BENCH_SANITIZE=1 additionally trains a few iterations
on the streamed store under the hot-path sanitizer (0 retraces /
0 implicit transfers — the streamed store feeds the same compiled
kernels).

    BENCH_INGEST_ROWS   base N        (default 200_000)
    BENCH_STREAM_CHUNK_ROWS  the small chunk of the A/B (default 8192)
    BENCH_INGEST_OUT    artifact path (unset = print only)
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N_BASE = int(os.environ.get("BENCH_INGEST_ROWS", 200_000))
CHUNK_SMALL = int(os.environ.get("BENCH_STREAM_CHUNK_ROWS", 8192))
CHUNK_LARGE = max(CHUNK_SMALL * 8, 65536)
F = 28
SANITIZE = os.environ.get("BENCH_SANITIZE", "0") not in ("0", "", "false")


def gen_rows(lo: int, hi: int, f: int = F):
    """Rows [lo, hi) as a pure function of the row index (Box-Muller on
    two counter-hashed uniforms): bitwise identical under ANY chunking,
    so streamed and monolithic construction see the same data without
    either holding more than its own chunk."""
    import numpy as np
    i = np.arange(lo, hi, dtype=np.float64)[:, None]
    j = np.arange(f, dtype=np.float64)[None, :]
    u1 = np.modf(np.sin(i * 12.9898 + j * 78.233) * 43758.5453)[0] % 1.0
    u2 = np.modf(np.sin(i * 39.3461 + j * 11.135) * 24634.6345)[0] % 1.0
    u1 = np.abs(u1).clip(1e-12, 1 - 1e-12)
    X = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * np.abs(u2))
    w = np.sin(np.arange(f) * 0.7 + 0.3) / np.sqrt(f)
    noise = np.sqrt(-2.0 * np.log(np.abs(np.modf(
        np.sin(i[:, 0] * 7.13 + 3.7) * 15731.743)[0]).clip(1e-12, 1))) \
        * np.cos(2.0 * np.pi * i[:, 0] * 0.618)
    y = (X @ w + 0.5 * noise > 0).astype(np.float64)
    return X, y


def _peak_rss_mb() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def worker(mode: str, rows: int, chunk: int) -> None:
    """One configuration in a fresh process; prints its own JSON."""
    import hashlib
    import numpy as np
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset

    cfg = config_from_params({"verbose": -1, "stream_chunk_rows": chunk})
    t0 = time.perf_counter()
    if mode == "monolithic":
        X, y = gen_rows(0, rows)
        ds = Dataset(X, y, config=cfg)
        bins, n = ds.bins, ds.num_data
    else:
        def chunks():
            for lo in range(0, rows, chunk):
                hi = min(lo + chunk, rows)
                Xc, yc = gen_rows(lo, hi)
                yield (Xc, yc)
        ds = Dataset.from_stream(chunks, cfg)
        bins, n = ds.bins[:, : ds.num_data], ds.num_data
    dt = time.perf_counter() - t0
    print(json.dumps({
        "mode": mode, "rows": int(n), "chunk_rows": chunk,
        "ingest_seconds": round(dt, 3),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "bins_sha1": hashlib.sha1(
            np.ascontiguousarray(bins).tobytes()).hexdigest()[:16],
    }))


def run_config(mode: str, rows: int, chunk: int) -> dict:
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker", mode,
         str(rows), str(chunk)],
        capture_output=True, text=True, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"worker {mode}/{rows}/{chunk} failed:\n"
                           f"{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> None:
    results = {
        "monolithic_n1": run_config("monolithic", N_BASE, CHUNK_SMALL),
        "monolithic_n4": run_config("monolithic", 4 * N_BASE, CHUNK_SMALL),
        "stream_small_n1": run_config("stream", N_BASE, CHUNK_SMALL),
        "stream_small_n4": run_config("stream", 4 * N_BASE, CHUNK_SMALL),
        "stream_large_n4": run_config("stream", 4 * N_BASE, CHUNK_LARGE),
    }
    # bitwise: within the bin-construction sample budget (N_BASE <=
    # bin_construct_sample_cnt) the streamed store equals the
    # monolithic one — the documented contract.  Beyond the budget the
    # mappers are sketch-derived (eps rank guarantee) while the batch
    # path subsamples, so the 4N stores are recorded but not compared.
    assert results["stream_small_n1"]["bins_sha1"] == \
        results["monolithic_n1"]["bins_sha1"], \
        "streamed store differs from batch within the sample budget"

    mono_growth = (results["monolithic_n4"]["peak_rss_mb"]
                   / max(results["monolithic_n1"]["peak_rss_mb"], 1.0))
    stream_growth = (results["stream_small_n4"]["peak_rss_mb"]
                     / max(results["stream_small_n1"]["peak_rss_mb"], 1.0))
    saving = (results["monolithic_n4"]["peak_rss_mb"]
              / max(results["stream_small_n4"]["peak_rss_mb"], 1.0))

    san = None
    if SANITIZE:
        # streamed store must feed the training kernels at steady state
        # with 0 retraces / 0 implicit transfers, like any other store
        import lightgbm_tpu as lgb
        from lightgbm_tpu.config import config_from_params
        from lightgbm_tpu.dataset import Dataset
        from lightgbm_tpu.diagnostics.sanitize import HotPathSanitizer
        cfg = config_from_params({"verbose": -1,
                                  "stream_chunk_rows": CHUNK_SMALL})

        def chunks():
            for lo in range(0, 50_000, CHUNK_SMALL):
                hi = min(lo + CHUNK_SMALL, 50_000)
                yield gen_rows(lo, hi)
        inner = Dataset.from_stream(chunks, cfg).compacted()
        from lightgbm_tpu.capi import _wrap_inner
        train = _wrap_inner(inner, {"objective": "binary", "verbose": -1,
                                    "tree_growth": "rounds",
                                    "num_leaves": 31})
        bst = lgb.Booster({"objective": "binary", "verbose": -1,
                           "tree_growth": "rounds", "num_leaves": 31},
                          train)
        for _ in range(3):      # compile + pipelined-path warm (bench.py)
            bst.update()
        float(bst._gbdt.train_score.score.sum())
        sanitizer = HotPathSanitizer(warmup=1, label="ingest/streamed")
        with sanitizer:
            for _ in range(4):
                with sanitizer.step():
                    bst.update()
        san = sanitizer.report()

    out = {
        "metric": f"streamed-vs-monolithic ingestion, {N_BASE}x{F} and "
                  f"{4 * N_BASE}x{F}, chunks {CHUNK_SMALL}/{CHUNK_LARGE}",
        "results": results,
        "monolithic_rss_growth_n1_to_n4": round(mono_growth, 2),
        "streamed_rss_growth_n1_to_n4": round(stream_growth, 2),
        "streamed_vs_monolithic_rss_at_n4": round(saving, 2),
    }
    if san is not None:
        out["sanitize"] = san
    print(json.dumps(out))
    out_path = os.environ.get("BENCH_INGEST_OUT", "")
    if out_path:
        with open(os.path.join(ROOT, out_path) if not
                  os.path.isabs(out_path) else out_path, "w") as f:
            json.dump(out, f, indent=1)
    # gates AFTER the JSON printed: streamed peak must be bounded by the
    # chunk (near-flat in N) while monolithic grows with N
    assert stream_growth < mono_growth, (
        f"streamed RSS grew {stream_growth:.2f}x from N to 4N, "
        f"monolithic {mono_growth:.2f}x — streaming is not bounding "
        "peak memory")
    assert saving >= 1.5, (
        f"streamed peak RSS only {saving:.2f}x below monolithic at 4N")
    if san is not None:
        assert san["retraces_after_warmup"] == 0, san
        assert san["implicit_transfers"] == 0, san


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        worker(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()
