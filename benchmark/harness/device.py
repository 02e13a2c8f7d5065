"""The device a run is on, as JAX reports it: a run that finds no TPU, or
fewer chips than the cell asks for, stops here with no result line."""
import json
import os


def require(chips: int) -> dict:
    from lightgbm_tpu.jaxutil import require_accelerator
    dev = require_accelerator()            # SystemExit on the CPU
    if dev["platform"] != "tpu":
        raise SystemExit(f"benchmark: platform {dev['platform']!r}, not a TPU")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, "
                         f"JAX reports {dev['count']}")
    return dev


def peaks(kind: str) -> dict:
    """The published peaks of this kind of device; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} "
                         "in harness/peaks.json")
    return table[kind]


def peak_bytes() -> int:
    """Most device memory the process held on its fullest device: the
    high-water mark of its buffers plus that of what its loaded programs
    reserve.

    The TPU runtime keeps the two apart.  `peak_bytes_in_use` counts buffers
    (the bin store, scores, results); a compiled program's temporaries (here
    the per-leaf histogram cache, the gather scratch, layout copies of the
    store) are reserved when it is loaded, stay reserved while it is, and show
    only under `bytes_reserved`.  The allocator's own events in the profiler
    trace say that both are gone for anyone else: `bytes_available` =
    `bytes_limit` - `bytes_allocated` - `bytes_reserved` to the byte.  The sum
    of the two marks can overstate the peak only by buffers that were freed
    before the largest program was loaded; `jobs/train.py` reads it right
    after the window, before the benchmark's own held-out evaluation, so the
    marks are the training job's.
    """
    import jax
    held = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise SystemExit("benchmark: the device reports no memory_stats()")
        held.append(stats["peak_bytes_in_use"]
                    + stats.get("peak_bytes_reserved", 0))
    return int(max(held))
