"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It holds no cell, configuration, metric, reader, generator or job by name:
`--workload` names `workloads/<cell>.json`, which names `configs/<config>.json`
and `jobs/<job>.py`; the configuration names `generators/<generator>.py`; and
each metric that `BENCHMARK.json` lists for the cell names
`metrics/<metric>.json`, which names `readers/<reader>.py` and gives it its
arguments.  A later PR adds files and manifest entries (README.md).

The last line of standard output is the result; the lines before it are
facts about the run, one JSON object each.  Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits with a code other
than 0.
"""
import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def say(**facts):
    """One line of facts about the run, stamped with the seconds since the
    process started."""
    facts["t"] = round(time.perf_counter() - T_START, 3)
    print(json.dumps(facts, default=float), flush=True)


def metrics_of(manifest: dict, section: str, cell: str):
    """Names of the section's metrics that this cell reports, in order."""
    return [m["name"] for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(names, run: dict) -> dict:
    """Each metric through its own reader.  Readers of one kind may first
    look at all their metrics together (`prepare`), e.g. to share out the
    device time so that no nanosecond is counted twice."""
    specs = [(n, load_json("metrics", n + ".json")) for n in names]
    readers = {}
    for name, spec in specs:
        readers.setdefault(spec["reader"], []).append((name, spec["args"]))
    modules = {r: importlib.import_module(f"benchmark.readers.{r}")
               for r in readers}
    for r, mine in readers.items():
        if hasattr(modules[r], "prepare"):
            modules[r].prepare(mine, run)
    out = {}
    for name, spec in specs:
        value = modules[spec["reader"]].read(name, spec["args"], run)
        if value is not None:       # nothing to read: left out of the line
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")

    from benchmark.harness import device, monitor, trace
    from lightgbm_tpu.jaxutil import enable_compile_cache
    job = importlib.import_module(f"benchmark.jobs.{cell['job']}")
    enable_compile_cache()
    compiles = monitor.CompileWatch()
    # Everything is imported by now and nothing has asked for a device: the
    # next call is the TPU runtime attaching to the chip.  That wait is the
    # machine's and not the program's, so `setup_s` leaves it out (PERF.md
    # section 2 has the runs); it is printed on the `start` line.
    t_asked = time.perf_counter()
    dev = device.require(int(cell["chips"]))
    attach_s = time.perf_counter() - t_asked
    peaks = device.peaks(dev["kind"])
    say(phase="start", workload=args.workload, seed=args.seed,
        seconds=args.seconds, trace=args.trace, device=dev,
        import_s=t_asked - T_START, attach_s=attach_s)

    run = job.run({"name": args.workload, "cell": cell, "config": config,
                   "seed": args.seed, "seconds": args.seconds,
                   "trace": bool(args.trace), "t_start": T_START,
                   "attach_s": attach_s, "say": say, "compiles": compiles})
    run.update(peaks=peaks, device=dev)

    section = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(metrics_of(manifest, section, args.workload), run)
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics,
              "device": dict(dev, memory_peak_bytes=run["memory"]["peak_bytes"])}
    if args.trace:
        tr = run["trace"]
        if tr is None or tr.busy_ns() <= 0:
            raise SystemExit("benchmark: the trace holds no device operation")
        result["device"].update(busy_s=tr.busy_ns() / 1e9,
                                window_s=tr.window_ns / 1e9)
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
