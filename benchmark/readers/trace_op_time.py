"""Device time of the operations a metric claims, from the profiler trace, in
milliseconds per traced iteration (mean over the devices).

A metric's arguments are patterns (`harness/trace.claims`): `module`, `op`,
`opcode`, `not_op`.  All metrics of this reader share out the trace together:
an event's self time goes to the first metric, in the manifest's order, that
claims it, so their values and the unmatched remainder add up to the device's
busy time.  The remainder is printed on a line before the result.
"""
import json

from benchmark.harness import trace


def prepare(metrics, run):
    tr = run["trace"]
    if tr is None or not tr.devices:
        return
    owned, unmatched, orphans = trace.partition(tr, metrics)
    run["trace_op_time"] = owned
    top = sorted(orphans.items(), key=lambda kv: -kv[1])[:10]
    print(json.dumps({
        "phase": "trace", "iters": run["iters"],
        "busy_ms_per_iter": tr.busy_ns() / 1e6 / run["iters"],
        "claimed_ms_per_iter": {k: v / 1e6 / run["iters"]
                                for k, v in owned.items()},
        "unmatched_ms_per_iter": unmatched / 1e6 / run["iters"],
        "unmatched_top": [[k, v / 1e6 / run["iters"]] for k, v in top],
    }), flush=True)


def read(name, args, run):
    owned = run.get("trace_op_time")
    if owned is None:
        return None
    return owned[name] / 1e6 / run["iters"]
