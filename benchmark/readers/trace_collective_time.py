"""Device time inside collectives, from the profiler trace, in milliseconds
per traced iteration (mean over the devices).

A metric's arguments are the patterns of `harness/trace.claims` (`opcode`:
`^all-reduce`, `^reduce-scatter` ... which also match the `-start` / `-done`
forms an asynchronous collective runs as; `module`, `op`, `not_op` narrow it).
The value is the self time of the events they claim: a collective that waits
for the slowest device is busy on the others for as long.

Unlike `trace_op_time` this reader shares nothing out: its events stay with
the `trace_op_time` metric that owns their program, so its value lies inside
theirs and is not to be added to them.  A trace of one device, or one that
holds no such event, is nothing to read.
"""
from benchmark.harness import trace


def read(name, args, run):
    tr = run["trace"]
    if tr is None or len(tr.devices) < 2 or not run["iters"]:
        return None
    found, total = False, 0.0
    for dev in tr.devices:
        for op in dev.ops:
            if trace.claims(args, op):
                found = True
                total += op.self_ns
    if not found:
        return None
    return total / len(tr.devices) / 1e6 / run["iters"]
