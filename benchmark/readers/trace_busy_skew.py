"""How unevenly the devices of a run were busy, from the profiler trace, in
percent: (busy time of the busiest device - busy time of the least busy one)
/ the mean busy time, inside the traced window.  Busy time is the union of a
device's `XLA Ops` intervals (`harness/trace.Device.busy`).  A trace of one
device is nothing to read."""


def read(name, args, run):
    tr = run["trace"]
    if tr is None or len(tr.devices) < 2:
        return None
    busy = [sum(e - s for s, e in d.busy) for d in tr.devices]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
