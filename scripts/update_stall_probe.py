"""Runs the benchmark command in this process with host stamps around it,
to tell a late `Booster.update()` of the window by its cause: the host
(a Python garbage collection, page faults, CPU time taken elsewhere) or
the device (the build itself ran longer).

    python3 scripts/update_stall_probe.py --workload bosch.full \
        --seed 4500001801 --seconds 30 --trace 0

Same arguments as `benchmark/run.py`, whose lines it prints unchanged;
then one more JSON line, `{"probe": ...}`, with

- `gc`: every collection of 5 ms or more, `[generation, start, seconds,
  collected]`;
- `update`: every `Booster.update()`, `[k, enter, leave]`, each stamp
  `[t, user CPU s, system CPU s, minor faults, major faults, voluntary
  and involuntary context switches]`;
- `done`: `[k, t]`, when the packed record of the build that update k
  enqueued was ready on the device, seen from a watcher thread that
  blocks on it (the update after it fetches the same record).

Times are seconds from the start of this script.  A late update whose
`done` of the previous build is on time and whose gap holds a long
collection or CPU time is the host's; one whose previous build finished
late is the device's.
"""
import gc
import json
import os
import queue
import resource
import sys
import threading
import time

T0 = time.perf_counter()


def main(argv) -> int:
    sys.path.insert(0, os.getcwd())
    collections, updates, done = [], [], []
    started = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        elif time.perf_counter() - started[0] >= 0.005:
            collections.append([info["generation"], started[0] - T0,
                                time.perf_counter() - started[0],
                                info["collected"]])

    def stamp():
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return [time.perf_counter() - T0, ru.ru_utime, ru.ru_stime,
                ru.ru_minflt, ru.ru_majflt, ru.ru_nvcsw, ru.ru_nivcsw]

    import lightgbm_tpu as lgb
    builds = queue.Queue()

    def watch():
        while True:
            k, arr = builds.get()
            if arr is None:
                return
            arr.block_until_ready()
            done.append([k, time.perf_counter() - T0])

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    real = lgb.Booster.update

    def update(self, *args, **kw):
        enter = stamp()
        out = real(self, *args, **kw)
        k = len(updates)
        updates.append([k, enter, stamp()])
        pending = getattr(self._gbdt, "_pending", None)
        if pending is not None:
            builds.put((k, pending[0]))
        return out

    gc.callbacks.append(on_gc)
    lgb.Booster.update = update
    try:
        from benchmark import run as bench_run
        rc = bench_run.main(argv)
    finally:
        lgb.Booster.update = real
        gc.callbacks.remove(on_gc)
        builds.put((None, None))
        watcher.join(timeout=60)
    print(json.dumps({"probe": argv, "rc": rc,
                      "objects": len(gc.get_objects()), "gc": collections,
                      "update": updates, "done": done}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
