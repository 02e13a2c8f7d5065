"""Host time inside the program's own spans, from the profiler trace, in
milliseconds per traced iteration.

The program marks its host phases with `jax.profiler.TraceAnnotation`s named
`lgbt.<phase>` (`lightgbm_tpu/profiling.phase`); they land on the main
thread's host line, on the clock of the device events, and `harness/trace.py`
keeps them among `Trace.frames` where that line is called `python` — under
the benchmark's own command it is `python3`, so on the chip there is nothing
to read until the harness keeps that line too (PERF.md section 7), and no
manifest entry uses this reader yet.  A metric's value is the time covered by
the spans named in `args["sum"]` less that of the spans named in
`args["minus"]` (spans nested in the first ones), cut to the traced window.
A program that writes no such span — or a span that `minus` names and the
trace lacks — is nothing to read.
"""


def _covered(trace, names) -> float:
    """Nanoseconds inside the window covered by the frames called one of
    `names`, or None when the trace holds no such frame."""
    w0, w1 = trace.window
    found, total = False, 0.0
    for name, start, end in trace.frames:
        if name in names:
            found = True
            total += max(0.0, min(end, w1) - max(start, w0))
    return total if found else None


def read(name, args, run):
    tr = run["trace"]
    if tr is None or not run["iters"]:
        return None
    total = _covered(tr, set(args["sum"]))
    less = _covered(tr, set(args["minus"])) if args.get("minus") else 0.0
    if total is None or less is None:
        return None
    return (total - less) / 1e6 / run["iters"]
