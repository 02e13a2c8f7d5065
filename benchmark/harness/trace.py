"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read.  Nothing here names a metric or a kernel: which device operations a
metric claims is a list of patterns in that metric's own file.

What a TPU trace holds (read by hand from a Higgs iteration on a v5e, PR 26):
one plane per chip, `/device:TPU:<n>`, with the lines

  `XLA Modules`   one event per executed program, named `jit_<fn>(<hash>)`
  `XLA Ops`       one event per HLO instruction, named by the instruction's
                  text (`%fusion.180 = s32[5250048,28]{0,1:T(8,128)} fusion(...`).
                  Control flow nests: a `while` or `conditional` event covers
                  the events of its body.  A Pallas call is a `custom-call`
                  named after the jitted function that holds it.
  `Async XLA Ops` copies in flight beside the ops (`copy-start` … `copy-done`);
                  they overlap the `XLA Ops` line and are not busy time here.

and `/host:CPU` with a line `python` that holds `jax.profiler.TraceAnnotation`
spans and the Python tracer's frames, on the same clock as the device lines.

Busy time of a device is the union of its `XLA Ops` events inside the traced
window.  Every event is also given its self time — its duration less what the
events nested in it cover — so that self times add up to the busy time exactly
and each nanosecond has one owner.
"""
import bisect
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE, HOST_LINE = "XLA Ops", "XLA Modules", "python"
INSTRUCTION = re.compile(
    r"^%?([^\s=(]+)(?:\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\()?")


@dataclass
class Op:
    """One device event: `name` the HLO instruction's name, `opcode` its
    operation (`fusion`, `custom-call`, `while` …), `shape` its result
    without the layout, times in nanoseconds."""
    name: str
    opcode: str
    shape: str
    module: str
    start: float
    end: float
    self_ns: float = 0.0

    @property
    def label(self):
        return f"{self.module}/{self.name}"


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)
    busy: list = field(default_factory=list)     # merged (start, end)


@dataclass
class Trace:
    devices: list
    spans: list          # (name, start, end) of the benchmark's annotations
    frames: list         # (name, start, end) of every other host event
    window: tuple        # (start, end) of the traced iterations

    @property
    def window_ns(self):
        return self.window[1] - self.window[0]

    def busy_ns(self):
        """Busy time inside the window, mean over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in d.busy)
                   for d in self.devices) / len(self.devices)


def split_instruction(text: str):
    """`%fusion.180 = s32[8]{0} fusion(...)` -> ("fusion.180", "fusion",
    "s32[8]"); a tuple result -> "(tuple)"; a bare name (`jit_f(123)`) ->
    (name, "", "")."""
    m = INSTRUCTION.match(text)
    if not m:
        return text, "", ""
    shape = m.group(2) or ""
    shape = "(tuple)" if shape.startswith("(") else shape.split("{")[0]
    return m.group(1), m.group(3) or "", shape


def _self_times(ops):
    """Ops sorted by (start, -duration): a stack of open events; each event
    takes its duration from its parent's self time."""
    stack = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        op.self_ns = op.end - op.start
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(op.end, parent.end) - op.start
        stack.append(op)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, span_prefix)


def _host_events(planes, span_prefix):
    """-> (the benchmark's annotations, every other frame of the `python`
    line), each (name, start, end)."""
    spans, frames = set(), []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                rec = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name.startswith(span_prefix):
                    spans.add(rec)      # whichever thread's line holds it
                elif line.name == HOST_LINE:
                    frames.append(rec)
    return sorted(spans, key=lambda r: r[1]), frames


def _device(plane, window) -> Device:
    lines = {line.name: line for line in plane.lines}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(")[0])
                  for ev in (lines[MODULES_LINE].events
                             if MODULES_LINE in lines else ()))
    mod_starts = [m[0] for m in mods]
    dev = Device(plane.name)
    for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
        s = max(ev.start_ns, window[0])
        e = min(ev.start_ns + ev.duration_ns, window[1])
        if e <= s:
            continue
        i = bisect.bisect_right(mod_starts, ev.start_ns) - 1
        module = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] else "?"
        name, opcode, shape = split_instruction(ev.name)
        dev.ops.append(Op(name, opcode, shape, module, s, e))
    dev.ops.sort(key=lambda op: (op.start, op.start - op.end))
    _self_times(dev.ops)
    dev.busy = _merge((op.start, op.end) for op in dev.ops)
    return dev


def reduce_planes(planes, span_prefix: str = "bench.") -> Trace:
    """The window is the benchmark's annotations, first start to last end,
    and events are cut to it; a trace without annotations is taken whole."""
    planes = list(planes)
    spans, frames = _host_events(planes, span_prefix)
    window = ((spans[0][1], max(e for _, _, e in spans)) if spans
              else (float("-inf"), float("inf")))
    devices = sorted((_device(p, window) for p in planes
                      if DEVICE_PLANE.match(p.name)), key=lambda d: d.name)
    ops = [op for d in devices for op in d.ops]
    if not spans and ops:
        window = (min(op.start for op in ops), max(op.end for op in ops))
    return Trace(devices, spans, frames, window)


def _matches(patterns, text):
    return any(re.search(p, text) for p in patterns)


def claims(args: dict, op: Op) -> bool:
    """Does a metric with these arguments claim this event?  `module` and
    `op` are lists of regular expressions (search, any one matches; a
    missing list matches all); `opcode` likewise; `not_op` excludes."""
    for key, text in (("module", op.module), ("op", op.name),
                      ("opcode", op.opcode)):
        if key in args and not _matches(args[key], text):
            return False
    if "not_op" in args and _matches(args["not_op"], op.name):
        return False
    return True


def partition(trace: Trace, claimants):
    """Give every event's self time to the first of `claimants`
    ((name, args) in the manifest's order) that claims it.  -> ({name: ns,
    mean over devices}, unmatched ns, {label: ns} of the unmatched)."""
    n = max(len(trace.devices), 1)
    owned = {name: 0.0 for name, _ in claimants}
    unmatched, orphans = 0.0, {}
    for dev in trace.devices:
        for op in dev.ops:
            for name, args in claimants:
                if claims(args, op):
                    owned[name] += op.self_ns / n
                    break
            else:
                unmatched += op.self_ns / n
                orphans[op.label] = orphans.get(op.label, 0.0) + op.self_ns / n
    return owned, unmatched, orphans


def top_ops(trace: Trace, k: int = 10):
    """The k device operations with most self time, [[label, seconds]],
    mean over devices; the label says module, instruction and opcode."""
    n = max(len(trace.devices), 1)
    total = {}
    for dev in trace.devices:
        for op in dev.ops:
            key = f"{op.label} {op.opcode} {op.shape}".strip()
            total[key] = total.get(key, 0.0) + op.self_ns / n
    return [[k_, v / 1e9] for k_, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10, named: int = 200):
    """Idle time of the first device inside the window, by what the host was
    doing: the benchmark's annotation that covers the gap's middle and, under
    it, the innermost host frame there.  [[label, seconds]], longest first;
    only the `named` longest gaps are looked up, the rest are summed as
    `(short gaps)`."""
    if not trace.devices:
        return []
    w0, w1 = trace.window
    busy = trace.devices[0].busy
    edges = [w0] + [t for s, e in busy for t in (s, e)] + [w1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    total = {}
    for dur, s, e in gaps[:named]:
        mid = (s + e) / 2
        span = next((n for n, a, b in trace.spans if a <= mid < b),
                    "(no annotation)")
        inner = min(((b - a, n) for n, a, b in trace.frames if a <= mid < b),
                    default=(0, ""))[1]
        label = f"{span} > {inner}" if inner else span
        total[label] = total.get(label, 0.0) + dur
    rest = sum(d for d, _, _ in gaps[named:])
    if rest:
        total["(short gaps)"] = rest
    return [[k_, v / 1e9] for k_, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
