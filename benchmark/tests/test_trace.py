"""The trace reduction on a hand-written trace whose every number can be
worked out by hand (data/tiny_tpu.xspace.txt), and on a trace recorded on a
v5e chip (data/higgs_v5e.xspace.txt.gz: two iterations of Higgs 10.5M x 28,
PR 26, device lines and host annotations only, instruction texts cut to 200
characters)."""
import gzip
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import trace
from benchmark.readers import trace_idle, trace_op_time

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(name):
    from jax.profiler import ProfileData
    opener = gzip.open if name.endswith(".gz") else open
    with opener(os.path.join(DATA, name), "rt") as f:
        return trace.reduce_planes(ProfileData.from_text_proto(f.read()).planes)


ORDER = ["hist_kernel_ms_per_iter", "exchange_ms_per_iter",
         "learner_other_ms_per_iter", "boosting_ms_per_iter"]


def claimants():
    """The trace_op_time metrics with their patterns, as run.py hands them
    to the reader: narrower claims first."""
    return [(n, bench_run.load_json("metrics", n + ".json")["args"])
            for n in ORDER]


def test_manifest_lists_the_claimants_in_that_order():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    listed = [n for n in names if bench_run.load_json(
        "metrics", n + ".json")["reader"] == "trace_op_time"]
    assert listed == [n for n in ORDER if n in listed] and len(listed) >= 3


def test_split_instruction():
    assert trace.split_instruction(
        "%fusion.180 = s32[5250048,28]{0,1:T(8,128)} fusion(s32[28,10500000]"
        "{1,0:T(8,128)} %get-tuple-element.87)") == (
        "fusion.180", "fusion", "s32[5250048,28]")
    assert trace.split_instruction(
        "%while.156 = (s32[]{:T(128)}, s32[10500000]{0:T(1024)}) while((s32[]"
        "{:T(128)}) %x)") == ("while.156", "while", "(tuple)")
    assert trace.split_instruction("jit_f(123)") == ("jit_f", "", "")


def test_hand_written_trace():
    tr = load("tiny_tpu.xspace.txt")
    assert [d.name for d in tr.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert tr.window == (500.0, 10500.0)            # first update .. fetch end
    d0, d1 = tr.devices
    assert d0.busy == [(1000.0, 9000.0), (9500.0, 10000.0)]
    assert d1.busy == [(1000.0, 5000.0)]
    assert tr.busy_ns() == (8500 + 4000) / 2
    self_ns = {op.name: op.self_ns for op in d0.ops}
    assert self_ns == {"while.1": 2500.0, "fusion.2": 2000.0,
                       "hist_multileaf_masked.3": 3000.0,
                       "all-reduce.4": 500.0, "add.5": 500.0}
    assert {op.name: op.module for op in d0.ops}["add.5"] == "jit_f"
    owned, unmatched, orphans = trace.partition(tr, claimants())
    assert owned == {"hist_kernel_ms_per_iter": 1500.0,
                     "exchange_ms_per_iter": 250.0,
                     "learner_other_ms_per_iter": (4500 + 4000) / 2,
                     "boosting_ms_per_iter": 250.0}
    assert unmatched == 0 and not orphans
    assert sum(owned.values()) + unmatched == tr.busy_ns()
    # a metric nobody wrote patterns for shows as unmatched, never vanishes
    owned, unmatched, orphans = trace.partition(tr, claimants()[:2])
    assert sum(owned.values()) + unmatched == tr.busy_ns()
    assert orphans["jit__unknown/while.1"] == 1250.0
    assert trace.idle_gaps(tr) == [
        ["bench.fetch > $array.py:631 _value", 1000 / 1e9],
        ["bench.update", 500 / 1e9]]
    assert trace.top_ops(tr, 2) == [
        ["jit__unknown/fusion.9 fusion s32[8]", 2000 / 1e9],
        ["jit__unknown/hist_multileaf_masked.3 custom-call s32[4,8,256,256]",
         1500 / 1e9]]


def test_readers_on_the_hand_written_trace(capsys):
    run = {"trace": load("tiny_tpu.xspace.txt"), "iters": 2}
    trace_op_time.prepare(claimants(), run)
    line = json.loads(capsys.readouterr().out)
    assert line["unmatched_ms_per_iter"] == 0
    assert trace_op_time.read("hist_kernel_ms_per_iter", {}, run) \
        == 1500 / 1e6 / 2
    assert trace_idle.read("x", {}, run) == pytest.approx(37.5)
    assert trace_op_time.read("x", {}, {"trace": None}) is None
    assert trace_idle.read("x", {}, {"trace": None}) is None


def test_recorded_v5e_trace():
    tr = load("higgs_v5e.xspace.txt.gz")
    assert len(tr.devices) == 1
    assert [n for n, _, _ in tr.spans] == ["bench.update", "bench.update",
                                           "bench.fetch"]
    busy = tr.busy_ns()
    assert 0.999 < busy / tr.window_ns <= 1.0       # the chip never waited
    owned, unmatched, _ = trace.partition(tr, claimants())
    assert sum(owned.values()) + unmatched == pytest.approx(busy, rel=1e-9)
    assert unmatched == 0
    per_iter = {k: v / 2e6 for k, v in owned.items()}
    assert per_iter["hist_kernel_ms_per_iter"] == pytest.approx(945.5, abs=1)
    assert per_iter["learner_other_ms_per_iter"] == pytest.approx(11553, abs=5)
    assert per_iter["boosting_ms_per_iter"] == pytest.approx(7.6, abs=0.1)
    assert per_iter["exchange_ms_per_iter"] == 0
    assert trace.top_ops(tr, 1)[0][0].startswith("jit__unknown/fusion.838")
