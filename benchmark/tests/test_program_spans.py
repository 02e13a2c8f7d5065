"""The readers of what the program itself reports (PR 28), on a hand-written
trace whose every number can be worked out by hand
(data/tiny_lgbt.xspace.txt): the host spans `lgbt.*` that
`lightgbm_tpu/profiling.phase` writes, the named programs, and the `tree/*`
work counters.  On a program that has none of them — the parent of that PR,
the older hand-written trace — every one of these readers finds nothing and
does not raise."""
import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark import run as bench_run
from benchmark.harness import trace
from benchmark.readers import (counter_delta, counter_ratio,
                               hist_ops_roofline, trace_host_span,
                               trace_op_time)
from benchmark.tests.test_trace import DATA, claimants, load

NEW = ["host_dispatch_ms_per_iter", "host_wait_ms_per_iter",
       "build_rounds_per_iter", "hist_passes_per_iter", "hist_slot_fill",
       "hist_ops_roofline"]
READERS = {"trace_host_span": trace_host_span, "counter_ratio": counter_ratio,
           "hist_ops_roofline": hist_ops_roofline,
           "counter_delta": counter_delta}
# two trees of seven leaves, worked out in tests/test_tracing.py: three
# rounds and four launches a tree, 22 slots of which 7 held a leaf
COUNTERS = {"tree/rounds": 6.0, "tree/hist_passes": 8.0,
            "tree/hist_slots": 44.0, "tree/hist_live_slots": 14.0,
            "tree/hist_mxu_ops": 2.0 * 4096 * 8 * 8 * 256}
PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12}


def spec(name):
    return bench_run.load_json("metrics", name + ".json")


def read(name, run):
    s = spec(name)
    return READERS[s["reader"]].read(name, s["args"], run)


def test_the_counter_metrics_are_the_manifests_last_four():
    """The two host-span metrics have their files and this rehearsal but
    are not listed: `harness/trace.py` keeps the frames of a host line
    named `python` alone, and under the benchmark's own command
    (`python3 ...`) the main thread's line is `python3`, so on the chip
    their reader finds nothing to read (PERF.md section 7)."""
    with open(bench_run.ROOT + "/BENCHMARK.json") as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]]
    assert listed[-4:] == NEW[2:]
    assert not set(NEW[:2]) & set(listed)
    assert {spec(n)["source"] for n in NEW[:2]} == {"program_span"}
    assert {spec(n)["source"] for n in NEW[2:5]} == {"program_counter"}
    assert spec("hist_ops_roofline")["source"] == "device_trace"


def test_named_programs_fall_to_the_same_claimants():
    tr = load("tiny_lgbt.xspace.txt")
    assert tr.window == (500.0, 10500.0)
    assert {op.module for op in tr.devices[0].ops} == {
        "jit_build_tree_rounds", "jit_gradients_binary"}
    owned, unmatched, _ = trace.partition(tr, claimants())
    assert owned == {"hist_kernel_ms_per_iter": 3000.0,
                     "exchange_ms_per_iter": 0.0,
                     "learner_other_ms_per_iter": 3000.0 + 2000.0,
                     "boosting_ms_per_iter": 500.0}
    assert unmatched == 0


def test_host_spans_by_hand():
    """The trace names its host line `python3`, as the chip does, and
    `harness/trace.py` keeps the frames of a line `python` alone: so today
    the reader finds nothing, which is why the manifest lists neither
    metric.  The `benchmark` PR that mends `_host_events` turns the first
    three assertions into the numbers below them, which are the reader's
    on the same trace with the line called `python`."""
    tr = load("tiny_lgbt.xspace.txt")
    assert tr.frames == []
    assert read("host_dispatch_ms_per_iter", {"trace": tr, "iters": 2}) is None
    assert read("host_wait_ms_per_iter", {"trace": tr, "iters": 2}) is None

    with open(os.path.join(DATA, "tiny_lgbt.xspace.txt")) as f:
        text = f.read().replace('name: "python3"', 'name: "python"')
    tr = trace.reduce_planes(ProfileData.from_text_proto(text).planes)
    assert ("lgbt.wait_device", 2300.0, 8200.0) in tr.frames
    run = {"trace": tr, "iters": 2}
    # lgbt.update: 1300 + 7000 ns inside the window (the one at 100..400
    # lies before it and counts nothing); lgbt.wait_device: 5900 ns
    assert read("host_dispatch_ms_per_iter", run) == pytest.approx(
        (1300 + 7000 - 5900) / 1e6 / 2)
    assert read("host_wait_ms_per_iter", run) == pytest.approx(
        5900 / 1e6 / 2)
    # a span that straddles the window's start is cut to it
    tr.window = (1000.0, 10500.0)
    assert read("host_dispatch_ms_per_iter", run) == pytest.approx(
        (900 + 7000 - 5900) / 1e6 / 2)


def test_counters_by_hand(capsys):
    tr = load("tiny_lgbt.xspace.txt")
    run = {"trace": tr, "iters": 2, "counters": dict(COUNTERS),
           "peaks": PEAKS, "store": {"hist_itemsize": 1}}
    assert read("build_rounds_per_iter", run) == 3.0
    assert read("hist_passes_per_iter", run) == 4.0
    assert read("hist_slot_fill", run) == pytest.approx(100 * 14 / 44)
    assert read("hist_ops_roofline", run) is None     # no kernel time yet
    trace_op_time.prepare(claimants(), run)
    capsys.readouterr()
    # 134,217,728 operations at 393e12 a second are 341.5 ns of the
    # kernel's 3000
    least_s = 2.0 * 4096 * 8 * 8 * 256 / 393e12
    assert read("hist_ops_roofline", run) == pytest.approx(
        100 * least_s / 3000e-9)
    assert read("hist_ops_roofline", run) == pytest.approx(11.384, abs=1e-3)
    run["store"] = {"hist_itemsize": 2}                # bf16 operands
    assert read("hist_ops_roofline", run) == pytest.approx(
        100 * least_s * 393 / 197 / 3000e-9)


def test_a_program_without_spans_or_counters_reads_as_nothing(capsys):
    run = {"trace": load("tiny_tpu.xspace.txt"), "iters": 2,
           "counters": {"tree/hist_rows_touched": 8192.0}, "peaks": PEAKS,
           "store": {"hist_itemsize": 1}}
    trace_op_time.prepare(claimants(), run)
    capsys.readouterr()
    assert [read(n, run) for n in NEW] == [None] * 6
    run["trace"] = None
    assert [read(n, run) for n in NEW[:2]] == [None, None]
    # a denominator that did not move is nothing to read either
    run["counters"] = {"tree/hist_live_slots": 0.0, "tree/hist_slots": 0.0}
    assert read("hist_slot_fill", run) is None
