"""The third configuration, `criteo_tb`, its four-chip cell `criteo_tb.data4`
and the five per-layer metrics restricted to it: the manifest lists them and
they resolve by file name with no other file touched; on four virtual devices
at a tiny row count the whole command runs the cell through the data-parallel
learner and its counter metrics read what the program counted; the two trace
readers that came with it are checked on a hand-written trace of four devices
(data/tiny_mesh4.xspace.txt) whose every number can be worked out by hand; and
a program that has no such counter, or a trace of one device, reads as nothing
and does not raise."""
import pytest

from benchmark import run as bench_run
from benchmark.readers import (counter_delta, trace_busy_skew,
                               trace_collective_time)
from benchmark.tests.test_manifest import check, manifest
from benchmark.tests.test_rehearsal import last_line, on_cpu  # noqa: F401
from benchmark.tests.test_trace import load

CELL = "criteo_tb.data4"
NEW = ["exchange_bytes_per_iter", "split_records_bytes_per_iter",
       "collective_ms_per_iter", "collectives_per_iter", "shard_busy_skew"]


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    m = manifest()
    check(m, bench_run.HERE)
    assert [c["name"] for c in m["configs"]][:3] == [
        "epsilon", "higgs", "criteo_tb"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "criteo_tb", "data4", 4)
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    config = bench_run.load_json("configs", "criteo_tb.json")
    assert (config["rows"], config["features"]) == (54_000_000, 67)
    assert config["reduced"] == ["rows"] and "rows" in config["reduced_why"]
    assert {"data", "click_rate", "min_data_in_leaf",
            "histogram_dtype"} <= set(config["assumed"])
    body = bench_run.load_json("workloads", CELL + ".json")
    assert body["params"] == {"tree_learner": "data"}
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW:                    # this cell and no other reports them
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "train_s_per_iter"
    reported = bench_run.metrics_of(m, "per_layer", CELL)
    assert set(NEW) <= set(reported) and "feed_fill" not in reported
    for w in m["workloads"]:
        if w["name"] != CELL:
            got = bench_run.metrics_of(m, "per_layer", w["name"])
            assert not set(NEW) & set(got) and "feed_fill" in got
    assert bench_run.metrics_of(m, "end_to_end", CELL) == [
        x["name"] for x in m["end_to_end"]]


def test_the_cell_on_four_virtual_devices(on_cpu, monkeypatch,  # noqa: F811
                                          capsys):
    """End to end at 6,000 rows: the data-parallel learner, the store on
    four devices, `correct`; then the traced run's counter metrics (the
    command stops at the device-trace check, after the readers have run)."""
    assert bench_run.main(["--workload", CELL, "--seed", "3600000011",
                           "--seconds", "1", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    warm = {f["phase"]: f for f in facts}["warm"]
    assert warm["mesh"]["data"] == 4 and warm["store_devices"] == 4
    assert warm["hist_exchange"] == "psum_scatter"
    assert warm["score_devices"] == 4                  # replicated
    assert res["correct"] is True and res["failed"] == 0

    seen, real = {}, bench_run.read_metrics

    def spy(names, run):
        seen.update(real(names, run))
        return seen

    monkeypatch.setattr(bench_run, "read_metrics", spy)
    with pytest.raises(SystemExit, match="no device operation"):
        bench_run.main(["--workload", CELL, "--seed", "3600000012",
                        "--seconds", "1", "--trace", "1"])
    got = {k: v["value"] for k, v in seen.items()}
    rounds, passes = got["build_rounds_per_iter"], got["hist_passes_per_iter"]
    # 40 columns a rehearsal keeps, 15 leaves: one slot chunk of 15
    cols, K, B = 40 // 4, 15, 256
    assert passes == rounds + 1
    assert got["exchange_bytes_per_iter"] == 4.0 * cols * 3 * B * (
        1 + K * rounds)
    assert got["split_records_bytes_per_iter"] == 4.0 * 4 * 11 * (
        1 + 2 * K * rounds)
    assert got["collectives_per_iter"] == 3 + 3 * rounds
    assert got["hist_rows_per_iter"] == passes * 6000
    # no device trace on the CPU: the trace readers found nothing to read
    assert "collective_ms_per_iter" not in got
    assert "shard_busy_skew" not in got and "feed_fill" not in got


def run_of(tr, iters=1):
    return {"trace": tr, "iters": iters, "counters": {}}


def test_collective_time_and_busy_skew_on_a_four_device_trace():
    tr = load("tiny_mesh4.xspace.txt")
    assert len(tr.devices) == 4
    args = bench_run.load_json("metrics", "collective_ms_per_iter.json")["args"]
    # device k: reduce-scatter 800 - 100 k, all-gather-start 50, -done 150,
    # the score program's all-gather 400 (cut at the window's end, 7000 ns)
    per_device = [800 - 100 * k + 50 + 150 + 400 for k in range(4)]
    assert trace_collective_time.read("x", args, run_of(tr)) == pytest.approx(
        sum(per_device) / 4 / 1e6)
    assert trace_collective_time.read("x", args, run_of(tr, 2)) == (
        pytest.approx(sum(per_device) / 4 / 1e6 / 2))
    # narrower patterns narrow it: the build's collectives alone
    build = dict(args, module=["^jit_build_tree"])
    assert trace_collective_time.read("x", build, run_of(tr)) == (
        pytest.approx(sum(p - 400 for p in per_device) / 4 / 1e6))
    # every device is busy 5000 ns: the kernel's skew hides in the wait
    # of the collective behind it
    busy = [sum(e - s for s, e in d.busy) for d in tr.devices]
    assert busy == [5000.0] * 4
    assert trace_busy_skew.read("x", {}, run_of(tr)) == 0.0
    # without the waits it shows: cut each device's first collective
    for k, d in enumerate(tr.devices):
        d.busy = [(1000.0, 1000.0 + 3000 + 100 * k)]
    assert trace_busy_skew.read("x", {}, run_of(tr)) == pytest.approx(
        100.0 * 300 / 3150)


def test_nothing_to_read_is_not_an_error():
    """One device (the accepted cells), no trace, or a program without the
    counter (the parent of the PR that added it)."""
    tr = load("tiny_tpu.xspace.txt")
    args = bench_run.load_json("metrics", "collective_ms_per_iter.json")["args"]
    two = run_of(tr)
    assert trace_collective_time.read("x", args, two) == pytest.approx(
        500 / 2 / 1e6)                           # its all-reduce, two devices
    assert trace_busy_skew.read("x", {}, two) == pytest.approx(
        100.0 * 4500 / 6250)
    tr.devices = tr.devices[:1]
    assert trace_collective_time.read("x", args, run_of(tr)) is None
    assert trace_busy_skew.read("x", {}, run_of(tr)) is None
    assert trace_collective_time.read("x", args, run_of(None)) is None
    assert trace_busy_skew.read("x", {}, run_of(None)) is None
    none = bench_run.load_json("metrics", "collectives_per_iter.json")["args"]
    assert counter_delta.read("x", none, {"counters": {"tree/rounds": 9.0},
                                          "iters": 1}) is None
