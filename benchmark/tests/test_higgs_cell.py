"""The second configuration, `higgs`, its cell `higgs.full` and the three
counter metrics that came with it (`feed_rows_per_iter`, `feed_fill`,
`partition_rows_per_iter`): the manifest lists them and they resolve by file
name; at a tiny size on the CPU the cell's traced run reads all three from the
program's counters under the gathered row feed, and under the masked one —
which copies no row — `feed_rows_per_iter` reads 0 and `feed_fill` finds
nothing to read; a program that has no such counter (the parent of the PR
that added them) reads as nothing and does not raise."""
import pytest

from benchmark import run as bench_run
from benchmark.readers import counter_delta, counter_ratio
from benchmark.tests.test_manifest import check, manifest
from benchmark.tests.test_rehearsal import TINY, on_cpu  # noqa: F401 (fixture)

NEW = ["feed_rows_per_iter", "feed_fill", "partition_rows_per_iter"]


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    m = manifest()
    check(m, bench_run.HERE)
    assert [c["name"] for c in m["configs"]] == ["epsilon", "higgs"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "higgs.full", "higgs", "full", 1)
    config = bench_run.load_json("configs", "higgs.json")
    assert (config["rows"], config["features"], config["reduced"]) == (
        10_500_000, 28, [])
    listed = m["per_layer"][-3:]
    assert [x["name"] for x in listed] == NEW
    for x in listed:                    # every cell reports them
        assert "workloads" not in x and x["source"] == "program_counter"
        assert (x["layer"], x["moves"]) == ("learner", "train_s_per_iter")
    for w in m["workloads"]:
        assert set(NEW) <= set(bench_run.metrics_of(m, "per_layer", w["name"]))


def traced_metrics(monkeypatch, hist_rows):
    """The per-layer metrics that a traced run of `higgs.full` reads here: on
    the CPU the trace holds no device operation, so the command stops after
    its readers have run, and what they returned is taken from them."""
    seen = {}
    tiny, real = bench_run.load_json, bench_run.read_metrics

    def pinned(*parts):
        doc = tiny(*parts)
        if parts[0] == "configs" and hist_rows:
            doc["params"] = dict(doc["params"], hist_rows=hist_rows)
        return doc

    def spy(names, run):
        seen.update(real(names, run))
        return seen

    monkeypatch.setattr(bench_run, "load_json", pinned)
    monkeypatch.setattr(bench_run, "read_metrics", spy)
    with pytest.raises(SystemExit, match="no device operation"):
        bench_run.main(["--workload", "higgs.full", "--seed", "3000000019",
                        "--seconds", "1", "--trace", "1"])
    return {k: v["value"] for k, v in seen.items()}


def test_gathered_feed_reports_all_three(on_cpu, monkeypatch, capsys):  # noqa: F811
    got = traced_metrics(monkeypatch, "gathered")
    assert got["feed_rows_per_iter"] > 0 and got["feed_rows_per_iter"] % 128 == 0
    assert 0 < got["feed_fill"] <= 100
    assert got["partition_rows_per_iter"] == (
        got["build_rounds_per_iter"] * TINY["rows"])
    # the root streams every row, each other launch copies its tier
    assert got["hist_rows_per_iter"] == (
        TINY["rows"] + got["feed_rows_per_iter"])


def test_masked_feed_copies_no_row(on_cpu, monkeypatch, capsys):  # noqa: F811
    got = traced_metrics(monkeypatch, None)      # the CPU's default feed
    assert got["feed_rows_per_iter"] == 0 and "feed_fill" not in got
    assert got["partition_rows_per_iter"] == (
        got["build_rounds_per_iter"] * TINY["rows"])


def test_a_program_without_the_counters_reads_as_nothing():
    run = {"iters": 2, "counters": {"tree/hist_rows_touched": 8192.0,
                                    "tree/rounds": 6.0}}
    readers = {"counter_delta": counter_delta, "counter_ratio": counter_ratio}
    for name in NEW:
        spec = bench_run.load_json("metrics", name + ".json")
        assert readers[spec["reader"]].read(name, spec["args"], run) is None
