"""The fifth configuration, `airline`, its cell `airline.full` and the
counter metric that came with it (`categorical_splits_per_iter`): the
manifest lists them and they resolve by file name with no other file
touched; at a tiny row count the whole command runs the cell — six code
columns marked categorical by params alone, the rounds learner, `correct`
— and the traced run's counter metric reads the categorical splits the
program counted; a program that has no such counter reads as nothing and
does not raise, and a program whose `Dataset.construct(params)` drops
`categorical_feature` (the parent of the PR that added the cell) is ended
at once, exit code 1, with the reason."""
import json

import pytest

from benchmark import run as bench_run
from benchmark.generators import airline
from benchmark.harness import dataset
from benchmark.readers import counter_delta
from benchmark.tests.test_manifest import check, manifest
from benchmark.tests.test_rehearsal import last_line, on_cpu  # noqa: F401

CELL = "airline.full"
NEW = "categorical_splits_per_iter"


def test_the_manifest_lists_the_configuration_the_cell_and_the_metric():
    m = manifest()
    check(m, bench_run.HERE)
    assert [c["name"] for c in m["configs"]][:5] == [
        "epsilon", "higgs", "criteo_tb", "allstate", "airline"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "airline", "full", 1)
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    config = bench_run.load_json("configs", "airline.json")
    assert config["features"] == 13 and config["generator"] == "airline"
    assert config["reduced"] == next(
        c for c in m["configs"] if c["name"] == "airline")["reduced"]
    assert config["rows"] <= config["published"]["rows"] == 115_000_000
    cats = [int(c) for c in config["params"]["categorical_feature"].split(",")]
    assert tuple(cats) == airline.CATEGORICAL
    assert [config["published"]["columns"][c] for c in cats] == \
        config["published"]["categorical_in_the_reference_run"]
    # what lgb.train gives a user: no selector set
    assert not {"enable_bundle", "sparse_store", "tree_growth",
                "hist_rows"} & set(config["params"])
    body = bench_run.load_json("workloads", CELL + ".json")
    assert body["params"] == {} and body["valid_rows"] == 1_000_000
    assert {"who", "why_one_chip", "why_one_cell"} <= set(body["assumed"])
    x = m["per_layer"][-1]
    assert x["name"] == NEW and x["workloads"] == [CELL]
    assert (x["layer"], x["moves"], x["source"]) == (
        "learner", "train_s_per_iter", "program_counter")
    assert bench_run.load_json("metrics", NEW + ".json")["reader"] == \
        "counter_delta"
    for w in m["workloads"]:
        got = bench_run.metrics_of(m, "per_layer", w["name"])
        assert (NEW in got) == (w["name"] == CELL)


def test_the_cell_end_to_end(on_cpu, monkeypatch, capsys):  # noqa: F811
    assert bench_run.main(["--workload", CELL, "--seed", "4300000011",
                           "--seconds", "1", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    by = {f["phase"]: f for f in facts}
    assert by["dataset"]["how"] == "binned"
    assert by["warm"]["learner"] == "RoundsTreeLearner"
    assert by["warm"]["store_devices"] == 1
    assert res["correct"] is True and res["failed"] == 0
    assert list(res["metrics"]) == bench_run.metrics_of(
        manifest(), "end_to_end", CELL)

    seen, real = {}, bench_run.read_metrics

    def spy(names, run):
        seen.update(real(names, run))
        return seen

    monkeypatch.setattr(bench_run, "read_metrics", spy)
    with pytest.raises(SystemExit, match="no device operation"):
        bench_run.main(["--workload", CELL, "--seed", "4300000012",
                        "--seconds", "1", "--trace", "1"])
    facts = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert {f["phase"]: f for f in facts}["dataset"]["how"] == "loaded"
    got = {k: v["value"] for k, v in seen.items()}
    # both traced trees split on code columns
    assert got[NEW] > 0
    assert got[NEW] <= got["hist_passes_per_iter"] * 15


def test_a_program_without_the_counter_reads_as_nothing():
    args = bench_run.load_json("metrics", NEW + ".json")["args"]
    assert counter_delta.read(NEW, args, {
        "counters": {"tree/rounds": 9.0}, "iters": 2}) is None
    assert counter_delta.read(NEW, args, {
        "counters": {args["counter"]: 8.0}, "iters": 2}) == 4.0


def test_a_program_that_drops_the_key_is_ended_with_the_reason(
        on_cpu, monkeypatch, capsys):  # noqa: F811
    """The parent's `construct`: it reads the constructor's argument and
    pandas dtypes, and not params."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset.construct

    def drops(self, extra_params=None):
        if extra_params:
            extra_params = {k: v for k, v in extra_params.items()
                            if k not in ("categorical_feature",
                                         "categorical_column", "cat_feature",
                                         "cat_column")}
        return real(self, extra_params)

    monkeypatch.setattr(lgb.Dataset, "construct", drops)
    with pytest.raises(RuntimeError, match="categorical_feature"):
        dataset.generate(bench_run.load_json("configs", "airline.json"),
                         1000, (0, dataset.TRAIN))
    with pytest.raises(RuntimeError, match="would train as numbers"):
        bench_run.main(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "0"])
    assert '"correct"' not in capsys.readouterr().out
