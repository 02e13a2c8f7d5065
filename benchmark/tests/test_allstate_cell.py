"""The fourth configuration, `allstate`, its cell `allstate.full` and the two
counter metrics that came with it (`unbundle_gather_elems_per_iter`,
`split_cells_per_iter`): the manifest lists them and they resolve by file name
with no other file touched; at a tiny row count and the FULL 4,228 columns the
whole command runs the cell — a scipy CSR matrix in, a plan with bundles, a
store of under 64 columns, the rounds learner, `correct` — and the traced
run's counter metrics read what the program counted; a program that has no
such counter (the parent of the PR that added them) reads as nothing and does
not raise, and is ended at once, exit code 1, where it is asked for the full
training set, which it would take longer to bin than a run may last."""
import json

import pytest
import scipy.sparse

from benchmark import run as bench_run
from benchmark.generators import allstate
from benchmark.harness import dataset
from benchmark.readers import counter_delta
from benchmark.tests.test_manifest import check, manifest
from benchmark.tests.test_rehearsal import last_line, on_cpu  # noqa: F401

CELL = "allstate.full"
NEW = ["unbundle_gather_elems_per_iter", "split_cells_per_iter"]


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    m = manifest()
    check(m, bench_run.HERE)
    assert [c["name"] for c in m["configs"]][:4] == [
        "epsilon", "higgs", "criteo_tb", "allstate"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "allstate", "full", 1)
    config = bench_run.load_json("configs", "allstate.json")
    assert (config["rows"], config["features"], config["reduced"]) == (
        12_184_290, 4228, [])
    pub = config["published"]
    assert pub["rows"] - pub["test_rows"] == config["rows"]
    assert "conflict" in config["guarantees"]
    assert {"data", "frequencies", "label", "csr", "held_out",
            "min_data_in_leaf", "histogram_dtype",
            "selectors"} <= set(config["assumed"])
    # what lgb.train gives a user: no selector set
    assert not {"enable_bundle", "sparse_store", "max_conflict_rate",
                "tree_growth"} & set(config["params"])
    body = bench_run.load_json("workloads", CELL + ".json")
    assert body["params"] == {} and body["valid_rows"] == 200_000
    assert {"who", "why_one_chip", "why_one_cell"} <= set(body["assumed"])
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert [x["name"] for x in m["per_layer"]][-2:] == NEW
    assert by_name[NEW[0]]["workloads"] == [CELL]
    assert "workloads" not in by_name[NEW[1]]       # every cell reports it
    for name in NEW:
        x = by_name[name]
        assert (x["layer"], x["moves"], x["source"]) == (
            "learner", "train_s_per_iter", "program_counter")
        spec = bench_run.load_json("metrics", name + ".json")
        assert spec["reader"] == "counter_delta"
    for w in m["workloads"]:
        got = bench_run.metrics_of(m, "per_layer", w["name"])
        assert NEW[1] in got and (NEW[0] in got) == (w["name"] == CELL)
    assert "feed_fill" not in bench_run.metrics_of(m, "per_layer", CELL)


@pytest.fixture
def full_width(on_cpu, monkeypatch):  # noqa: F811
    """The rehearsal's cut, but at the full 4,228 columns, and the training
    set (8,000 rows) above the generator's CSR bound."""
    tiny = bench_run.load_json

    def wide(*parts):
        doc = tiny(*parts)
        if parts[0] == "configs":
            doc["features"] = 4228
            doc["rows"] = 8000
        return doc

    monkeypatch.setattr(bench_run, "load_json", wide)
    monkeypatch.setattr(allstate, "CSR_ABOVE_ROWS", 5000)


def test_the_cell_end_to_end_at_the_full_width(full_width, monkeypatch,
                                               capsys):
    X, _ = allstate.make(8000, 4228, (0, dataset.TRAIN))
    assert scipy.sparse.isspmatrix_csr(X)
    assert bench_run.main(["--workload", CELL, "--seed", "3900000011",
                           "--seconds", "1", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    by = {f["phase"]: f for f in facts}
    assert by["dataset"]["how"] == "binned"
    warm = by["warm"]
    assert warm["learner"] == "RoundsTreeLearner"
    assert warm["store_shape"][0] < 64 and warm["store_devices"] == 1
    assert by["checks"]["sparse_fallbacks"] == 0
    assert res["correct"] is True and res["failed"] == 0
    assert list(res["metrics"]) == bench_run.metrics_of(
        manifest(), "end_to_end", CELL)

    seen, real = {}, bench_run.read_metrics

    def spy(names, run):
        seen.update(real(names, run))
        return seen

    monkeypatch.setattr(bench_run, "read_metrics", spy)
    with pytest.raises(SystemExit, match="no device operation"):
        bench_run.main(["--workload", CELL, "--seed", "3900000012",
                        "--seconds", "1", "--trace", "1"])
    facts = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert {f["phase"]: f for f in facts}["dataset"]["how"] == "loaded"
    got = {k: v["value"] for k, v in seen.items()}
    # every metric with no `workloads` list reads here but the device's
    want = set(bench_run.metrics_of(manifest(), "per_layer", CELL))
    assert want - set(got) == {
        "device_idle_share.train", "hist_kernel_ms_per_iter", "hist_roofline",
        "learner_other_ms_per_iter", "boosting_ms_per_iter",
        "hist_ops_roofline"}
    # 15 leaves: the root's slot, then one chunk a pass, searched twice
    slots = 1 + 2 * 15 * (got["hist_passes_per_iter"] - 1)
    cells = got["split_cells_per_iter"]
    assert cells % (slots * 256) == 0
    feats = cells / (slots * 256)
    # searched in feature space (8,000 rows leave rare levels unused)
    assert 8 * warm["store_shape"][0] < feats <= 4228
    assert got["unbundle_gather_elems_per_iter"] == 3 * cells


def test_a_program_without_the_counters_reads_as_nothing():
    for name in NEW:
        args = bench_run.load_json("metrics", name + ".json")["args"]
        assert counter_delta.read(name, args, {
            "counters": {"tree/rounds": 9.0}, "iters": 2}) is None
        assert counter_delta.read(name, args, {
            "counters": {args["counter"]: 8.0}, "iters": 2}) == 4.0
