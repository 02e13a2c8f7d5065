"""The whole command on the CPU at a tiny size, one run per kind.

The command itself refuses to run without a TPU.  The override that lets it
run here lives in this file: the device check, the peaks lookup and the memory
reading are replaced, the cell is cut to a few thousand rows and pinned to the
rounds learner (on the CPU `tree_growth=auto` picks another one), and the
binned files go to a temporary directory.  No number from these runs is a
measurement.
"""
import json

import pytest

from benchmark import run as bench_run
from benchmark.harness import dataset, device

TINY = {"rows": 6000, "valid_rows": 3000, "check_rows": 1000}


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(device, "require", lambda chips: {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())})
    monkeypatch.setattr(device, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(device, "peak_bytes", lambda: 1)
    monkeypatch.setattr(dataset, "CACHE_DIR", str(tmp_path))
    real = bench_run.load_json

    def tiny(*parts):
        doc = real(*parts)
        if parts[0] == "configs":
            doc["rows"] = TINY["rows"]
            doc["features"] = min(doc["features"], 40)
            doc["params"] = dict(doc["params"], num_leaves=15,
                                 min_sum_hessian_in_leaf=1.0,
                                 tree_growth="rounds")
        if parts[0] == "workloads":
            doc["valid_rows"] = TINY["valid_rows"]
            doc["check_rows"] = TINY["check_rows"]
        return doc

    monkeypatch.setattr(bench_run, "load_json", tiny)


def last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


def manifest():
    with open(bench_run.os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_end_to_end_line(cell, on_cpu, capsys):
    assert bench_run.main(["--workload", cell, "--seed", "2147483659",
                           "--seconds", "2", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    want = bench_run.metrics_of(manifest(), "end_to_end", cell)
    assert list(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    by_phase = {f["phase"]: f for f in facts}
    assert by_phase["dataset"]["how"] == "binned"
    assert by_phase["window"]["compile_in_window"]["programs"] == 0
    # a second run loads the binned file and sees the same test split
    assert bench_run.main(["--workload", cell, "--seed", "2147483659",
                           "--seconds", "1", "--trace", "0"]) == 0
    res2, facts2 = last_line(capsys)
    assert {f["phase"]: f for f in facts2}["dataset"]["how"] == "loaded"
    assert res2["metrics"]["valid_auc"] == res["metrics"]["valid_auc"]
    # another seed trains on the same rows, is judged on the same test split
    # and checks predictions on other rows
    assert bench_run.main(["--workload", cell, "--seed", "5",
                           "--seconds", "1", "--trace", "0"]) == 0
    res3, facts3 = last_line(capsys)
    by_phase3 = {f["phase"]: f for f in facts3}
    assert by_phase3["dataset"]["how"] == "loaded"
    assert (by_phase3["checks"]["valid_logloss_after_quality_iters"]
            == by_phase["checks"]["valid_logloss_after_quality_iters"])
    assert res3["metrics"]["valid_auc"] == res["metrics"]["valid_auc"]
    assert (by_phase3["held_out"]["check_sum"]
            != by_phase["held_out"]["check_sum"])


def test_four_chip_cell_on_four_virtual_devices(on_cpu, capsys):
    assert bench_run.main(["--workload", "higgs.data4", "--seed", "7",
                           "--seconds", "1", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    warm = {f["phase"]: f for f in facts}["warm"]
    assert warm["mesh"]["data"] == 4 and warm["store_devices"] == 4
    assert res["correct"] is True


def test_traced_run_reports_what_it_can_read(on_cpu, capsys):
    """A CPU trace has no `/device:TPU` plane, so the run ends at the check
    that a traced run saw device operations — after the job, the counters and
    the host-clock readers have run.  The trace reduction itself is tested in
    test_trace.py."""
    with pytest.raises(SystemExit, match="no device operation"):
        bench_run.main(["--workload", "higgs.full", "--seed", "7",
                        "--seconds", "1", "--trace", "1"])
    facts = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert {f["phase"]: f for f in facts}["window"]["iters"] == 2


def test_no_tpu_no_result(capsys):
    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "higgs.full", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert '"correct"' not in capsys.readouterr().out
