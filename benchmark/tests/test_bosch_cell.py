"""The sixth configuration, `bosch`, its cell `bosch.full`, its job
`train_missing` and the two counter metrics that came with it
(`missing_splits_per_iter`, `missing_left_share`): the manifest lists them
and they resolve by file name with no other file touched; at a tiny row
count the whole command runs the cell — NaN bins, the rounds learner,
`correct` against walk_missing on rows holding NaN, against the train
score of training rows and against a two-sided numpy root split, each of
which turns false on a fault planted in the mechanism it reads — and the
traced run's counter metrics read the splits on features with a NaN bin;
a program
that has no such counter reads as nothing and does not raise, and a
program whose `Dataset.construct()` bins NaN as 0.0 (the parent of the PR
that added the cell) is ended at once, exit code 1, with the reason.  The
plain reference `walk_missing` routes every row without NaN as `walk`
does on a model without missing bits, and NaN by the reference's rule on
one with them."""
import json

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.generators import bosch
from benchmark.harness import dataset, walk, walk_missing
from benchmark.readers import counter_delta, counter_ratio
from benchmark.tests.test_manifest import check, manifest
from benchmark.tests.test_rehearsal import last_line, on_cpu  # noqa: F401

CELL = "bosch.full"
NEW = ("missing_splits_per_iter", "missing_left_share")


def test_the_manifest_lists_the_configuration_the_cell_and_the_metrics():
    m = manifest()
    check(m, bench_run.HERE)
    assert [c["name"] for c in m["configs"]][-1] == "bosch"
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bosch", "full", 1)
    assert [w["name"] for w in m["workloads"]][-1] == CELL
    config = bench_run.load_json("configs", "bosch.json")
    assert config["generator"] == "bosch" and config["reduced"] == []
    assert config["rows"] == config["published"]["rows"] == 1_183_747
    assert config["features"] == bosch.FEATURES == 968
    p = config["params"]
    assert p["min_sum_hessian_in_leaf"] == 1e-3 and "use_missing" not in p
    assert not {"enable_bundle", "sparse_store", "tree_growth",
                "hist_rows"} & set(p)
    body = bench_run.load_json("workloads", CELL + ".json")
    assert body["job"] == "train_missing" and body["params"] == {}
    assert (body["valid_rows"], body["check_rows"]) == (236_750, 50_000)
    assert {"who", "why_one_chip", "why_one_cell"} <= set(body["assumed"])
    assert [x["name"] for x in m["per_layer"][-2:]] == list(NEW)
    for x in m["per_layer"][-2:]:
        assert x["workloads"] == [CELL]
        assert (x["layer"], x["moves"], x["source"]) == (
            "learner", "train_s_per_iter", "program_counter")
    for w in m["workloads"]:
        got = bench_run.metrics_of(m, "per_layer", w["name"])
        assert all((n in got) == (w["name"] == CELL) for n in NEW)


def test_the_rows_hold_nan_at_the_source_share():
    X, y = bosch.make(20_000, bosch.FEATURES, (3, dataset.CHECK))
    assert X.dtype == np.float32 and X.shape == (20_000, 968)
    assert 0.78 < np.isnan(X).mean() < 0.84
    assert np.isnan(X).any(axis=0).all()          # every column holds NaN
    assert 0.003 < y.mean() < 0.009
    X2, _ = bosch.make(20_000, bosch.FEATURES, (3, dataset.CHECK))
    assert np.array_equal(np.isnan(X), np.isnan(X2))


def test_the_cell_end_to_end(on_cpu, monkeypatch, capsys):  # noqa: F811
    assert bench_run.main(["--workload", CELL, "--seed", "4300000011",
                           "--seconds", "1", "--trace", "0"]) == 0
    res, facts = last_line(capsys)
    by = {f["phase"]: f for f in facts}
    assert by["dataset"]["how"] == "binned"
    assert by["warm"]["learner"] == "RoundsTreeLearner"
    assert res["correct"] is True and res["failed"] == 0
    assert by["checks"]["walk_routing_flips"] == 0
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
    # the traced trees split on columns that hold NaN
    assert got[NEW[0]] > 0
    assert 0.0 <= got[NEW[1]] <= 100.0


def _plant(fault, monkeypatch):
    """Plants one fault in the program; -> the check that has to catch
    it."""
    import jax.numpy as jnp
    from lightgbm_tpu import tree
    from lightgbm_tpu.learner import rounds
    from lightgbm_tpu.ops import split
    if fault == "partition":      # NaN rows right, whatever the record says
        real = rounds.bundle_predicate_params

        def predicate(ftbl, feat, thr, is_cat, missing_bins=None,
                      default_left=None):
            if default_left is not None:
                default_left = jnp.zeros_like(default_left)
            return real(ftbl, feat, thr, is_cat, missing_bins, default_left)

        monkeypatch.setattr(rounds, "bundle_predicate_params", predicate)
        return "train_score_matches_walk"
    if fault == "predictor":      # a raw NaN as 0.0 at every numerical split
        monkeypatch.setattr(tree, "nan_goes_left", lambda dt, threshold: (
            np.asarray(threshold, np.float64) >= 0.0)
            & ~tree.is_categorical(np.asarray(dt, np.int64)))
        return "predict_matches_walk"
    real = split.split_gain_matrix  # the losing side of the NaN bin recorded

    def losing(*args, **kw):
        gain, GL, HL, CL, dl = real(*args, **kw)
        return gain, GL, HL, CL, None if dl is None else ~dl

    monkeypatch.setattr(split, "split_gain_matrix", losing)
    return "root_split_is_the_references"


@pytest.mark.parametrize("fault", ["partition", "predictor", "search"])
def test_a_planted_fault_turns_correct_false(fault, on_cpu, monkeypatch,
                                             capsys):  # noqa: F811
    """Each mechanism the cell names, broken in the program alone, reads
    as `correct` false through the job's own checks: the partition
    against the record's default side (the train score leaves the
    walk), a predictor that reads NaN as 0.0 (the predictions leave
    it), a search that records the losing side of the NaN bin (the
    root split is not the two-sided reference's)."""
    import jax
    catches = _plant(fault, monkeypatch)
    jax.clear_caches()              # no program traced before the fault
    try:
        assert bench_run.main(["--workload", CELL, "--seed", "4300000013",
                               "--seconds", "1", "--trace", "0"]) == 0
    finally:
        monkeypatch.undo()
        jax.clear_caches()          # nor one with it after this test
    res, facts = last_line(capsys)
    checks = {f["phase"]: f for f in facts}["checks"]
    assert res["correct"] is False
    assert checks[catches] is False
    # the others stand: the fault is what the check reads
    assert checks["train_rows_are_the_generators"] is True
    assert checks["iterations_ran"] is True


def test_a_program_without_the_counters_reads_as_nothing():
    a = bench_run.load_json("metrics", NEW[0] + ".json")["args"]
    assert counter_delta.read(NEW[0], a, {
        "counters": {"tree/rounds": 9.0}, "iters": 2}) is None
    b = bench_run.load_json("metrics", NEW[1] + ".json")["args"]
    assert counter_ratio.read(NEW[1], b, {"counters": {}}) is None
    assert counter_ratio.read(NEW[1], b, {"counters": {
        b["numerator"]: 3.0, b["denominator"]: 12.0}}) == 25.0


def test_a_program_that_bins_nan_as_zero_is_ended_with_the_reason(
        on_cpu, monkeypatch, capsys):  # noqa: F811
    """The parent's binning: NaN as 0.0, no NaN bin."""
    from lightgbm_tpu import binning
    monkeypatch.setattr(binning.BinMapper, "nan_bin", property(
        lambda self: -1))
    with pytest.raises(RuntimeError, match="bins NaN as 0.0"):
        dataset.generate(bench_run.load_json("configs", "bosch.json"),
                         1000, (0, dataset.TRAIN))
    with pytest.raises(RuntimeError, match="would train as zeros"):
        bench_run.main(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "0"])
    assert '"correct"' not in capsys.readouterr().out


def _model(seed, missing):
    """Model text of a few random trees over 3 features: numerical splits
    (with missing type NaN and either default side where `missing`) and
    one categorical split."""
    rng = np.random.default_rng(seed)
    trees = []
    for i in range(3):
        dt = np.array([0, 0, 0, 1])
        if missing:
            dt[:3] = rng.choice([8, 10], 3)
        trees.append("\n".join([
            f"Tree={i}", "num_leaves=5", "split_feature=0 1 2 2",
            "threshold=" + " ".join(f"{v:.17g}" for v in
                                    [rng.normal(), -0.25, 0.5, 3.0]),
            "decision_type=" + " ".join(map(str, dt)),
            "left_child=1 -1 3 -3", "right_child=2 -2 -4 -5",
            "leaf_value=" + " ".join(f"{v:.17g}" for v in rng.normal(
                size=5)), ""]))
    return "\n".join(trees) + "\nend of trees\n"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_walk_missing_agrees_with_walk_where_it_must(seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(400, 3))
    X[:, 2] = rng.integers(0, 6, 400) + rng.uniform(0, 0.9, 400)
    plain = _model(seed, missing=False)
    assert np.array_equal(
        walk.raw_margins(walk.parse_model(plain), X),
        walk_missing.raw_margins(walk_missing.parse_model(plain), X))
    # NaN: None-type splits compare it as 0.0, NaN-type ones send it to
    # their default side, categorical ones right
    Xn = X.copy()
    Xn[::3, 0] = np.nan
    Xn[1::3, 2] = np.nan
    for text in (plain, _model(seed, missing=True)):
        got = walk_missing.raw_margins(walk_missing.parse_model(text), Xn)
        want = np.zeros(len(Xn))
        for t in walk_missing.parse_model(text):
            for r, x in enumerate(Xn):
                node = 0
                while node >= 0:
                    d, thr = t["decision_type"][node], t["threshold"][node]
                    v = x[t["split_feature"][node]]
                    if d & 1:
                        left = not np.isnan(v) and int(v) == int(thr)
                    elif np.isnan(v):
                        left = bool(d & 2) if (d >> 2) & 3 == 2 else thr >= 0
                    else:
                        left = v <= thr
                    node = (t["left_child"] if left
                            else t["right_child"])[node]
                want[r] += t["leaf_value"][~node]
        assert np.allclose(got, want, rtol=0, atol=1e-12)


def test_the_grouped_root_histogram_is_split_references():
    """split_missing.histogram counts by (grad, hess) level pairs; its
    sums are split_reference.histogram's, one pair or many."""
    from benchmark.harness import split_missing, split_reference
    rng = np.random.default_rng(8)
    store = rng.integers(0, 40, (5, 3000)).astype(np.uint8)
    for gq, hq in ((np.where(rng.random(3000) < 0.01, -126, 127),
                    np.full(3000, 127)),
                   (rng.integers(-127, 128, 3000),
                    rng.integers(0, 128, 3000))):
        assert np.array_equal(
            split_missing.histogram(store, gq, hq, 64),
            split_reference.histogram(store, gq.astype(np.int64),
                                      hq.astype(np.int64), 64))
