"""`BENCHMARK.json` against the contract it was written to, as far as a file
check can go, and the resolution by name that `run.py` relies on: every cell,
configuration, metric, reader, generator and job that the manifest names is a
file under `benchmark/`.  The last test adds the cell `epsilon.goss` of the
README's worked example to a copy and shows that it resolves with one new file
and one manifest entry."""
import copy
import importlib
import json
import os
import re
import shutil

from benchmark import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def manifest():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def check(m: dict, bench_dir: str) -> None:
    def doc(*parts):
        with open(os.path.join(bench_dir, *parts)) as f:
            return json.load(f)

    assert set(m) == KEYS
    assert m["paths"] == ["benchmark"] and 1 <= m["run_seconds"] <= 51
    assert len(m["command"]) <= 32 and all(line(w) for w in m["command"])
    assert m["command"][1].startswith("benchmark/")

    configs = {c["name"]: c for c in m["configs"]}
    assert len(configs) == len(m["configs"]) <= 24
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        body = doc("configs", c["name"] + ".json")
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert "assumed" in body and line(body["source"])
        importlib.import_module(f"benchmark.generators.{body['generator']}").make
    assert len({c["file"] for c in m["configs"]}) == len(configs)

    cells = {w["name"]: w for w in m["workloads"]}
    assert 1 <= len(cells) == len(m["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(cells)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        body = doc("workloads", w["name"] + ".json")
        assert (body["config"], body["chips"], body["why"]) == (
            w["config"], w["chips"], w["why"])
        assert {"job", "params", "warmup_iters", "quality_iters",
                "trace_iters", "valid_rows", "check_rows", "reduced",
                "assumed"} <= set(body)
        # every selector the program resolves itself stays at its default
        assert not {"hist_rows", "tree_growth", "hist_exchange", "sparse_store",
                    "predict_kernel"} & (set(body["params"])
                                         | set(doc("configs", w["config"] + ".json")["params"]))
        importlib.import_module(f"benchmark.jobs.{body['job']}").run
    assert {w["config"] for w in m["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(cells) // 4)

    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and 2 <= len(e2e) == len(m["end_to_end"]) <= 16
    assert 1 <= len(m["per_layer"]) <= 128
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    for x in m["end_to_end"] + m["per_layer"]:
        end = x["name"] in e2e
        want = ({"name", "unit", "better", "bound", "source"} if end else
                {"name", "unit", "better", "source", "layer", "moves"})
        assert set(x) - {"workloads"} == want, x["name"]
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
        where = set(x.get("workloads", cells))
        assert where and where <= set(cells)
        spec = doc("metrics", x["name"] + ".json")
        for key in ("unit", "better", "source") + (() if end else ("layer", "moves")):
            assert spec[key] == x[key], (x["name"], key)
        importlib.import_module(f"benchmark.readers.{spec['reader']}").read
        if end:
            assert x["source"] in ("host_clock", "device_trace")
            assert 0.01 <= x["bound"] <= 0.1
        else:
            assert line(x["layer"]) and x["moves"] in e2e
            assert where <= set(e2e[x["moves"]].get("workloads", cells))
    for cell in cells:
        assert "setup_s" in bench_run.metrics_of(m, "end_to_end", cell)
        assert len(bench_run.metrics_of(m, "end_to_end", cell)) >= 2
        assert bench_run.metrics_of(m, "per_layer", cell)


def test_manifest_meets_the_contract():
    m = manifest()
    check(m, bench_run.HERE)
    assert len(json.dumps(m)) < 64 * 1024


def test_file_names_under_paths():
    for root, dirs, files in os.walk(bench_run.HERE):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for f in files:
            assert PATH.match(os.path.relpath(os.path.join(root, f),
                                              bench_run.ROOT)), f


def test_a_new_cell_is_one_file_and_one_entry(tmp_path):
    """README.md's worked example: `epsilon.goss`."""
    bench = tmp_path / "benchmark"
    shutil.copytree(bench_run.HERE, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    readme = open(os.path.join(bench_run.HERE, "README.md")).read()
    blocks = re.findall(r"```json\n(.*?)```", readme, re.S)
    cell_file, entry = json.loads(blocks[0]), json.loads(blocks[1])
    (bench / "workloads" / "epsilon.goss.json").write_text(json.dumps(cell_file))
    m = copy.deepcopy(manifest())
    m["workloads"].append(entry)
    check(m, str(bench))
    assert all(p.read_bytes() == b for p, b in before.items())
    assert "exchange_ms_per_iter" not in bench_run.metrics_of(
        m, "per_layer", "epsilon.goss")
    assert bench_run.metrics_of(m, "end_to_end", "epsilon.goss") == \
        [x["name"] for x in m["end_to_end"]]
