"""Aux subsystem tests: PMML export, profiling timers, native loader."""
import os
import xml.etree.ElementTree as ET

import numpy as np
import pytest

pytestmark = pytest.mark.quick

import lightgbm_tpu as lgb


@pytest.fixture(scope="module")
def model(binary_example):
    X, y, _, _ = binary_example
    return lgb.train({"objective": "binary", "verbose": -1,
                      "min_data_in_leaf": 10}, lgb.Dataset(X, y),
                     num_boost_round=3, verbose_eval=False)


def test_pmml_export(model, tmp_path):
    from lightgbm_tpu.pmml import save_pmml, model_to_pmml
    p = tmp_path / "model.pmml"
    save_pmml(model, str(p))
    root = ET.parse(p).getroot()  # well-formed XML
    ns = "{http://www.dmg.org/PMML-4_2}"
    segs = root.findall(f".//{ns}Segment")
    assert len(segs) == model.num_trees()
    assert root.findall(f".//{ns}TreeModel")
    s = model_to_pmml(model)
    assert "SimplePredicate" in s


def test_profiling_timers(tmp_path):
    """The phase accumulators run while telemetry is configured, and only
    then: there is no other switch."""
    from lightgbm_tpu import profiling, telemetry
    rng = np.random.RandomState(3)
    X = rng.randn(400, 6)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)

    def train():
        profiling.reset()
        lgb.train({"objective": "binary", "verbose": -1,
                   "min_data_in_leaf": 10}, lgb.Dataset(X, y),
                  num_boost_round=2, verbose_eval=False)
        return profiling.timings()

    assert train() == {}
    telemetry.configure(str(tmp_path / "spans.jsonl"))
    try:
        totals = train()
    finally:
        telemetry.reset()
    assert totals.get("tree", 0) > 0
    assert totals.get("boosting", 0) > 0
    assert totals.get("update", 0) >= totals["tree"]
    profiling.reset()


def test_native_loader_matches_numpy(examples_dir):
    from lightgbm_tpu import native
    import lightgbm_tpu.dataset as dsm
    path = f"{examples_dir}/lambdarank/rank.train"  # libsvm
    res = native.parse_text_native(path, False, 0)
    if res is None:
        pytest.skip("native library not built")
    Xn, yn = res
    lib = native._LIB
    native._LIB = None
    try:
        Xp, yp, _ = dsm.parse_text_file(path)
    finally:
        native._LIB = lib
    np.testing.assert_allclose(Xn, Xp)
    np.testing.assert_allclose(yn, yp)


def test_native_bin_numerical_matches_searchsorted():
    from lightgbm_tpu.native import bin_numerical_native
    rng = np.random.RandomState(0)
    X = rng.randn(500, 4)
    uppers = [np.sort(rng.randn(17)) for _ in range(3)]
    for u in uppers:
        u[-1] = np.inf
    out = bin_numerical_native(X, [0, 2, 3], uppers)
    if out is None:
        pytest.skip("native library not built")
    for j, (col, u) in enumerate(zip([0, 2, 3], uppers)):
        expect = np.searchsorted(u, X[:, col], side="left")
        np.testing.assert_array_equal(out[j], expect)


@pytest.mark.quick
def test_parameters_doc_in_sync(tmp_path):
    """docs/Parameters.md is generated from config.py; drift fails here.
    The generator runs against a COPY so a failing run never rewrites
    the tracked file (which would make a retry silently pass)."""
    import shutil
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gen = os.path.join(root, "scripts", "gen_parameters_doc.py")
    sandbox = tmp_path / "repo"
    (sandbox / "scripts").mkdir(parents=True)
    (sandbox / "docs").mkdir()
    shutil.copy(gen, sandbox / "scripts" / "gen_parameters_doc.py")
    env = dict(os.environ, PYTHONPATH=root)
    r = subprocess.run([sys.executable, str(sandbox / "scripts" /
                                            "gen_parameters_doc.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0, r.stderr
    fresh = (sandbox / "docs" / "Parameters.md").read_text()
    tracked = open(os.path.join(root, "docs", "Parameters.md")).read()
    assert fresh == tracked, \
        "docs/Parameters.md is stale; run scripts/gen_parameters_doc.py"


def test_dump_model_field_parity(model):
    """dump_model JSON matches the reference's DumpModel field-for-field
    (gbdt.cpp:658-692 top level; tree.cpp:326-365 per tree/node)."""
    d = model.dump_model()
    for k in ("name", "num_class", "num_tree_per_iteration", "label_index",
              "max_feature_idx", "feature_names", "tree_info"):
        assert k in d, k
    assert d["name"] == "tree"
    assert len(d["tree_info"]) == 3

    def walk(node, depth=0):
        if "leaf_index" in node:
            assert set(node) == {"leaf_index", "leaf_parent", "leaf_value",
                                 "leaf_count"}, set(node)
            return
        assert set(node) == {"split_index", "split_feature", "split_gain",
                             "threshold", "decision_type", "internal_value",
                             "internal_count", "left_child",
                             "right_child"}, set(node)
        # reference decision-type names (tree.h GetDecisionTypeName)
        assert node["decision_type"] in ("no_greater", "is")
        walk(node["left_child"], depth + 1)
        walk(node["right_child"], depth + 1)

    for i, ti in enumerate(d["tree_info"]):
        assert ti["tree_index"] == i
        for k in ("num_leaves", "shrinkage", "has_categorical",
                  "tree_structure"):
            assert k in ti, k
        walk(ti["tree_structure"])


def test_python_api_doc_in_sync(tmp_path):
    """docs/Python-API.md is generated from the live package; drift
    fails here (same sandbox pattern as the Parameters.md check)."""
    import shutil
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gen = os.path.join(root, "scripts", "gen_python_api_doc.py")
    sandbox = tmp_path / "repo"
    (sandbox / "scripts").mkdir(parents=True)
    (sandbox / "docs").mkdir()
    shutil.copy(gen, sandbox / "scripts" / "gen_python_api_doc.py")
    env = dict(os.environ, PYTHONPATH=root)
    r = subprocess.run([sys.executable, str(sandbox / "scripts" /
                                            "gen_python_api_doc.py")],
                       capture_output=True, text=True, timeout=180,
                       env=env)
    assert r.returncode == 0, r.stderr
    fresh = (sandbox / "docs" / "Python-API.md").read_text()
    tracked = open(os.path.join(root, "docs", "Python-API.md")).read()
    assert fresh == tracked, \
        "docs/Python-API.md is stale; run scripts/gen_python_api_doc.py"


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: it is used and nothing is set in
    code; unset: <checkout>/.jax_cache, a fixed path."""
    import jax
    from lightgbm_tpu.jaxutil import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert enable_compile_cache() == os.path.join(root, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))]
    assert jax.config.jax_compilation_cache_dir == was


def test_measuring_scripts_fail_without_an_accelerator():
    """jaxutil.require_accelerator: on the CPU tier it exits instead of
    letting a CPU timing be written under a device metric's name."""
    from lightgbm_tpu.jaxutil import require_accelerator
    with pytest.raises(SystemExit, match="no accelerator"):
        require_accelerator()
