"""Tier-1 guard for the counter-name bug class (PR 9 caught a
writer/reader counter decoupling by hand — a count site re-typed the
string a constant already canonicalized): every
profiling.count/count_deferred/observe call site must use the
module-level canonical constant when one exists, and no two counter
names may differ only by prefix/separator style (both would sanitize to
the same Prometheus metric name).  Mirrors tests/test_config_coverage.py
— the codified-invariant pattern."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.quick

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_checker():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ccn", os.path.join(ROOT, "scripts", "check_counter_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_package_counter_names_are_clean():
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "check_counter_names.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "counter names OK" in r.stdout


def test_literal_retyping_a_constant_is_flagged():
    mod = _load_checker()
    consts = {"serve.chunk_retries": ("lightgbm_tpu/profiling.py",
                                      "SERVE_CHUNK_RETRIES")}
    sites = mod.scan_source(
        'profiling.count("serve.chunk_retries")\n', "x.py")
    assert sites == [("x.py", 1, "serve.chunk_retries")]
    findings = mod.lint(sites, consts)
    assert len(findings) == 1
    assert "SERVE_CHUNK_RETRIES" in findings[0]


def test_constant_usage_is_not_flagged():
    mod = _load_checker()
    consts = {"serve.chunk_retries": ("lightgbm_tpu/profiling.py",
                                      "SERVE_CHUNK_RETRIES")}
    # a Name/Attribute first argument is not a literal site at all
    sites = mod.scan_source(
        "profiling.count(profiling.SERVE_CHUNK_RETRIES)\n"
        "count(SERVE_CHUNK_RETRIES, 2)\n", "x.py")
    assert sites == []
    assert mod.lint(sites, consts) == []


def test_prefix_style_twins_are_flagged():
    mod = _load_checker()
    sites = (mod.scan_source('profiling.count("serve.swap")\n', "a.py")
             + mod.scan_source('profiling.count("serve/swap")\n', "b.py"))
    findings = mod.lint(sites, {})
    assert len(findings) == 1
    assert "serve.swap" in findings[0] and "serve/swap" in findings[0]
    assert "a.py:1" in findings[0] and "b.py:1" in findings[0]


def test_style_twin_against_a_constant_is_flagged():
    """A literal that matches a CONSTANT's value up to separator style
    is the exact decoupling shape: the writer bumps one spelling, the
    reader queries the other."""
    mod = _load_checker()
    consts = {"registry/swap_failures": ("lightgbm_tpu/profiling.py",
                                         "REGISTRY_SWAP_FAILURES")}
    sites = mod.scan_source(
        'profiling.count("registry.swap_failures")\n', "x.py")
    findings = mod.lint(sites, consts)
    assert len(findings) == 1
    assert "registry.swap_failures" in findings[0]


def test_observe_and_count_deferred_sites_are_scanned():
    mod = _load_checker()
    sites = mod.scan_source(
        'profiling.observe("serve.latency_ms", 1.0)\n'
        'profiling.count_deferred(("tree/x",), v)\n'
        'other.call("not.a.counter")\n', "x.py")
    assert [(s[2]) for s in sites] == ["serve.latency_ms", "tree/x"]


def test_a_tuple_of_names_is_scanned_name_by_name():
    """count_deferred names its counters in a tuple."""
    mod = _load_checker()
    consts = mod.canonical_constants()
    sites = mod.scan_source(
        'profiling.count_deferred(("tree/rounds", "tree/y", K), vec)\n',
        "x.py")
    assert [s[2] for s in sites] == ["tree/rounds", "tree/y"]
    findings = mod.lint(sites, consts)
    assert len(findings) == 1 and "TREE_ROUNDS" in findings[0]


def test_canonical_constants_are_harvested():
    mod = _load_checker()
    consts = mod.canonical_constants()
    assert consts["serve.chunk_retries"][1] == "SERVE_CHUNK_RETRIES"
    assert consts["registry/swap_failures"][1] == "REGISTRY_SWAP_FAILURES"
    assert consts["sanitize/retraces"][1] == "RETRACES"
