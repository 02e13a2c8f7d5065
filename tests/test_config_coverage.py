"""Tier-1 guard for the dead-config bug class (`enable_bundle` sat in
Config unconsumed for several releases): every Config field must either
be consumed somewhere in the package or sit on the explicit allowlist in
scripts/check_config_coverage.py with a reason."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.quick

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_config_field_is_consumed_or_allowlisted():
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "check_config_coverage.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "config coverage OK" in r.stdout


def _load_checker():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ccc", os.path.join(ROOT, "scripts", "check_config_coverage.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_stale_allowlist_entry_fails(capsys):
    """An allowlisted field that IS consumed in code must fail — the
    allowlist can only shrink consciously."""
    mod = _load_checker()
    mod.ALLOWLIST["num_leaves"] = "pretend-inert (consumed everywhere)"
    assert mod.main() == 1
    out = capsys.readouterr().out
    assert "STALE ALLOWLIST" in out
    assert "num_leaves" in out


def test_consumption_ignores_comments_and_docstrings():
    """A field named only in prose must count as neither consumed nor
    allowlist-staling — including docstrings with escape sequences,
    where a value-based replace() would silently no-op."""
    mod = _load_checker()
    code = mod._code_only(
        'x = 1  # the future cfg.fused_tree override\n'
        'y = getattr(cfg, "hist_exchange", "auto")\n'
        'def f():\n'
        '    """line one.\\nmentions mesh_shape in prose."""\n'
        '    return 1\n')
    assert "fused_tree" not in code     # comment stripped
    assert "mesh_shape" not in code     # escaped docstring stripped
    assert "hist_exchange" in code      # string literals still count
