"""Fail when a `Config` field is dead: parsed and accepted but consumed
nowhere in the package and not on the explicit not-yet-implemented
allowlist — AND fail when an allowlist entry goes stale (the field is
now consumed in code), so the allowlist can only shrink consciously.

The bug class this guards against: `enable_bundle` / `max_conflict_rate`
shipped in the Config dataclass for several releases while nothing read
them — silently-accepted parameters that do nothing are worse than a
rejection, because users believe they tuned something.

Consumption is matched against CODE ONLY: comments and docstrings are
stripped before the word search, so a field discussed in prose ("the
future hist_dtype override...") neither counts as consumed nor masks a
stale allowlist entry.  Run from the tier-1 suite
(tests/test_config_coverage.py) and standalone:

    python scripts/check_config_coverage.py
"""
import ast
import dataclasses
import io
import os
import re
import sys
import tokenize

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Fields that are DELIBERATELY accepted-but-inert, each with the reason.
# Adding a field here must be a conscious decision in code review — new
# Config fields are otherwise required to be consumed somewhere.
ALLOWLIST = {
    # reference-compat parameters with no TPU analog
    # (is_enable_sparse / sparse_threshold left this list in PR 14:
    # both now gate the CSR sparse store's auto resolution,
    # dataset.resolve_sparse_store)
    "gpu_platform_id": "OpenCL selector kept for config compatibility",
    "gpu_device_id": "OpenCL selector kept for config compatibility",
    "gpu_use_dp": "OpenCL precision dial; histogram_dtype is the analog",
    "time_out": "socket-network timeout; collectives have no knob here",
}


def _docstring_spans(src: str) -> list:
    """(start_line, end_line) of every module/class/function docstring
    LITERAL, from the AST — positions, not values, so escape sequences
    and implicit concatenation cannot defeat the strip."""
    spans = []
    try:
        tree = ast.parse(src)
    except SyntaxError:              # pragma: no cover
        return spans
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                c = body[0].value
                spans.append((c.lineno, c.end_lineno))
    return spans


def _code_only(src: str) -> str:
    """Source with comment tokens and docstring STRING tokens removed
    (matched by token position against the AST docstring spans — a
    value-based replace() silently no-ops whenever the docstring
    contains an escape sequence).  Non-docstring strings survive:
    getattr(cfg, "hist_exchange") style consumption must still count."""
    spans = _docstring_spans(src)
    out = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                continue
            if tok.type == tokenize.STRING and any(
                    s <= tok.start[0] <= e for s, e in spans):
                continue
            out.append(tok.string if tok.type not in
                       (tokenize.NEWLINE, tokenize.NL) else "\n")
            out.append(" ")
    except tokenize.TokenError:      # pragma: no cover — ill-formed file
        return src
    return "".join(out)


def consumed_fields():
    """Names referenced as a word in CODE anywhere in the package
    outside config.py (attribute reads like cfg.max_bin, dict keys,
    kwargs, getattr strings) — comments and docstrings stripped."""
    blob = []
    pkg = os.path.join(ROOT, "lightgbm_tpu")
    for root, _dirs, files in os.walk(pkg):
        for f in sorted(files):
            if f.endswith(".py") and f != "config.py":
                with open(os.path.join(root, f)) as fh:
                    blob.append(_code_only(fh.read()))
    return "\n".join(blob)


def main() -> int:
    from lightgbm_tpu.config import Config

    blob = consumed_fields()
    dead = []
    stale_allow = []
    for f in dataclasses.fields(Config):
        used = re.search(rf"\b{re.escape(f.name)}\b", blob) is not None
        if not used and f.name not in ALLOWLIST:
            dead.append(f.name)
        if used and f.name in ALLOWLIST:
            stale_allow.append(f.name)
    rc = 0
    if dead:
        rc = 1
        print("DEAD CONFIG FIELDS (accepted but consumed nowhere; wire "
              "them up or add to the allowlist with a reason):")
        for name in dead:
            print(f"  - {name}")
    if stale_allow:
        rc = 1
        print("STALE ALLOWLIST ENTRIES (now consumed; remove from "
              "scripts/check_config_coverage.py ALLOWLIST):")
        for name in stale_allow:
            print(f"  - {name}")
    if rc == 0:
        n = len(dataclasses.fields(Config))
        print(f"config coverage OK: {n} fields, "
              f"{len(ALLOWLIST)} allowlisted as intentionally inert")
    return rc


if __name__ == "__main__":
    sys.exit(main())
