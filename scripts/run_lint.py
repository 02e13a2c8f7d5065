"""graftlint CLI — JAX-hazard + SPMD-collective + thread-safety lints.

Three rule families run over the package in one invocation:

- graftlint (lint.py): JAX hazards in traced code — host syncs,
  retrace hazards, dtype drift, nondeterminism;
- shardlint (lint.py): SPMD collective correctness inside shard_map
  regions;
- threadlint (threadlint.py): concurrency correctness in the threaded
  serving/router/online plane — unguarded shared state, lock-order
  cycles, blocking under a lock, Condition misuse.

Prints `path:line: rule: message [in qualname]` findings and exits
nonzero when any survive suppressions and the reviewed allowlist
(scripts/lint_allowlist.txt) — or when an allowlist entry has gone
STALE (its path::rule::qualname no longer exists or no longer produces
a finding), mirroring the stale-allowlist rule
scripts/check_config_coverage.py enforces for config keys: the
allowlist can only shrink consciously.  Threadlint rules share the
allowlist file and the stale audit — each linter audits exactly its
own rules' entries.

`--json` emits machine-readable findings on stdout
(file/line/rule/qualname/message plus the stale entries) with a
one-line summary on stderr, for CI annotation.  `--rules a,b,...` restricts the run to the named rules
(the stale audit is skipped then: with rules filtered out, absence of
a finding proves nothing).  Run from tier-1
(tests/test_lint_clean.py) and standalone:

    python scripts/run_lint.py [--json] [--rules r1,r2] [paths...]

Stdlib-only (no jax import): the gate costs milliseconds.
"""
import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# load lint.py / threadlint.py by PATH, not through the package:
# `import lightgbm_tpu` initializes the whole framework (jax included,
# ~10 s); the linters are pure stdlib and must stay a
# milliseconds-cheap gate.  lint.py must be loaded (and registered)
# first — threadlint rides its Package/FuncInfo machinery.
_spec = importlib.util.spec_from_file_location(
    "graftlint", os.path.join(ROOT, "lightgbm_tpu", "diagnostics",
                              "lint.py"))
_lint = importlib.util.module_from_spec(_spec)
sys.modules["graftlint"] = _lint    # dataclasses resolves annotations here
_spec.loader.exec_module(_lint)
lint_run, load_allowlist = _lint.lint_run, _lint.load_allowlist

_tspec = importlib.util.spec_from_file_location(
    "threadlint", os.path.join(ROOT, "lightgbm_tpu", "diagnostics",
                               "threadlint.py"))
_threadlint = importlib.util.module_from_spec(_tspec)
sys.modules["threadlint"] = _threadlint
_tspec.loader.exec_module(_threadlint)

ALLOWLIST_FILE = os.path.join(ROOT, "scripts", "lint_allowlist.txt")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(ROOT, "lightgbm_tpu")],
                    help="files or directories (default: the package)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings on stdout "
                         "(file/line/rule/qualname/message + stale "
                         "allowlist entries); summary goes to stderr")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule names to run (default: "
                         "all graftlint + shardlint + threadlint "
                         "rules); skips the stale-allowlist audit")
    ap.add_argument("--allowlist", default=ALLOWLIST_FILE,
                    help="reviewed allowlist file (default: "
                         "scripts/lint_allowlist.txt)")
    ap.add_argument("--no-allowlist", action="store_true",
                    help="ignore the allowlist (show everything the "
                         "rules match; disables the stale-entry check)")
    args = ap.parse_args(argv)

    allow = {} if args.no_allowlist else load_allowlist(args.allowlist)
    # each linter owns its rules' allowlist entries — and audits exactly
    # those for staleness, so a threadlint entry can never look stale to
    # graftlint (which never emits threadlint rules) or vice versa
    thread_rules = set(_threadlint.RULES)
    thread_allow = {k: v for k, v in allow.items() if k[1] in thread_rules}
    graft_allow = {k: v for k, v in allow.items()
                   if k[1] not in thread_rules}
    rules = (None if args.rules is None
             else {r.strip() for r in args.rules.split(",") if r.strip()})

    paths = [os.path.abspath(p) for p in args.paths]
    # The stale-allowlist audit needs WHOLE-PACKAGE context: whether an
    # entry still produces its finding can depend on cross-file
    # reachability (log.py's entry fires only when ops/histogram.py is
    # in scope to mark log.warning traced).  Partial-path and
    # partial-rule runs therefore skip the audit instead of flagging
    # spuriously.
    pkg_dir = os.path.join(ROOT, "lightgbm_tpu")
    full_scope = any(p == pkg_dir for p in paths) and rules is None

    run_graft = rules is None or bool(rules - thread_rules)
    run_thread = rules is None or bool(rules & thread_rules)
    findings, stale = [], []
    if run_graft:
        gf, gs = lint_run(paths, ROOT, graft_allow, check_stale=full_scope)
        findings += gf
        stale += gs
    if run_thread:
        tf, ts = _threadlint.lint_run(paths, ROOT, thread_allow,
                                      check_stale=full_scope)
        findings += tf
        stale += ts
    if rules is not None:
        # "suppression" findings (reason-less allow comments) always
        # surface — a rule filter must not hide a broken suppression
        findings = [f for f in findings
                    if f.rule in rules or f.rule == "suppression"]
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    rc = 1 if (findings or stale) else 0

    by_rule = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    if findings or stale:
        parts = [f"{r}: {n}" for r, n in sorted(by_rule.items())]
        if stale:
            parts.append(f"stale-allowlist: {len(stale)}")
        summary = (f"graftlint: {len(findings)} finding(s), "
                   f"{len(stale)} stale allowlist entr"
                   f"{'y' if len(stale) == 1 else 'ies'} "
                   f"({', '.join(parts)})")
    else:
        summary = ("graftlint OK: no JAX-hazard, SPMD, or "
                   "thread-safety findings")

    if args.as_json:
        print(json.dumps({
            "ok": rc == 0,
            "findings": [{"file": f.path, "line": f.line, "rule": f.rule,
                          "qualname": f.qualname, "message": f.message}
                         for f in findings],
            "stale_allowlist": stale,
        }))
        print(summary, file=sys.stderr)
        return rc

    for f in findings:
        print(f.render())
    for s in stale:
        print(f"stale allowlist entry: {s}")
    print(summary)
    return rc


if __name__ == "__main__":
    sys.exit(main())
