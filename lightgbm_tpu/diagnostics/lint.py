"""graftlint — AST static analysis for JAX hot-path hazards.

Four PRs of hot-path work made performance depend on invariants the
Python type system cannot see: jitted tree builders must not retrace
across boosting iterations, no tracer may leak to host mid-loop, and
every per-iteration implicit device→host transfer is a pipeline stall
(the dominant scaling tax of the GPU boosting literature,
arXiv:1706.08359 §5 / arXiv:1806.11248 §4).  This pass codifies those
invariants the way scripts/check_config_coverage.py codifies config
liveness: violations fail in CI, not in the next on-chip bench window.

Rules
-----
- ``host-sync``: device→host synchronization hazards.  ``.item()``
  anywhere; ``float()``/``int()``/``bool()`` or ``np.asarray``/
  ``np.array`` applied to a device value; implicit ``__bool__``
  (``if tracer:`` / ``while tracer:`` / ``assert tracer``) inside
  functions reachable from jit.  Device values are found by a local
  dataflow: names assigned from ``jnp.*``/``lax.*``/``jax.*`` calls or
  from calls into known-jitted package functions (``jax.device_get``
  results are host values and exempt — it is the sanctioned, batchable
  fetch).
- ``retrace-hazard``: per-iteration recompile/upload hazards.  Call
  sites of known-jitted functions passing a ``Config``-derived
  attribute (``cfg.x`` / ``config.x`` / ``self.config.x``) to a
  parameter not in ``static_argnames`` (config scalars are fixed per
  run: bake them static or close over them with ``functools.partial``
  so a changed config is an intentional retrace, not a silent per-call
  upload); ``print``/``log.*`` calls and f-strings formatting device
  values inside traced bodies (trace-time host effects).
- ``dtype-drift``: float64 leaking into traced code with x64 disabled.
  ``np.float64``/``jnp.float64`` casts, ``dtype="float64"``,
  ``astype(float64)``, and float literals outside float32 range (they
  silently become ``0``/``inf`` when the tracer downcasts).
- ``nondeterminism``: ``time.*`` clocks and ``random``/``np.random``
  draws inside traced bodies — they execute at trace time, bake one
  arbitrary value into the compiled program, and make retraces
  unreproducible.

shardlint rules (SPMD collective correctness)
---------------------------------------------
The data-parallel learners' correctness rests on collective invariants:
a mismatched ``axis_name`` is an unbound-axis trace error (or, worse, a
reduction over the wrong mesh axis), a collective skipped by one shard
is a pod-wide deadlock on real hardware, and a shard-local value
steering replicated control flow silently grows different trees per
device.  These rules lean on the same traced-region call graph:

- ``collective-mismatch``: a collective (``psum``/``psum_scatter``/
  ``all_gather``/``pmean``/``all_to_all``/…/``axis_index``) whose axis
  name is not an axis of any mesh constructed in the linted tree
  (string-literal axes at the call site, axis-parameter bindings like
  ``data_axis="rows"`` at any call site, and ``PartitionSpec``
  literals are all checked); and a literal-axis collective in traced
  code NOT reachable from any ``shard_map`` body — nothing binds the
  axis, so the trace fails (or the collective silently no-ops under a
  vmapped alias).
- ``divergent-collective``: a ``lax.cond``/``lax.switch`` in traced
  SPMD code where one branch performs a collective (directly or
  through the call graph) and another does not, unless the predicate
  is provably replicated (derived from ``psum``-family results or
  ``combine_sharded_records``); or any branch collective gated by a
  provably shard-local predicate.  Shards disagreeing on the predicate
  enter different branches and the collective deadlocks cross-host.
- ``scatter-divisibility``: a ``psum_scatter`` call whose enclosing
  function (or a lexically enclosing ancestor) carries no static
  divisibility guarantee for the scattered axis — an
  ``assert … % … == 0``, an ``if … % …: raise`` guard, pad-to-multiple
  arithmetic (``nd * ((x + nd - 1) // nd)``), or a call to the
  ``pad_cols_to_ndev`` helper (learner/common.py).  Without one, a
  non-tiling axis surfaces as a raw XLA shape error at trace time.
- ``replication-leak``: a provably shard-local value (derived from
  ``axis_index``/``psum_scatter``/``all_to_all``/``ppermute`` without
  an intervening replicating collective) flowing into a
  ``lax.cond``/``lax.switch`` predicate or a ``lax.fori_loop`` bound —
  control flow the growth loops require to be bitwise-replicated
  across shards (PRs 3-4).  The runtime half of this contract is
  ``diagnostics/sanitize.DivergenceSanitizer``.

Traced-region discovery: jit roots are ``@jax.jit`` /
``functools.partial(jax.jit, static_argnames=...)`` decorators,
``jax.jit(f)`` / ``jax.jit(functools.partial(f, ...))`` /
``jax.jit(jax.shard_map(f, ...))`` call sites, and bodies handed to
``lax.{fori_loop,while_loop,scan,cond,switch}`` / ``jax.vmap`` /
``shard_map`` (lax control flow traces its body even outside jit).
Reachability then propagates through same-package calls (local names,
``self.method``, and ``from ..x import y`` imports).

Suppressions
------------
Inline, on the finding line or the line above, with a REQUIRED reason::

    x = float(total)  # graftlint: allow(host-sync) — chosen sync point

or a reviewed allowlist entry in scripts/lint_allowlist.txt
(``path::rule::qualname — reason``), mirroring the config-coverage
allowlist: adding one is a conscious review decision.

Run: ``python scripts/run_lint.py`` (nonzero exit on findings); tier-1
runs it from tests/test_lint_clean.py.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

RULES = ("host-sync", "retrace-hazard", "dtype-drift", "nondeterminism",
         "collective-mismatch", "divergent-collective",
         "scatter-divisibility", "replication-leak")

# float32 finite range; literals outside it (except 0) drift under jit
_F32_MAX = 3.4028235e38
_F32_TINY = 1.1754944e-38

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*allow\(\s*([a-z-]+(?:\s*,\s*[a-z-]+)*)\s*\)"
    r"\s*(?:[-—–:]+\s*)?(.*)")

_DEVICE_MODULES = {"jnp", "lax"}          # jnp.x(...) / lax.x(...)
_DEVICE_JAX_SUBMODULES = {"lax", "nn", "numpy", "random", "scipy"}
# fetch APIs whose results are HOST values (the sanctioned sync points)
_HOST_FETCHES = {("jax", "device_get")}
# SPMD collectives (blocking cross-shard comms).  A shard that skips
# one while its peers enter it deadlocks the mesh on real hardware —
# the hazard class behind divergent-collective.
_COMM_COLLECTIVES = {"psum", "psum_scatter", "pmean", "pmax", "pmin",
                     "all_gather", "all_to_all", "ppermute", "pshuffle"}
# collectives whose RESULT is bitwise-replicated across the axis
# (clears the shard-local taint)…
_REPLICATED_RESULT = {"psum", "pmean", "pmax", "pmin", "all_gather"}
# …and primitives whose result is shard-VARYING by construction
# (sets the taint)
_SHARD_LOCAL_RESULT = {"psum_scatter", "all_to_all", "ppermute",
                       "pshuffle", "axis_index"}
# package helpers whose documented contract is a replicated result
# (ops/split.combine_sharded_records: all_gather + identical argmax on
# every shard) — the taint lattice treats them like psum
_REPLICATING_HELPERS = {"combine_sharded_records"}
# 0-based position of the axis-name argument
_COLLECTIVE_AXIS_POS = {"axis_index": 0}
# keyword names that carry mesh-axis bindings at call sites
# (functools.partial(build_tree, data_axis="data") and friends)
_AXIS_KWARG = re.compile(r"(^axis_name$)|(_axis$)")
# divisibility-guard helpers recognized by scatter-divisibility
# (learner/common.py: padding and the trace-time ValueError guard)
_PAD_HELPERS = {"pad_cols_to_ndev", "check_scatter_divisible"}

_TRACE_WRAPPER_FN_ARGS = {
    # callee suffix -> 0-based positions of traced-function arguments
    "fori_loop": (2,),
    "while_loop": (0, 1),
    "scan": (0,),
    "cond": (1, 2),
    "switch": (1,),
    "vmap": (0,),
    "checkpoint": (0,),
    "remat": (0,),
    "shard_map": (0,),
}


@dataclass
class Finding:
    path: str            # repo-relative
    line: int
    rule: str
    message: str
    qualname: str        # enclosing function ('<module>' at top level)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule}: {self.message} "
                f"[in {self.qualname}]")


@dataclass(eq=False)          # identity hash: one node, one entry
class FuncInfo:
    module: str
    qualname: str
    node: ast.AST                      # FunctionDef / AsyncFunctionDef
    params: Tuple[str, ...]
    statics: Set[str] = field(default_factory=set)
    tracer_params: Set[str] = field(default_factory=set)
    traced: bool = False
    is_jit_root: bool = False          # has its own jit cache + statics
    smap: bool = False                 # reachable from a shard_map body


@dataclass
class ModuleInfo:
    name: str
    path: str                          # repo-relative
    tree: ast.Module
    lines: List[str]
    funcs: Dict[str, FuncInfo] = field(default_factory=dict)
    # local name -> (module, name) for from-imports; name -> module for
    # module imports/aliases
    imports: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    mod_aliases: Dict[str, str] = field(default_factory=dict)
    # attribute names assigned from device expressions anywhere in the
    # module (`self.score = jnp.asarray(...)`) — lets the dataflow see
    # `float(self.score)` through object state, not just local names —
    # and attrs assigned HOST values (`self.label = np.asarray(...)`):
    # a name that appears in both is ambiguous across classes and is
    # excluded from the package-wide registry
    device_attrs: Set[str] = field(default_factory=set)
    host_attrs: Set[str] = field(default_factory=set)


def _devicey_chain(chain: Optional[Tuple[str, ...]]) -> bool:
    """True when a call through this attribute chain returns a device
    value (jnp.*/lax.*/jax.* constructors and transforms); False for the
    host-returning introspection and fetch APIs."""
    if not chain:
        return False
    if chain[:2] == ("jax", "device_get"):
        return False                           # the sanctioned fetch
    if chain[0] in _DEVICE_MODULES:
        return chain[-1] not in ("dtype", "result_type", "issubdtype",
                                 "ndim", "shape", "size")
    if chain[0] == "jax" and (len(chain) == 2
                              or chain[1] in _DEVICE_JAX_SUBMODULES):
        return chain[-1] not in ("device_get", "process_count",
                                 "process_index", "devices",
                                 "local_devices", "default_backend")
    return False


def _collective_name(node: ast.AST) -> Optional[str]:
    """'psum' / 'all_gather' / … / 'axis_index' when `node` is the
    callee of an SPMD collective (jax.lax.psum, lax.psum, or a bare
    from-import name); None otherwise."""
    chain = _attr_chain(node)
    if not chain:
        return None
    name = chain[-1]
    if name not in _COMM_COLLECTIVES and name != "axis_index":
        return None
    if len(chain) == 1 or chain[0] in ("jax", "lax"):
        return name
    return None


def _collective_axis_arg(call: ast.Call, name: str) -> Optional[ast.AST]:
    """The axis-name argument expression of a collective call."""
    pos = _COLLECTIVE_AXIS_POS.get(name, 1)
    if pos < len(call.args):
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value
    return None


def _str_constants(expr: ast.AST) -> Set[str]:
    """Every string literal inside `expr` (an axis argument may be a
    name, a tuple of names, or a conditional like
    `"data" if dd > 1 else None`)."""
    out: Set[str] = set()
    for n in ast.walk(expr):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.add(n.value)
    return out


def _attr_chain(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """('jax','lax','fori_loop') for jax.lax.fori_loop; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _static_argnames_from_call(call: ast.Call) -> Set[str]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            out: Set[str] = set()
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for e in v.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        out.add(e.value)
            return out
    return set()


def _is_jit_expr(node: ast.AST) -> Optional[Set[str]]:
    """Static-argname set when `node` evaluates to a jit transform
    (jax.jit / jit / functools.partial(jax.jit, ...)), else None."""
    chain = _attr_chain(node)
    if chain and chain[-1] == "jit" and (len(chain) == 1 or chain[0] == "jax"):
        return set()
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain and chain[-1] == "partial":
            if node.args and _is_jit_expr(node.args[0]) is not None:
                return _static_argnames_from_call(node)
        if chain and chain[-1] == "jit" and (len(chain) == 1
                                             or chain[0] == "jax"):
            return _static_argnames_from_call(node)
    return None


def _module_name_for(path: str, root: str) -> str:
    rel = os.path.relpath(path, root)
    mod = rel[:-3].replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _resolve_relative(module: str, node: ast.ImportFrom) -> Optional[str]:
    if node.level == 0:
        return node.module
    parts = module.split(".")
    if node.level > len(parts):
        return None
    base = parts[: len(parts) - node.level]
    if node.module:
        base = base + node.module.split(".")
    return ".".join(base) if base else None


class _ModuleIndexer(ast.NodeVisitor):
    """Pass 1: function defs, imports, direct jit roots, local aliases."""

    def __init__(self, mi: ModuleInfo):
        self.mi = mi
        self.stack: List[str] = []
        # function-local aliases: name -> (funcname, partial_statics|None)
        self.aliases: Dict[str, Tuple[str, Optional[Set[str]]]] = {}

    # -- imports --------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.mi.mod_aliases[a.asname or a.name.split(".")[0]] = a.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        src = _resolve_relative(self.mi.name, node)
        if src is None:
            return
        for a in node.names:
            if a.name == "*":
                continue
            local = a.asname or a.name
            self.mi.imports[local] = (src, a.name)

    # -- functions ------------------------------------------------------
    def _visit_func(self, node) -> None:
        qual = ".".join(self.stack + [node.name])
        params = tuple(
            a.arg for a in (node.args.posonlyargs + node.args.args
                            + node.args.kwonlyargs))
        fi = FuncInfo(self.mi.name, qual, node, params)
        for dec in node.decorator_list:
            statics = _is_jit_expr(dec)
            if statics is not None:
                fi.traced = fi.is_jit_root = True
                fi.statics = statics
                fi.tracer_params = set(params) - statics
        self.mi.funcs[qual] = fi
        # bare-name index for intra-module resolution (last def wins;
        # nested helpers are usually unique per module in this codebase)
        self.mi.funcs.setdefault(node.name, fi)
        if self.mi.funcs[node.name].qualname != qual and "." not in qual:
            self.mi.funcs[node.name] = fi     # top level shadows nested
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def visit_Assign(self, node: ast.Assign) -> None:
        # track `f = some_func` / `f = functools.partial(some_func, ...)`
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            tgt = node.targets[0].id
            ref = _callable_ref(node.value)
            if ref is not None:
                self.aliases[tgt] = ref
        # track `self.x = jnp.asarray(...)`-style device-attribute state
        # vs `self.x = np.asarray(...)`-style host state
        if isinstance(node.value, ast.Call):
            chain = _attr_chain(node.value.func)
            if _devicey_chain(chain):
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        self.mi.device_attrs.add(t.attr)
            elif chain and (chain[0] in ("np", "numpy")
                            or chain[:2] == ("jax", "device_get")):
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        self.mi.host_attrs.add(t.attr)
        self.generic_visit(node)


def _callable_ref(expr: ast.AST) -> Optional[Tuple[str, Optional[Set[str]]]]:
    """(function-name, bound-statics) when `expr` is a bare function
    reference or functools.partial(fn, ...).  bound-statics is a set of
    parameter names bound by the partial (empty for a bare reference) or
    None when the bindings cannot be determined (a ``**kw`` splat) — in
    that case callers must NOT assume the remaining parameters are
    tracers."""
    if isinstance(expr, ast.Name):
        return (expr.id, set())
    if isinstance(expr, ast.Call):
        chain = _attr_chain(expr.func)
        if chain and chain[-1] == "partial" and expr.args:
            inner = expr.args[0]
            if isinstance(inner, ast.Name):
                bound: Optional[Set[str]] = set()
                for kw in expr.keywords:
                    if kw.arg is None:       # **kw splat: bindings unknown
                        bound = None
                        break
                    bound.add(kw.arg)
                return (inner.id, bound)
    return None


class Package:
    """Parsed package + traced-region call graph."""

    def __init__(self, root: str):
        self.root = root
        self.modules: Dict[str, ModuleInfo] = {}
        self._alias_maps: Dict[str, Dict[str, Tuple[str, Optional[Set[str]]]]] = {}

    # -- loading --------------------------------------------------------
    def add_file(self, path: str) -> None:
        with open(path) as fh:
            src = fh.read()
        mod = _module_name_for(path, self.root)
        mi = ModuleInfo(mod, os.path.relpath(path, self.root),
                        ast.parse(src, filename=path), src.splitlines())
        ix = _ModuleIndexer(mi)
        ix.visit(mi.tree)
        self.modules[mod] = mi
        self._alias_maps[mod] = ix.aliases

    def add_tree(self, pkg_dir: str) -> None:
        for dirpath, _dirs, files in os.walk(pkg_dir):
            for f in sorted(files):
                if f.endswith(".py"):
                    self.add_file(os.path.join(dirpath, f))

    def device_attrs(self) -> Set[str]:
        """Package-wide attribute names assigned ONLY from device
        expressions: an attr any class also assigns a host value
        ('label': device in objectives, numpy in metrics) is ambiguous
        across objects and excluded (built once after loading)."""
        if not hasattr(self, "_device_attrs"):
            dev: Set[str] = set()
            host: Set[str] = set()
            for mi in self.modules.values():
                dev |= mi.device_attrs
                host |= mi.host_attrs
            self._device_attrs = dev - host
        return self._device_attrs

    def mesh_axes(self) -> Set[str]:
        """Union of mesh axis names constructed anywhere in the linted
        tree: string literals in the axis-names argument of ``Mesh(…)``
        / ``make_mesh(…)`` calls and in ``axis_names=`` keywords.  Empty
        when no mesh is built here (partial-tree lint runs) — the
        axis-name checks then stand down rather than flag everything."""
        if not hasattr(self, "_mesh_axes"):
            axes: Set[str] = set()
            for mi in self.modules.values():
                for node in ast.walk(mi.tree):
                    if not isinstance(node, ast.Call):
                        continue
                    chain = _attr_chain(node.func)
                    # Mesh(devices, axis_names) and the modern
                    # jax.make_mesh(axis_shapes, axis_names) both carry
                    # the names at position 1
                    if chain and chain[-1] in ("Mesh", "make_mesh") \
                            and len(node.args) >= 2:
                        axes |= _str_constants(node.args[1])
                    for kw in node.keywords:
                        if kw.arg == "axis_names":
                            axes |= _str_constants(kw.value)
            self._mesh_axes = axes
        return self._mesh_axes

    def func_has_collective(self, fi: Optional[FuncInfo],
                            _seen: Optional[Set[int]] = None) -> bool:
        """Does `fi` perform a blocking SPMD collective, directly or
        through same-package calls?  (axis_index is not a comm op and
        does not count.)"""
        if fi is None:
            return False
        if not hasattr(self, "_coll_memo"):
            self._coll_memo: Dict[int, bool] = {}
        memo = self._coll_memo
        if id(fi) in memo:
            return memo[id(fi)]
        seen = _seen if _seen is not None else set()
        if id(fi) in seen:
            return False                       # cycle: no new evidence
        seen.add(id(fi))
        mi = self.modules[fi.module]
        result = False
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            cname = _collective_name(node.func)
            if cname is not None and cname in _COMM_COLLECTIVES:
                result = True
                break
            target = self.resolve_callee(mi, fi.qualname, node.func)
            if target is not None and target is not fi \
                    and self.func_has_collective(target, seen):
                result = True
                break
        if _seen is None or result:
            memo[id(fi)] = result
        return result

    def branch_has_collective(self, mi: ModuleInfo, qual: str,
                              expr: ast.AST) -> Optional[bool]:
        """Whether a lax.cond/lax.switch branch argument performs a
        collective: True/False when determinable, None when the branch
        reference cannot be resolved (no false positives on unknowns)."""
        if isinstance(expr, ast.Lambda):
            for n in ast.walk(expr.body):
                if isinstance(n, ast.Call):
                    cname = _collective_name(n.func)
                    if cname is not None and cname in _COMM_COLLECTIVES:
                        return True
                    target = self.resolve_callee(mi, qual, n.func)
                    if target is not None \
                            and self.func_has_collective(target):
                        return True
            return False
        refs = [fn for fn, _extra in self._fn_refs(mi, expr)
                if fn is not None]
        if not refs:
            return None
        return any(self.func_has_collective(fn) for fn in refs)

    # -- resolution -----------------------------------------------------
    def resolve(self, module: str, name: str) -> Optional[FuncInfo]:
        mi = self.modules.get(module)
        if mi is None:
            return None
        if name in mi.funcs:
            return mi.funcs[name]
        if name in mi.imports:
            src_mod, src_name = mi.imports[name]
            if src_mod != module:
                return self.resolve(src_mod, src_name)
        alias = self._alias_maps.get(module, {}).get(name)
        if alias is not None:
            return self.resolve(module, alias[0])
        return None

    def resolve_callee(self, mi: ModuleInfo, qual: str,
                       func: ast.AST) -> Optional[FuncInfo]:
        """Resolve a Call callee to a package FuncInfo: bare name,
        self.method, or imported-module attribute."""
        if isinstance(func, ast.Name):
            return self.resolve(mi.name, func.id)
        chain = _attr_chain(func)
        if not chain or len(chain) != 2:
            return None
        base, attr = chain
        if base == "self":
            # method in the same class: replace the last qualname part
            parts = qual.split(".")
            for cut in range(len(parts) - 1, 0, -1):
                cand = ".".join(parts[:cut] + [attr])
                if cand in mi.funcs:
                    return mi.funcs[cand]
            return None
        if base in mi.mod_aliases:
            return self.resolve(mi.mod_aliases[base], attr)
        if base in mi.imports:          # `from .ops import eval as deval`
            src_mod, src_name = mi.imports[base]
            return self.resolve(f"{src_mod}.{src_name}", attr)
        return None

    # -- traced-region discovery ---------------------------------------
    def mark_traced(self) -> None:
        work: List[FuncInfo] = [fi for mi in self.modules.values()
                                for fi in set(mi.funcs.values()) if fi.traced]

        def mark(fi: Optional[FuncInfo], tracer_params: bool = False,
                 statics: Optional[Set[str]] = None) -> None:
            if fi is None:
                return
            new_statics = statics or set()
            if not fi.traced:
                fi.traced = True
                if tracer_params:
                    fi.tracer_params = set(fi.params) - new_statics
                fi.statics |= new_statics
                work.append(fi)
            elif tracer_params and not fi.tracer_params and not fi.is_jit_root:
                fi.tracer_params = set(fi.params) - new_statics
                fi.statics |= new_statics

        # seed: jit()/shard_map()/lax-control-flow call sites anywhere.
        # A partial() with a **splat hides which parameters are bound
        # (extra is None): the body is traced but parameters must not be
        # assumed tracers, or every static config branch would flag.
        for mi in self.modules.values():
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                statics = _is_jit_expr(node.func)
                if statics is not None and node.args:
                    for fn, extra in self._fn_refs(mi, node.args[0]):
                        mark(fn, tracer_params=extra is not None,
                             statics=statics | (extra or set()))
                    continue
                chain = _attr_chain(node.func)
                if chain and chain[-1] in _TRACE_WRAPPER_FN_ARGS:
                    for pos in _TRACE_WRAPPER_FN_ARGS[chain[-1]]:
                        if pos < len(node.args):
                            for fn, extra in self._fn_refs(mi,
                                                           node.args[pos]):
                                mark(fn, tracer_params=extra is not None,
                                     statics=extra or set())

        # propagate through same-package calls from traced bodies
        seen: Set[Tuple[str, str]] = set()
        while work:
            fi = work.pop()
            key = (fi.module, fi.qualname)
            if key in seen:
                continue
            seen.add(key)
            mi = self.modules[fi.module]
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Call):
                    target = self.resolve_callee(mi, fi.qualname, node.func)
                    if target is not None and not target.traced:
                        mark(target)
                    # functools.partial(fn, ...) inside traced bodies:
                    # fn will be called traced (lax.cond branch tables)
                    ref = _callable_ref(node)
                    if ref is not None and isinstance(node, ast.Call) \
                            and ref[0] != getattr(node.func, "id", None):
                        mark(self.resolve(mi.name, ref[0]),
                             tracer_params=False)

        # shard_map reachability (shardlint): the bodies handed to
        # shard_map, then everything they call
        # (including lax control-flow bodies and partial aliases) — the
        # region where mesh axes are bound and collectives are legal
        smap_work: List[FuncInfo] = []

        def mark_smap(fn: Optional[FuncInfo]) -> None:
            if fn is not None and not fn.smap:
                fn.smap = True
                smap_work.append(fn)

        for mi in self.modules.values():
            for node in ast.walk(mi.tree):
                if not isinstance(node, ast.Call):
                    continue
                chain = _attr_chain(node.func)
                if chain and chain[-1] == "shard_map" and node.args:
                    for fn, _extra in self._fn_refs(mi, node.args[0]):
                        mark_smap(fn)
        seen_s: Set[Tuple[str, str]] = set()
        while smap_work:
            fi = smap_work.pop()
            key = (fi.module, fi.qualname)
            if key in seen_s:
                continue
            seen_s.add(key)
            mi = self.modules[fi.module]
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                mark_smap(self.resolve_callee(mi, fi.qualname, node.func))
                ref = _callable_ref(node)
                if ref is not None:
                    mark_smap(self.resolve(mi.name, ref[0]))
                chain = _attr_chain(node.func)
                if chain and chain[-1] in _TRACE_WRAPPER_FN_ARGS:
                    for pos in _TRACE_WRAPPER_FN_ARGS[chain[-1]]:
                        if pos < len(node.args):
                            for fn, _extra in self._fn_refs(mi,
                                                            node.args[pos]):
                                mark_smap(fn)

    def _fn_refs(self, mi: ModuleInfo, expr: ast.AST
                 ) -> Iterable[Tuple[Optional[FuncInfo], Optional[Set[str]]]]:
        """FuncInfos referenced by a jit/shard_map/lax-wrapper argument:
        a name, functools.partial(name, ...), a [list] of names (switch),
        or a nested shard_map/partial call."""
        if isinstance(expr, (ast.Tuple, ast.List)):
            for e in expr.elts:
                yield from self._fn_refs(mi, e)
            return
        ref = _callable_ref(expr)
        if ref is not None:
            name, bound = ref
            # chase local `fn = functools.partial(f, **kw)` aliases,
            # merging binding knowledge: an unknown (**splat) binding
            # anywhere in the chain means parameters must not be
            # assumed tracers
            amap = self._alias_maps.get(mi.name, {})
            hops: Set[str] = set()
            while name in amap and name not in hops:
                hops.add(name)
                aname, abound = amap[name]
                bound = (None if bound is None or abound is None
                         else bound | abound)
                name = aname
            yield self.resolve(mi.name, name), bound
            return
        if isinstance(expr, ast.Call):     # jit(shard_map(fn, ...))
            chain = _attr_chain(expr.func)
            if chain and chain[-1] in _TRACE_WRAPPER_FN_ARGS and expr.args:
                yield from self._fn_refs(mi, expr.args[0])


# ---------------------------------------------------------------------------
# rule checks
# ---------------------------------------------------------------------------


class _Dataflow:
    """Per-function device-value tracking (names only, straight-line
    approximation: later assignments overwrite earlier ones)."""

    def __init__(self, pkg: Package, mi: ModuleInfo, fi: FuncInfo):
        self.pkg = pkg
        self.mi = mi
        self.fi = fi
        self.devicey_names: Set[str] = set(fi.tracer_params)
        # shardlint taint lattice: names KNOWN shard-local (derived from
        # axis_index / psum_scatter / all_to_all / ppermute with no
        # intervening replicating collective) vs names KNOWN replicated
        # (derived from psum-family results / combine_sharded_records).
        # Everything else — parameters included — is unknown and fires
        # no rule: the runtime DivergenceSanitizer owns that remainder.
        self.shard_local_names: Set[str] = set()
        self.replicated_names: Set[str] = set()

    def is_devicey(self, expr: ast.AST) -> bool:
        if isinstance(expr, ast.Name):
            return expr.id in self.devicey_names
        if isinstance(expr, ast.Call):
            chain = _attr_chain(expr.func)
            if chain:
                if chain[:2] == ("jax", "device_get"):
                    return False                       # sanctioned fetch
                if _devicey_chain(chain):
                    return True
                if chain[0] in _DEVICE_MODULES or chain[0] == "jax":
                    return False                       # host-returning API
            # only jit ROOTS reliably return device arrays; a merely
            # reachable-from-jit helper called with host args at trace
            # time returns host values (padded_bin_count etc.)
            target = self.pkg.resolve_callee(self.mi, self.fi.qualname,
                                             expr.func)
            if target is not None and target.is_jit_root:
                return True
            # method call on a devicey value: x.sum(), x.reshape(...)
            if isinstance(expr.func, ast.Attribute):
                return self.is_devicey(expr.func.value)
            return False
        if isinstance(expr, ast.BinOp):
            return self.is_devicey(expr.left) or self.is_devicey(expr.right)
        if isinstance(expr, ast.UnaryOp):
            return self.is_devicey(expr.operand)
        if isinstance(expr, ast.Compare):
            # identity/containment tests (`x is None`) never call the
            # tracer's __bool__ and return a host bool — not a hazard
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in expr.ops):
                return False
            return self.is_devicey(expr.left) or any(
                self.is_devicey(c) for c in expr.comparators)
        if isinstance(expr, ast.BoolOp):
            return any(self.is_devicey(v) for v in expr.values)
        if isinstance(expr, ast.Subscript):
            return self.is_devicey(expr.value)
        if isinstance(expr, ast.Attribute):
            if expr.attr in ("shape", "ndim", "dtype", "size", "itemsize",
                             "nbytes", "at"):
                return expr.attr == "at" and self.is_devicey(expr.value)
            # object state: an attribute assigned from a device
            # expression (self.score = jnp.asarray(...)) is a device
            # value wherever it is read — float(self.score) is the same
            # stall as float(score).  Scoping controls collisions: a
            # direct `self.x` read matches only attrs registered in the
            # SAME module (objectives' device self.label must not taint
            # metrics' host self.label); a multi-hop read through
            # another object (`self.train_score.score`) is cross-class
            # by construction and consults the package-wide registry.
            b, levels = expr.value, 1
            while isinstance(b, ast.Attribute):
                b, levels = b.value, levels + 1
            if isinstance(b, ast.Name) and b.id == "self":
                if levels == 1 and expr.attr in (self.mi.device_attrs
                                                 - self.mi.host_attrs):
                    return True
                if levels >= 2 and expr.attr in self.pkg.device_attrs():
                    return True
            return self.is_devicey(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self.is_devicey(e) for e in expr.elts)
        if isinstance(expr, ast.IfExp):
            return self.is_devicey(expr.body) or self.is_devicey(expr.orelse)
        return False

    # -- shardlint taint lattice ---------------------------------------
    def is_shard_local(self, expr: ast.AST) -> bool:
        """Provably shard-varying: axis_index / psum_scatter /
        all_to_all / ppermute results and anything derived from them
        (conservative through calls: a tainted argument taints the
        result, except through the replicating collectives/helpers)."""
        if isinstance(expr, ast.Name):
            return expr.id in self.shard_local_names
        if isinstance(expr, ast.Call):
            cname = _collective_name(expr.func)
            if cname is not None:
                if cname in _SHARD_LOCAL_RESULT:
                    return True
                if cname in _REPLICATED_RESULT:
                    return False
            if isinstance(expr.func, ast.Name) \
                    and expr.func.id in _REPLICATING_HELPERS:
                return False
            chain = _attr_chain(expr.func)
            if chain and chain[-1] in _REPLICATING_HELPERS:
                return False
            if any(self.is_shard_local(a) for a in expr.args) or any(
                    self.is_shard_local(kw.value) for kw in expr.keywords):
                return True
            if isinstance(expr.func, ast.Attribute):       # x.sum() etc.
                return self.is_shard_local(expr.func.value)
            return False
        if isinstance(expr, ast.BinOp):
            return (self.is_shard_local(expr.left)
                    or self.is_shard_local(expr.right))
        if isinstance(expr, ast.UnaryOp):
            return self.is_shard_local(expr.operand)
        if isinstance(expr, ast.Compare):
            return self.is_shard_local(expr.left) or any(
                self.is_shard_local(c) for c in expr.comparators)
        if isinstance(expr, ast.BoolOp):
            return any(self.is_shard_local(v) for v in expr.values)
        if isinstance(expr, ast.Subscript):
            return (self.is_shard_local(expr.value)
                    or self.is_shard_local(expr.slice))
        if isinstance(expr, ast.Attribute):
            return self.is_shard_local(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self.is_shard_local(e) for e in expr.elts)
        if isinstance(expr, ast.IfExp):
            return (self.is_shard_local(expr.body)
                    or self.is_shard_local(expr.orelse))
        return False

    def is_replicated(self, expr: ast.AST) -> bool:
        """Provably replicated across shards: literals, psum-family /
        combine_sharded_records results, and pure elementwise math over
        replicated operands.  Used only to SILENCE divergent-collective
        on predicates the analysis can vouch for — unknowns stay
        findings (suppress with a written reason)."""
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, ast.Name):
            return expr.id in self.replicated_names
        if isinstance(expr, ast.Call):
            cname = _collective_name(expr.func)
            if cname is not None:
                return cname in _REPLICATED_RESULT
            if isinstance(expr.func, ast.Name) \
                    and expr.func.id in _REPLICATING_HELPERS:
                return True
            chain = _attr_chain(expr.func)
            if chain and chain[-1] in _REPLICATING_HELPERS:
                return True
            # device math (jnp.sum(replicated) etc.) preserves
            # replication when every operand is replicated
            if chain and _devicey_chain(chain) and (expr.args
                                                    or expr.keywords):
                return all(self.is_replicated(a) for a in expr.args) \
                    and all(self.is_replicated(kw.value)
                            for kw in expr.keywords)
            return False
        if isinstance(expr, ast.BinOp):
            return (self.is_replicated(expr.left)
                    and self.is_replicated(expr.right))
        if isinstance(expr, ast.UnaryOp):
            return self.is_replicated(expr.operand)
        if isinstance(expr, ast.Compare):
            return self.is_replicated(expr.left) and all(
                self.is_replicated(c) for c in expr.comparators)
        if isinstance(expr, ast.BoolOp):
            return all(self.is_replicated(v) for v in expr.values)
        if isinstance(expr, ast.Subscript):
            return self.is_replicated(expr.value)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return all(self.is_replicated(e) for e in expr.elts)
        if isinstance(expr, ast.IfExp):
            return (self.is_replicated(expr.body)
                    and self.is_replicated(expr.orelse))
        return False

    def note_assign(self, node: ast.AST) -> None:
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            return
        dev = self.is_devicey(value)
        sl = self.is_shard_local(value)
        rep = self.is_replicated(value)
        for t in targets:
            if isinstance(t, ast.Name):
                if dev:
                    self.devicey_names.add(t.id)
                else:
                    self.devicey_names.discard(t.id)
                if sl:
                    self.shard_local_names.add(t.id)
                else:
                    self.shard_local_names.discard(t.id)
                if rep:
                    self.replicated_names.add(t.id)
                else:
                    self.replicated_names.discard(t.id)


def _has_float64(expr: ast.AST) -> Optional[ast.AST]:
    for n in ast.walk(expr):
        chain = _attr_chain(n)
        if chain and chain[-1] in ("float64", "double") and chain[0] in (
                "np", "numpy", "jnp"):
            return n
        if isinstance(n, ast.Constant) and n.value in ("float64", "double"):
            return n
    return None


def _config_attr(expr: ast.AST) -> Optional[str]:
    """Name of a Config field read inside `expr` (cfg.x / config.x /
    self.config.x / anything.config.x), or None."""
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute):
            base = n.value
            if isinstance(base, ast.Name) and base.id in ("cfg", "config"):
                return n.attr
            if isinstance(base, ast.Attribute) and base.attr == "config":
                return n.attr
    return None


class _Checker(ast.NodeVisitor):
    """Rule checks over one function body (or module top level)."""

    def __init__(self, pkg: Package, mi: ModuleInfo, fi: Optional[FuncInfo],
                 findings: List[Finding]):
        self.pkg = pkg
        self.mi = mi
        self.fi = fi
        self.traced = fi is not None and fi.traced
        self.qual = fi.qualname if fi is not None else "<module>"
        self.flow = _Dataflow(pkg, mi, fi) if fi is not None else None
        self.findings = findings

    # -- helpers --------------------------------------------------------
    def _emit(self, node: ast.AST, rule: str, msg: str) -> None:
        self.findings.append(Finding(self.mi.path, node.lineno, rule, msg,
                                     self.qual))

    def _devicey(self, expr: ast.AST) -> bool:
        return self.flow is not None and self.flow.is_devicey(expr)

    # -- assignments feed the dataflow ---------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        if self.flow is not None:
            self.flow.note_assign(node)

    visit_AugAssign = visit_Assign
    visit_AnnAssign = visit_Assign

    # -- host-sync ------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        chain = _attr_chain(func)
        # .item(): a one-element device→host sync wherever it appears
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args:
            self._emit(node, "host-sync",
                       ".item() forces a blocking device→host sync; "
                       "batch scalar fetches with jax.device_get at the "
                       "loop boundary")
        # float()/int()/bool() of a device value
        if isinstance(func, ast.Name) and func.id in ("float", "int", "bool") \
                and len(node.args) == 1 and self._devicey(node.args[0]):
            self._emit(node, "host-sync",
                       f"{func.id}() on a device value blocks on a "
                       "device→host transfer; keep it on device or fetch "
                       "explicitly (batched) with jax.device_get")
        # np.asarray / np.array of a device value
        if chain and chain[0] in ("np", "numpy", "onp") \
                and chain[-1] in ("asarray", "array", "ascontiguousarray") \
                and node.args and self._devicey(node.args[0]):
            self._emit(node, "host-sync",
                       f"{'.'.join(chain)} of a device value is an "
                       "implicit device→host transfer; use jax.device_get "
                       "(explicit, transfer-guard-clean, batchable)")
        if self.traced:
            self._check_traced_call(node, chain)
        self._check_config_static(node)
        self._check_shard_rules(node, chain)
        self.generic_visit(node)

    # -- shardlint: SPMD collective correctness -------------------------
    def _check_shard_rules(self, node: ast.Call,
                           chain: Optional[Tuple[str, ...]]) -> None:
        axes = self.pkg.mesh_axes()
        cname = _collective_name(node.func)
        if cname is not None:
            axis = _collective_axis_arg(node, cname)
            consts = _str_constants(axis) if axis is not None else set()
            for c in sorted(consts):
                if axes and c not in axes:
                    self._emit(
                        node, "collective-mismatch",
                        f"{cname} over axis '{c}', which is not an axis "
                        f"of any mesh built here (known axes: "
                        f"{sorted(axes)}); a mismatched axis_name is an "
                        "unbound-axis trace error under shard_map — or a "
                        "reduction over the wrong mesh axis")
            if consts and self.fi is not None and self.fi.traced \
                    and not self.fi.smap:
                self._emit(
                    node, "collective-mismatch",
                    f"{cname} over axis "
                    f"'{'/'.join(sorted(consts))}' in traced code not "
                    "reachable from any shard_map body: nothing binds "
                    "the axis, so the trace fails (wrap the caller in "
                    "shard_map or thread the axis name as a "
                    "None-guarded parameter)")
            if cname == "psum_scatter" and self.traced \
                    and not self._has_divisibility_guard():
                self._emit(
                    node, "scatter-divisibility",
                    "psum_scatter with no static divisibility guarantee "
                    "for the scattered axis in the enclosing function "
                    "chain: a size that does not tile the mesh axis is "
                    "a raw XLA shape error at trace time; pad with "
                    "learner/common.pad_cols_to_ndev (or guard with "
                    "`if size % ndev: raise ValueError(...)`)")
        # axis-parameter bindings at any call site
        # (functools.partial(build_tree, data_axis="rows") …)
        for kw in node.keywords:
            if kw.arg and _AXIS_KWARG.search(kw.arg):
                for c in sorted(_str_constants(kw.value)):
                    if axes and c not in axes:
                        self._emit(
                            kw.value, "collective-mismatch",
                            f"axis binding {kw.arg}='{c}' names no axis "
                            f"of any mesh built here (known axes: "
                            f"{sorted(axes)}); the collective it reaches "
                            "will trace with an unbound axis name")
        # PartitionSpec literals must name real mesh axes too
        is_pspec = (chain and chain[-1] == "PartitionSpec") or (
            isinstance(node.func, ast.Name)
            and self.mi.imports.get(node.func.id, ("", ""))[1]
            == "PartitionSpec")
        if is_pspec:
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                for c in sorted(_str_constants(a)):
                    if axes and c not in axes:
                        self._emit(
                            node, "collective-mismatch",
                            f"PartitionSpec names axis '{c}', which is "
                            f"not an axis of any mesh built here (known "
                            f"axes: {sorted(axes)})")
        # divergent collectives + shard-local control flow
        is_lax = chain and (len(chain) == 1 or chain[0] in ("jax", "lax"))
        if is_lax and chain[-1] in ("cond", "switch") and self.traced \
                and len(node.args) >= 2:
            pred = node.args[0]
            if chain[-1] == "cond":
                branches = list(node.args[1:3])
            else:
                b = node.args[1]
                branches = (list(b.elts)
                            if isinstance(b, (ast.List, ast.Tuple))
                            else [b])
            infos = [self.pkg.branch_has_collective(self.mi, self.qual, b)
                     for b in branches]
            known = [i for i in infos if i is not None]
            any_coll = any(known)
            pred_sl = self._shard_local(pred)
            if any_coll and pred_sl:
                self._emit(
                    node, "divergent-collective",
                    f"collective inside a lax.{chain[-1]} branch gated "
                    "by a shard-local predicate: shards disagree on the "
                    "branch, some skip the collective, and the mesh "
                    "deadlocks cross-host; make the predicate "
                    "replicated (psum the inputs) or hoist the "
                    "collective out of the branch")
            elif any_coll and False in known \
                    and not self._replicated(pred):
                self._emit(
                    node, "divergent-collective",
                    f"collective in only one branch of a "
                    f"lax.{chain[-1]} whose predicate is not provably "
                    "replicated: if any shard ever disagrees on the "
                    "predicate, the shards that skip the branch "
                    "deadlock the collective; prove the predicate "
                    "replicated (derive it from psum/"
                    "combine_sharded_records) or suppress with the "
                    "replication argument written down")
            if pred_sl:
                self._emit(
                    pred, "replication-leak",
                    f"shard-local value steers a lax.{chain[-1]} "
                    "predicate: the growth loops require control flow "
                    "to be bitwise-replicated across shards (PRs 3-4) — "
                    "reduce it with psum/all_gather first")
        if is_lax and chain[-1] == "fori_loop" and self.traced:
            for bound in node.args[:2]:
                if self._shard_local(bound):
                    self._emit(
                        bound, "replication-leak",
                        "shard-local value as a fori_loop bound: shards "
                        "run different trip counts, so any collective "
                        "in the body deadlocks and replicated state "
                        "diverges; psum the bound first")

    def _shard_local(self, expr: ast.AST) -> bool:
        return self.flow is not None and self.flow.is_shard_local(expr)

    def _replicated(self, expr: ast.AST) -> bool:
        return self.flow is not None and self.flow.is_replicated(expr)

    def _has_divisibility_guard(self) -> bool:
        """Static divisibility evidence for psum_scatter in the lexical
        function chain: an assert with a `%` test, an `if … % …: raise`
        guard, pad-to-multiple arithmetic `nd * ((x + nd - 1) // nd)`,
        or a pad_cols_to_ndev call."""
        if self.fi is None:
            return False
        chain_fis = [self.fi]
        parts = self.fi.qualname.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            anc = self.mi.funcs.get(".".join(parts[:cut]))
            if anc is not None and anc not in chain_fis:
                chain_fis.append(anc)

        def has_mod(expr: ast.AST) -> bool:
            return any(isinstance(b, ast.BinOp) and isinstance(b.op, ast.Mod)
                       for b in ast.walk(expr))

        for fi in chain_fis:
            for n in ast.walk(fi.node):
                if isinstance(n, ast.Assert) and has_mod(n.test):
                    return True
                if isinstance(n, ast.If) and has_mod(n.test) and any(
                        isinstance(s, ast.Raise) for s in n.body):
                    return True
                if isinstance(n, ast.Call):
                    cchain = _attr_chain(n.func)
                    if cchain and cchain[-1] in _PAD_HELPERS:
                        return True
                if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult):
                    for a, b in ((n.left, n.right), (n.right, n.left)):
                        if isinstance(b, ast.BinOp) \
                                and isinstance(b.op, ast.FloorDiv) \
                                and ast.dump(b.right) == ast.dump(a):
                            return True
        return False

    def _check_traced_call(self, node: ast.Call,
                           chain: Optional[Tuple[str, ...]]) -> None:
        func = node.func
        # print / logging inside traced code
        if isinstance(func, ast.Name) and func.id == "print":
            self._emit(node, "retrace-hazard",
                       "print() inside traced code runs at trace time "
                       "only (or forces a callback); use "
                       "jax.debug.print or hoist out of the jit region")
        if chain and len(chain) >= 2 and chain[0] in ("log", "logging",
                                                      "logger", "Log"):
            self._emit(node, "retrace-hazard",
                       f"{'.'.join(chain)}() inside traced code is a "
                       "trace-time host effect; hoist logging out of the "
                       "jit region")
        # nondeterminism
        if chain:
            if chain[0] == "time" and chain[-1] in (
                    "time", "perf_counter", "monotonic", "time_ns",
                    "process_time"):
                self._emit(node, "nondeterminism",
                           f"{'.'.join(chain)}() in traced code executes "
                           "once at trace time and bakes a stale constant "
                           "into the compiled program")
            if chain[0] == "random" or chain[:2] in (("np", "random"),
                                                     ("numpy", "random")):
                self._emit(node, "nondeterminism",
                           f"{'.'.join(chain)}() in traced code draws at "
                           "trace time (one arbitrary constant per "
                           "compile); thread a jax.random key instead")
        # dtype-drift: astype(float64)
        if isinstance(func, ast.Attribute) and func.attr == "astype" \
                and node.args and _has_float64(node.args[0]) is not None:
            self._emit(node, "dtype-drift",
                       "astype(float64) inside traced code silently "
                       "downcasts to f32 with x64 disabled; pin the "
                       "intended dtype explicitly")

    def _check_config_static(self, node: ast.Call) -> None:
        """Config-derived Python value passed to a jitted function's
        traced (non-static) parameter."""
        if self.fi is None:
            target = None
        else:
            target = self.pkg.resolve_callee(self.mi, self.qual, node.func)
        if target is None or not target.is_jit_root:
            return
        params = list(target.params)
        for i, arg in enumerate(node.args):
            fieldname = _config_attr(arg)
            if fieldname is None:
                continue
            pname = params[i] if i < len(params) else f"arg{i}"
            if pname not in target.statics:
                self._emit(
                    arg, "retrace-hazard",
                    f"Config field '{fieldname}' flows into jitted "
                    f"'{target.qualname}' parameter '{pname}' which is "
                    "not in static_argnames: a per-call scalar upload, "
                    "and a silent retrace hazard if it reaches shape or "
                    "branch logic; declare it static or bind it with "
                    "functools.partial")
        for kw in node.keywords:
            if kw.arg is None:
                continue
            fieldname = _config_attr(kw.value)
            if fieldname is not None and kw.arg not in target.statics:
                self._emit(
                    kw.value, "retrace-hazard",
                    f"Config field '{fieldname}' flows into jitted "
                    f"'{target.qualname}' parameter '{kw.arg}' which is "
                    "not in static_argnames; declare it static or bind "
                    "it with functools.partial")

    # -- implicit __bool__ on tracers ----------------------------------
    def _check_test(self, test: ast.AST, kind: str) -> None:
        if self.traced and self._devicey(test):
            self._emit(test, "host-sync",
                       f"`{kind}` on a traced value calls __bool__ on a "
                       "tracer (TracerBoolConversionError under jit, a "
                       "blocking sync when eager); use lax.cond/jnp.where")

    def visit_If(self, node: ast.If) -> None:
        self._check_test(node.test, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_test(node.test, "while")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_test(node.test, "ternary if")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._check_test(node.test, "assert")
        self.generic_visit(node)

    # -- f-strings formatting device values -----------------------------
    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if self.traced:
            for v in node.values:
                if isinstance(v, ast.FormattedValue) and self._devicey(v.value):
                    self._emit(node, "retrace-hazard",
                               "f-string formats a traced value: renders "
                               "the tracer repr at trace time (and forces "
                               "a sync when eager); use jax.debug.print")
                    break
        self.generic_visit(node)

    # -- dtype drift on literals / dtype kwargs -------------------------
    def visit_Constant(self, node: ast.Constant) -> None:
        if self.traced and isinstance(node.value, float) and node.value != 0.0:
            a = abs(node.value)
            if a > _F32_MAX or a < _F32_TINY:
                self._emit(node, "dtype-drift",
                           f"float literal {node.value!r} is outside "
                           "float32 range and becomes 0/inf when the "
                           "tracer downcasts with x64 disabled")

    def visit_keyword(self, node: ast.keyword) -> None:
        if self.traced and node.arg == "dtype" \
                and _has_float64(node.value) is not None:
            self._emit(node.value, "dtype-drift",
                       "dtype=float64 inside traced code is quietly f32 "
                       "with x64 disabled; pin float32 (or int32) "
                       "explicitly")
        self.generic_visit(node)

    # keep nested defs inside their own _Checker run
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if self.fi is not None and node is not self.fi.node:
            return                      # separate FuncInfo covers it
        for d in node.decorator_list:
            self.visit(d)
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self.visit(node.body)


# np.float64(...) calls in traced code (checker-level, needs chain only)
def _np_float64_calls(fi: FuncInfo, mi: ModuleInfo,
                      findings: List[Finding]) -> None:
    for node in ast.walk(fi.node):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain and chain[-1] in ("float64", "double") \
                    and chain[0] in ("np", "numpy", "jnp"):
                findings.append(Finding(
                    mi.path, node.lineno, "dtype-drift",
                    "np.float64 cast inside traced code silently becomes "
                    "f32 with x64 disabled; pin float32 or hoist to host",
                    fi.qualname))


# ---------------------------------------------------------------------------
# suppression handling
# ---------------------------------------------------------------------------


def _suppressions_for(lines: Sequence[str], lineno: int
                      ) -> Optional[Tuple[Set[str], str]]:
    """(rules, reason) from a graftlint comment on `lineno` or the line
    above (1-indexed); None when no suppression applies."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _SUPPRESS_RE.search(lines[ln - 1])
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                return rules, m.group(2).strip()
    return None


def load_allowlist(path: str) -> Dict[Tuple[str, str, str], str]:
    """path::rule::qualname -> reason entries from the reviewed file."""
    out: Dict[Tuple[str, str, str], str] = {}
    if not os.path.exists(path):
        return out
    with open(path) as fh:
        for raw in fh:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            body, _, reason = line.partition("—")
            if not reason:
                body, _, reason = line.partition(" - ")
            parts = [p.strip() for p in body.strip().split("::")]
            if len(parts) == 3:
                out[(parts[0], parts[1], parts[2])] = reason.strip()
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_paths(paths: Sequence[str], root: str,
               allowlist: Optional[Dict[Tuple[str, str, str], str]] = None,
               used_allowlist: Optional[Set[Tuple[str, str, str]]] = None
               ) -> List[Finding]:
    """Run every rule over `paths` (files or directories).  Returns
    unsuppressed findings; suppressions without a reason are findings
    themselves (`suppression` rule).  When `used_allowlist` is given it
    is filled with the allowlist keys that actually matched a finding —
    the input of the stale-entry check (stale_allowlist_entries)."""
    findings, _stale = lint_run(paths, root, allowlist,
                                used_allowlist=used_allowlist,
                                check_stale=False)
    return findings


def stale_allowlist_entries(
        allowlist: Dict[Tuple[str, str, str], str],
        used: Set[Tuple[str, str, str]],
        linted_paths: Set[str], root: str) -> List[str]:
    """Allowlist entries that no longer earn their keep: the file was
    linted and the key matched no finding (fix landed, or the qualname
    was renamed), or the file no longer exists.  Entries for files
    outside the linted set are left alone, and CALLERS must only run
    this audit over the whole package — whether an entry still produces
    its finding can depend on cross-file context (traced-reachability,
    mesh axes), so a partial-tree run cannot judge even its own files
    (scripts/run_lint.py gates on full scope).  Mirrors
    check_config_coverage.py's stale-allowlist rule: the list may only
    shrink consciously."""
    out: List[str] = []
    for (path, rule, qual), _reason in sorted(allowlist.items()):
        if (path, rule, qual) in used:
            continue
        if path in linted_paths:
            out.append(f"{path}::{rule}::{qual} — no longer produces a "
                       "finding; remove the entry")
        elif not os.path.exists(os.path.join(root, path)):
            out.append(f"{path}::{rule}::{qual} — file no longer exists; "
                       "remove the entry")
    return out


def lint_run(paths: Sequence[str], root: str,
             allowlist: Optional[Dict[Tuple[str, str, str], str]] = None,
             used_allowlist: Optional[Set[Tuple[str, str, str]]] = None,
             check_stale: bool = True
             ) -> Tuple[List[Finding], List[str]]:
    """lint_paths plus the stale-allowlist audit: returns
    (findings, stale-entry descriptions)."""
    pkg = Package(root)
    for p in paths:
        if os.path.isdir(p):
            pkg.add_tree(p)
        else:
            pkg.add_file(p)
    pkg.mark_traced()
    allowlist = allowlist or {}
    used: Set[Tuple[str, str, str]] = (used_allowlist
                                       if used_allowlist is not None
                                       else set())

    raw: List[Finding] = []
    for mi in pkg.modules.values():
        funcs = {id(fi.node): fi for fi in mi.funcs.values()}
        for fi in set(funcs.values()):
            _Checker(pkg, mi, fi, raw).visit(fi.node)
            if fi.traced:
                _np_float64_calls(fi, mi, raw)
        # module top level (rare, but .item() at import time counts)
        top = _Checker(pkg, mi, None, raw)
        for stmt in mi.tree.body:
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                top.visit(stmt)

    # dedupe (nested defs can be visited from two scopes)
    seen: Set[Tuple[str, int, str, str]] = set()
    findings: List[Finding] = []
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        key = (f.path, f.line, f.rule, f.message)
        if key in seen:
            continue
        seen.add(key)
        mi = next(m for m in pkg.modules.values() if m.path == f.path)
        sup = _suppressions_for(mi.lines, f.line)
        if sup is not None and f.rule in sup[0]:
            if not sup[1]:
                findings.append(Finding(
                    f.path, f.line, "suppression",
                    f"graftlint: allow({f.rule}) has no reason; "
                    "suppressions must say why (\"# graftlint: "
                    "allow(rule) — reason\")", f.qualname))
            continue
        wl = allowlist.get((f.path, f.rule, f.qualname))
        if wl is not None:
            used.add((f.path, f.rule, f.qualname))
            if wl:
                continue
            findings.append(Finding(
                f.path, f.line, "suppression",
                "allowlist entry has no reason", f.qualname))
            continue
        findings.append(f)
    stale: List[str] = []
    if check_stale:
        linted = {m.path for m in pkg.modules.values()}
        stale = stale_allowlist_entries(allowlist, used, linted, root)
    return findings, stale
