"""Runtime retrace/transfer sanitizer for the training and serving hot
paths.

graftlint (lint.py) catches the hazards the AST can see; this module
catches the ones only the runtime can: a jitted builder silently
retracing across boosting iterations (each retrace is seconds of XLA
compile on the TPU queue), and implicit host↔device transfers sneaking
into the pipelined loop (each one a dispatch stall — the dominant
scaling tax of accelerator tree boosting, arXiv:1706.08359 §5).

Two mechanisms, wrapped in one context manager:

- ``jax.transfer_guard(guard)`` around the loop: with the default
  ``"disallow"``, any IMPLICIT transfer raises at the violating dispatch
  while the explicit APIs (``jax.device_put`` / ``jax.device_get``) the
  fixed hot path uses stay legal.  Violations caught at ``step()``
  granularity increment ``sanitize/implicit_transfers``.
- compilation-event capture via ``jax_log_compiles``: a logging handler
  on the ``jax`` logger counts "Compiling <name>" records per step;
  compiles after the declared warmup increment ``sanitize/retraces``.

A third mechanism, ``DivergenceSanitizer``, is the runtime half of the
shardlint static rules (lint.py): under ``BENCH_SANITIZE=1`` both mesh
learners fingerprint the replicated growth-loop state (the packed tree
arrays and leaf counts — the materialization of the split records after
``combine_sharded_records``) on every device each iteration and
hard-fail on any cross-shard bitwise mismatch — the failure mode
``shard_map(..., check_vma=False)`` cannot see and a 2-D mesh turns
into a pod-wide deadlock.

Counters land in the always-on profiling registry
(``sanitize/retraces``, ``sanitize/implicit_transfers``,
``sanitize/compiles_total``, ``sanitize/divergence_checks``,
``sanitize/divergences``), so bench.py records them in its JSON line
and the /stats endpoint can expose them.  ``BENCH_SANITIZE=1`` modes in
bench.py / scripts/bench_serve.py / scripts/profile_hotpath.py and the
MULTICHIP dryrun gate assert all of them are zero after warmup (with
``divergence_checks > 0`` proving the divergence probe actually ran on
multi-device meshes).

Backend caveat: the guard is enforced by the backend's dispatch layer
and is a no-op for some transfer directions on some platforms (e.g.
device→host on the CPU backend is zero-copy and never fires).  Probe
with ``transfer_guard_effective()``; tests that require the guard carry
the ``sanitize`` pytest marker so they can be deselected where it is
inert.
"""
from __future__ import annotations

import contextlib
import logging
from contextlib import contextmanager
from typing import Iterator, Optional

from .. import profiling

RETRACES = "sanitize/retraces"
IMPLICIT_TRANSFERS = "sanitize/implicit_transfers"
COMPILES_TOTAL = "sanitize/compiles_total"
DIVERGENCE_CHECKS = "sanitize/divergence_checks"
DIVERGENCES = "sanitize/divergences"

# Lock-sanitizer counters (diagnostics/locksan.py — the runtime half of
# the threadlint static rules, the way DivergenceSanitizer is shardlint's):
#  - LOCK_ACQUIRES: outermost acquisitions seen by the instrumented shim
#    (>0 proves the shim was armed and actually on the benched path);
#  - LOCK_WAITS: acquisitions that found the lock busy and had to block
#    (the contention metric; the wait itself lands in LOCK_WAIT_MS);
#  - LOCK_CYCLES: lock-ORDER cycles detected at acquire time — a thread
#    acquired B-then-A after some thread established A-then-B.  The
#    serving benches assert this stays 0 (a nonzero value is a latent
#    ABBA deadlock that timing has not yet cashed in).
# LOCK_HOLD_MS / LOCK_WAIT_MS are bounded sample reservoirs (per-lock
# labeled series ride the same base names via profiling.labeled).
LOCK_ACQUIRES = "sanitize/lock_acquires"
LOCK_WAITS = "sanitize/lock_waits"
LOCK_CYCLES = "sanitize/lock_cycles"
LOCK_HOLD_MS = "sanitize/lock_hold_ms"
LOCK_WAIT_MS = "sanitize/lock_wait_ms"

# Retrace signal: "Finished tracing + transforming <name> for pjit" fires
# on every (re)trace, INCLUDING compiles served from the persistent
# compilation cache (which skip the "Compiling <name>" backend message
# entirely — counting only that one under-reports retraces whenever
# .jax_cache is warm).  A steady-state iteration emits neither.
_TRACE_MARKER = "Finished tracing + transforming "
_COMPILE_MARKER = "Compiling "


def sanitize_enabled(env: str = "BENCH_SANITIZE") -> bool:
    """One truthiness rule for the BENCH_SANITIZE gates (bench.py,
    scripts/bench_serve.py, scripts/profile_hotpath.py) so the three
    chip-queue entry points cannot diverge.  bench.py re-states the rule
    inline at module level because importing this package there would
    initialize jax before its backend-liveness probe."""
    import os
    return os.environ.get(env, "0") not in ("0", "", "false")


def _is_transfer_guard_error(e: BaseException) -> bool:
    msg = str(e)
    return "Disallowed" in msg and "transfer" in msg


def transfer_guard_effective() -> bool:
    """True when jax.transfer_guard("disallow") actually raises on an
    implicit host→device transfer on this backend (probe with an eager
    op whose scalar operand must be uploaded)."""
    import jax
    import jax.numpy as jnp
    x = jnp.zeros(2)            # committed before the guard
    try:
        with jax.transfer_guard("disallow"):
            (x * 2.0).block_until_ready()
    except Exception as e:      # noqa: BLE001 — backend-specific error type
        return _is_transfer_guard_error(e)
    return False


def _replica_digests(x) -> list:
    """(device, sha1-digest) per REPLICATED copy of `x`: every
    addressable shard whose buffer covers the whole array.  Fewer than
    two full copies (sharded arrays, single device) → [] — there is
    nothing cross-shard to compare.  Fetches are explicit
    ``jax.device_get`` so the probe stays legal under the transfer
    guard's "disallow"."""
    import hashlib

    import jax
    import numpy as np
    shards = getattr(x, "addressable_shards", None)
    if not shards or len(shards) < 2:
        return []
    out = []
    for s in shards:
        if tuple(s.data.shape) != tuple(x.shape):
            return []                  # genuinely sharded, not replicated
        buf = np.ascontiguousarray(jax.device_get(s.data))
        out.append((s.device, hashlib.sha1(buf.tobytes()).hexdigest()))
    return out


class DivergenceSanitizer:
    """Cross-shard replication checker — the runtime half of shardlint.

    The static rules (diagnostics/lint.py shardlint family) prove what
    the AST can see; this closes over what it cannot: whether the
    REPLICATED growth-loop state (split records post-
    ``combine_sharded_records``, leaf counts, the packed tree arrays)
    is actually bitwise-identical on every device after each iteration.
    The mesh learners run ``shard_map(..., check_vma=False)``, so a
    shard-local value leaking into replicated control flow produces
    per-device buffers that silently disagree — a wrong answer on CPU
    and the prelude to a pod-wide deadlock on real hardware.

    ``check(name, value)`` fingerprints every jax.Array leaf of a
    pytree per device (sha1 over the raw buffer) and compares:
    identical → one ``sanitize/divergence_checks`` tick; any mismatch →
    ``sanitize/divergences`` plus (strict mode, the default) an
    immediate AssertionError naming the leaf and per-device digests.
    Multi-process runs compare this process's addressable devices; the
    cross-host copies are covered by every host running the same check.
    """

    def __init__(self, label: str = "growth-loop", strict: bool = True):
        self.label = label
        self.strict = strict
        self.checks = 0
        self.divergences = 0
        self.evidence = []

    def check(self, name: str, value) -> int:
        """Fingerprint a pytree of (assumed-replicated) device arrays.
        Returns the number of NEW divergences found."""
        import jax
        before = self.divergences
        try:
            items = [(jax.tree_util.keystr(p), leaf) for p, leaf in
                     jax.tree_util.tree_leaves_with_path(value)]
        except AttributeError:         # older jax: positional labels
            items = [(str(i), leaf) for i, leaf in
                     enumerate(jax.tree_util.tree_leaves(value))]
        for key, leaf in items:
            digs = _replica_digests(leaf)
            if len(digs) < 2:
                continue
            self.checks += 1
            profiling.count(DIVERGENCE_CHECKS)
            if len({d for _, d in digs}) > 1:
                self.divergences += 1
                profiling.count(DIVERGENCES)
                ev = (name, key, [(str(dev), d[:12]) for dev, d in digs])
                if len(self.evidence) < 16:
                    self.evidence.append(ev)
                if self.strict:
                    raise AssertionError(
                        f"cross-shard divergence [{self.label}] in "
                        f"'{name}/{key}': a replicated growth-loop value "
                        f"differs across devices {ev[2]} — a shard-local "
                        "value leaked into replicated state (silent "
                        "wrong answer here, deadlock shape on a real "
                        "mesh)")
        return self.divergences - before

    def report(self) -> dict:
        return {"label": self.label,
                "divergence_checks": self.checks,
                "divergences": self.divergences,
                "evidence": self.evidence[:4]}


_divergence: Optional[DivergenceSanitizer] = None


def divergence_sanitizer() -> DivergenceSanitizer:
    """The process-wide strict instance the learner hooks feed."""
    global _divergence
    if _divergence is None:
        _divergence = DivergenceSanitizer(label="hot-path")
    return _divergence


def maybe_check_divergence(name: str, value) -> None:
    """Hot-loop hook (both mesh learners call this after every tree
    build): no-op unless BENCH_SANITIZE is on, else a strict
    cross-shard replication check of `value`."""
    if not sanitize_enabled():
        return
    divergence_sanitizer().check(name, value)


class _CompileCounter(logging.Handler):
    """Counts trace events (the retrace signal — see _TRACE_MARKER) and
    backend compiles separately from the jax_log_compiles record
    stream.  One user-level retrace emits one-or-more trace records
    (inner pjits trace too); the contract asserted is ZERO, so the
    event count being an upper bound is fine and the captured names
    point at the offending program."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.count = 0               # trace events (retrace signal)
        self.compiles = 0            # backend "Compiling" events
        self.names = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:       # noqa: BLE001 — never break the hot path
            return
        if msg.startswith(_TRACE_MARKER):
            self.count += 1
            if len(self.names) < 64:     # bounded evidence for reports
                self.names.append(
                    msg[len(_TRACE_MARKER):].split(" for ")[0])
        elif msg.startswith(_COMPILE_MARKER):
            self.compiles += 1


class HotPathSanitizer:
    """Context manager asserting the zero-retrace / zero-implicit-
    transfer contract of a steady-state loop.

    Usage::

        with HotPathSanitizer(warmup=1) as san:
            for _ in range(iters):
                with san.step():
                    bst.update()
        assert san.retraces == 0 and san.implicit_transfers == 0

    ``warmup`` steps may compile freely (first call after a cold cache);
    compiles in any later step count as retraces.  A transfer-guard
    violation inside ``step()`` increments the counter and, with
    ``strict=False`` (default), is swallowed so one run can report the
    total instead of dying at the first violation — note the violating
    iteration's work is aborted mid-dispatch, so non-strict mode is for
    *measuring* breakage, not for training through it.
    """

    def __init__(self, warmup: int = 1, guard: str = "disallow",
                 strict: bool = False, label: str = "hot_path",
                 d2d_guard: str = "allow"):
        self.warmup = int(warmup)
        self.guard = guard
        # device→device resharding (e.g. the replicated gradient
        # scattering into a shard_map mesh) is legitimate SPMD traffic,
        # not the host-sync stall class this sanitizer hunts — allowed
        # by default, tightten via d2d_guard="disallow" to audit it too
        self.d2d_guard = d2d_guard
        self.strict = strict
        self.label = label
        self.steps = 0
        self.retraces = 0
        self.implicit_transfers = 0
        self.compiles_total = 0
        self.trace_events = 0
        self.compile_names = []
        # cross-shard divergence counters over this window (the
        # DivergenceSanitizer feeds the profiling registry; the deltas
        # land in report()/check() beside the retrace counters)
        self.divergence_checks = 0
        self.divergences = 0
        self._div0 = (0.0, 0.0)
        # lock-sanitizer counters over this window (diagnostics/locksan
        # feeds the profiling registry when armed; zero when disarmed)
        self.lock_acquires = 0
        self.lock_waits = 0
        self.lock_cycles = 0
        self._lock0 = (0.0, 0.0, 0.0)
        self._handler: Optional[_CompileCounter] = None
        self._prev_log_compiles = None
        self._prev_propagate = None

    # -- lifecycle ------------------------------------------------------
    def __enter__(self) -> "HotPathSanitizer":
        import jax
        self._handler = _CompileCounter()
        lg = logging.getLogger("jax")
        lg.addHandler(self._handler)
        # capture without spraying WARNING-level compile logs to stderr
        self._prev_propagate = lg.propagate
        lg.propagate = False
        self._prev_log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._div0 = (profiling.counter_value(DIVERGENCE_CHECKS),
                      profiling.counter_value(DIVERGENCES))
        self._lock0 = (profiling.counter_value(LOCK_ACQUIRES),
                       profiling.counter_value(LOCK_WAITS),
                       profiling.counter_value(LOCK_CYCLES))
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        import jax
        jax.config.update("jax_log_compiles", self._prev_log_compiles)
        lg = logging.getLogger("jax")
        lg.removeHandler(self._handler)
        lg.propagate = self._prev_propagate
        self.trace_events = self._handler.count
        self.compiles_total = self._handler.compiles
        self.compile_names = list(self._handler.names)
        profiling.count(RETRACES, self.retraces)
        profiling.count(IMPLICIT_TRANSFERS, self.implicit_transfers)
        profiling.count(COMPILES_TOTAL, self.compiles_total)
        self.divergence_checks = int(
            profiling.counter_value(DIVERGENCE_CHECKS) - self._div0[0])
        self.divergences = int(
            profiling.counter_value(DIVERGENCES) - self._div0[1])
        self.lock_acquires = int(
            profiling.counter_value(LOCK_ACQUIRES) - self._lock0[0])
        self.lock_waits = int(
            profiling.counter_value(LOCK_WAITS) - self._lock0[1])
        self.lock_cycles = int(
            profiling.counter_value(LOCK_CYCLES) - self._lock0[2])
        return False

    # -- per-iteration accounting --------------------------------------
    @contextmanager
    def step(self) -> Iterator[None]:
        """One hot-loop iteration.  Warmup steps run UNGUARDED (a cold
        cache may legitimately compile, and compiling transfers
        constants); post-warmup steps run under the transfer guard and
        attribute compile events to retraces."""
        import jax
        before = self._handler.count
        guarded = self.steps >= self.warmup
        try:
            with contextlib.ExitStack() as stack:
                if guarded:
                    stack.enter_context(
                        jax.transfer_guard_host_to_device(self.guard))
                    stack.enter_context(
                        jax.transfer_guard_device_to_host(self.guard))
                    stack.enter_context(
                        jax.transfer_guard_device_to_device(self.d2d_guard))
                yield
        except Exception as e:   # noqa: BLE001 — classify, then re-raise
            if guarded and _is_transfer_guard_error(e):
                self.implicit_transfers += 1
                if self.strict:
                    raise
            else:
                raise
        finally:
            self.steps += 1
            new = self._handler.count - before
            if self.steps > self.warmup and new:
                self.retraces += new

    # -- reporting ------------------------------------------------------
    def report(self) -> dict:
        """JSON-ready summary (bench.py embeds this under "sanitize")."""
        return {
            "label": self.label,
            "guard": self.guard,
            "steps": self.steps,
            "warmup": self.warmup,
            "retraces_after_warmup": self.retraces,
            "implicit_transfers": self.implicit_transfers,
            "trace_events_total": self.trace_events,
            "compiles_total": self.compiles_total,
            # cross-shard replication audit over this window (the
            # DivergenceSanitizer; >0 checks only on multi-device
            # meshes with BENCH_SANITIZE on)
            "divergence_checks": self.divergence_checks,
            "divergences": self.divergences,
            # lock-order audit over this window (diagnostics/locksan;
            # acquires > 0 proves the instrumented shim was armed)
            "lock_acquires": self.lock_acquires,
            "lock_waits": self.lock_waits,
            "lock_cycles": self.lock_cycles,
            # first offending program names — the evidence a regression
            # report needs to find the retracing call site
            "retrace_names": self.compile_names[-8:] if self.retraces else [],
        }

    def check(self) -> None:
        """Raise with a diagnostic when the zero/zero/zero contract is
        broken (retraces, implicit transfers, cross-shard divergences,
        lock-order cycles)."""
        if self.retraces or self.implicit_transfers or self.divergences \
                or self.lock_cycles:
            from . import locksan
            raise AssertionError(
                f"hot-path sanitizer [{self.label}]: "
                f"{self.retraces} retrace(s), "
                f"{self.implicit_transfers} implicit transfer(s), "
                f"{self.divergences} cross-shard divergence(s) and "
                f"{self.lock_cycles} lock-order cycle(s) after "
                f"{self.warmup} warmup step(s) over {self.steps} steps; "
                f"recent compiles: {self.compile_names[-8:]}; "
                f"lock cycles: {locksan.cycles()[:4]}")
