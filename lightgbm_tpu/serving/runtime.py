"""Compiled-predictor runtime for online scoring.

The offline path (`application.Predictor` → `Booster.predict`) re-traces
the XLA walker for every new batch shape and rebuilds the TreeStack per
call.  Online traffic is the opposite workload: millions of small,
odd-shaped requests against one slowly-changing model.  This runtime
keeps the accelerator executable warm the way the GPU boosting serving
literature prescribes (arXiv:1806.11248 §5, arXiv:2011.02022):

- executables are AOT-compiled once per (replica, model generation, row
  bucket, output kind) via ``jax.jit(...).lower(...).compile()`` and
  cached — a cache hit does zero tracing and zero compilation;
- request rows are bucketed to powers of two between
  ``min_bucket_rows`` and ``max_batch_rows`` and padded up, so every
  shape in the wild lands on one of O(log) warm executables;
- the ensemble traversal itself is the ``predict_kernel`` dial
  (ops/predict.py): ``tensorized`` (the `auto` resolution) walks every
  tree of every class in ONE fused gather/select program — `depth` loop
  steps for the whole ensemble; ``walk`` keeps the per-class vmapped
  walk as the A/B baseline;
- the model is REPLICATED across local devices (`replicas`): each
  replica owns a device-resident copy of the stacked ensemble and its
  own executable cache, and requests dispatch to the least-loaded
  replica — every local chip serves, which is the fleet story behind
  "heavy traffic from millions of users";
- the per-request feature buffer is donated on accelerator backends, so
  XLA may reuse it for the output and skip one HBM round trip;
- with ``serve_quantize=binned`` (quantize="binned" + a refbin mapper
  set here), every chunk quantizes to uint8 bin ids at ingress and the
  traversal compares integer bins end-to-end (ops/predict.py
  predict_ensemble_quantized): the request buffer ships 4x smaller and
  scores stay bit-identical to the raw kernel by construction
  (lightgbm_tpu/quantize.py);
- the sigmoid/softmax output transform runs inside the compiled program
  ("value" kind) — the host only sees finished predictions.

Cache hits/misses, compile seconds, executed rows, and per-replica
dispatch counts are recorded through the always-on `profiling` counters
(exposed at the server's /stats endpoint).

Replica health (docs/Robustness.md): every dispatch failure counts
against its replica; after ``failure_threshold`` CONSECUTIVE failures
the replica's circuit breaker opens and it stops receiving traffic.  A
failed chunk is retried ONCE on the least-loaded healthy replica, so
one bad chip degrades capacity, not availability.  Broken replicas
readmit through a half-open probe: after ``probe_after`` dispatches
were routed around a broken replica, one live request probes it — a
success closes the breaker, a failure re-opens it for another
``probe_after`` window (deterministic, count-based — no wall clock).
With ZERO healthy replicas, dispatch raises `NoHealthyReplicaError`,
which the HTTP layer maps to 503 (retryable) instead of a raw 500.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from .. import log, profiling, telemetry
from ..diagnostics import faults, locksan
from ..log import LightGBMError

OUTPUT_KINDS = ("value", "raw")


class NoHealthyReplicaError(LightGBMError):
    """Every replica's circuit breaker is open — shed load (HTTP 503)."""


class _ReplicaFailure(Exception):
    """Internal: a dispatch failed on a specific replica (carries the
    replica index so the retry can exclude it)."""

    def __init__(self, replica_index: int, error: BaseException):
        super().__init__(f"replica {replica_index} failed: {error}")
        self.replica_index = replica_index
        self.error = error


def row_bucket(n: int, min_bucket: int, max_bucket: int) -> int:
    """Smallest power-of-two bucket >= n within [min_bucket, max_bucket]."""
    b = max(1, min_bucket)
    while b < n and b < max_bucket:
        b <<= 1
    return min(b, max_bucket)


def resolve_runtime(booster, *, serve_quantize: str = "auto",
                    refbin=None, **kw) -> "PredictorRuntime":
    """Build a PredictorRuntime honoring the ``serve_quantize`` dial —
    the ONE place the auto/binned/raw policy lives (ModelRegistry and
    the CLI batch Predictor both route through here).

    ``raw`` → raw-feature runtime.  ``binned`` → binned runtime; ANY
    failure (missing/invalid sidecar, unrepresentable thresholds,
    compile error) propagates.  ``auto`` → binned whenever the refbin
    source yields a valid mapper set, raw otherwise (one log line says
    why).  ``refbin`` may be a sidecar path, a Dataset, or a zero-arg
    callable returning either — the registry defers its sha-validated
    sidecar load into the try this way.
    """
    import os

    from ..config import SERVE_QUANTIZE_MODES
    if serve_quantize not in SERVE_QUANTIZE_MODES:
        raise ValueError(f"unknown serve_quantize: {serve_quantize!r}; "
                         f"use one of {SERVE_QUANTIZE_MODES}")
    if serve_quantize != "raw":
        try:
            rb = refbin() if callable(refbin) else refbin
            if rb is None:
                raise LightGBMError(
                    "no .refbin frozen-mapper sidecar (Dataset."
                    "save_refbin, or an online-published model)")
            if isinstance(rb, str) and not os.path.exists(rb):
                raise LightGBMError(f"no .refbin sidecar at {rb}")
            return PredictorRuntime(booster, quantize="binned",
                                    refbin=rb, **kw)
        except Exception as e:
            if serve_quantize == "binned":
                raise
            log.info("serve_quantize=auto: serving raw features "
                     f"({type(e).__name__}: {e})")
    return PredictorRuntime(booster, **kw)


def resolve_serve_replicas(replicas: int = 0) -> list:
    """The local devices a serving fleet replicates onto.

    ``0`` (auto) = every local device on accelerator backends, ONE on
    the CPU tier (the virtual host-platform devices jax carves out of
    one socket share the same cores — replicating executables there
    multiplies compile time, not throughput).  An explicit count is
    honored on any backend (tests and the CPU bench force it), capped
    at the local device count.
    """
    import jax
    devs = list(jax.local_devices())
    if replicas <= 0:
        return devs if jax.default_backend() in ("tpu", "gpu") else devs[:1]
    return devs[: min(replicas, len(devs))]


class _Replica:
    """One device's copy of the model: device-resident stacks plus its
    own executable cache and dispatch/health bookkeeping."""
    __slots__ = ("index", "device", "stacks", "compiled", "exe_bytes",
                 "inflight", "dispatches", "failures", "broken", "skips",
                 "probes")

    def __init__(self, index: int, device, stacks):
        self.index = index
        self.device = device
        self.stacks = stacks
        self.compiled: Dict[Tuple[int, str], object] = {}
        # estimated device bytes per compiled executable (same keys as
        # `compiled`) — what the catalog's serve_cache_budget_mb LRU
        # accounting sums
        self.exe_bytes: Dict[Tuple[int, str], int] = {}
        self.inflight = 0
        self.dispatches = 0
        self.failures = 0       # CONSECUTIVE dispatch failures
        self.broken = False     # circuit breaker open
        self.skips = 0          # dispatches routed around while broken
        self.probes = 0         # half-open probes attempted


class PredictorRuntime:
    """Warm-executable predictor for one model generation.

    Immutable once built: hot swap creates a fresh runtime for the next
    generation and atomically replaces the reference (registry.py), so
    in-flight requests keep scoring against a consistent model.
    """

    # dispatches routed AROUND a broken replica before one live request
    # probes it (half-open); count-based so chaos runs are deterministic
    PROBE_AFTER = 8

    def __init__(self, booster, *, num_iteration: int = -1,
                 max_batch_rows: int = 4096, min_bucket_rows: int = 16,
                 generation: int = 0, predict_kernel: Optional[str] = None,
                 replicas: int = 0, failure_threshold: int = 3,
                 probe_after: Optional[int] = None,
                 quantize: str = "raw", refbin=None,
                 model_id: Optional[str] = None):
        import jax
        from ..ops.predict import resolve_predict_kernel

        gbdt = booster._gbdt if hasattr(booster, "_gbdt") else booster
        gbdt._flush_pending()
        if not gbdt.models:
            raise LightGBMError("cannot build a PredictorRuntime from a "
                                "model with no trees")
        if max_batch_rows < 1:
            raise ValueError("max_batch_rows must be >= 1")
        min_bucket_rows = max(1, min(min_bucket_rows, max_batch_rows))
        self.generation = generation
        # catalog tenant id (None outside the multi-tenant catalog):
        # stamps replica spans and the per-model telemetry labels
        self.model_id = model_id
        self.max_batch_rows = int(max_batch_rows)
        self.min_bucket_rows = int(min_bucket_rows)
        self.objective = gbdt.objective
        self.K = max(1, gbdt.K)
        self.num_features = gbdt.max_feature_idx + 1
        if predict_kernel is None:
            # the model's own training config carries the dial when the
            # serving entry point does not pass one explicitly
            predict_kernel = getattr(getattr(gbdt, "config", None),
                                     "predict_kernel", "auto")
        self.predict_kernel = resolve_predict_kernel(predict_kernel)
        used = gbdt._num_used_models(num_iteration)
        # request-path quantization (docs/serving.md "Binned inference"):
        # "binned" rebins the model against the frozen refbin mapper set,
        # quantizes every chunk at ingress, and traverses integer bins
        # end-to-end — bit-identical scores, 4x smaller request buffer
        if quantize not in ("raw", "binned"):
            raise ValueError("PredictorRuntime quantize must be 'raw' or "
                             f"'binned', got {quantize!r} (the auto "
                             "resolution happens in resolve_runtime)")
        self._quantizer = None
        self.variant = quantize
        if quantize == "binned":
            from ..quantize import (FeatureQuantizer, load_refbin,
                                    rebin_models_for_serving)
            if refbin is None:
                raise LightGBMError(
                    "binned serving needs a refbin mapper set (a .refbin "
                    "sidecar path or a Dataset)")
            if isinstance(refbin, str):
                refbin = load_refbin(refbin)
            if refbin.num_total_features != self.num_features:
                raise LightGBMError(
                    f"refbin mapper set covers "
                    f"{refbin.num_total_features} features, the model "
                    f"expects {self.num_features}")
            rebin_models_for_serving(gbdt.models[:used], refbin)
            self._quantizer = FeatureQuantizer(refbin.mappers,
                                               refbin.used_features)
            if self.predict_kernel != "tensorized":
                log.info("serve_quantize=binned traverses the tensorized "
                         "binned stack; the predict_kernel="
                         f"{self.predict_kernel} dial applies to raw "
                         "serving only")
        host_stacks = self._build_host_stacks(gbdt, used)
        # the per-chunk device buffer: quantized uint8/uint16 bins over
        # the used features, or the raw f32 feature matrix
        if self._quantizer is not None:
            self._buf_dtype = self._quantizer.dtype
            self._buf_cols = self._quantizer.num_columns
        else:
            self._buf_dtype = np.float32
            self._buf_cols = self.num_features
        self._device_value = self._device_value_fn()
        self._init_fleet(host_stacks, replicas, failure_threshold,
                         probe_after)

    def _init_fleet(self, host_stacks, replicas: int,
                    failure_threshold: int,
                    probe_after: Optional[int]) -> None:
        """Replica fleet + dispatch bookkeeping, shared verbatim by the
        cross-model GroupRuntime (serving/superstack.py) — breaker
        semantics and cache accounting must not fork per runtime
        flavor."""
        import jax

        # the fleet: one model copy + executable cache per local device
        self.replicas: List[_Replica] = [
            _Replica(i, dev, jax.device_put(host_stacks, dev))
            for i, dev in enumerate(resolve_serve_replicas(replicas))]
        # persistent chunk fan-out pool (threads spawn on demand): a
        # per-request executor would pay thread spawn/teardown inside
        # the serving hot path on every multi-chunk request.  Replicas
        # are the parallel resource, so the pool is sized to the fleet
        # and shared across concurrent requests; workers exit when the
        # runtime is garbage-collected after a hot swap.
        self._fanout = (ThreadPoolExecutor(
            max_workers=len(self.replicas),
            thread_name_prefix="lgbt-serve-fanout")
            if len(self.replicas) > 1 else None)
        self._lock = locksan.lock("serve.runtime")
        self._rr = 0                  # round-robin tie-break cursor
        self.cache_hits = 0
        self.cache_misses = 0
        # replica circuit breaker (module docstring): consecutive
        # failures to open, routed-around dispatches to half-open probe
        self.failure_threshold = max(1, int(failure_threshold))
        self.probe_after = max(1, int(self.PROBE_AFTER if probe_after
                                      is None else probe_after))
        self.chunk_retries = 0

    @property
    def replica_count(self) -> int:
        return len(self.replicas)

    def replica_dispatches(self) -> List[int]:
        """Per-replica dispatch counts (the /stats fleet view)."""
        with self._lock:
            return [r.dispatches for r in self.replicas]

    def replica_health(self) -> List[dict]:
        """Per-replica breaker state (the /stats `replicas.health`
        view: which chips carry traffic, which are circuit-broken and
        how close their half-open probe is)."""
        with self._lock:
            return [{"index": r.index,
                     "state": "broken" if r.broken else "healthy",
                     "consecutive_failures": r.failures,
                     "dispatches": r.dispatches,
                     "skips_since_broken": r.skips,
                     "probes": r.probes}
                    for r in self.replicas]

    def healthy_count(self) -> int:
        with self._lock:
            return sum(1 for r in self.replicas if not r.broken)

    # -- model stacking -------------------------------------------------

    def _build_host_stacks(self, gbdt, used: int):
        """Host-numpy ensemble stacks — device_put once per replica.

        binned (serve_quantize=binned): ONE stack over every class with
        thresholds in bin space — the PERFECT layout for shallow
        numerical ensembles (bin ids exact in the f32 lanes), the
        integer-record SoA (int16 lanes on TPU) otherwise; the same
        layout-auto predicate as the raw path, so the two variants
        always make the same layout choice for a given model.
        tensorized: ONE stack over every class (`self._meta` static).
        walk: one TreeStack per class (None for a never-trained class,
        its raw row stays 0 like GBDT._predict_raw_device).
        """
        from ..ops.predict import build_ensemble, stack_trees
        trees_by_class = [
            [gbdt.models[i] for i in range(used) if i % self.K == k]
            for k in range(self.K)]
        # retained for the cross-model co-stacking overlay
        # (serving/superstack.py): a GroupRuntime concatenates its
        # members' trees into one super-stack, and must stack exactly
        # the tree set this runtime scores solo (binned variants have
        # already been rebinned in place above)
        self._trees_by_class = trees_by_class
        if self.variant == "binned":
            stack, meta = build_ensemble(trees_by_class, binned=True)
            self._meta = meta
            return stack
        if self.predict_kernel == "tensorized":
            stack, meta = build_ensemble(trees_by_class, binned=False)
            self._meta = meta
            return stack
        self._meta = None
        stacks: List = []
        self._depths: List[int] = []
        for trees in trees_by_class:
            if not trees:
                stacks.append(None)
                self._depths.append(1)
                continue
            # stack_trees returns device arrays on the default device;
            # numpy round-trip keeps replica placement explicit
            stack = stack_trees(trees, binned=False)
            stacks.append(type(stack)(*map(np.asarray, stack)))
            self._depths.append(
                max(max((t.max_depth_grown for t in trees), default=1), 1))
        return stacks

    # -- compiled-program construction ---------------------------------

    def _device_value_fn(self):
        """Device-side raw→prediction transform for the "value" output
        kind, or None when there is nothing to fuse: identity transforms
        share the raw program (compiling a byte-identical twin per
        bucket would double the cache for nothing), and objectives with
        no known device form fall back to the host transform on the raw
        program's result."""
        import jax
        from ..objectives import Objective

        obj = self.objective
        if obj is None or type(obj).convert_output is Objective.convert_output:
            return None                                  # identity: use raw
        name = getattr(obj, "name", "")
        if name in ("binary", "multiclassova"):
            sig = float(obj.sigmoid)
            return lambda raw: jax.nn.sigmoid(sig * raw)
        if name == "multiclass":
            return lambda raw: jax.nn.softmax(raw, axis=0)
        return None                                      # host fallback

    def _run_kind(self, kind: str) -> str:
        """The executable kind a request actually runs: "value" maps to
        the raw program whenever no device transform is fused."""
        return kind if kind == "raw" or self._device_value is not None \
            else "raw"

    def _raw_fn(self):
        """The traced ensemble-traversal body, (stacks, X) -> [K, rows]."""
        if self.variant == "binned":
            from ..ops.predict import predict_ensemble_quantized
            meta = self._meta

            def fn(stacks, Xb):
                return predict_ensemble_quantized(stacks, Xb, meta=meta)
            return fn
        if self.predict_kernel == "tensorized":
            from ..ops.predict import predict_ensemble_any
            meta = self._meta

            def fn(stacks, X):
                return predict_ensemble_any(stacks, X, meta=meta)
            return fn
        from ..ops.predict import ensemble_raw
        depths = tuple(self._depths)

        def fn(stacks, X):
            return ensemble_raw(stacks, X, depths=depths)
        return fn

    def _program(self, kind: str):
        """The traceable program body for one output kind — (stacks, X)
        -> [K, rows].  GroupRuntime overrides this with the grouped
        traversal; everything downstream (_build's AOT compile, the
        executable cache, warmup, dispatch) is shared."""
        raw_fn = self._raw_fn()
        device_value = self._device_value if kind == "value" else None

        def fn(stacks, X):
            raw = raw_fn(stacks, X)                        # [K, bucket]
            if device_value is not None:
                raw = device_value(raw)
            return raw
        return fn

    def _build(self, replica: _Replica, bucket: int, kind: str):
        """AOT-compile the traversal for one (replica, bucket, kind) —
        the only place an XLA compilation can happen after the runtime
        is built."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        fn = self._program(kind)
        # no donation of X: the [K, bucket] output cannot alias the
        # [bucket, F] request buffer, on the chip as on the CPU
        x_spec = jax.ShapeDtypeStruct(
            (bucket, self._buf_cols), jnp.dtype(self._buf_dtype),
            sharding=SingleDeviceSharding(replica.device))
        t0 = time.perf_counter()
        compiled = (jax.jit(fn)
                    .lower(replica.stacks, x_spec)
                    .compile())
        dt = time.perf_counter() - t0
        profiling.add("serve/compile", dt, force=True)
        profiling.count("serve.compile_seconds", dt)
        return compiled

    def _exe_bytes(self, compiled, bucket: int) -> int:
        """Estimated device bytes one compiled executable keeps live —
        what the catalog's serve_cache_budget_mb accounting charges.
        XLA's own memory analysis where the backend reports it; the
        analytic request-buffer + output + temp-free floor otherwise."""
        try:
            ma = compiled.memory_analysis()
            total = int((getattr(ma, "argument_size_in_bytes", 0) or 0)
                        + (getattr(ma, "output_size_in_bytes", 0) or 0)
                        + (getattr(ma, "temp_size_in_bytes", 0) or 0))
            if total > 0:
                return total
        except Exception:  # noqa: BLE001 — estimate, never a failure
            pass
        in_bytes = bucket * self._buf_cols * np.dtype(self._buf_dtype).itemsize
        return int(in_bytes + self.K * bucket * 4)

    def _get_executable(self, replica: _Replica, bucket: int, kind: str):
        # the kernel VARIANT is part of the key: a binned and a raw
        # executable at the same (bucket, kind) are different programs
        # over different buffer dtypes and must never collide
        key = (bucket, kind, self.variant)
        with self._lock:
            exe = replica.compiled.get(key)
            if exe is not None:
                self.cache_hits += 1
                profiling.count("serve.cache_hit")
                return exe
        # compile outside the lock (minutes-long on big models); the
        # double-build race just wastes one compile, never corrupts
        exe = self._build(replica, bucket, kind)
        with self._lock:
            winner = replica.compiled.setdefault(key, exe)
            replica.exe_bytes.setdefault(key, self._exe_bytes(exe, bucket))
            self.cache_misses += 1
            profiling.count("serve.cache_miss")
        return winner

    # -- introspection --------------------------------------------------

    def buckets_compiled(self) -> List[Tuple[int, str]]:
        """Distinct (bucket, kind) pairs compiled on ANY replica (the
        kernel variant is uniform per runtime and elided — swap warmup
        carries buckets across variants)."""
        with self._lock:
            keys = set()
            for r in self.replicas:
                keys.update((b, k) for b, k, _v in r.compiled)
            return sorted(keys)

    def cache_bytes(self) -> int:
        """Estimated device bytes held by this runtime's compiled
        executables across every replica — the quantity the catalog's
        `serve_cache_budget_mb` LRU accounting sums per tenant."""
        with self._lock:
            return sum(sum(r.exe_bytes.values()) for r in self.replicas)

    def evict_executables(self) -> int:
        """Drop every compiled executable (every replica) — the
        catalog's LRU budget enforcement.  The model stacks stay
        device-resident, so the tenant keeps serving; its next request
        simply recompiles its bucket (counted as churn through
        serve/cache_evictions and the ordinary cache-miss counters).
        In-flight dispatches keep their own executable references and
        finish untouched."""
        with self._lock:
            n = sum(len(r.compiled) for r in self.replicas)
            for r in self.replicas:
                r.compiled.clear()
                r.exe_bytes.clear()
        if n:
            profiling.count(profiling.SERVE_CACHE_EVICTIONS, n)
            if self.model_id is not None:
                profiling.count(profiling.labeled(
                    profiling.SERVE_CACHE_EVICTIONS,
                    model=self.model_id), n)
            log.info(f"serving cache evicted {n} compiled executables"
                     + (f" (model {self.model_id})" if self.model_id
                        else "")
                     + " to honor serve_cache_budget_mb")
        return n

    def warmup(self, buckets: Sequence[int] = (),
               kinds: Sequence[str] = OUTPUT_KINDS) -> None:
        """Compile + execute the given row buckets on EVERY replica so
        the first real request after a (re)load never pays compile
        latency.  Defaults to BOTH output kinds: a value-only warmup
        used to leave the first "raw" request compiling on the request
        path (identity objectives share one program, so warming both is
        free there).  Used by ModelRegistry before a hot swap goes
        live."""
        buckets = sorted({row_bucket(b, self.min_bucket_rows,
                                     self.max_batch_rows)
                          for b in (buckets or (1,))})
        run_kinds = sorted({self._run_kind(k) for k in kinds})
        for replica in self.replicas:
            for b in buckets:
                for kind in run_kinds:
                    # bin 0 is a valid bin everywhere, so the all-zeros
                    # buffer warms the binned variant too
                    zeros = np.zeros((b, self._buf_cols), self._buf_dtype)
                    self._run_compiled(b, kind, zeros, replica=replica)

    # -- prediction -----------------------------------------------------

    def _pick_replica(self, exclude: FrozenSet[int] = frozenset(),
                      allow_probe: bool = True) -> _Replica:
        """Least-loaded HEALTHY dispatch with a round-robin tie-break.

        Broken replicas are routed around; each route-around bumps
        their skip counter, and once it reaches ``probe_after`` the
        next request becomes that replica's half-open probe (a probe
        failure retries on a healthy replica like any other failure,
        so the probing client is still served).  Raises
        NoHealthyReplicaError when no replica is dispatchable.
        """
        with self._lock:
            n = len(self.replicas)
            best = probe = None
            for off in range(n):
                r = self.replicas[(self._rr + off) % n]
                if r.index in exclude:
                    continue
                if r.broken:
                    r.skips += 1
                    if (allow_probe and probe is None
                            and r.skips >= self.probe_after
                            and r.inflight == 0):    # single-flight probe
                        probe = r
                    continue
                if best is None or r.inflight < best.inflight:
                    best = r
            if probe is not None:
                probe.skips = 0
                probe.probes += 1
                profiling.count(profiling.SERVE_REPLICA_PROBES)
                best = probe
            if best is None:
                raise NoHealthyReplicaError(
                    f"no healthy predictor replica ({n} total, "
                    f"{len(exclude)} excluded); retry later")
            self._rr = (best.index + 1) % n
            best.inflight += 1
            best.dispatches += 1
            return best

    def _note_success(self, replica: _Replica) -> None:
        readmitted = False
        with self._lock:
            replica.failures = 0
            if replica.broken:
                replica.broken = False
                replica.skips = 0
                readmitted = True
                profiling.count(profiling.SERVE_REPLICA_READMITTED)
        if readmitted:
            log.info(f"serving replica {replica.index} readmitted "
                     "(half-open probe succeeded)")
            telemetry.event("serve.breaker", replica=replica.index,
                            state="closed",
                            generation=self.generation)

    def _note_failure(self, replica: _Replica, error: BaseException) -> None:
        with self._lock:
            replica.failures += 1
            profiling.count(profiling.SERVE_REPLICA_FAILURES)
            opened = (not replica.broken
                      and replica.failures >= self.failure_threshold)
            reopened = replica.broken
            if opened:
                replica.broken = True
                replica.skips = 0
                profiling.count(profiling.SERVE_REPLICA_BROKEN)
            if reopened:
                replica.skips = 0     # probe failed: wait another window
        if opened:
            log.warning(
                f"serving replica {replica.index} circuit-broken after "
                f"{replica.failures} consecutive failures "
                f"({type(error).__name__}: {error}); traffic fails over "
                "to the surviving replicas")
        if opened or reopened:
            telemetry.event("serve.breaker", replica=replica.index,
                            state="open" if opened else "probe_failed",
                            error=f"{type(error).__name__}: {error}",
                            generation=self.generation)

    def _run_compiled(self, bucket: int, kind: str, Xpad: np.ndarray,
                      replica: Optional[_Replica] = None,
                      exclude: FrozenSet[int] = frozenset()):
        import jax
        pinned = replica is not None
        if replica is None:
            # a retry (non-empty exclude) must land on a HEALTHY replica:
            # routing it to a broken one's half-open probe could fail the
            # request while healthy replicas sit idle
            replica = self._pick_replica(exclude,
                                         allow_probe=not exclude)
        else:                          # warmup pins the replica itself
            with self._lock:
                replica.inflight += 1
                replica.dispatches += 1
        try:
            # the replica-level hop of a request's trace: which chip ran
            # this chunk, at which bucket/kind, under which generation
            # (and, in the multi-tenant catalog, for which model id)
            with telemetry.span("serve.replica", replica=replica.index,
                                bucket=bucket, kind=kind,
                                variant=self.variant,
                                generation=self.generation,
                                **({"model": self.model_id}
                                   if self.model_id is not None else {})):
                # chaos seams: a dispatch raising (any replica / THIS
                # replica) is the circuit breaker's trigger condition
                faults.check("serve.dispatch")
                faults.check(f"serve.dispatch.r{replica.index}")
                exe = self._get_executable(replica, bucket, kind)
                # explicit device_put/device_get keeps the serving loop
                # clean under the sanitizer's transfer guard
                # (BENCH_SANITIZE in scripts/bench_serve.py): implicit
                # conversions here would be one h2d + one d2h violation
                # per request
                out = exe(replica.stacks,
                          jax.device_put(Xpad.astype(self._buf_dtype,
                                                     copy=False),
                                         replica.device))
                res = jax.device_get(out).astype(np.float64)  # [K, bucket]
        except Exception as e:
            self._note_failure(replica, e)
            if pinned:                 # warmup: surface the raw error
                raise
            raise _ReplicaFailure(replica.index, e) from e
        else:
            self._note_success(replica)
            return res
        finally:
            with self._lock:
                replica.inflight -= 1

    def _predict_chunk(self, X: np.ndarray, kind: str) -> np.ndarray:
        if self._quantizer is not None:
            # ingress quantization: raw f64 rows → uint8/uint16 original
            # per-feature bins, host-side (numpy — thread-safe under the
            # chunk fan-out pool).  The device buffer shrinks 4x vs f32,
            # which is the bytes/row the canonical counter tracks.
            X = self._quantizer.quantize(X)
            profiling.count(profiling.SERVE_QUANTIZE_BYTES_IN, X.nbytes)
        n = X.shape[0]
        bucket = row_bucket(n, self.min_bucket_rows, self.max_batch_rows)
        if n < bucket:
            # pad rows carry bin 0 / feature 0.0 — sliced off below
            X = np.pad(X, ((0, bucket - n), (0, 0)))
        try:
            out = self._run_compiled(bucket, kind, X)
        except _ReplicaFailure as f:
            # retry ONCE on a healthy replica other than the one that
            # failed; its executable cache is as warm as the failed
            # one's (warmup covers every replica), so the retry never
            # compiles on the request path
            with self._lock:
                # chunks retry concurrently on the fan-out pool; this
                # read-modify-write needs the runtime lock
                self.chunk_retries += 1
            profiling.count(profiling.SERVE_CHUNK_RETRIES)
            try:
                out = self._run_compiled(bucket, kind, X,
                                         exclude=frozenset(
                                             {f.replica_index}))
            except NoHealthyReplicaError:
                if self.healthy_count() == 0:
                    raise              # total outage: 503, retryable
                # only the exclusion emptied the pool (single-replica
                # fleet, breaker not yet open): surface the real error
                raise f.error from f
            except _ReplicaFailure as f2:
                raise f2.error from f2
        return out[:, :n]

    def predict(self, X: np.ndarray, kind: str = "value") -> np.ndarray:
        """Score [n, F] rows; returns the same shapes as Booster.predict
        ([n] for K==1, [n, K] otherwise).

        Arbitrary n: full ``max_batch_rows`` slabs plus one bucketed
        remainder, so every executed shape hits the warm cache — the
        final partial chunk pads up instead of retracing.  Each chunk
        dispatches to the least-loaded replica independently — and
        concurrently on a multi-replica fleet — so one large request
        fans out across the fleet.
        """
        if kind not in OUTPUT_KINDS:
            raise ValueError(
                f"unknown output kind {kind!r}; use one of {OUTPUT_KINDS} "
                "(leaf indices go through Booster.predict(pred_leaf=True))")
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        if X.ndim == 1:
            X = X.reshape(1, -1)
        if X.shape[1] > self.num_features:
            # wider input is legal (reference predictor semantics: extra
            # trailing columns are ignored; the walk only gathers
            # feature indices the model knows)
            X = np.ascontiguousarray(X[:, :self.num_features])
        elif X.shape[1] < self.num_features:
            raise LightGBMError(
                f"request has {X.shape[1]} features, model expects "
                f"{self.num_features}")
        n = X.shape[0]
        if n == 0:
            return (np.zeros(0) if self.K == 1
                    else np.zeros((0, self.K)))
        if self._quantizer is not None:
            profiling.count(profiling.SERVE_BINNED_REQUESTS)
        run_kind = self._run_kind(kind)
        starts = range(0, n, self.max_batch_rows)
        with profiling.phase("serve/execute", force=True):
            if len(starts) == 1 or self._fanout is None:
                parts = [self._predict_chunk(X[a:a + self.max_batch_rows],
                                             run_kind)
                         for a in starts]
            else:
                # a multi-chunk request on a multi-replica fleet really
                # does fan out: chunks dispatch CONCURRENTLY (each
                # dispatch picks the least-loaded replica), so
                # wall-clock is ~chunks/replicas slabs, not a
                # sequential scan that merely rotates replicas.  The
                # caller's span context rides into the pool threads
                # explicitly (thread locals do not follow map work).
                ctx = telemetry.current()
                parts = list(self._fanout.map(
                    lambda a: telemetry.call_in_context(
                        ctx, self._predict_chunk,
                        X[a:a + self.max_batch_rows], run_kind),
                    starts))
        raw = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
        out = raw[0] if self.K == 1 else raw.T
        if kind == "value" and run_kind == "raw" and self.objective is not None:
            out = self.objective.convert_output(out)
        profiling.count("serve.rows", n)
        return out
