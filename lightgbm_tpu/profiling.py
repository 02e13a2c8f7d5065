"""Host phase spans, counters and sample reservoirs.

`phase(name)` is the one way the training path marks a host phase
(boosting/bagging/tree/score/metric — the reference's std::chrono
accumulators, gbdt.cpp:20-29,50-60): it always opens a
`jax.profiler.TraceAnnotation("lgbt.<name>")`, so a profiler session
shows the program's spans on the same clock as the device events, and
it feeds the wall-clock accumulators only when `telemetry.configure`
has switched them on (the `train.iteration` event's `phases` field).
With `force=True` (the serving `/stats` phases) it is the accumulator
alone, always on, and opens no span.  The
accumulators time the host: around asynchronous dispatch they measure
the enqueue, not the device work.

The module also keeps what a compiled program says about its own
operations (`publish_program` / `phase_tables`, at the end): which
phase each instruction belongs to and which slot tier a histogram
launch was made for, for whoever reads a device trace.
"""
from __future__ import annotations

import math
import re
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Deque, Dict, Iterator, Optional, Tuple

# telemetry.configure() flips this so the phase accumulators run (and
# feed per-iteration records + /metrics) whenever span tracing is on
_PHASES_FORCED = False

# prefix of every span the program writes into a profiler trace
SPAN_PREFIX = "lgbt."


def force_phases(on: bool = True) -> None:
    """Switch the phase accumulators on (telemetry.configure does;
    telemetry.reset undoes)."""
    global _PHASES_FORCED
    _PHASES_FORCED = bool(on)

_totals: Dict[str, float] = defaultdict(float)

# Always-on counters and bounded sample reservoirs (the serving layer's
# request/cache/latency metrics flow through these whether or not
# telemetry is configured — a production /stats endpoint cannot depend
# on a debug switch).  Guarded by one lock: serving increments from many
# threads.
_lock = threading.Lock()
_counters: Dict[str, float] = defaultdict(float)
_samples: Dict[str, Deque[float]] = {}
_SAMPLE_CAP = 4096
# one pending (device vector, calls) per (names, fold) of
# count_deferred, which accumulates DEVICE-side, so an arbitrarily long
# training run holds exactly one live buffer per key; folded into
# _counters on read
_deferred: Dict[tuple, tuple] = {}

# Canonical counter names of the data-parallel tree learners' comms
# layer, fed through count_deferred (device-side accumulation, no sync
# on the pipelined path) and read by bench.py / the MULTICHIP dryrun:
#  - HIST_ROWS_TOUCHED: rows processed by histogram kernels (global sum
#    across shards).
#  - HIST_EXCHANGE_BYTES: PER-DEVICE histogram-collective payload —
#    bytes of reduced histogram each device materializes per pass (the
#    full [K, F, 3, B] tensor under psum, its F/ndev slice under
#    psum_scatter), summed over passes.
#  - SPLIT_RECORDS_BYTES: per-device bytes of the psum_scatter path's
#    best-split-record allgather ([ndev, K, 11] f32 per pass; zero
#    under psum, which exchanges no records).
# The BENCH_SANITIZE divergence audit (diagnostics/sanitize.py
# DivergenceSanitizer) feeds two more counters through count():
# sanitize/divergence_checks (cross-shard fingerprint comparisons of
# the replicated tree state) and sanitize/divergences (bitwise
# mismatches — the hard-fail condition); bench.py and the MULTICHIP
# dryrun record both beside the retrace/transfer counters.
HIST_ROWS_TOUCHED = "tree/hist_rows_touched"
HIST_EXCHANGE_BYTES = "tree/hist_exchange_bytes"
SPLIT_RECORDS_BYTES = "tree/split_records_bytes"

# Work counters of the rounds build (learner/rounds.build_tree_rounds
# adds them on the device, where each launch is made; they ride its
# stats vector like the ones above):
#  - TREE_ROUNDS: rounds of the build's while_loop (every round
#    partitions all rows once and splits every splittable leaf).
#  - HIST_PASSES: histogram kernel launches, the root's included.
#  - HIST_SLOTS: leaf slots those launches were made for — the K of
#    each launch (its matmul has 3K value rows), 1 at the root.
#  - HIST_LIVE_SLOTS: the slots among them that held a leaf;
#    live / slots is how full the launches ran.
#  - HIST_MXU_OPS: multiply-adds x 2 that the dense launches'
#    contractions perform as executed (ops/histogram.
#    masked_hist_mxu_ops): the padded rows, value rows and bins
#    included, the store's padded columns, which no launch
#    histograms, not; summed across shards.  The sparse kernels
#    add 0: their contraction runs over entry blocks, not rows.
#  - PARTITION_ROWS: rows whose place in the leaf-id vector a round
#    rewrites, as executed: every row of the shard in every round
#    today (a partition that moved only the split leaves' rows would
#    count fewer), summed across shards.
#  - STORE_COPY_ROWS: rows of the bin store that the build program
#    copies into a padded form in front of a kernel — the partition of
#    each round and each histogram launch whose wrapper cannot tile
#    the store as it stands (ops/partition.partition_store_copy_rows,
#    ops/histogram.masked_store_copy_rows), summed across shards.  0
#    for the store as RoundsTreeLearner lays it out on the chip; a
#    retuned row chunk that the layout no longer fits shows here.
#  - EXCHANGE_COLLECTIVES: collectives the build launches across the
#    mesh, as executed: the legs of each histogram exchange (one psum
#    or psum_scatter a pass, two on a 2-D mesh's scatter), the
#    all_gather of each batch of best-split records under psum_scatter,
#    and the root's psum of the leaf totals there.  Per device (every
#    shard launches the same ones); 0 without a mesh.  The closing psum
#    of this vector's own global slots is not counted.
#  - SPLIT_CELLS: histogram cells that split search scans, as padded
#    and as executed: searched slots (1 at the root, twice the slots of
#    the tier an executed chunk ran at, live or not) x the features of
#    the array the search runs over x padded bins — the store's padded
#    columns (a device's slice under psum_scatter), bundled or not: a bundled
#    store is searched in its own cells (ops/split.best_split_in_store);
#    every original feature only where a bundled histogram is first
#    unbundled.  Per device.  Static per pass, so it does not ride the
#    vector: RoundsTreeLearner folds it on the host from HIST_SLOTS
#    (learner/rounds.search_counters, count_deferred's `fold`); the
#    other learners do not count it.
#  - UNBUNDLE_GATHER_ELEMS: histogram elements that an unbundle in
#    front of that search gathers through its [F, B] index table
#    (ops/split.unbundle_hist): 3 x F x B per searched slot where a
#    bundle plan packs a categorical feature, the one store the rounds
#    learner still unbundles; 0 on every other.  Folded the same way.
#  - HIST_PAD_COLUMNS: columns of the padded store (to the kernels'
#    feature group, or to the scatter's slices) that the dense
#    launches do not histogram — no one-hot, no contraction, exact
#    zeros handed back (ops/histogram.masked_pad_columns) — summed over
#    launches and shards.  Static per launch: folded the same way from
#    HIST_PASSES; 0 on a store with no padded column and on the sparse
#    path.
TREE_ROUNDS = "tree/rounds"
HIST_PASSES = "tree/hist_passes"
HIST_SLOTS = "tree/hist_slots"
HIST_LIVE_SLOTS = "tree/hist_live_slots"
HIST_MXU_OPS = "tree/hist_mxu_ops"
PARTITION_ROWS = "tree/partition_rows"
STORE_COPY_ROWS = "tree/store_copy_rows"
EXCHANGE_COLLECTIVES = "tree/exchange_collectives"
SPLIT_CELLS = "tree/split_cells"
UNBUNDLE_GATHER_ELEMS = "tree/unbundle_gather_elems"
HIST_PAD_COLUMNS = "tree/hist_pad_columns"
# Outside the build, counted on the host by count() at each leaf-id
# update of a score (boosting/score_updater._add_leaf_to_row):
#  - SCORE_GATHER_ROWS: rows whose leaf ids that update has to fetch
#    from another device's memory: 0 where score and leaf ids share a
#    row layout or lie on one device, the id count where the ids come
#    sharded over a mesh and the score does not.  Read off the two
#    shardings, no sync; the 0 registers the key.
SCORE_GATHER_ROWS = "tree/score_gather_rows"
# Counted on the host by count() where a build's tree arrays become a
# host tree (learner/fused.tree_arrays_to_host), from the is_cat they
# carry: no device operation, no sync:
#  - CATEGORICAL_SPLITS: splits on a categorical feature (one category
#    against the rest) in the trees collected.  A tree with none counts
#    0, which registers the key.
CATEGORICAL_SPLITS = "tree/categorical_splits"
#  - MISSING_SPLITS: splits on a feature with a NaN bin (missing type
#    NaN; binning.BinMapper.nan_bin).  Counted only for a training set
#    that has such a feature: a program or a store without one has no
#    key.
#  - MISSING_DEFAULT_LEFT: those of them that send the NaN bin left
#    (decision_type's default_left bit).
MISSING_SPLITS = "tree/missing_splits"
MISSING_DEFAULT_LEFT = "tree/missing_default_left"
# Nothing increments these three since the row feed they counted went;
# they stay, at 0 from the start, only for benchmark/ (jobs/train.py and
# the feed_rows_per_iter metric read them) until ROADMAP B0.5 drops it.
HIST_ROWS_DOWNGRADES = "tree/hist_rows_downgrades"
FEED_ROWS = "tree/feed_rows"
FEED_LIVE_ROWS = "tree/feed_live_rows"
_RETIRED_COUNTERS = (HIST_ROWS_DOWNGRADES, FEED_ROWS, FEED_LIVE_ROWS)
_counters.update(dict.fromkeys(_RETIRED_COUNTERS, 0.0))

# Canonical sparse-store counters (docs/Sparse.md), the nnz-scaling
# evidence behind the sparse-vs-dense CTR A/B:
#  - SPARSE_NNZ_TOUCHED: stored (column, bin) entries processed by the
#    nonzero-iterating histogram kernels, summed over passes (global
#    across shards, like HIST_ROWS_TOUCHED).  The dense equivalent is
#    rows_touched x store columns; the ratio is the bench gate.
#  - SPARSE_FALLBACKS: times a sparse store had to materialize its
#    dense [F_eff, N] matrix for a consumer without a sparse path
#    (feature-sharded learners, binned score replay, binary-cache
#    writes) — silent densification is an operator-visible signal.
SPARSE_NNZ_TOUCHED = "tree/sparse_nnz_touched"
SPARSE_FALLBACKS = "tree/sparse_fallbacks"

# Canonical robustness counters (docs/Robustness.md), fed through
# count() by the serving fleet's failover machinery and the registry:
#  - REGISTRY_SWAP_FAILURES: hot-swap candidates rejected (corrupt/torn
#    model files, failed compiles) — the old generation kept serving.
#  - serve.replica_failures / serve.replica_broken /
#    serve.replica_readmitted / serve.replica_probes: per-event breaker
#    transitions; serve.chunk_retries counts failed chunks re-run on a
#    healthy replica.  All surfaced at the server's /stats endpoint so
#    silent degradation is an operator-visible signal.
REGISTRY_SWAP_FAILURES = "registry/swap_failures"
SERVE_CHUNK_RETRIES = "serve.chunk_retries"
SERVE_REPLICA_FAILURES = "serve.replica_failures"
SERVE_REPLICA_BROKEN = "serve.replica_broken"
SERVE_REPLICA_READMITTED = "serve.replica_readmitted"
SERVE_REPLICA_PROBES = "serve.replica_probes"

# Canonical binned-inference counters (docs/serving.md "Binned
# inference"), fed through count() by the serving runtime's ingress
# quantization (serve_quantize=binned):
#  - SERVE_QUANTIZE_BYTES_IN: bytes of the quantized uint8/uint16
#    request buffers shipped to the device — ~4x below what the same
#    rows cost as f32, the memory-bandwidth win of fixed-point
#    traversal.
#  - SERVE_BINNED_REQUESTS: predict() calls that ran the binned kernel
#    variant (raw-variant runtimes count nothing here).
SERVE_QUANTIZE_BYTES_IN = "serve/quantize_bytes_in"
SERVE_BINNED_REQUESTS = "serve/binned_requests"

# Canonical multi-tenant catalog counters (docs/serving.md
# "Multi-tenant catalog"), fed through count() by the ModelCatalog's
# LRU budget enforcement and the registries' shadow-canary machinery:
#  - SERVE_CACHE_EVICTIONS: compiled executables dropped to fit the
#    `serve_cache_budget_mb` device-memory budget (the churn metric —
#    an evicted tenant's next request recompiles).
#  - SERVE_SHADOW_SCORED: requests double-scored on a staged candidate
#    generation (stable answered the client; the candidate's answer
#    only fed the divergence log).
#  - SERVE_SHADOW_ADOPTIONS / SERVE_SHADOW_REJECTIONS: canary verdicts
#    — candidates promoted to stable after `serve_shadow_requests`
#    comparisons vs candidates discarded (divergence over the gate, or
#    a candidate that could not score).
SERVE_CACHE_EVICTIONS = "serve/cache_evictions"
SERVE_SHADOW_SCORED = "serve/shadow_scored"
SERVE_SHADOW_ADOPTIONS = "serve/shadow_adoptions"
SERVE_SHADOW_REJECTIONS = "serve/shadow_rejections"

# Cross-model co-stacked serving (serving/superstack.py,
# docs/serving.md "Cross-model batching"):
#  - SERVE_GROUP_COMPILES: XLA compilations charged to a GROUP's shared
#    super-stack executable (the per-group labeled series rides the
#    same name) — the quantity co-stacking divides by the group size.
#  - SERVE_GROUP_RESTACKS: super-stack rebuilds after a member tenant's
#    hot swap (cache-transplanting restacks included; only restacks
#    whose program changed also show up as group compiles).
#  - SERVE_GROUP_SEGMENT_ROWS / SERVE_GROUP_STACKED_ROWS: mixed-batch
#    rows demuxed through a group executable, split by the RESOLVED
#    costack kernel — segment (per-row tree-segment gather: node math
#    ~1x a solo tenant's) vs stacked (walk-all: ~G x node math where
#    launch overhead hides it).  The per-group labeled series ride the
#    same names; summed they equal the grouped share of serve.rows.
#  - SERVE_GROUP_QUANTIZE_SHARED: rows a binned group quantized ONCE
#    against its members' shared refbin mapper set at ingress instead
#    of once per member job — the host-CPU dedup of the shared ingress
#    quantizer (rows also counted in SERVE_QUANTIZE_BYTES_IN by bytes).
SERVE_GROUP_COMPILES = "serve/group_compiles"
SERVE_GROUP_RESTACKS = "serve/group_restacks"
SERVE_GROUP_SEGMENT_ROWS = "serve/group_segment_rows"
SERVE_GROUP_STACKED_ROWS = "serve/group_stacked_rows"
SERVE_GROUP_QUANTIZE_SHARED = "serve/group_quantize_shared"

# Canonical router-tier counters (docs/Router.md), fed through count()
# by the task=route process fronting M backend serving processes:
#  - ROUTER_REQUESTS: /predict requests accepted by the router (the
#    per-model and per-backend labeled series ride the same base name).
#  - ROUTER_RETRIES: proxied dispatches that failed at the transport
#    layer and were re-run once on a different healthy backend (the
#    router-scope analogue of serve.chunk_retries).
#  - ROUTER_REJECTED: requests shed with 503 — the `route_max_inflight`
#    admission cap, or no healthy backend placeable for the model.
#  - ROUTER_BACKEND_FAILURES / ROUTER_BACKEND_BROKEN /
#    ROUTER_BACKEND_READMITTED / ROUTER_BACKEND_PROBES: per-event
#    breaker transitions of the per-backend circuit breakers (the PR 9
#    replica state machine one level up).
#  - ROUTER_REHASHES: requests whose placement (override target or
#    ring-home backend) was open-breaker and re-placed onto the next
#    healthy backend clockwise — the drain-re-placement churn metric.
ROUTER_REQUESTS = "router/requests"
ROUTER_RETRIES = "router/retries"
ROUTER_REJECTED = "router/rejected"
ROUTER_BACKEND_FAILURES = "router/backend_failures"
ROUTER_BACKEND_BROKEN = "router/backend_broken"
ROUTER_BACKEND_READMITTED = "router/backend_readmitted"
ROUTER_BACKEND_PROBES = "router/backend_probes"
ROUTER_REHASHES = "router/rehashes"

# Every canonical counter constant of this module, in one tuple: the
# Prometheus exposition (telemetry.prometheus_text) seeds each of these
# at 0 so a scrape always covers the full canonical set, and the
# counter-name lint (scripts/check_counter_names.py) enforces that call
# sites use the constants instead of re-typing the strings.
CANONICAL_COUNTERS = (
    HIST_ROWS_TOUCHED, HIST_EXCHANGE_BYTES, SPLIT_RECORDS_BYTES,
    HIST_ROWS_DOWNGRADES, TREE_ROUNDS, HIST_PASSES, HIST_SLOTS,
    HIST_LIVE_SLOTS, HIST_MXU_OPS, FEED_ROWS, FEED_LIVE_ROWS,
    PARTITION_ROWS, STORE_COPY_ROWS, EXCHANGE_COLLECTIVES,
    SPLIT_CELLS, UNBUNDLE_GATHER_ELEMS, HIST_PAD_COLUMNS, SCORE_GATHER_ROWS,
    CATEGORICAL_SPLITS, MISSING_SPLITS, MISSING_DEFAULT_LEFT,
    SPARSE_NNZ_TOUCHED, SPARSE_FALLBACKS,
    REGISTRY_SWAP_FAILURES, SERVE_CHUNK_RETRIES, SERVE_REPLICA_FAILURES,
    SERVE_REPLICA_BROKEN, SERVE_REPLICA_READMITTED, SERVE_REPLICA_PROBES,
    SERVE_QUANTIZE_BYTES_IN, SERVE_BINNED_REQUESTS,
    SERVE_CACHE_EVICTIONS, SERVE_SHADOW_SCORED, SERVE_SHADOW_ADOPTIONS,
    SERVE_SHADOW_REJECTIONS, SERVE_GROUP_COMPILES, SERVE_GROUP_RESTACKS,
    SERVE_GROUP_SEGMENT_ROWS, SERVE_GROUP_STACKED_ROWS,
    SERVE_GROUP_QUANTIZE_SHARED,
    ROUTER_REQUESTS, ROUTER_RETRIES, ROUTER_REJECTED,
    ROUTER_BACKEND_FAILURES, ROUTER_BACKEND_BROKEN,
    ROUTER_BACKEND_READMITTED, ROUTER_BACKEND_PROBES, ROUTER_REHASHES,
)


def labeled(name: str, **labels) -> str:
    """Registry key for a LABELED counter/reservoir series.

    ``labeled("serve.requests", model="de")`` returns
    ``serve.requests{model="de"}``, which `telemetry.prometheus_text`
    renders as the Prometheus series
    ``lgbt_serve_requests_total{model="de"}`` — one metric FAMILY with
    one series per label set, instead of a name-mangled counter per
    tenant.  Label values must be identifier-shaped (the multi-tenant
    catalog validates model ids against ``[A-Za-z0-9._-]{1,64}`` before
    they reach here); the base name follows the same rules as unlabeled
    counters (scripts/check_counter_names.py lints `labeled` call sites
    like any other registry call)."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return f"{name}{{{inner}}}"


@contextmanager
def phase(name: str, force: bool = False, **attrs) -> Iterator[None]:
    """Mark a host phase of training: a `TraceAnnotation` named
    "lgbt.<name>" (with `attrs` as its stats) that a running profiler
    session records and that costs a disabled-TraceMe check otherwise;
    wall-clock is accumulated under `name` only while telemetry is
    configured.  With `force=True` (the serving `/stats` phases) it is
    the accumulator alone: always timed, and no span."""
    timed = force or _PHASES_FORCED
    t0 = time.perf_counter() if timed else 0.0
    try:
        if force:
            yield
        else:
            import jax
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **attrs):
                yield
    finally:
        if timed:
            add(name, time.perf_counter() - t0, force=True)


def add(name: str, seconds: float, force: bool = False) -> None:
    if force or _PHASES_FORCED:
        with _lock:
            _totals[name] += seconds


def count(name: str, inc: float = 1.0) -> None:
    """Bump an always-on counter (thread-safe)."""
    with _lock:
        _counters[name] += inc


def count_deferred(names: Tuple[str, ...], values, fold=None) -> None:
    """Accumulate a DEVICE vector against a tuple of counters, one
    element per name, without forcing a host sync (the pipelined
    trainer must not stall on a metrics fetch — the device→host
    transfer that motivates _train_one_iter_pipelined).  Accumulation
    happens device-side (`+` dispatches asynchronously): one device add
    per call and one live buffer per (names, fold); the totals are
    converted and folded into the counters on the next
    counter_value()/counters() read, where the caller has chosen to pay
    the sync.

    `fold(totals, calls)`, if given, is called on the host at that read
    with the fetched totals and the number of calls they sum, and
    returns `(name, increment)` pairs: counters that are a function of
    what the vector already counts cost the device nothing.  It is part
    of the key, so hand over the same object every time, and one that
    holds no device memory."""
    key = (names, fold)
    with _lock:
        prev = _deferred.get(key)
        _deferred[key] = ((values, 1) if prev is None
                          else (prev[0] + values, prev[1] + 1))


def _drain_deferred_locked() -> None:
    """Fold pending device totals into _counters; caller holds _lock.
    ONE batched explicit fetch for every pending vector (jax.device_get
    blocks until the values are ready; per-name float() was one sync per
    counter, and implicit under the sanitizer's transfer guard)."""
    if not _deferred:
        return
    import jax
    pending = list(_deferred.items())
    vals = jax.device_get([total for _, (total, _) in pending])
    for ((names, fold), (_, calls)), vec in zip(pending, vals):
        for name, v in zip(names, vec):
            _counters[name] += float(v)
        for name, inc in (fold(vec, calls) if fold is not None else ()):
            _counters[name] += inc
    _deferred.clear()


def counter_value(name: str) -> float:
    with _lock:
        _drain_deferred_locked()
        return _counters.get(name, 0.0)


def counters(prefix: str = "", sync: bool = True) -> Dict[str, float]:
    with _lock:
        if sync:
            _drain_deferred_locked()
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def counters_nosync(prefix: str = "") -> Dict[str, float]:
    """Host-visible counter values WITHOUT draining the deferred device
    totals — safe on the pipelined training path (no device sync).
    `count_deferred` accumulations lag until the next counters()/
    snapshot() read pays the sync; counters recorded with count() are
    exact.  The per-iteration training telemetry reads through here."""
    return counters(prefix, sync=False)


def observe(name: str, value: float) -> None:
    """Record one sample into a bounded reservoir (for percentiles)."""
    with _lock:
        dq = _samples.get(name)
        if dq is None:
            dq = _samples[name] = deque(maxlen=_SAMPLE_CAP)
        dq.append(value)


def _summary_of(vals) -> Dict[str, float]:
    """Nearest-rank percentiles (ceil(p*n)-1) over pre-sorted samples.
    The previous ``int(p * n)`` indexing overshot nearest-rank by one
    position — p50 of [1, 2] returned 2 and p99 of 100 samples returned
    the max — which matters because p99 is the SLO number the serve
    bench gates on."""
    if not vals:
        return {"count": 0}

    def q(p: float) -> float:
        return vals[min(len(vals) - 1, max(0, math.ceil(p * len(vals)) - 1))]

    return {"count": len(vals), "p50": q(0.50), "p95": q(0.95),
            "p99": q(0.99), "max": vals[-1]}


def summary(name: str) -> Dict[str, float]:
    """count/p50/p95/p99/max over the retained samples of `name` — p99
    is the serving SLO metric the sustained-QPS bench gates on."""
    with _lock:
        vals = sorted(_samples.get(name, ()))
    return _summary_of(vals)


def snapshot() -> Tuple[Dict[str, float], Dict[str, Dict[str, float]]]:
    """ONE locked snapshot of the whole registry for a /metrics scrape:
    (counters, {name: summary}) — deferred device totals drain here
    (the scrape pays the sync, same contract as counters())."""
    with _lock:
        # graftlint: allow(blocking-under-lock) — the deferred drain syncs device buffers under _lock BY CONTRACT: the scrape pays the one sync so hot paths never do (counters_nosync is the lock-free read)
        _drain_deferred_locked()
        ctrs = dict(_counters)
        sums = {name: _summary_of(sorted(dq))
                for name, dq in _samples.items()}
    return ctrs, sums


def timings() -> Dict[str, float]:
    """Phase totals (the /stats view of the phase accumulators)."""
    with _lock:
        return dict(_totals)


def reset() -> None:
    with _lock:
        _totals.clear()
        _counters.clear()
        _counters.update(dict.fromkeys(_RETIRED_COUNTERS, 0.0))
        _samples.clear()
        _deferred.clear()
        _programs.clear()


@contextmanager
def device_trace(logdir: str) -> Iterator[None]:
    """jax.profiler trace wrapper — the TPU analog of the reference's GPU
    transfer/kernel timing logs (gpu_tree_learner.cpp:538-542).  View with
    TensorBoard or xprof.  Also emitted as a telemetry span carrying the
    logdir, so the xprof device trace can be lined up against the host
    span timeline under the same trace id (scripts/trace_view.py)."""
    import jax

    from . import telemetry
    jax.profiler.start_trace(logdir)
    try:
        with telemetry.span("profiling.device_trace", logdir=logdir):
            yield
    finally:
        jax.profiler.stop_trace()


# ---- phase tables: what a compiled program says about its own operations ----
#
# A device trace names each event after an HLO instruction of the program
# that ran; which phase of the build the instruction belongs to and, for a
# histogram kernel launch, how many slots it was made for are facts about
# the PROGRAM, and the program states them: publish_program() keeps a
# recipe (the jitted callable and its abstract arguments, no buffer) and
# phase_tables() turns it, when somebody asks and never otherwise, into
# {instruction name: {"phase", "slots"}} from the compiled program's own
# text.  Joined by instruction name with the trace's `XLA Ops` events (or
# an xprof capture) it splits the program's device time by phase and by
# slot tier; benchmark/readers/trace_phase_time.py is the reader.

@dataclass
class _Recipe:
    """How to obtain a compiled program, then what was made of it:
    `tables` memoises the parse, after which callable and arguments are
    let go (and with them the loaded executable that the callable's own
    cache keeps alive)."""
    jitted: Any
    args: Any
    slots_of: Optional[Callable]
    tables: Optional[Dict[str, Dict[str, dict]]] = None


# one entry per program name, the newest publisher's: a process that
# builds learner after learner holds one recipe
_programs: Dict[str, _Recipe] = {}

_HLO_HEADER = re.compile(r"^HloModule\s+([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_ASSIGN = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_HLO_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLEE = re.compile(
    r"\b(body|condition|true_computation|false_computation|to_apply|calls)"
    r"=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_PHASE = re.compile(re.escape(SPAN_PREFIX) + r"\w+")


def publish_program(name: str, jitted, args, slots_of=None) -> None:
    """Register how to obtain the compiled program `name` (an XLA module
    name, `jit_<function>`): `jitted.lower(*args).compile()`, with `args`
    the call's abstract arguments (jax.ShapeDtypeStruct, with the
    sharding where the call's array is committed to one).  Costs a dict
    write; nothing is lowered until phase_tables() is called.

    `slots_of(instruction name, opcode, result dims)` -> K or None says
    which instructions are histogram kernel launches, and for how many
    slots: the publisher owns the kernels' names and shapes."""
    with _lock:
        _programs[name] = _Recipe(jitted, args, slots_of)


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at `start`."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _hlo_instruction(line: str):
    """One instruction line of an HLO text -> (name, opcode, result dims
    or None for a tuple, operand text, attribute text), or None."""
    m = _HLO_ASSIGN.match(line)
    if m is None:
        return None
    i = m.end()
    if line.startswith("(", i):                 # a tuple's shape
        j, dims = _balanced(line, i), None
    else:
        j = line.find(" ", i)
        if j < 0:
            return None
        d = re.match(r"\w+\[([\d,]*)\]", line[i:j])
        dims = (tuple(int(x) for x in d.group(1).split(",") if x)
                if d else ())
    op = _HLO_OPCODE.match(line, j)
    if op is None:
        return None
    k = _balanced(line, op.end() - 1)
    return m.group(1), op.group(1), dims, line[op.end():k - 1], line[k:]


def phase_table_of(text: str, slots_of=None) -> Tuple[str, Dict[str, dict]]:
    """(module name, {instruction name: {"phase", "slots"}}) of an
    optimised HLO text (`compiled.as_text()`).

    Listed is every instruction of the entry computation and of each
    computation that control flow runs — while bodies and conditions,
    conditional branches, calls — which are the instructions that can
    appear as events on a trace's `XLA Ops` line; a fusion's body and the
    scalar computations of a reduce or a sort are not.  Names are unique
    in a module.  `phase` is the innermost "lgbt.<phase>" of the
    instruction's op_name (a fusion carries the name XLA gave the fused
    instruction).  One that states none — the compiler's own copies,
    bitcasts and tuples — takes its consumers' where it has any in its
    computation (None where they disagree), else that of the
    instruction that runs its computation: a gather or scatter that the
    compiler expands into a loop is a `while` named after the operation
    whose body's instructions carry no name.  `slots` is `slots_of`'s
    word."""
    header = _HLO_HEADER.match(text)
    module = header.group(1) if header else ""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _HLO_COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
        elif line.startswith("}"):
            cur = None
        else:
            ins = _hlo_instruction(line)
            if ins is not None:
                cur.append(ins)
    table: Dict[str, dict] = {}
    seen, stack = set(), [(entry, None)]
    while stack:
        comp, callers = stack.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        names = {ins[0] for ins in comps[comp]}
        wanted: Dict[str, set] = {}     # name -> phases of its consumers
        for name, opcode, dims, operands, attrs in reversed(comps[comp]):
            own = _PHASE.findall("".join(_HLO_OP_NAME.findall(attrs)))
            asked = wanted.get(name, ())
            if own:
                phase = own[-1]
            elif asked:
                phase = next(iter(asked)) if len(asked) == 1 else None
            else:
                phase = callers
            for operand in set(re.findall(r"[\w.\-]+", operands)) & names:
                wanted.setdefault(operand, set()).add(phase)
            table[name] = {"phase": phase, "slots": (
                slots_of(name, opcode, dims) if slots_of else None)}
            if opcode == "fusion":
                continue
            for key, callee in _HLO_CALLEE.findall(attrs):
                if key != "to_apply" or opcode == "call":
                    stack.append((callee, phase))
            for group in _HLO_BRANCHES.findall(attrs):
                stack += [(c.strip().lstrip("%"), phase)
                          for c in group.split(",")]
    return module, table


def phase_tables() -> Dict[str, Dict[str, dict]]:
    """{XLA module name: phase table} of every published program
    (phase_table_of says what a table holds); {} where none was.

    The first call after a publish lowers and compiles the recipe — in
    the process that already ran the program both are reads of jit's own
    caches (milliseconds), in a fresh one a real compile or a read of the
    persistent compilation cache — and parses the text; later calls
    return the memo.  Raises what the lowering raises."""
    with _lock:
        todo = list(_programs.items())
    out = {}
    for name, r in todo:
        if r.tables is None:
            module, table = phase_table_of(
                r.jitted.lower(*r.args).compile().as_text(), r.slots_of)
            r.tables = {module or name: table}
            r.jitted = r.args = r.slots_of = None
        out.update(r.tables)
    return out
