"""Unified telemetry (lightgbm_tpu/telemetry.py): structured span
tracing with end-to-end trace-id propagation, the Prometheus /metrics
exposition, per-iteration training records, the /stats process block,
and the zero-overhead-when-off contract.

Every test that enables telemetry tears it down (the module fixture
calls telemetry.reset()) so one test's sink can never leak into the
next — the same discipline as the serving tests' server teardown.
"""
import http.client
import json
import os
import re
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import profiling, telemetry
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.diagnostics.sanitize import (HotPathSanitizer,
                                               transfer_guard_effective)

pytestmark = pytest.mark.quick

needs_guard = pytest.mark.skipif(
    not transfer_guard_effective(),
    reason="jax.transfer_guard is a no-op on this backend")


@pytest.fixture
def telem(tmp_path):
    """Enable span tracing into a per-test sink; always reset after."""
    path = str(tmp_path / "spans.jsonl")
    telemetry.configure(path, process="test")
    try:
        yield path
    finally:
        telemetry.reset()


def _records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _by_name(recs):
    out = {}
    for rec in recs:
        out.setdefault(rec["name"], []).append(rec)
    return out


def _train_binary(num_leaves=15, rounds=5, seed=7, n=400, f=10):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f)
    w = rng.randn(f)
    z = X @ w
    y = (z > np.median(z)).astype(float)
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "num_leaves": num_leaves, "min_data_in_leaf": 5},
                      lgb.Dataset(X, y))
    for _ in range(rounds):
        bst.update()
    assert bst.num_trees() > 0
    return bst, X, y


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------


def test_disabled_path_is_one_shared_noop():
    """Telemetry off: span() hands out ONE singleton (no allocation),
    event() returns after the cached check, and no file appears."""
    assert not telemetry.enabled()
    s1 = telemetry.span("a", x=1)
    s2 = telemetry.span("b")
    assert s1 is s2                      # no span objects allocated
    with s1 as sp:
        assert sp.trace_id is None
    telemetry.event("nothing", y=2)      # no sink: must be a no-op
    assert telemetry.current() is None
    assert telemetry.config_in_effect()["path"] is None


def test_span_nesting_trace_and_parent_ids(telem):
    with telemetry.span("outer", foo=1) as outer:
        assert outer.trace_id and outer.span_id
        with telemetry.span("inner"):
            telemetry.event("tick", n=3)
    recs = _records(telem)
    assert [r["name"] for r in recs] == ["tick", "inner", "outer"]
    tick, inner, outer_rec = recs
    assert tick["trace"] == inner["trace"] == outer_rec["trace"]
    assert inner["parent"] == outer_rec["span"]
    assert tick["parent"] == inner["span"]
    assert outer_rec["parent"] is None
    assert outer_rec["attrs"] == {"foo": 1}
    assert outer_rec["dur_ms"] >= inner["dur_ms"] >= 0
    assert outer_rec["proc"] == "test" and outer_rec["kind"] == "span"
    assert tick["kind"] == "event"


def test_explicit_ids_and_trace_context(telem):
    tid = "f" * 32
    with telemetry.span("adopted", trace_id=tid):
        pass
    with telemetry.trace_context(tid, "1234567890abcdef"):
        telemetry.event("under-ctx")
    ctx = (tid, "feedbeef00000000")
    telemetry.call_in_context(ctx, lambda: telemetry.event("via-call"))
    recs = _records(telem)
    assert all(r["trace"] == tid for r in recs)
    assert recs[1]["parent"] == "1234567890abcdef"
    assert recs[2]["parent"] == "feedbeef00000000"


def test_span_error_status(telem):
    with pytest.raises(ValueError):
        with telemetry.span("boom"):
            raise ValueError("nope")
    (rec,) = _records(telem)
    assert rec["status"] == "error"
    assert rec["error"].startswith("ValueError")


# ---------------------------------------------------------------------------
# profiling.summary percentile fix (nearest-rank)
# ---------------------------------------------------------------------------


def test_summary_nearest_rank_percentiles():
    """Pin p50/p95/p99 on known arrays: the old int(p*n) indexing
    overshot nearest-rank (p50 of [1,2] said 2; p99 of 100 samples said
    the max) — this is the SLO number the serve bench gates on."""
    name = "test.summary_nearest_rank"
    profiling.observe(name, 1.0)
    profiling.observe(name, 2.0)
    s = profiling.summary(name)
    assert s == {"count": 2, "p50": 1.0, "p95": 2.0, "p99": 2.0,
                 "max": 2.0}
    name2 = name + ".hundred"
    for v in range(1, 101):              # 1..100, nearest-rank = value
        profiling.observe(name2, float(v))
    s = profiling.summary(name2)
    assert s["p50"] == 50.0
    assert s["p95"] == 95.0
    assert s["p99"] == 99.0              # NOT the max
    assert s["max"] == 100.0
    name3 = name + ".one"
    profiling.observe(name3, 7.0)
    assert profiling.summary(name3) == {"count": 1, "p50": 7.0,
                                        "p95": 7.0, "p99": 7.0,
                                        "max": 7.0}
    assert profiling.summary(name + ".absent") == {"count": 0}


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

# one metric line: name, optional label set (per-model series like
# {model="de"}, summary {quantile="0.x"}, or both), numeric value
_METRIC_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'-?\d+(\.\d+)?([eE][+-]?\d+)?$')


def test_prometheus_text_is_valid_exposition():
    profiling.count("test.prom_counter", 3)
    profiling.observe("test.prom_lat", 1.5)
    profiling.observe("test.prom_lat", 2.5)
    text = telemetry.prometheus_text({"test.prom_gauge": 4.5,
                                      "test.none_gauge": None})
    lines = text.splitlines()
    assert lines, "empty exposition"
    seen_types = {}
    for ln in lines:
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ")
            seen_types[name] = kind
            continue
        if ln.startswith("#"):
            continue
        assert _METRIC_LINE.match(ln), f"bad exposition line: {ln!r}"
    # every canonical profiling counter is covered, even at zero
    for cname in profiling.CANONICAL_COUNTERS:
        m = telemetry.sanitize_metric_name(cname) + "_total"
        assert seen_types.get(m) == "counter", f"missing canonical {m}"
        assert any(ln.startswith(m + " ") for ln in lines)
    assert "lgbt_test_prom_counter_total 3" in lines
    assert seen_types["lgbt_test_prom_lat"] == "summary"
    assert 'lgbt_test_prom_lat{quantile="0.5"} 1.5' in lines
    assert "lgbt_test_prom_lat_count 2" in lines
    assert seen_types["lgbt_test_prom_gauge"] == "gauge"
    assert "lgbt_test_prom_gauge 4.5" in lines
    assert "lgbt_test_none_gauge" not in text   # None gauges are absent
    # process gauges ride every scrape
    assert seen_types["lgbt_process_uptime_seconds"] == "gauge"
    assert seen_types["lgbt_process_resident_memory_bytes"] == "gauge"


def test_sanitize_metric_name():
    assert (telemetry.sanitize_metric_name("serve.chunk_retries")
            == "lgbt_serve_chunk_retries")
    assert (telemetry.sanitize_metric_name("registry/swap_failures")
            == "lgbt_registry_swap_failures")
    assert (telemetry.sanitize_metric_name("a..b//c")
            == "lgbt_a_b_c")


def test_standalone_metrics_server():
    srv = telemetry.start_metrics_server(0)
    try:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        body = r.read().decode()
        assert r.status == 200
        assert r.getheader("Content-Type").startswith("text/plain")
        assert "lgbt_process_uptime_seconds" in body
        conn.request("GET", "/healthz")
        assert conn.getresponse().read() == b'{"status": "ok"}\n'
        conn.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# serving integration: /metrics, /stats process block, trace ingress
# ---------------------------------------------------------------------------


def _server(model_path, **kw):
    from lightgbm_tpu.serving import ModelRegistry, PredictionServer
    reg = ModelRegistry(model_path, params={"verbose": -1})
    return PredictionServer(reg, port=0, model_poll_seconds=0, **kw)


def _post_predict(host, port, X, headers=None):
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        body = "\n".join(json.dumps([float(v) for v in row]) for row in X)
        conn.request("POST", "/predict", body, headers=headers or {})
        r = conn.getresponse()
        text = r.read().decode()
        assert r.status == 200, f"HTTP {r.status}: {text}"
        return r, text
    finally:
        conn.close()


def _get(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.getheader("Content-Type"), r.read().decode()
    finally:
        conn.close()


def test_serving_metrics_endpoint_and_process_block(tmp_path):
    bst, X, _ = _train_binary()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)
    with _server(model) as srv:
        _post_predict(srv.host, srv.port, X[:4])
        status, ctype, text = _get(srv.host, srv.port, "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        lines = text.splitlines()
        for ln in lines:
            if not ln.startswith("#"):
                assert _METRIC_LINE.match(ln), f"bad line: {ln!r}"
        # counters the request just bumped, canonical zeros, and the
        # serve gauges are all present
        assert any(ln.startswith("lgbt_serve_requests_total ")
                   for ln in lines)
        # canonical counters are present even when untouched (earlier
        # tests in a full run may have bumped them — presence, not
        # value, is the contract here; the zero-seeding is pinned in
        # test_prometheus_text_is_valid_exposition)
        assert any(ln.startswith("lgbt_registry_swap_failures_total ")
                   for ln in lines)
        assert "lgbt_serve_model_generation 1" in lines
        assert any(ln.startswith("lgbt_serve_healthy_replicas ")
                   for ln in lines)
        assert any(ln.startswith("lgbt_serve_queue_depth ")
                   for ln in lines)
        assert any(ln.startswith('lgbt_serve_latency_ms{quantile="0.99"}')
                   for ln in lines)
        # /stats gains the process block with typed fields
        status, _, body = _get(srv.host, srv.port, "/stats")
        assert status == 200
        proc = json.loads(body)["process"]
        assert isinstance(proc["uptime_s"], float) and proc["uptime_s"] >= 0
        assert isinstance(proc["rss_mb"], float) and proc["rss_mb"] > 0
        assert isinstance(proc["peak_rss_mb"], float)
        assert proc["backend"] == "cpu"
        assert isinstance(proc["device_count"], int)
        assert proc["device_count"] >= 1
        assert isinstance(proc["device_kind"], str)
        assert proc["version"] == lgb.__version__
        assert isinstance(proc["telemetry"], dict)
        assert proc["telemetry"]["enabled"] is False


def test_http_trace_ingress_and_span_propagation(tmp_path, telem):
    """One /predict request produces spans sharing a single trace id
    from HTTP ingress through batcher dispatch to replica execution —
    and the id round-trips to the client."""
    bst, X, _ = _train_binary()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)
    tid = "a1" * 16
    with _server(model) as srv:
        r, _ = _post_predict(srv.host, srv.port, X[:4],
                             headers={"X-Trace-Id": tid})
        assert r.getheader("X-Trace-Id") == tid
        # object-body trace_id field works too
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
        body = json.dumps({"rows": [[float(v) for v in X[0]]],
                           "trace_id": "b2" * 16})
        conn.request("POST", "/predict", body)
        r2 = conn.getresponse()
        r2.read()
        assert r2.status == 200 and r2.getheader("X-Trace-Id") == "b2" * 16
        # with telemetry on and no id supplied, the server MINTS one
        r3, _ = _post_predict(srv.host, srv.port, X[:2])
        minted = r3.getheader("X-Trace-Id")
        assert minted and len(minted) == 32
    names = _by_name(_records(telem))
    for needed in ("serve.request", "serve.batch", "serve.replica",
                   "serve.dispatch"):
        assert needed in names, f"missing {needed} spans"
        assert any(r["trace"] == tid for r in names[needed]), needed
    req = [r for r in names["serve.request"] if r["trace"] == tid][0]
    disp = [r for r in names["serve.dispatch"] if r["trace"] == tid][0]
    assert disp["parent"] == req["span"]
    assert disp["attrs"]["generation"] == 1
    assert any(r["trace"] == minted for r in names["serve.request"])


def test_e2e_trace_propagation_serve_to_online_to_swap(tmp_path, telem):
    """The acceptance loop: a serve request's trace id rides
    append_traffic → the daemon's window → refit → publish (sidecar
    carries the originating ids) → registry hot-swap (adopts the
    refresh's trace id) — the whole serve→train→serve cycle is
    reconstructable from trace ids alone."""
    from lightgbm_tpu.online.stream import TrafficLog, append_traffic
    from lightgbm_tpu.online.trainer import OnlineTrainer
    from lightgbm_tpu.serving import ModelRegistry

    bst, X, y = _train_binary()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)
    registry = ModelRegistry(model, params={"verbose": -1})
    gen1 = registry.generation

    # the label joiner's half: served rows + labels + their trace ids
    traffic = str(tmp_path / "traffic.jsonl")
    tid = "c3" * 16
    append_traffic(traffic, X[:60], y[:60], trace_ids=tid)
    append_traffic(traffic, X[60:120], y[60:120],
                   trace_ids=["d4" * 16] * 60)
    tl = TrafficLog(traffic)
    tl.read_new()
    assert set(tl.last_trace_ids) == {tid, "d4" * 16}

    cfg = config_from_params({
        "verbose": -1, "objective": "binary",
        "online_trigger_rows": 100, "online_mode": "refit"})
    trainer = OnlineTrainer(bst, traffic, model, config=cfg, resume=False)
    time.sleep(0.05)      # distinct publish mtime for the registry poll
    assert trainer.poll_once()

    meta = json.load(open(model + ".meta.json"))
    assert tid in meta["origin_trace_ids"]
    assert "d4" * 16 in meta["origin_trace_ids"]
    refresh_tid = meta["trace_id"]
    assert refresh_tid

    assert registry.poll_once()
    assert registry.generation == gen1 + 1

    names = _by_name(_records(telem))
    for name in ("online.refresh", "online.refit", "online.publish",
                 "serve.swap"):
        assert name in names, f"missing {name}"
        assert any(r["trace"] == refresh_tid for r in names[name]), name
    refresh = [r for r in names["online.refresh"]
               if r["trace"] == refresh_tid][0]
    assert refresh["attrs"]["origin_traces"] == 2
    swap = [r for r in names["serve.swap"]
            if r["trace"] == refresh_tid][0]
    assert swap["attrs"]["generation"] == gen1 + 1


def test_malformed_body_trace_id_is_dropped_not_echoed(tmp_path, telem):
    """The body `trace_id` field is attacker-shaped bytes that would be
    echoed into a response HEADER: CR/LF (header injection), oversize,
    or otherwise malformed ids are dropped at ingress — a fresh id is
    minted instead and no injected header appears."""
    bst, X, _ = _train_binary()
    model = str(tmp_path / "m.txt")
    bst.save_model(model)
    with _server(model) as srv:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)
        evil = "abc\r\nSet-Cookie: pwned=1"
        body = json.dumps({"rows": [[float(v) for v in X[0]]],
                           "trace_id": evil})
        conn.request("POST", "/predict", body)
        r = conn.getresponse()
        r.read()
        assert r.status == 200
        assert r.getheader("Set-Cookie") is None
        echoed = r.getheader("X-Trace-Id")
        assert echoed != evil and "\r" not in (echoed or "")
        assert echoed and len(echoed) == 32          # minted instead
        # oversize ids are dropped too
        conn.request("POST", "/predict", json.dumps(
            {"rows": [[float(v) for v in X[0]]], "trace_id": "x" * 300}))
        r2 = conn.getresponse()
        r2.read()
        assert r2.getheader("X-Trace-Id") != "x" * 300
        conn.close()
    recs = _records(telem)
    assert not any(rec["trace"] == evil for rec in recs)


def test_configure_reenables_after_sink_failure(tmp_path):
    """A dead sink degrades to disabled (never takes the loop down);
    an explicit configure() with the SAME path must bring it back."""
    path = str(tmp_path / "s.jsonl")
    try:
        telemetry.configure(path)
        assert telemetry.enabled()
        telemetry._enabled = False       # what _write does on OSError
        telemetry.configure(path)
        assert telemetry.enabled()
        with telemetry.span("back"):
            pass
        assert _records(path)[-1]["name"] == "back"
    finally:
        telemetry.reset()


def test_online_window_trace_cap_is_enforced(tmp_path):
    """One backlog poll carrying more distinct trace ids than the cap
    must not blow the provenance set past it (the whole set lands in
    the meta sidecar AND the write-ahead intent)."""
    from lightgbm_tpu.online.stream import append_traffic
    from lightgbm_tpu.online.trainer import OnlineTrainer
    bst, X, y = _train_binary()
    traffic = str(tmp_path / "t.jsonl")
    append_traffic(traffic, X[:40], y[:40],
                   trace_ids=[f"id{i:04d}" for i in range(40)])
    cfg = config_from_params({"verbose": -1, "objective": "binary",
                              "online_trigger_rows": 10_000})
    trainer = OnlineTrainer(bst, traffic, str(tmp_path / "pub.txt"),
                            config=cfg, resume=False)
    trainer._WINDOW_TRACES_CAP = 5
    assert trainer.poll_once() is False      # trigger not reached
    assert len(trainer._window_traces) == 5


# ---------------------------------------------------------------------------
# training telemetry
# ---------------------------------------------------------------------------


def test_train_iteration_and_eval_records(telem):
    rng = np.random.RandomState(3)
    X = rng.rand(300, 8)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 7,
              "min_data_in_leaf": 5, "metric": "binary_logloss"}
    bst = lgb.Booster(params, lgb.Dataset(X, y))
    for _ in range(3):
        bst.update()
    res = bst._gbdt.eval_train()
    assert res
    names = _by_name(_records(telem))
    iters = names["train.iteration"]
    assert len(iters) == 3
    assert [r["attrs"]["iteration"] for r in iters] == [1, 2, 3]
    assert iters[-1]["attrs"]["trees"] >= iters[0]["attrs"]["trees"]
    assert iters[0]["attrs"]["rows"] == 300
    assert iters[0]["attrs"]["seconds"] > 0
    # telemetry switches the phase accumulators on, so the
    # per-iteration record carries phase wall-clock
    assert any("tree" in r["attrs"]["phases"] for r in iters)
    assert "counters" in iters[0]["attrs"]
    evs = names["train.eval"]
    assert evs and evs[-1]["attrs"]["results"]
    set_name, metric_name, val = evs[-1]["attrs"]["results"][0]
    assert set_name == "training" and isinstance(val, float)


def test_checkpoint_and_resume_spans(tmp_path, telem):
    bst, X, y = _train_binary(rounds=3)
    ckpt = str(tmp_path / "ck.json")
    bst._gbdt.save_checkpoint(ckpt)
    from lightgbm_tpu.boosting.gbdt import load_checkpoint
    state = load_checkpoint(ckpt)
    assert state is not None
    names = _by_name(_records(telem))
    (rec,) = names["train.checkpoint"]
    assert rec["attrs"]["path"] == ckpt
    assert rec["attrs"]["trees"] == bst.num_trees()
    assert rec["status"] == "ok"


def test_fault_firing_becomes_event(telem):
    from lightgbm_tpu.diagnostics import faults
    faults.reset()
    try:
        faults.arm("telemetry.test_site:1")
        assert faults.fire("telemetry.test_site") is True
        assert faults.fire("telemetry.test_site") is False  # seq 2 unarmed
    finally:
        faults.reset()
    names = _by_name(_records(telem))
    (rec,) = names["fault.fired"]
    assert rec["attrs"] == {"site": "telemetry.test_site", "seq": 1}


# ---------------------------------------------------------------------------
# zero-overhead / sanitize contract
# ---------------------------------------------------------------------------


def test_telemetry_off_creates_no_file(tmp_path):
    """The whole training + serving flow with telemetry off must not
    allocate spans or touch the filesystem."""
    assert not telemetry.enabled()
    before = set(os.listdir(tmp_path))
    bst, X, _ = _train_binary(rounds=2)
    assert telemetry.span("x") is telemetry.span("y")
    assert set(os.listdir(tmp_path)) == before


@needs_guard
@pytest.mark.sanitize
def test_train_loop_stays_zero_zero_with_telemetry_on(telem):
    """The acceptance contract: the pipelined rounds-learner steady
    state does ZERO retraces and ZERO implicit transfers per iteration
    WITH span tracing + per-iteration records enabled — telemetry adds
    host-side writes only, never a device sync."""
    rng = np.random.RandomState(7)
    X = rng.randn(4000, 12)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "tree_growth": "rounds"}
    ds = lgb.Dataset(X, y).construct(params)
    bst = lgb.Booster(params, ds)
    san = HotPathSanitizer(warmup=3, label="telemetry-loop")
    with san:
        for _ in range(8):
            with san.step():
                bst.update()
    san.check()
    assert san.retraces == 0 and san.implicit_transfers == 0
    recs = _by_name(_records(telem))
    assert len(recs["train.iteration"]) == 8


@needs_guard
@pytest.mark.sanitize
def test_serve_probe_stays_zero_zero_with_telemetry_on(telem):
    """The bench_serve probe shape: warm PredictorRuntime requests do
    ZERO retraces / ZERO implicit transfers with replica spans being
    emitted (the transfer guard is thread-local, so the probe calls the
    runtime directly like scripts/bench_serve.py does)."""
    from lightgbm_tpu.serving import PredictorRuntime
    bst, X, _ = _train_binary()
    rt = PredictorRuntime(bst, max_batch_rows=64, min_bucket_rows=16)
    rt.warmup([16], ("value",))
    san = HotPathSanitizer(warmup=1, label="serve-telemetry")
    with san:
        for i in range(6):
            with san.step():
                rt.predict(X[: 8 + i], kind="value")
    san.check()
    recs = _by_name(_records(telem))
    assert len(recs["serve.replica"]) >= 6     # warmup + probe spans


# ---------------------------------------------------------------------------
# chrome-trace export
# ---------------------------------------------------------------------------


def test_trace_view_convert(telem, tmp_path):
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(root, "scripts", "trace_view.py"))
    tv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tv)

    with telemetry.span("op", foo=1):
        telemetry.event("tick")
    out = tv.convert(_records(telem))
    evs = out["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(spans) == 1 and spans[0]["name"] == "op"
    assert spans[0]["dur"] >= 1.0 and spans[0]["args"]["foo"] == 1
    assert len(instants) == 1 and instants[0]["name"] == "tick"
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    assert spans[0]["pid"] == instants[0]["pid"]
    # --trace filtering keeps only the asked-for trace
    other = dict(_records(telem)[0], trace="z" * 32)
    filtered = tv.convert(_records(telem) + [other],
                          only_trace="z" * 32)
    assert [e for e in filtered["traceEvents"] if e["ph"] != "M"] \
        and all(e["args"]["trace"] == "z" * 32
                for e in filtered["traceEvents"] if e["ph"] != "M")
    # the CLI writes a parseable artifact
    dst = str(tmp_path / "out.trace.json")
    assert tv.main([telem, dst]) == 0
    assert json.load(open(dst))["traceEvents"]


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------


def test_config_keys_and_aliases(tmp_path):
    path = str(tmp_path / "cfg_spans.jsonl")
    try:
        cfg = config_from_params({"verbose": -1, "trace_path": path,
                                  "prometheus_port": 0})
        assert cfg.telemetry_path == path
        assert cfg.metrics_port == 0
        assert telemetry.enabled()           # config enables the sink
        assert telemetry.config_in_effect()["path"] == path
        # a later config WITHOUT the key must not disable it
        config_from_params({"verbose": -1})
        assert telemetry.enabled()
    finally:
        telemetry.reset()
    for alias in ("telemetry", "span_path"):
        try:
            cfg = config_from_params({"verbose": -1, alias: path})
            assert cfg.telemetry_path == path
        finally:
            telemetry.reset()
    cfg = config_from_params({"verbose": -1, "telemetry_port": 1234})
    assert cfg.metrics_port == 1234
    with pytest.raises(ValueError):
        config_from_params({"verbose": -1, "metrics_port": 70000})
