"""Generate docs/Parameters.md from the Config dataclass — the analog of
the reference's hand-maintained docs/Parameters.md, kept un-driftable by
deriving it from the single source of truth (config.py)."""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from lightgbm_tpu.config import Config, PARAM_ALIASES  # noqa: E402


def main():
    inv = {}
    for alias, canon in PARAM_ALIASES.items():
        inv.setdefault(canon, []).append(alias)
    lines = [
        "# Parameters",
        "",
        "All parameters of `lightgbm_tpu`, generated from "
        "`lightgbm_tpu/config.py` by `scripts/gen_parameters_doc.py` "
        "(do not edit by hand; regenerate instead).",
        "",
        "Names, defaults, and aliases follow the reference "
        "(`include/LightGBM/config.h:86-284`, alias table `:342-436`). "
        "Parameters are accepted as Python `params` dict keys, as "
        "`key=value` CLI arguments, and as `key = value` lines in a "
        "config file.",
        "",
        "| Parameter | Default | Type | Aliases |",
        "|---|---|---|---|",
    ]
    for f in dataclasses.fields(Config):
        if f.default is not dataclasses.MISSING:
            d = f.default
        elif f.default_factory is not dataclasses.MISSING:  # type: ignore
            d = f.default_factory()                          # type: ignore
        else:
            d = ""
        dv = repr(d) if isinstance(d, str) else str(d)
        t = (f.type.replace("typing.", "") if isinstance(f.type, str)
             else getattr(f.type, "__name__", str(f.type)))
        al = ", ".join(f"`{a}`" for a in sorted(inv.get(f.name, [])))
        lines.append(f"| `{f.name}` | `{dv}` | {t} | {al} |")
    lines += [
        "",
        "## Objectives",
        "",
        "`regression` (l2), `regression_l1`, `huber`, `fair`, `poisson`, "
        "`binary`, `lambdarank`, `multiclass` (softmax), `multiclassova` "
        "— reference `src/objective/` parity, see "
        "`lightgbm_tpu/objectives.py`.",
        "",
        "## Metrics",
        "",
        "`l1`, `l2`, `rmse`, `huber`, `fair`, `poisson`, "
        "`binary_logloss`, `binary_error`, `auc`, `multi_logloss`, "
        "`multi_error`, `ndcg@k`, `map@k` — host and device "
        "implementations (`lightgbm_tpu/metrics.py`, "
        "`lightgbm_tpu/ops/eval.py`).",
        "",
        "## Categorical columns",
        "",
        "`categorical_column` (aliases `categorical_feature`, "
        "`cat_feature`, `cat_column`) names the columns whose values are "
        "category codes: indices (`0,3,5`), or `name:` and feature names "
        "(`name:Origin,Dest`). It is read wherever a dataset is binned: a "
        "data file, the C API, and `lgb.Dataset(X, y).construct(params)` "
        "or `lgb.train(params, lgb.Dataset(X, y))`, where it adds to the "
        "constructor's `categorical_feature` and to pandas `category` "
        "columns. A split on such a column sends the rows of one category "
        "left. Categories past the binning's cut (at least `max_bin` "
        "kept, and as many more as 98 % of the sample takes) share one "
        "bin that is never a threshold, so their rows go right in "
        "training as in every predictor.",
        "",
        "## TPU-specific parameters",
        "",
        "- `histogram_dtype` (default `float32`): MXU input precision for "
        "histogram accumulation; `bfloat16` is validated at AUC parity "
        "(`tests/test_bf16.py`). `int8` is the BENCHMARK DEFAULT since "
        "its full-shape 500-iteration validation (test AUC 0.889807 vs "
        "the reference binary's 0.889423 on identical data, "
        "`northstar_int8_accuracy.json`); it enables per-pass symmetric "
        "gradient quantization with exact int32 accumulation on the "
        "batched-rounds learner only (2x MXU throughput on v5e; other "
        "learners fall back to float32 with a warning; auto-reverts to "
        "bfloat16 above 16M rows/device to keep the int32 accumulator "
        "exact).",
        "- `tree_learner`: `serial` | `feature` | `data` | `voting` | "
        "`data2d` — the distributed axes map onto a `jax.sharding.Mesh` "
        "instead of socket/MPI machine lists.",
        "- `hist_exchange` (default `auto`, alias `histogram_reduce`): "
        "data-parallel histogram collective. `psum` all-reduces the "
        "full `[K, F, 3, B]` histogram onto every device; "
        "`psum_scatter` reduce-scatters over the feature axis so each "
        "device owns only its `F/ndev` slice, split-searches that "
        "slice, and all_gathers the tiny per-leaf best-split records "
        "(the reference's `Network::ReduceScatter` ownership model) — "
        "per-device comms volume drops ~`ndev`x, and split-search work "
        "too. `auto` = psum_scatter when the "
        "per-pass payload "
        "reaches the `hist_exchange_min_bytes` crossover, psum below "
        "it. On a 2-D `data2d` mesh with the rounds learner, the "
        "exchange decomposes into a psum over the data axis plus a "
        "reduce-scatter over the feature axis "
        "(docs/Distributed-Data.md). See docs/Readme.md "
        "\"Histogram exchange\".",
        "- `hist_exchange_min_bytes` (default `-1`, aliases "
        "`hist_exchange_threshold`, `histogram_exchange_min_bytes`): "
        "the `hist_exchange=auto` crossover in bytes — below it the "
        "full psum is cheaper than reduce-scatter plus the per-leaf "
        "record allgather.  `-1` keeps the built-in 1 MiB default (or "
        "the `LGBT_HIST_EXCHANGE_MIN_BYTES` env override for ad-hoc "
        "on-chip tuning); `>= 0` pins it.  The measured crossover on "
        "chip lands in `hist_exchange_ab_measured.json`.",
        "- `bin_find` (default `auto`, aliases `bin_finding`, "
        "`distributed_bin_find`): how distributed / out-of-core bin "
        "boundaries are found.  `allgather` derives mappers from the "
        "process-allgathered global sample (the validated exact path); "
        "`sketch` merges per-host mergeable quantile summaries in one "
        "O(F/eps) collective so NO host ever materializes the global "
        "sample; `auto` stays exact while the combined sample fits "
        "`bin_construct_sample_cnt` and switches to sketches beyond.  "
        "See docs/Distributed-Data.md.",
        "- `sketch_eps` (default `0.001`, aliases "
        "`quantile_sketch_eps`, `sketch_epsilon`): rank-error knob of "
        "the quantile sketch — each summary keeps O(1/eps) weighted "
        "entries per feature, and derived boundaries carry the "
        "documented eps rank guarantee.  Tight enough that every "
        "distinct value fits, the sketch is EXACT (bitwise the "
        "allgather boundaries).",
        "- `stream_chunk_rows` (default `262144`, aliases "
        "`stream_chunk_size`, `ingest_chunk_rows`): row-chunk size of "
        "streamed construction (`Dataset.from_stream` and the "
        "two-round file loader) — peak host memory of ingestion "
        "scales with this, not the dataset length "
        "(bench_ingest_measured.json).",
        "",
        "- `predict_kernel` (default `auto`, aliases "
        "`prediction_kernel`, `predict_engine`): device ensemble-"
        "traversal kernel. `tensorized` (the `auto` resolution) "
        "flattens every tree of every class into one padded SoA and "
        "advances all rows x all trees one depth level per step — "
        "`depth` fused gather/select passes for the whole ensemble, "
        "with shallow numerical ensembles re-laid out as perfect "
        "binary trees (arithmetic navigation, fused leaf values); a "
        "binned-input variant replays whole models onto validation "
        "scores with integer bin compares.  `walk` keeps the per-class "
        "vmapped tree walk as the A/B baseline.  See docs/serving.md.",
        "- `serve_replicas` (default `0`, aliases `serving_replicas`, "
        "`num_replicas`): serving-fleet size — compiled predictors "
        "replicated across local devices with least-loaded dispatch.  "
        "`0` = every local device on accelerator backends, one on the "
        "CPU tier; an explicit count caps at the local device count.",
        "- `max_pending_rows` (default `0`, aliases "
        "`serve_max_pending_rows`, `pending_rows_cap`): admission "
        "control — once this many rows are queued, further requests "
        "shed load with HTTP 503 instead of growing an unbounded "
        "queue.  High-water mark: a single over-cap request on an idle "
        "server still admits (the runtime chunks it), bounding the "
        "queue at cap + one request.  `0` = unbounded.",
        "- `serve_quantize` (default `auto`, aliases "
        "`serving_quantize`, `quantized_serving`): request-path "
        "feature quantization.  `binned` quantizes every request "
        "chunk against the model's `.refbin` frozen-mapper sidecar at "
        "ingress (uint8/uint16 bin ids, a >=4x smaller device buffer "
        "than f32) and traverses integer bins end-to-end — "
        "bit-identical scores to the raw kernel by construction, and "
        "the registry REFUSES a serve/swap whose sidecar is missing, "
        "torn, or sha1-mismatched vs the publish meta.  `raw` keeps "
        "f32 feature traversal.  `auto` picks binned whenever a valid "
        "sidecar is present and falls back to raw otherwise.  See "
        "docs/serving.md \"Binned inference\".",
        "- `serve_models` (default empty, aliases `serving_models`, "
        "`model_catalog`): multi-tenant serving catalog — `id=path` "
        "entries, one independent model per tenant id.  `/predict` "
        "routes by `?model=`, the `\"model\"` body field, or the "
        "`X-Model-Id` header; requests naming no model land on the "
        "default tenant (`input_model` when set, else the first "
        "entry).  Each tenant gets its own registry (hot-swap, shadow "
        "canary, replica breakers), batcher (per-tenant "
        "`max_pending_rows` admission budget), executable caches, and "
        "per-model `/stats` + labeled `/metrics` accounting.  Entries "
        "accept per-tenant `;key=value` override suffixes "
        "(`de=/m/de.txt;replicas=2;costack=off`): `replicas` (pins the "
        "tenant's fleet size and forces it solo), `serve_quantize`, "
        "`max_pending_rows`, and `costack=off` — fleet-wide aliases "
        "work as override keys too, and malformed overrides are "
        "startup errors.  Also "
        "consumed by `task=online`: one refresh daemon per entry "
        "sharing the traffic tail (keyed rows, keyed publish paths).  "
        "See docs/serving.md \"Multi-tenant catalog\".",
        "- `serve_cache_budget_mb` (default `0`, aliases "
        "`serve_cache_budget`, `cache_budget_mb`): device-memory "
        "budget (MiB) for the catalog's compiled-executable caches "
        "across ALL tenants.  Beyond it, the least-recently-used "
        "tenants' executables are evicted (never the most recently "
        "used tenant's; model stacks stay resident, so evicted "
        "tenants keep serving and recompile on their next request — "
        "`serve/cache_evictions` counts the churn).  `0` = unlimited.  "
        "Under co-stacking a group is ONE eviction unit (recency = its "
        "most recently used member), so a group is never half-warm.",
        "- `serve_costack` (default `true`, aliases `costack`, "
        "`cross_model_batching`): cross-model batched serving — "
        "catalog tenants sharing (num_class, kernel variant, leaf "
        "tier) co-stack onto ONE compiled executable per (row bucket, "
        "output kind); mixed batches coalesce requests across tenants "
        "into one traversal launch and demux BITWISE-identically to "
        "per-tenant dispatch.  Tenants with a `replicas` override, "
        "`costack=off`, or no compatible peer serve solo; a member's "
        "republish restacks only its group (same-shape republishes "
        "transplant the compiled executables — zero recompiles).  "
        "`false` restores the strict per-tenant layout.  See "
        "docs/serving.md \"Cross-model batching\".",
        "- `serve_shadow_fraction` (default `0.0`, aliases "
        "`shadow_fraction`, `canary_fraction`): shadow-canary "
        "publishes — with a fraction > 0, a republished model is "
        "STAGED and this fraction of requests is double-scored on it "
        "(stable still answers every client; shadow scoring runs "
        "after the clients' futures resolve), logging per-request "
        "divergence until the verdict.  `0` = immediate hot swap.",
        "- `serve_shadow_requests` (default `32`, aliases "
        "`shadow_requests`, `canary_requests`): shadowed comparisons "
        "required before the canary verdict (adopt or reject).",
        "- `serve_shadow_max_divergence` (default `-1.0`, aliases "
        "`shadow_max_divergence`, `canary_max_divergence`): reject "
        "the candidate when any shadowed |candidate - stable| "
        "divergence exceeds this (`>= 0`); negative = log-only, "
        "always adopt after the quorum.",
        "",
        "## Routing",
        "",
        "- `route_backends` (default empty, aliases `router_backends`, "
        "`backends`): the serving fleet behind `task=route` — "
        "comma-separated `host:port` backends, plus optional "
        "`model_id=host:port` entries that pin a tenant's placement "
        "(an explicit override beats the consistent-hash ring).  "
        "Unpinned tenants place by consistent hash, so adding or "
        "removing one backend re-places only the tenants that hashed "
        "onto it.  See docs/Router.md.",
        "- `route_port` (default `8180`, aliases `router_port`, "
        "`routing_port`): the router's listen port (listen host comes "
        "from `serve_host`).",
        "- `route_health_interval_ms` (default `1000`, aliases "
        "`router_health_interval_ms`, `route_health_ms`): period of "
        "the background `/healthz` sweep over every backend — probe "
        "successes readmit circuit-broken backends, probe failures "
        "open breakers without waiting for live traffic, and the "
        "parsed payloads feed the fleet staleness view at `/stats`.  "
        "`0` = no background sweep (the count-based live-traffic "
        "probes still readmit).",
        "- `route_backend_timeout_ms` (default `30000`, aliases "
        "`router_backend_timeout_ms`, `backend_timeout_ms`): "
        "per-dispatch socket timeout toward a backend; a timeout is a "
        "transport failure — it counts toward the backend's breaker "
        "and the request retries once elsewhere.",
        "- `route_max_inflight` (default `0`, aliases "
        "`router_max_inflight`, `route_inflight_cap`): cap on "
        "concurrently proxied requests; past it the router sheds with "
        "HTTP 503 + `Retry-After` instead of stacking proxy threads "
        "on slow backends.  `0` = unbounded.",
        "- the router's breaker threshold is `replica_failure_"
        "threshold` — the serving fleet's replica state machine one "
        "level up, sharing its knob.",
        "",
        "## Online learning",
        "",
        "- `refit_decay_rate` (default `0.9`, aliases `decay_rate`, "
        "`refit_decay`): leaf-value blending weight for refit — "
        "`new = decay * old + (1 - decay) * newton_output` (reference "
        "`refit_decay_rate` semantics).  `0` replaces leaf values "
        "outright (refitting on the original training data then "
        "reproduces them), `1` freezes the model.  Used by "
        "`Booster.refit`, `task=refit`, and the `task=online` daemon.  "
        "See `docs/Online-Learning.md`.",
        "- `refit_min_rows` (default `20`, aliases `min_refit_rows`, "
        "`refit_min_data`): leaves routed fewer fresh rows than this "
        "keep their old value — a starved leaf's Newton step is noise, "
        "and a zero-hessian leaf would divide by zero.  Floors at 1.",
        "- `online_trigger_rows` (default `4096`, aliases "
        "`online_trigger`, `trigger_rows`): the `task=online` daemon "
        "refreshes the model once this many new labeled traffic rows "
        "accumulated in the streaming window; it also seeds the "
        "window's store-capacity tier.",
        "- `online_mode` (default `'refit'`, alias `refresh_mode`): "
        "what a refresh does.  `refit` reweights the existing tree "
        "structures' leaves on the window (~one ensemble traversal "
        "plus one scan — no tree growth, no retraces at steady "
        "state); `continue` appends `num_iterations` fresh trees via "
        "continued boosting (`reset_training_data` replay).",
        "",
        "## Exclusive Feature Bundling",
        "",
        "- `enable_bundle` (default `True`, aliases `efb`, `bundle`): "
        "pack mutually-exclusive (mostly-default) features into shared "
        "histogram columns, shrinking the dominant `[rows, features]` "
        "matmul dimension of the training hot path.  Lossless when no "
        "bundled features conflict; splits, models, and predictions "
        "always stay in original feature space.  See `docs/Bundling.md`.",
        "- `max_conflict_rate` (default `0.0`, alias `max_conflict`): "
        "per-bundle tolerated fraction of rows where two members are "
        "both non-default.  `0.0` bundles only provably exclusive "
        "features; small values (e.g. `0.01`) trade exactness for more "
        "compaction, like the reference's EFB.",
        "",
        "## Observability",
        "",
        "- `telemetry_path` (default `''`, aliases `telemetry`, "
        "`trace_path`, `span_path`): structured span tracing — every "
        "process role appends JSONL span/event records "
        "(trace-id/span-id/parent-id, monotonic durations) to this "
        "path, with trace ids propagated end-to-end through the "
        "serve→train→serve loop.  Convert with "
        "`scripts/trace_view.py` (chrome://tracing / Perfetto).  Empty "
        "= off; the hot paths then cost one cached check.  The "
        "`LIGHTGBM_TPU_TELEMETRY` env var is the config-free switch.  "
        "See `docs/Observability.md`.",
        "- `metrics_port` (default `0`, aliases `prometheus_port`, "
        "`telemetry_port`): standalone Prometheus /metrics listener "
        "for roles without their own HTTP server (`task=train`, "
        "`task=online`, `task=predict`) — profiling counters, "
        "nearest-rank latency quantiles, process/device gauges in text "
        "exposition format.  `0` = off.  `task=serve` always serves "
        "the same payload at its own `/metrics` endpoint.",
        "",
    ]
    dest = os.path.join(ROOT, "docs", "Parameters.md")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "w") as fh:
        fh.write("\n".join(lines))
    print(f"wrote {dest} ({len(dataclasses.fields(Config))} parameters)")


if __name__ == "__main__":
    main()
