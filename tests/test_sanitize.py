"""Runtime sanitizer (diagnostics/sanitize.py): retrace counting via
jax_log_compiles capture, implicit-transfer counting via
jax.transfer_guard, and the zero/zero acceptance contract on a real
boosting loop (the BENCH_SANITIZE=1 assertion in miniature).

Transfer-guard tests carry the `sanitize` marker (pytest.ini): the guard
is backend-enforced and a no-op for some directions on some platforms —
they self-skip when the probe says so."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.diagnostics.sanitize import (
    DivergenceSanitizer, HotPathSanitizer, transfer_guard_effective)

pytestmark = pytest.mark.quick

_GUARD_OK = transfer_guard_effective()
needs_guard = pytest.mark.skipif(
    not _GUARD_OK, reason="jax.transfer_guard is a no-op on this backend")
# the cross-shard divergence checks need >= 2 devices to compare
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="divergence checks need >= 2 devices to compare replicas")


# ---------------------------------------------------------------------------
# compile-event capture
# ---------------------------------------------------------------------------


def test_retrace_counting_attributes_warmup_vs_steady():
    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.ones(7)            # allocated OUTSIDE the guarded steps
    san = HotPathSanitizer(warmup=1)
    with san:
        with san.step():                       # warmup: may compile
            f(x).block_until_ready()
        with san.step():                       # same shape: cache hit
            f(x).block_until_ready()
    assert san.steps == 2
    assert san.retraces == 0, san.compile_names
    assert san.implicit_transfers == 0


def test_retrace_detected_on_shape_change():
    @jax.jit
    def g(x):
        return x * 3 - 1

    x5, x9 = jnp.ones(5), jnp.ones(9)
    san = HotPathSanitizer(warmup=1)
    with san:
        with san.step():
            g(x5).block_until_ready()
        with san.step():                       # NEW shape: silent retrace
            g(x9).block_until_ready()
    assert san.retraces >= 1, san.report()
    assert san.trace_events >= san.retraces
    assert "g" in san.compile_names
    with pytest.raises(AssertionError, match="retrace"):
        san.check()


def test_report_shape():
    san = HotPathSanitizer(warmup=0, label="unit")
    with san:
        with san.step():
            jnp.ones(3).block_until_ready()
    rep = san.report()
    assert rep["label"] == "unit"
    assert rep["steps"] == 1
    assert set(rep) >= {"retraces_after_warmup", "implicit_transfers",
                        "compiles_total", "guard", "warmup"}


def test_counters_land_in_profiling_registry():
    from lightgbm_tpu import profiling
    from lightgbm_tpu.diagnostics import sanitize as S
    base = profiling.counter_value(S.COMPILES_TOTAL)
    san = HotPathSanitizer(warmup=0)
    with san:
        with san.step():
            jnp.zeros(2).block_until_ready()
    assert profiling.counter_value(S.COMPILES_TOTAL) >= base


# ---------------------------------------------------------------------------
# transfer guard
# ---------------------------------------------------------------------------


@needs_guard
@pytest.mark.sanitize
def test_implicit_transfer_counted_not_raised():
    x = jnp.ones(4)
    san = HotPathSanitizer(warmup=0)
    with san:
        with san.step():
            # eager op with a host scalar operand: implicit h2d upload
            (x * 2.5).block_until_ready()
    assert san.implicit_transfers == 1
    with pytest.raises(AssertionError, match="implicit transfer"):
        san.check()


@needs_guard
@pytest.mark.sanitize
def test_strict_mode_reraises():
    x = jnp.ones(4)
    san = HotPathSanitizer(warmup=0, strict=True)
    with pytest.raises(Exception, match="[Tt]ransfer"):
        with san:
            with san.step():
                (x * 2.5).block_until_ready()
    assert san.implicit_transfers == 1


@needs_guard
@pytest.mark.sanitize
def test_explicit_transfers_stay_legal():
    san = HotPathSanitizer(warmup=0)
    with san:
        with san.step():
            a = jax.device_put(np.ones(3, np.float32))
            b = jax.device_get(a * a)
    assert san.implicit_transfers == 0
    assert b.shape == (3,)


@needs_guard
@pytest.mark.sanitize
def test_warmup_steps_run_unguarded():
    x = jnp.ones(4)
    san = HotPathSanitizer(warmup=1)
    with san:
        with san.step():                       # warmup: transfer is fine
            (x * 2.5).block_until_ready()
        with san.step():                       # steady state: counted
            (x * 3.5).block_until_ready()
    assert san.implicit_transfers == 1


# ---------------------------------------------------------------------------
# cross-shard divergence sanitizer (the runtime half of shardlint)
# ---------------------------------------------------------------------------


def _mesh_and_smap():
    from jax.sharding import Mesh, PartitionSpec as P
    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()).reshape(n), ("data",))
    # replication is what these tests break on purpose
    return mesh, P, functools.partial(jax.shard_map, check_vma=False)


@needs_mesh
@pytest.mark.sanitize
def test_divergence_clean_replicated_output():
    """A genuinely replicated shard_map output (psum result) passes:
    one check per leaf, zero divergences."""
    mesh, P, smap = _mesh_and_smap()
    f = jax.jit(smap(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                     in_specs=P("data"), out_specs=P()))
    out = f(jnp.arange(len(jax.devices()), dtype=jnp.float32))
    san = DivergenceSanitizer(label="unit")
    assert san.check("psum", {"v": out}) == 0
    assert san.checks == 1 and san.divergences == 0
    rep = san.report()
    assert rep["divergence_checks"] == 1 and rep["divergences"] == 0


@needs_mesh
@pytest.mark.sanitize
def test_divergence_detects_shard_local_leak():
    """The true positive the static pass cannot close over: an
    out_specs=P() result that actually varies per shard (an axis_index
    leak under check_vma=False) — per-device fingerprints differ and
    strict mode hard-fails naming the leaf."""
    mesh, P, smap = _mesh_and_smap()
    f = jax.jit(smap(
        lambda x: (jnp.sum(x)
                   + jax.lax.axis_index("data").astype(jnp.float32)
                   ).reshape(1),
        mesh=mesh, in_specs=P("data"), out_specs=P()))
    bad = f(jnp.arange(len(jax.devices()), dtype=jnp.float32))
    lax_san = DivergenceSanitizer(label="unit", strict=False)
    assert lax_san.check("leak", {"tree": bad}) == 1
    assert lax_san.divergences == 1
    assert lax_san.evidence and lax_san.evidence[0][0] == "leak"
    with pytest.raises(AssertionError, match="cross-shard divergence"):
        DivergenceSanitizer(label="unit").check("leak", {"tree": bad})


@needs_mesh
@pytest.mark.sanitize
def test_divergence_skips_genuinely_sharded_arrays():
    """Row-sharded outputs (leaf_id etc.) are not replicated state and
    must not count as checks — no false positives on legal sharding."""
    mesh, P, smap = _mesh_and_smap()
    f = jax.jit(smap(lambda x: x * 2.0, mesh=mesh, in_specs=P("data"),
                     out_specs=P("data")))
    sharded = f(jnp.arange(len(jax.devices()) * 4, dtype=jnp.float32))
    san = DivergenceSanitizer(label="unit")
    assert san.check("sharded", {"rows": sharded}) == 0
    assert san.checks == 0


@needs_mesh
@pytest.mark.sanitize
def test_divergence_hooks_fire_in_mesh_training(monkeypatch):
    """BENCH_SANITIZE=1 turns on the learner hooks: a data-parallel
    boosting loop fingerprints the replicated tree arrays every
    iteration (divergence_checks grows, divergences stays 0) and the
    counters land in the HotPathSanitizer report."""
    monkeypatch.setenv("BENCH_SANITIZE", "1")
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(13)
    X = rng.randn(3000, 8)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "tree_growth": "rounds"}
    ds = lgb.Dataset(X, y).construct(params)
    bst = lgb.Booster(params, ds)
    san = HotPathSanitizer(warmup=2, label="divergence-loop")
    with san:
        for _ in range(4):
            with san.step():
                bst.update()
    san.check()
    rep = san.report()
    assert rep["divergence_checks"] > 0
    assert rep["divergences"] == 0


@needs_mesh
@pytest.mark.sanitize
def test_divergence_hooks_off_by_default(monkeypatch):
    """Without BENCH_SANITIZE the hooks are a no-op — the hot path pays
    one env read, no device fetches."""
    monkeypatch.delenv("BENCH_SANITIZE", raising=False)
    from lightgbm_tpu import profiling
    from lightgbm_tpu.diagnostics import sanitize as S
    base = profiling.counter_value(S.DIVERGENCE_CHECKS)
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(1500, 6)
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 7,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "tree_growth": "rounds"}
    ds = lgb.Dataset(X, y).construct(params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):
        bst.update()
    assert profiling.counter_value(S.DIVERGENCE_CHECKS) == base


# ---------------------------------------------------------------------------
# the acceptance contract on a real boosting loop
# ---------------------------------------------------------------------------


def _train_sanitized(params, n=6000, iters=5, warmup=3):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.randn(n, 12)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0
         ).astype(np.float64)
    ds = lgb.Dataset(X, y).construct(params)
    bst = lgb.Booster(params, ds)
    san = HotPathSanitizer(warmup=warmup, label="test-loop")
    with san:
        for _ in range(warmup + iters):
            with san.step():
                bst.update()
    return bst, san


@needs_guard
@pytest.mark.sanitize
def test_rounds_learner_loop_is_zero_zero():
    """The BENCH_SANITIZE acceptance contract: the batched-rounds
    pipelined hot path does ZERO retraces and ZERO implicit transfers
    per iteration after warmup."""
    bst, san = _train_sanitized({
        "objective": "binary", "verbose": -1, "num_leaves": 15,
        "min_data_in_leaf": 5, "tree_growth": "rounds"})
    san.check()                                # raises on any violation
    assert san.retraces == 0
    assert san.implicit_transfers == 0
    assert bst.current_iteration() >= 5


@needs_guard
@pytest.mark.sanitize
def test_rounds_learner_loop_with_bagging_is_zero_zero():
    """The bag redraw (device_put upload + device mask build) stays
    explicit mid-loop."""
    _, san = _train_sanitized({
        "objective": "binary", "verbose": -1, "num_leaves": 15,
        "min_data_in_leaf": 5, "tree_growth": "rounds",
        "bagging_fraction": 0.6, "bagging_freq": 2},
        warmup=4)
    san.check()


@needs_guard
@pytest.mark.sanitize
def test_fused_learner_mesh_loop_is_zero_zero():
    """The fused SPMD learner under a data-parallel shard_map mesh (the
    MULTICHIP dryrun topology, on the virtual CPU device platform):
    zero retraces / zero implicit transfers after warmup through the
    non-pipelined add_tree scoring path too."""
    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device platform")
    _, san = _train_sanitized({
        "objective": "binary", "verbose": -1, "num_leaves": 7,
        "min_data_in_leaf": 5, "tree_learner": "data"},
        n=4096, iters=4, warmup=4)
    san.check()


@needs_guard
@pytest.mark.sanitize
def test_eval_path_is_one_batched_fetch():
    """Per-iteration eval over a valid set stays guard-clean: metric
    kernels return lazy device scalars and GBDT._materialize_evals does
    one explicit batched device_get (the satellite fix for the
    one-sync-per-metric stall)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(11)
    X = rng.randn(4000, 10)
    y = (X[:, 0] + 0.4 * rng.randn(4000) > 0).astype(np.float64)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "metric": ["auc", "binary_logloss", "binary_error"],
              "min_data_in_leaf": 5, "tree_growth": "rounds"}
    ds = lgb.Dataset(X, y).construct(params)
    bst = lgb.Booster(params, ds)
    vd = lgb.Dataset(X[:1000], y[:1000], reference=ds)
    bst.add_valid(vd, "v0")
    san = HotPathSanitizer(warmup=3, label="eval-loop")
    with san:
        for _ in range(6):
            with san.step():
                bst.update()
                res = bst._gbdt.eval_valid()
    san.check()
    assert len(res) == 3
    assert all(isinstance(v, float) for _, _, v, _ in res)


@needs_guard
@pytest.mark.sanitize
def test_ranking_and_multiclass_eval_are_guard_clean():
    """ndcg/map@k results unstack in one jitted program (eager vals[i]
    uploaded a slice index per k) and the multiclass kernels take the
    cached device sum_weights scalar — both were per-iteration implicit
    transfers the review's sanitizer run caught."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    n, q = 2000, 50
    X = rng.randn(n, 8)
    yr = rng.randint(0, 4, size=n).astype(float)
    params = {"objective": "lambdarank", "metric": ["ndcg", "map"],
              "verbose": -1, "num_leaves": 15, "min_data_in_leaf": 5,
              "ndcg_eval_at": [1, 3], "tree_growth": "rounds"}
    ds = lgb.Dataset(X, yr, group=np.full(q, n // q)).construct(params)
    bst = lgb.Booster(params, ds)
    san = HotPathSanitizer(warmup=3, label="rank-eval")
    with san:
        for _ in range(6):
            with san.step():
                bst.update()
                res = bst._gbdt.eval_train()
    san.check()
    assert [m for _, m, _, _ in res] == ["ndcg@1", "ndcg@3",
                                         "map@1", "map@3"]

    ym = rng.randint(0, 3, size=n).astype(float)
    params2 = {"objective": "multiclass", "num_class": 3,
               "metric": ["multi_logloss", "multi_error"], "verbose": -1,
               "num_leaves": 15, "min_data_in_leaf": 5,
               "tree_growth": "rounds"}
    ds2 = lgb.Dataset(X, ym).construct(params2)
    b2 = lgb.Booster(params2, ds2)
    san2 = HotPathSanitizer(warmup=4, label="multi-eval")
    with san2:
        for _ in range(7):
            with san2.step():
                b2.update()
                b2._gbdt.eval_train()
    san2.check()
