"""Tensorized ensemble-traversal kernel: parity vs the per-class walk
(fp32 bitwise on dyadic leaf values, tolerance elsewhere), the binned
replay variant, layout auto-selection, and the serving fleet
(multi-replica dispatch, both-kinds warmup, zero-recompile acceptance
under predict_kernel=tensorized).
"""
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (MicroBatcher, ModelRegistry,
                                  PredictorRuntime, resolve_serve_replicas)
from lightgbm_tpu.tree import (CATEGORICAL_DECISION, NUMERICAL_DECISION,
                               Tree)

pytestmark = pytest.mark.quick


# -- tree/ensemble fixtures ---------------------------------------------


def _rand_tree(rng, F, leaves=31, maxdepth=6, cat_frac=0.0, dyadic=False):
    t = Tree(leaves)
    while t.num_leaves < leaves:
        cand = [l for l in range(t.num_leaves) if t.leaf_depth[l] < maxdepth]
        if not cand:
            break
        leaf = int(rng.choice(cand))
        f = int(rng.randint(F))
        if rng.rand() < cat_frac:
            bt, thr = CATEGORICAL_DECISION, float(rng.randint(4))
        else:
            bt, thr = NUMERICAL_DECISION, float(rng.rand())
        if dyadic:     # exactly representable: any f32 sum order is exact
            lv = float(rng.randint(-16, 16)) / 16.0
            rv = float(rng.randint(-16, 16)) / 16.0
        else:
            lv, rv = float(rng.randn() * 0.1), float(rng.randn() * 0.1)
        t.split(leaf, f, bt, int(thr), f, thr, lv, rv, 10, 10, 1.0)
    return t


def _walk_raw(trees_by_class, X):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import ensemble_raw, stack_trees
    stacks, depths = [], []
    for trees in trees_by_class:
        if not trees:
            stacks.append(None)
            depths.append(1)
            continue
        stacks.append(jax.tree_util.tree_map(
            jax.device_put, stack_trees(trees, binned=False)))
        depths.append(max(max(t.max_depth_grown for t in trees), 1))
    return np.asarray(ensemble_raw(stacks, jnp.asarray(X),
                                   depths=tuple(depths)))


def _tens_raw(trees_by_class, X, layout="auto"):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import build_ensemble, predict_ensemble_any
    stack, meta = build_ensemble(trees_by_class, binned=False, layout=layout)
    stack = jax.device_put(stack)
    return (np.asarray(predict_ensemble_any(stack, jnp.asarray(X),
                                            meta=meta)), stack)


# -- kernel-level parity -------------------------------------------------


def test_dyadic_bitwise_parity_both_layouts():
    """fp32 BITWISE equality vs the walk on dyadic leaf values, for the
    perfect relayout AND the padded-SoA fallback."""
    from lightgbm_tpu.ops.predict import EnsembleStack, PerfectEnsemble
    rng = np.random.RandomState(0)
    F = 12
    X = rng.rand(513, F).astype(np.float32)
    X[5, 3] = np.nan                   # NaN falls right in both kernels
    tbc = [[_rand_tree(rng, F, dyadic=True) for _ in range(40)]]
    ref = _walk_raw(tbc, X)
    got_p, st_p = _tens_raw(tbc, X)
    got_s, st_s = _tens_raw(tbc, X, layout="soa")
    assert isinstance(st_p, PerfectEnsemble)
    assert isinstance(st_s, EnsembleStack)
    assert np.array_equal(ref, got_p)
    assert np.array_equal(ref, got_s)


@pytest.mark.parametrize("kernel", ["walk", "perfect", "soa"])
def test_f32_threshold_never_rounds_above_the_f64_one(kernel):
    """A raw threshold whose nearest f32 lies ABOVE it: a row holding
    exactly that f32 value goes RIGHT in the f64 host walk, so it must
    go right in every device stack too (ops/predict.threshold_f32)."""
    x = np.float32(0.3)
    thr = float(x) - 1e-9               # nearest f32 is x itself, > thr
    assert np.float32(thr) == x and float(np.float32(thr)) > thr
    t = Tree(2)
    t.split(0, 0, NUMERICAL_DECISION, 0, 0, thr, -1.0, 1.0, 10, 10, 1.0)
    X = np.array([[x], [np.nextafter(x, np.float32(0))]], np.float32)
    host = t.predict_raw(X.astype(np.float64))
    assert host.tolist() == [1.0, -1.0]
    if kernel == "walk":
        got = _walk_raw([[t]], X)
    else:
        got, _ = _tens_raw([[t]], X,
                           layout="auto" if kernel == "perfect" else "soa")
    assert np.asarray(got).reshape(-1).tolist() == host.tolist()


@pytest.mark.parametrize("leaves,maxdepth", [(2, 1), (3, 2), (15, 4),
                                             (63, 8), (40, 30)])
def test_parity_across_depths(leaves, maxdepth):
    rng = np.random.RandomState(leaves)
    F = 9
    X = rng.rand(257, F).astype(np.float32)
    tbc = [[_rand_tree(rng, F, leaves=leaves, maxdepth=maxdepth)
            for _ in range(7)]]
    ref = _walk_raw(tbc, X)
    got, _ = _tens_raw(tbc, X)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


def test_parity_multiclass_stump_and_empty_class():
    rng = np.random.RandomState(3)
    F = 8
    X = rng.rand(200, F).astype(np.float32)
    stump = Tree(2)
    stump.leaf_value[0] = 0.625
    tbc = [[_rand_tree(rng, F), _rand_tree(rng, F)],
           [stump, _rand_tree(rng, F)],
           []]
    ref = _walk_raw(tbc, X)
    got, _ = _tens_raw(tbc, X)
    assert got.shape == (3, 200)
    assert np.allclose(got[2], 0.0)    # untrained class row stays zero
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


@pytest.mark.parametrize("num_class", [1, 3])
@pytest.mark.parametrize("layout", ["auto", "soa"])
def test_compensated_pair_is_the_float64_sum_of_the_trees(layout,
                                                          num_class):
    """Every device kernel sums the trees' float32 leaf values as a pair
    of parts (ops/predict._class_sums) that rounds once: each margin is
    the float64 sum of those leaf values over the same routes to within
    half a float32 ulp, where a float32 sum of the 200 trees, one
    rounding a tree, strays past 1e-6.  The per-class walk agrees."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import build_ensemble, predict_ensemble_any
    rng = np.random.RandomState(7 + num_class)
    F = 6
    X = rng.rand(300, F).astype(np.float32)
    tbc = []
    for _ in range(num_class):
        trees = [_rand_tree(rng, F, leaves=15, maxdepth=5)
                 for _ in range(200)]
        for t in trees:       # margins of ~10, leaf values float32-exact
            t.leaf_value[:] = np.float32(t.leaf_value + 0.05)
        tbc.append(trees)
    per_tree = [np.stack([t.predict_raw(X.astype(np.float64))
                          for t in trees]) for trees in tbc]
    ref = np.stack([v.sum(axis=0) for v in per_tree])
    stack, meta = build_ensemble(tbc, binned=False, layout=layout)
    stack = jax.device_put(stack)
    got = np.asarray(predict_ensemble_any(stack, jnp.asarray(X), meta=meta))
    assert got.shape == (num_class, 300) and got.dtype == np.float32
    half_ulp = np.spacing(np.abs(ref).astype(np.float32)) / 2
    assert np.all(np.abs(got - ref) <= half_ulp + 1e-9)
    assert np.array_equal(got, _walk_raw(tbc, X))
    plain = np.stack([v.astype(np.float32).sum(axis=0, dtype=np.float32)
                      for v in per_tree])
    assert np.abs(plain - ref).max() > 1e-6


def test_categorical_routes_through_soa_bitwise():
    from lightgbm_tpu.ops.predict import EnsembleStack
    rng = np.random.RandomState(4)
    F = 6
    X = np.floor(rng.rand(300, F) * 5).astype(np.float32)
    tbc = [[_rand_tree(rng, F, cat_frac=0.4) for _ in range(8)]]
    ref = _walk_raw(tbc, X)
    got, st = _tens_raw(tbc, X)
    assert isinstance(st, EnsembleStack)   # cat splits veto perfect layout
    assert np.array_equal(ref, got)


def test_nan_routes_right_and_no_dead_lane():
    """NaN rows route RIGHT in both kernels (``v <= t`` is False; the
    categorical compare's finite mask matches nothing), and the node
    record carries exactly the five live lanes — the never-populated
    ``default_left`` lane PR 7 reserved is deleted (binned serving
    derives missing routing from the quantizer's sentinel bin
    instead; tests/test_serve_binned.py)."""
    from lightgbm_tpu.ops.predict import _LANES, EnsembleMeta
    assert _LANES == 5
    assert "any_default_left" not in EnsembleMeta._fields
    rng = np.random.RandomState(5)
    F = 4
    t = _rand_tree(rng, F, leaves=8, maxdepth=3, dyadic=True)
    X = rng.rand(64, F).astype(np.float32)
    X[10:, :] = np.nan
    got, st = _tens_raw([[t]], X, layout="soa")
    assert st.nodes.shape[-1] == 5
    ref = _walk_raw([[t]], X)
    assert np.array_equal(ref, got)
    # all-NaN rows land on the rightmost leaf (every compare fails)
    node = 0
    while True:
        nxt = int(t.right_child[node])
        if nxt < 0:
            rightmost = ~nxt
            break
        node = nxt
    assert np.allclose(got[0][10:], t.leaf_value[rightmost])


def test_deep_ensemble_over_budget_uses_soa(monkeypatch):
    import lightgbm_tpu.ops.predict as P
    monkeypatch.setattr(P, "PERFECT_SLOT_BUDGET", 64)
    rng = np.random.RandomState(6)
    F = 5
    X = rng.rand(100, F).astype(np.float32)
    tbc = [[_rand_tree(rng, F, leaves=15, maxdepth=8, dyadic=True)
            for _ in range(4)]]
    ref = _walk_raw(tbc, X)
    got, st = _tens_raw(tbc, X)
    assert isinstance(st, P.EnsembleStack)
    assert np.array_equal(ref, got)


# -- trained-model parity (EFB, multiclass, NaN rows) --------------------


def _train(params, X, y, rounds=6):
    bst = lgb.Booster(dict({"verbose": -1, "min_data_in_leaf": 5}, **params),
                      lgb.Dataset(X, y))
    for _ in range(rounds):
        bst.update()
    assert bst.num_trees() > 0
    return bst


def _runtime_pair(bst, **kw):
    rt_t = PredictorRuntime(bst, predict_kernel="tensorized", **kw)
    rt_w = PredictorRuntime(bst, predict_kernel="walk", **kw)
    assert rt_t.predict_kernel == "tensorized"
    assert rt_w.predict_kernel == "walk"
    return rt_t, rt_w


def test_trained_binary_parity_with_nan_rows():
    rng = np.random.RandomState(7)
    X = rng.rand(500, 10)
    y = (X @ rng.randn(10) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 31}, X, y)
    rt_t, rt_w = _runtime_pair(bst, max_batch_rows=256)
    Xq = X[:100].copy()
    Xq[3, 2] = np.nan
    Xq[9, :] = np.nan
    for kind in ("value", "raw"):
        a = rt_t.predict(Xq, kind=kind)
        b = rt_w.predict(Xq, kind=kind)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(rt_t.predict(X[:50]), bst.predict(X[:50]),
                               atol=1e-6)


def test_trained_multiclass_and_efb_parity():
    rng = np.random.RandomState(8)
    # one-hot block makes EFB bundle columns
    Xd = rng.rand(400, 4)
    oh = np.zeros((400, 12))
    oh[np.arange(400), rng.randint(12, size=400)] = 1.0
    X = np.hstack([Xd, oh])
    y = (Xd[:, 0] * 3 + oh.argmax(1) % 3).astype(int) % 3
    bst = _train({"objective": "multiclass", "num_class": 3,
                  "num_leaves": 15, "enable_bundle": True}, X, y, rounds=4)
    rt_t, rt_w = _runtime_pair(bst, max_batch_rows=512)
    a = rt_t.predict(X[:120])
    b = rt_w.predict(X[:120])
    assert a.shape == b.shape == (120, 3)
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(a, bst.predict(X[:120]), atol=1e-6)


# -- binned replay (ScoreUpdater.add_trees) ------------------------------


def _replay_scores(bst, ds, kernel):
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.score_updater import ScoreUpdater
    gbdt = bst._gbdt
    bins_np = ds.bins.astype(np.int32)
    pad = np.zeros((bins_np.shape[0], 1), np.int32)
    bins_t = jnp.asarray(np.concatenate([bins_np, pad], axis=1).T.copy())
    su = ScoreUpdater(bins_t, ds.num_data, gbdt.K,
                      feat_tbl=ds.bundle_feat_table())
    su.add_trees(gbdt.models, gbdt.K, kernel)
    return su.get()


def test_binned_replay_matches_sequential_walk_and_raw_predict():
    rng = np.random.RandomState(9)
    X = rng.rand(300, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y)
    ds = bst.train_set._inner
    a = _replay_scores(bst, ds, "tensorized")
    b = _replay_scores(bst, ds, "walk")
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    # and both equal the raw ensemble prediction on the training rows
    np.testing.assert_allclose(a.reshape(-1),
                               bst.predict(X, raw_score=True), atol=1e-5)


def test_binned_replay_efb_store():
    rng = np.random.RandomState(10)
    oh = np.zeros((300, 10))
    oh[np.arange(300), rng.randint(10, size=300)] = rng.rand(300) + 0.5
    X = np.hstack([rng.rand(300, 3), oh])
    y = (X @ rng.randn(13) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 15,
                  "enable_bundle": True}, X, y)
    ds = bst.train_set._inner
    assert ds.bundle_feat_table() is not None   # EFB actually engaged
    a = _replay_scores(bst, ds, "tensorized")
    b = _replay_scores(bst, ds, "walk")
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_add_valid_replay_parity_between_kernels():
    """Booster.add_valid after training replays the existing model onto
    the valid scores — identical evals under both kernels."""
    rng = np.random.RandomState(11)
    X = rng.rand(400, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    Xv, yv = X[300:], y[300:]
    evals = {}
    for kernel in ("tensorized", "walk"):
        bst = _train({"objective": "binary", "num_leaves": 15,
                      "predict_kernel": kernel}, X[:300], y[:300])
        bst.add_valid(lgb.Dataset(Xv, yv, reference=bst.train_set), "v")
        evals[kernel] = bst._gbdt.eval_valid()
    for (s1, n1, v1, _), (s2, n2, v2, _) in zip(evals["tensorized"],
                                                evals["walk"]):
        assert (s1, n1) == (s2, n2)
        np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-6)


# -- serving fleet -------------------------------------------------------


def test_resolve_serve_replicas():
    import jax
    devs = jax.local_devices()         # 8 virtual CPU devices (conftest)
    assert len(resolve_serve_replicas(0)) == 1        # auto on CPU: 1
    assert len(resolve_serve_replicas(3)) == min(3, len(devs))
    assert len(resolve_serve_replicas(999)) == len(devs)


def test_multi_replica_parity_and_dispatch():
    rng = np.random.RandomState(12)
    X = rng.rand(300, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y, rounds=3)
    rt = PredictorRuntime(bst, max_batch_rows=64, min_bucket_rows=16,
                          replicas=4)
    assert rt.replica_count == 4
    ref = bst.predict(X[:32])
    # sequential traffic: the round-robin tie-break spreads idle fleets
    for _ in range(4):
        np.testing.assert_allclose(rt.predict(X[:32]), ref, atol=1e-6)
    d = rt.replica_dispatches()
    assert sum(d) >= 4 and sum(1 for x in d if x > 0) >= 2
    # concurrent traffic: every prediction correct, all dispatch counted
    errs = []

    def worker():
        try:
            got = rt.predict(X[:32])
            np.testing.assert_allclose(got, ref, atol=1e-6)
        except Exception as e:         # surface in the main thread
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs
    assert sum(rt.replica_dispatches()) == sum(d) + 12


def test_large_request_chunks_fan_out_concurrently():
    """ONE multi-chunk request on a multi-replica fleet dispatches its
    chunks concurrently (not a sequential scan that merely rotates
    replicas): two chunks must be in flight at once — pinned with a
    2-party barrier inside the chunk path — and the request must spread
    across both replicas with exact output."""
    rng = np.random.RandomState(21)
    X = rng.rand(256, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y, rounds=3)
    rt = PredictorRuntime(bst, max_batch_rows=64, min_bucket_rows=64,
                          replicas=2)
    rt.warmup(buckets=(64,))           # keep compiles off the timed path
    ref = bst.predict(X)
    barrier = threading.Barrier(2, timeout=60)
    orig = rt._predict_chunk

    def spy(Xc, kind):
        try:
            barrier.wait()             # passes only if 2 chunks overlap
        except threading.BrokenBarrierError:
            pass
        return orig(Xc, kind)

    rt._predict_chunk = spy
    d0 = rt.replica_dispatches()
    got = rt.predict(X)                # 4 chunks of 64 rows, 2 replicas
    np.testing.assert_allclose(got, ref, atol=1e-6)
    assert not barrier.broken          # sequential chunks would time out
    dd = [b - a for a, b in zip(d0, rt.replica_dispatches())]
    assert sum(dd) == 4
    assert sum(1 for x in dd if x > 0) == 2    # one request, whole fleet


def test_warmup_covers_both_kinds_and_all_replicas():
    rng = np.random.RandomState(13)
    X = rng.rand(200, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 7}, X, y, rounds=2)
    rt = PredictorRuntime(bst, max_batch_rows=64, min_bucket_rows=16,
                          replicas=2)
    rt.warmup((16,))                   # default kinds: BOTH
    misses = rt.cache_misses
    assert misses == 4                 # 2 replicas x (value, raw)
    # no compile on the request path for either kind, on any replica
    for _ in range(4):
        rt.predict(X[:10])
        rt.predict(X[:10], kind="raw")
    assert rt.cache_misses == misses


def test_zero_recompile_acceptance_tensorized(tmp_path):
    """The PR-1 zero-recompile acceptance, re-run under
    predict_kernel=tensorized with a multi-replica registry."""
    rng = np.random.RandomState(14)
    X = rng.rand(300, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    reg = ModelRegistry(path, params={"verbose": -1}, max_batch_rows=256,
                        predict_kernel="tensorized", replicas=2,
                        warmup_buckets=(32,))
    rt = reg.current()
    assert rt.predict_kernel == "tensorized"
    assert rt.replica_count == 2
    misses = rt.cache_misses
    for _ in range(10):
        got = rt.predict(X[:20])       # bucket 32, warm on every replica
        np.testing.assert_allclose(got, bst.predict(X[:20]), atol=1e-6)
        rt.predict(X[:20], kind="raw")
    assert rt.cache_misses == misses
