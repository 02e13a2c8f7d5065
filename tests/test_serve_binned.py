"""Binned int8 inference on the request path (serve_quantize=binned):
ingress quantizer exactness vs the raw f32 kernels, bitwise
raw-vs-binned parity on trained binary/multiclass/EFB/categorical
models (NaN rows and unseen categories included), padded-remainder
chunks on a 2-replica fleet, the registry's refbin sidecar contract
(missing / torn / sha1-mismatched sidecars refuse the swap, old
generation keeps serving), and the zero-recompile acceptance re-run
under the binned variant.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import BinMapper, CATEGORICAL, MISSING_NAN, NUMERICAL
from lightgbm_tpu.log import LightGBMError
from lightgbm_tpu.quantize import (FeatureQuantizer, file_sha1,
                                   load_refbin, rebin_models_for_serving)
from lightgbm_tpu.serving import ModelRegistry, PredictorRuntime

pytestmark = pytest.mark.quick


def _train(params, X, y, rounds=6):
    ds = lgb.Dataset(X, y)
    bst = lgb.Booster(dict({"verbose": -1, "min_data_in_leaf": 5},
                           **params), ds)
    for _ in range(rounds):
        bst.update()
    return bst, ds.construct()._inner


def _assert_bitwise(bst, refbin, Xq, replicas=1, **kw):
    """raw and binned runtimes agree BITWISE on both output kinds."""
    rt_raw = PredictorRuntime(bst, replicas=replicas, **kw)
    rt_bin = PredictorRuntime(bst, replicas=replicas, quantize="binned",
                              refbin=refbin, **kw)
    assert rt_bin.variant == "binned"
    for kind in ("value", "raw"):
        a = rt_raw.predict(Xq, kind=kind)
        b = rt_bin.predict(Xq, kind=kind)
        assert np.array_equal(a, b), f"kind={kind} diverged"
    return rt_raw, rt_bin


# ---------------------------------------------------------------------------
# FeatureQuantizer: serve-policy exactness units
# ---------------------------------------------------------------------------


def _num_mapper(uppers):
    m = BinMapper(bin_type=NUMERICAL, num_bin=len(uppers),
                  is_trivial=False,
                  bin_upper_bound=np.asarray(uppers, np.float64))
    return m


def test_quantizer_matches_f32_compare_at_f64_boundaries():
    """A float64 value strictly above a threshold that COLLAPSES onto it
    in f32 must still route left, because the raw kernel compares in
    f32 — the case a float64 ingress searchsorted would misroute."""
    t = 1.0 + 1e-9                        # f32(t) == 1.0
    v = 1.0 + 2e-9                        # v > t in f64, f32(v) == 1.0
    m = _num_mapper([t, 2.0, np.inf])
    q = FeatureQuantizer([m], [0])
    bins = q.quantize(np.array([[v], [1.0], [2.5], [0.5]]))
    tbin = int(m.value_to_bin(np.array([t]))[0])
    assert np.float32(v) <= np.float32(t)            # the raw compare
    assert bins[0, 0] <= tbin                        # ... reproduced
    assert bins[1, 0] <= tbin
    assert bins[2, 0] > tbin
    assert bins[3, 0] <= tbin


def test_quantizer_nan_inf_sentinel():
    m = _num_mapper([0.25, 0.5, 0.75, np.inf])
    q = FeatureQuantizer([m], [0])
    b = q.quantize(np.array([[np.nan], [np.inf], [-np.inf], [0.6],
                             [0.0]]))
    assert b.dtype == np.uint8
    # NaN -> the bin of 0.0: a numerical split of missing type None
    # compares NaN as 0.0 in the raw kernels
    assert b[0, 0] == b[4, 0] == 0
    assert b[1, 0] == m.num_bin - 1                  # +inf -> last bin
    assert b[2, 0] == 0                              # -inf -> first bin
    # the sentinel (unseen categories) exceeds every threshold bin
    assert q.missing_bin > m.num_bin - 1
    # a mapper with a NaN bin: NaN -> that bin, values as before
    m.missing_type, m.num_bin = MISSING_NAN, m.num_bin + 1
    q = FeatureQuantizer([m], [0])
    b = q.quantize(np.array([[np.nan], [np.inf], [0.6]]))
    assert b[:, 0].tolist() == [m.nan_bin, m.nan_bin - 1, 2]


def test_quantizer_unseen_category_sentinel():
    m = BinMapper(bin_type=CATEGORICAL, num_bin=3, is_trivial=False,
                  bin_2_categorical=[7, -3, 12])
    q = FeatureQuantizer([m], [0])
    b = q.quantize(np.array([[7.0], [-3.9], [12.2], [5.0], [np.nan],
                             [1e30]]))
    assert b[0, 0] == 0                              # category 7 -> bin 0
    assert b[1, 0] == 1                              # int trunc: -3.9 -> -3
    assert b[2, 0] == 2                              # 12.2 -> 12
    assert b[3, 0] == q.missing_bin                  # unseen -> sentinel
    assert b[4, 0] == q.missing_bin                  # NaN -> sentinel
    assert b[5, 0] == q.missing_bin                  # huge -> no category


def test_quantizer_dtype_widens_past_255_bins():
    m = _num_mapper(list(np.arange(299.0)) + [np.inf])
    q = FeatureQuantizer([m], [0])
    assert q.dtype == np.uint16 and q.missing_bin == 0xFFFF
    b = q.quantize(np.array([[250.5], [np.nan]]))
    assert b[0, 0] == 251 and b[1, 0] == 0       # NaN -> the bin of 0.0


def test_grid_quantizer_matches_searchsorted_adversarially():
    """The integer-keyed grid index must reproduce the f32 searchsorted
    bin EXACTLY — hammered on the exact bounds, their f32 neighbors,
    +/-0.0, subnormals, huge magnitudes, and wide log-spaced bound
    sets (which stress the key-space cell budget)."""
    from lightgbm_tpu.quantize import _NumericGrid, _f32_keys
    rng = np.random.RandomState(0)
    bound_sets = [
        np.sort(rng.rand(62)),
        np.sort(rng.randn(200) * 1e3),
        np.sort(np.concatenate([10.0 ** rng.uniform(-30, 30, 100),
                                -(10.0 ** rng.uniform(-30, 30, 100))])),
        np.array([-1e-45, 0.0, 1e-45, 1.0]),
    ]
    grids_built = 0
    for ub in bound_sets:
        ub32 = np.concatenate([ub, [np.inf]]).astype(np.float32)
        g = _NumericGrid(ub32)
        fin = ub32[:-1]
        probes = np.concatenate([
            fin, np.nextafter(fin, -np.inf), np.nextafter(fin, np.inf),
            rng.randn(4000).astype(np.float32) * np.float32(1e2),
            (10.0 ** rng.uniform(-38, 38, 2000)).astype(np.float32),
            np.array([0.0, -0.0, np.float32(1e-45), np.float32(-1e-45),
                      np.float32(3.4e38), np.float32(-3.4e38), np.inf,
                      -np.inf], np.float32)])
        want = np.searchsorted(ub32, probes, side="left")
        if g.ok:
            grids_built += 1
            got = g.lookup(_f32_keys(probes + np.float32(0.0)))
            assert np.array_equal(got, want)
        # the full quantizer agrees whichever path a feature takes
        # (grid, or the searchsorted fallback when adjacent-key
        # boundaries break the cell budget — the denormal set)
        m = _num_mapper(ub32.astype(np.float64))
        q = FeatureQuantizer([m], [0])
        got_q = q.quantize(probes.astype(np.float64).reshape(-1, 1))
        assert np.array_equal(got_q[:, 0], want)
    assert grids_built >= 3                  # the grid is the hot path


def test_quantizer_skips_trivial_features():
    m0 = _num_mapper([0.5, np.inf])
    triv = BinMapper()                               # is_trivial=True
    q = FeatureQuantizer([triv, m0], [1])
    b = q.quantize(np.array([[9.9, 0.4], [9.9, 0.6]]))
    assert b.shape == (2, 1)
    assert b[0, 0] == 0 and b[1, 0] == 1


# ---------------------------------------------------------------------------
# bitwise raw-vs-binned parity on trained models
# ---------------------------------------------------------------------------


def test_parity_binary_with_nan_rows():
    rng = np.random.RandomState(0)
    X = rng.rand(1500, 12)
    y = (X @ rng.randn(12) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 31}, X, y)
    Xq = X[:257].copy()
    Xq[3, 5] = np.nan
    Xq[4, :] = np.nan
    Xq[5, 0] = np.inf
    Xq[6, 1] = -np.inf
    _assert_bitwise(bst, inner, Xq)


def test_parity_multiclass():
    rng = np.random.RandomState(1)
    X = rng.rand(1200, 8)
    y = rng.randint(0, 3, 1200).astype(float)
    bst, inner = _train({"objective": "multiclass", "num_class": 3,
                         "num_leaves": 15}, X, y, rounds=4)
    Xq = X[:100].copy()
    Xq[0, 2] = np.nan
    _assert_bitwise(bst, inner, Xq)


def test_parity_efb_bundled_store():
    rng = np.random.RandomState(2)
    n = 2500
    X = np.zeros((n, 24))
    X[np.arange(n), rng.randint(0, 8, n)] = 1.0     # exclusive one-hots
    X[:, 8:] = rng.rand(n, 16)
    y = (X @ rng.randn(24) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15,
                         "enable_bundle": True}, X, y)
    assert inner.bundle_plan is not None            # EFB actually active
    Xq = X[:130].copy()
    Xq[7, 20] = np.nan
    _assert_bitwise(bst, inner, Xq)


def test_parity_categorical_with_unseen_categories():
    rng = np.random.RandomState(3)
    n = 1500
    X = rng.rand(n, 6)
    X[:, 0] = rng.randint(0, 5, n)                  # categorical column
    y = ((X[:, 0] == 2) | (X[:, 3] > 0.6)).astype(float)
    ds = lgb.Dataset(X, y, categorical_feature=[0])
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "min_data_in_leaf": 5, "num_leaves": 15},
                      ds)
    for _ in range(6):
        bst.update()
    inner = ds.construct()._inner
    Xq = X[:200].copy()
    Xq[0, 0] = 77.0                                 # unseen category
    Xq[1, 0] = -4.0                                 # unseen negative
    Xq[2, 0] = np.nan
    Xq[3, 0] = 2.9                                  # int-truncates to 2
    _assert_bitwise(bst, inner, Xq)


def test_parity_padded_remainder_on_two_replica_fleet():
    rng = np.random.RandomState(4)
    X = rng.rand(1000, 10)
    y = (X @ rng.randn(10) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    # 3 full 64-row chunks + a 45-row remainder padded to bucket 64
    Xq = X[:237].copy()
    Xq[200, 3] = np.nan
    rt_raw, rt_bin = _assert_bitwise(bst, inner, Xq, replicas=2,
                                     max_batch_rows=64,
                                     min_bucket_rows=16)
    assert sum(1 for d in rt_bin.replica_dispatches() if d > 0) == 2


def test_binned_buffer_is_4x_smaller_and_counted():
    from lightgbm_tpu import profiling
    rng = np.random.RandomState(5)
    X = rng.rand(800, 16)
    y = (X @ rng.randn(16) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    rt = PredictorRuntime(bst, replicas=1, quantize="binned", refbin=inner)
    q0 = profiling.counter_value(profiling.SERVE_QUANTIZE_BYTES_IN)
    r0 = profiling.counter_value(profiling.SERVE_BINNED_REQUESTS)
    rt.predict(X[:200])
    qb = profiling.counter_value(profiling.SERVE_QUANTIZE_BYTES_IN) - q0
    assert profiling.counter_value(profiling.SERVE_BINNED_REQUESTS) == r0 + 1
    assert rt._buf_dtype == np.uint8
    raw_bytes = 200 * rt.num_features * 4            # the f32 buffer
    assert 0 < qb <= raw_bytes / 4                   # >= 4x smaller


def test_binned_layout_matches_raw_layout_choice():
    """The binned runtime's layout auto mirrors the raw path: shallow
    numerical models traverse the PERFECT layout with bin ids in the
    f32 lanes; categorical models fall to the integer-record SoA
    (int16 lanes on TPU only — CPU XLA's int16 gathers de-vectorize,
    so the CPU tier keeps int32)."""
    import jax
    from lightgbm_tpu.ops.predict import EnsembleStack, PerfectEnsemble
    rng = np.random.RandomState(6)
    X = rng.rand(900, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 31}, X, y)
    rt = PredictorRuntime(bst, replicas=1, quantize="binned", refbin=inner)
    st = rt.replicas[0].stacks
    assert isinstance(st, PerfectEnsemble)
    rt_raw = PredictorRuntime(bst, replicas=1)
    assert isinstance(rt_raw.replicas[0].stacks, PerfectEnsemble)
    # categorical SPLITS → SoA, integer record
    Xc = X.copy()
    Xc[:, 0] = rng.randint(0, 5, 900)
    yc = (Xc[:, 0] == 2).astype(float)       # forces categorical splits
    ds = lgb.Dataset(Xc, yc, categorical_feature=[0])
    bc = lgb.Booster({"objective": "binary", "verbose": -1,
                      "min_data_in_leaf": 5, "num_leaves": 15}, ds)
    for _ in range(4):
        bc.update()
    bc._gbdt._flush_pending()
    assert any((t.decision_type[: t.num_leaves - 1] == 1).any()
               for t in bc._gbdt.models)
    rt_c = PredictorRuntime(bc, replicas=1, quantize="binned",
                            refbin=ds.construct()._inner)
    st_c = rt_c.replicas[0].stacks
    assert isinstance(st_c, EnsembleStack)
    want = np.int16 if jax.default_backend() == "tpu" else np.int32
    assert np.dtype(st_c.nodes.dtype) == want


# ---------------------------------------------------------------------------
# refbin contract: runtime + registry refusal semantics
# ---------------------------------------------------------------------------


def test_runtime_refuses_mismatched_refbin():
    rng = np.random.RandomState(7)
    X = rng.rand(900, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, _ = _train({"objective": "binary", "num_leaves": 15}, X, y)
    # a refbin frozen from DIFFERENT data: the model's thresholds are
    # not boundaries of its mappers
    other = lgb.Dataset(rng.rand(900, 6) * 100.0, y)
    other.construct()
    with pytest.raises(LightGBMError,
                       match="does not match|cannot represent"):
        PredictorRuntime(bst, replicas=1, quantize="binned",
                         refbin=other._inner)


def test_loaded_model_foreign_refbin_refused_not_misrouted(tmp_path):
    """A LOADED model rebinned against a foreign mapper set (the online
    daemon's window-frozen mappers are the real-world case) must be
    REFUSED, not served: its thresholds fall inside the sidecar's bins
    and the integer compare would silently misroute the rows between a
    threshold and the next boundary."""
    rng = np.random.RandomState(17)
    X = rng.rand(900, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, _ = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = str(tmp_path / "model.txt")
    bst.save_model(mp)
    foreign = lgb.Dataset(rng.rand(400, 6), y[:400])   # other sample
    foreign.construct()
    foreign._inner.save_refbin(mp + ".refbin")
    with pytest.raises(LightGBMError, match="cannot represent"):
        ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                      serve_quantize="binned")
    # auto degrades to raw instead of misrouting
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="auto")
    assert reg.current().variant == "raw"


def test_online_trainer_adopts_input_refbin_for_exact_binned(tmp_path):
    """The serve→train→serve loop: a daemon seeded with a model that
    ships its training-mapper sidecar adopts those mappers, publishes
    the SAME mapper set (sha-stamped), and the refit generation serves
    binned bitwise-identical to raw."""
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.online import OnlineTrainer, append_traffic
    rng = np.random.RandomState(18)
    X = rng.rand(1200, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    inp = str(tmp_path / "input.txt")
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "online_trigger_rows": 128,
              "refit_decay_rate": 0.0, "refit_min_rows": 1,
              "input_model": inp}
    ds = lgb.Dataset(X[:800], y[:800])
    bst = lgb.Booster(dict(params), ds)
    for _ in range(5):
        bst.update()
    bst.save_model(inp)
    ds.save_refbin(inp + ".refbin")
    traffic = str(tmp_path / "traffic.jsonl")
    pub = str(tmp_path / "pub.txt")
    tr = OnlineTrainer(lgb.Booster(params=dict(params), model_file=inp),
                       traffic, pub, config=config_from_params(params))
    assert tr._window is not None           # mappers adopted at init
    append_traffic(traffic, X[800:1100], y[800:1100])
    assert tr.poll_once() is True
    assert file_sha1(pub + ".refbin") == file_sha1(inp + ".refbin")
    meta = json.load(open(pub + ".meta.json"))
    assert meta["refbin_sha1"] == file_sha1(pub + ".refbin")
    reg = ModelRegistry(pub, params={"verbose": -1}, replicas=1,
                        serve_quantize="auto")
    assert reg.current().variant == "binned"
    raw = ModelRegistry(pub, params={"verbose": -1}, replicas=1,
                        serve_quantize="raw").current()
    Xq = X[:200].copy()
    Xq[0, 3] = np.nan
    assert np.array_equal(reg.current().predict(Xq), raw.predict(Xq))


def _publish(tmp_path, bst, inner, name="model.txt"):
    mp = str(tmp_path / name)
    bst.save_model(mp)
    inner.save_refbin(mp + ".refbin")
    return mp


def test_registry_binned_missing_refbin_refuses(tmp_path):
    rng = np.random.RandomState(8)
    X = rng.rand(700, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, _ = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = str(tmp_path / "model.txt")
    bst.save_model(mp)
    with pytest.raises(Exception):
        ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                      serve_quantize="binned")
    # auto degrades to raw instead
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="auto")
    assert reg.current().variant == "raw"


def test_registry_auto_picks_binned_with_refbin(tmp_path):
    rng = np.random.RandomState(9)
    X = rng.rand(700, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = _publish(tmp_path, bst, inner)
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="auto")
    rt = reg.current()
    assert rt.variant == "binned"
    # bitwise vs the raw-variant runtime on the same loaded model (the
    # Booster.predict host path transforms in f64 — different code, so
    # the bitwise bar is runtime-vs-runtime)
    raw_rt = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                           serve_quantize="raw").current()
    assert raw_rt.variant == "raw"
    assert np.array_equal(rt.predict(X[:40]), raw_rt.predict(X[:40]))


def test_registry_refuses_torn_refbin_swap_old_generation_serves(tmp_path):
    rng = np.random.RandomState(10)
    X = rng.rand(900, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = _publish(tmp_path, bst, inner)
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="binned")
    want = reg.current().predict(X[:30])
    # republish: new model bytes land, but the refbin is TORN (half the
    # sidecar) — the PR 9 no-tmp-discipline failure shape
    for _ in range(2):
        bst.update()
    bst.save_model(mp)
    blob = open(mp + ".refbin", "rb").read()
    with open(mp + ".refbin", "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert reg.poll_once() is False                  # swap refused
    assert reg.current().generation == 1
    assert reg.swap_failures == 1
    assert reg.last_swap_error is not None           # /stats-visible
    assert np.array_equal(reg.current().predict(X[:30]), want)
    # sidecar healed -> SIGHUP-style forced reload swaps generation 2
    inner.save_refbin(mp + ".refbin")
    assert reg.maybe_reload(force=True) is True
    assert reg.current().generation == 2
    assert reg.current().variant == "binned"
    np.testing.assert_allclose(reg.current().predict(X[:30]),
                               bst.predict(X[:30]), rtol=0, atol=1e-6)


def test_registry_refuses_sha1_mismatch_vs_publish_meta(tmp_path):
    rng = np.random.RandomState(11)
    X = rng.rand(700, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = _publish(tmp_path, bst, inner)
    with open(mp + ".meta.json", "w") as f:
        json.dump({"generation": 1, "refbin_sha1": "0" * 40}, f)
    with pytest.raises(LightGBMError, match="sha1"):
        ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                      serve_quantize="binned")
    # the matching fingerprint is accepted
    with open(mp + ".meta.json", "w") as f:
        json.dump({"generation": 1,
                   "refbin_sha1": file_sha1(mp + ".refbin")}, f)
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="binned")
    assert reg.current().variant == "binned"


def test_load_refbin_adopts_stored_settings(tmp_path):
    rng = np.random.RandomState(12)
    X = rng.rand(600, 5)
    y = (X @ rng.randn(5) > 0).astype(float)
    ds = lgb.Dataset(X, y)
    ds.construct({"max_bin": 63, "verbose": -1})
    p = str(tmp_path / "m.refbin")
    ds._inner.save_refbin(p)
    ref = load_refbin(p)                  # no config handed in
    assert ref.config.max_bin == 63
    assert ref.num_total_features == 5


def test_rebin_models_refuses_trivial_split_feature():
    rng = np.random.RandomState(13)
    X = rng.rand(900, 6)
    y = (X[:, 0] > 0.5).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    bst._gbdt._flush_pending()
    assert bst._gbdt.models
    # a mapper set where every model split feature is trivial
    Xc = np.zeros((100, 6))
    triv = lgb.Dataset(Xc, np.zeros(100))
    triv.construct()
    with pytest.raises(LightGBMError, match="trivial"):
        rebin_models_for_serving(bst._gbdt.models, triv._inner)


# ---------------------------------------------------------------------------
# acceptance: zero recompiles at steady state under serve_quantize=binned
# ---------------------------------------------------------------------------


def test_zero_recompile_acceptance_binned(tmp_path):
    """The PR-1/PR-7 zero-recompile acceptance re-run under
    serve_quantize=binned on a 2-replica registry: after warmup no
    request of either output kind compiles on the request path, and
    every answer is bitwise the raw path's."""
    rng = np.random.RandomState(14)
    X = rng.rand(900, 8)
    y = (X @ rng.randn(8) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = _publish(tmp_path, bst, inner)
    reg = ModelRegistry(mp, params={"verbose": -1}, max_batch_rows=256,
                        replicas=2, warmup_buckets=(32,),
                        serve_quantize="binned")
    rt = reg.current()
    assert rt.variant == "binned" and rt.replica_count == 2
    want = PredictorRuntime(bst, replicas=1).predict(X[:20])  # raw, bitwise
    misses = rt.cache_misses
    for _ in range(10):
        assert np.array_equal(rt.predict(X[:20]), want)
        rt.predict(X[:20], kind="raw")
    assert rt.cache_misses == misses


def test_server_stats_expose_binned_variant(tmp_path, monkeypatch):
    from lightgbm_tpu.serving import PredictionServer
    rng = np.random.RandomState(15)
    X = rng.rand(700, 6)
    y = (X @ rng.randn(6) > 0).astype(float)
    bst, inner = _train({"objective": "binary", "num_leaves": 15}, X, y)
    mp = _publish(tmp_path, bst, inner)
    reg = ModelRegistry(mp, params={"verbose": -1}, replicas=1,
                        serve_quantize="auto")
    with PredictionServer(reg, port=0, model_poll_seconds=0) as srv:
        import http.client
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=30)
        body = "\n".join(json.dumps([float(v) for v in r])
                         for r in X[:5])
        conn.request("POST", "/predict", body)
        resp = conn.getresponse()
        assert resp.status == 200
        got = [json.loads(l) for l in resp.read().decode().splitlines()]
        conn.close()
        # bitwise the raw-variant runtime's answers (Booster.predict's
        # host-side f64 transform is a different code path)
        want = PredictorRuntime(bst, replicas=1).predict(X[:5])
        assert np.array_equal(np.asarray(got), want)
        stats = srv.stats()
    assert stats["replicas"]["serve_quantize"] == "binned"
    assert stats["binned_requests"] >= 1
    assert stats["quantize_bytes_in"] > 0
