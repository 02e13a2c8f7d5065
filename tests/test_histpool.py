"""Histogram-memory bounding (reference HistogramPool LRU cap,
feature_histogram.hpp:313-475).

The TPU learners keep a [num_leaves, F, 3, B] per-leaf histogram cache for
the parent-subtraction trick; when that exceeds the histogram_pool_size
budget they switch to direct child histograms (2x hist passes, O(1)
leaf-hist memory).  Both modes must grow the same trees.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Dataset as InnerDataset
from lightgbm_tpu.learner.rounds import RoundsTreeLearner


@pytest.fixture(scope="module")
def xy():
    rng = np.random.RandomState(0)
    X = rng.randn(3000, 10)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.randn(3000) > 0).astype(float)
    return X, y


def _train(X, y, extra):
    params = {"objective": "binary", "num_leaves": 31, "verbose": -1,
              "tree_growth": "rounds", **extra}
    return lgb.train(params, lgb.Dataset(X, y), num_boost_round=6)


def test_nocache_mode_matches_cache_mode(xy):
    X, y = xy
    b1 = _train(X, y, {})
    b2 = _train(X, y, {"histogram_pool_size": 0.001})  # force bounded mode
    assert b1._gbdt.learner.cache_parent_hist
    assert not b2._gbdt.learner.cache_parent_hist
    assert np.abs(b1.predict(X) - b2.predict(X)).max() < 1e-4
    assert ([t.num_leaves for t in b1._gbdt.models]
            == [t.num_leaves for t in b2._gbdt.models])


@pytest.mark.quick
def test_epsilon_shape_selects_bounded_path():
    """At Epsilon width (F=2000, 255 leaves) the learner honors
    histogram_pool_size: a tight budget selects the bounded path, a
    roomy one keeps the cache.  The unset default is device-aware
    (a quarter of reported device memory, >= 1.5 GB floor): on a 16 GB
    chip the 1.57 GB full-Epsilon cache stays on the fast subtraction
    path, while the conservative floor would bound it."""
    from lightgbm_tpu.learner.common import _default_pool_budget
    rng = np.random.RandomState(0)
    X = rng.randn(64, 2000)
    ds = InnerDataset(X, rng.rand(64))
    tight = RoundsTreeLearner(ds, Config(num_leaves=255,
                                         histogram_pool_size=50.0))
    assert not tight.cache_parent_hist
    roomy = RoundsTreeLearner(ds, Config(num_leaves=255,
                                         histogram_pool_size=4000.0))
    assert roomy.cache_parent_hist
    # full Epsilon geometry: [255 leaves, 2000 features, 3, 256 bins] f32
    eps_cache = 4 * 255 * 2000 * 3 * 256
    assert eps_cache > 1.5e9          # the floor would force bounded mode
    assert _default_pool_budget() >= 1.5e9


@pytest.mark.quick
def test_default_budget_reads_device_memory(monkeypatch):
    """The device-aware branch: with a reported 16 GB bytes_limit the
    default budget is 4 GB (so the 1.57 GB full-Epsilon cache keeps the
    fast subtraction path); with no stats it falls back to the floor."""
    import jax
    from lightgbm_tpu.learner import common

    class FakeDev:
        def __init__(self, stats):
            self._s = stats

        def memory_stats(self):
            return self._s

    monkeypatch.setattr(jax, "local_devices",
                        lambda: [FakeDev({"bytes_limit": 16e9})])
    assert common._default_pool_budget() == 4e9
    assert common.use_parent_hist_cache(
        Config(num_leaves=255), 2000, 256)      # Epsilon cache fits
    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev(None)])
    assert common._default_pool_budget() == 1.5e9
    assert not common.use_parent_hist_cache(
        Config(num_leaves=255), 2000, 256)      # floor bounds it


@pytest.mark.quick
def test_tpu_without_memory_stats_is_an_error(monkeypatch):
    """No assumed 4 GB / 16 GB: on TPU a device that reports no
    bytes_limit raises; the CPU tier keeps its constant."""
    import jax
    from lightgbm_tpu.learner import common

    class FakeDev:
        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    assert common.device_bytes_limit() is None            # CPU tier
    assert common._default_pool_budget() == 1.5e9         # its floor
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="bytes_limit"):
        common.device_bytes_limit()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        common._default_pool_budget()
