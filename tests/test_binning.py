"""Unit tests against NumPy oracles for the numeric core — what the
reference never had (SURVEY.md §4 'add what the reference lacks')."""
import numpy as np
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.quick

from lightgbm_tpu.binning import (find_bin, find_bin_mappers, BinMapper,
                                  NUMERICAL, CATEGORICAL)
from lightgbm_tpu.ops.histogram import (hist_xla, hist_multileaf_masked)
from lightgbm_tpu.ops.split import best_split, leaf_split_gain, leaf_output


def test_binmapper_roundtrip_monotone():
    rng = np.random.RandomState(0)
    vals = np.concatenate([rng.randn(5000), np.zeros(1000)])
    m = find_bin(vals, len(vals), max_bin=63, min_data_in_bin=3)
    b = m.value_to_bin(vals)
    assert b.max() < m.num_bin
    # binning is monotone: sorted values → non-decreasing bins
    sv = np.sort(vals)
    sb = m.value_to_bin(sv)
    assert (np.diff(sb.astype(int)) >= 0).all()


def test_binmapper_categorical_top_frequency():
    rng = np.random.RandomState(1)
    vals = rng.choice([0, 1, 2, 3, 50], p=[0.5, 0.3, 0.1, 0.07, 0.03],
                      size=10000).astype(np.float64)
    m = find_bin(vals, len(vals), max_bin=255, min_data_in_bin=3,
                 bin_type=CATEGORICAL)
    assert m.bin_type == CATEGORICAL
    b0 = m.value_to_bin(np.array([0.0]))[0]
    # most frequent category gets the first bin after any default handling
    assert m.bin_to_value(int(b0)) == 0.0


def test_histogram_oracle():
    rng = np.random.RandomState(2)
    C, F, B = 3000, 7, 128
    gb = rng.randint(0, 100, size=(C, F)).astype(np.int32)
    g = rng.randn(C).astype(np.float32)
    h = np.abs(rng.randn(C)).astype(np.float32)
    vals = jnp.stack([jnp.asarray(g), jnp.asarray(h),
                      jnp.ones(C, jnp.float32)])
    hist = np.asarray(hist_xla(jnp.asarray(gb), vals, num_bins_padded=B))
    oracle = np.zeros((F, 3, B), np.float64)
    for f in range(F):
        np.add.at(oracle[f, 0], gb[:, f], g)
        np.add.at(oracle[f, 1], gb[:, f], h)
        np.add.at(oracle[f, 2], gb[:, f], 1.0)
    np.testing.assert_allclose(hist, oracle, rtol=1e-4, atol=1e-4)


def test_multileaf_histogram_oracle():
    rng = np.random.RandomState(3)
    C, F, B, K = 2000, 5, 128, 6
    gb = rng.randint(0, 100, size=(F, C)).astype(np.int32)
    lid = rng.randint(0, 10, C).astype(np.int32)
    g = rng.randn(C).astype(np.float32)
    h = np.abs(rng.randn(C)).astype(np.float32)
    gh8 = jnp.zeros((8, C), jnp.float32).at[0].set(g).at[1].set(h) \
        .at[2].set(1.0)
    sl = np.array([3, 7, -1, 0, 9, -1], np.int32)
    out = np.asarray(hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), gh8, jnp.asarray(sl),
        num_bins_padded=B, backend="xla"))
    for k, leaf in enumerate(sl):
        m = (lid == leaf) if leaf >= 0 else np.zeros(C, bool)
        for f in range(F):
            oracle = np.zeros(B)
            np.add.at(oracle, gb[f][m], g[m])
            np.testing.assert_allclose(out[k, f, 0], oracle, rtol=1e-4,
                                       atol=1e-4)


@pytest.mark.parametrize("live_frac,amp,int8_store", [
    (1.0, None, False),          # all rows live
    (0.6, None, False),          # rows a bag dropped: zero row mask
    (1.0, 2.0, False),           # GOSS-amplified gradients
    (0.8, 2.0, True),            # int8 value-128 store (the chip's layout)
])
def test_masked_histogram_equals_numpy_exactly(live_frac, amp, int8_store):
    """All three channels of `hist_multileaf_masked` against `np.add.at`,
    to the bit: gradients on an integer grid over 64, hessians over 128
    and a power-of-two amplification make every float32 partial sum
    exact in any order.  Dropped rows reach the kernel as rows whose
    value rows are zero, an empty slot (-1) has to come back all zero,
    and the odd row count leaves a ragged last chunk."""
    rng = np.random.RandomState(11)
    n, f, b, L, B = 4097, 9, 250, 12, 256
    bins = rng.randint(0, b, size=(f, n)).astype(np.int32)
    lid = rng.randint(0, L, size=n).astype(np.int32)
    live = (rng.rand(n) < live_frac).astype(np.float32)
    gh8 = np.zeros((8, n), np.float32)
    gh8[0] = rng.randint(-512, 512, size=n) / 64.0
    gh8[1] = rng.randint(0, 256, size=n) / 128.0
    if amp is not None:
        gh8[:2, rng.rand(n) < 0.5] *= amp
    gh8[2] = live
    gh8[:2] *= live
    store = ((bins.astype(np.int16) - 128).astype(np.int8)
             if int8_store else bins)
    sl = np.array([3, 7, -1, 0], np.int32)
    out = np.asarray(hist_multileaf_masked(
        jnp.asarray(store), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype="float32"))
    want = np.zeros((len(sl), f, 3, B), np.float64)
    for k, leaf in enumerate(sl):
        rows = np.flatnonzero(lid == leaf)
        for j in range(f):
            for c in range(3):
                np.add.at(want[k, j, c], bins[j, rows], gh8[c, rows])
    np.testing.assert_array_equal(out, want.astype(np.float32))
    assert out[2].max() == 0.0 and out[:, :, 2].sum() > 0


def test_best_split_oracle():
    """Exhaustive scan oracle for one feature."""
    rng = np.random.RandomState(4)
    B = 128
    nb = 20
    g = rng.randn(nb).astype(np.float64)
    h = np.abs(rng.randn(nb)).astype(np.float64) + 0.1
    c = rng.randint(1, 50, nb).astype(np.float64)
    hist = np.zeros((1, 3, B), np.float32)
    hist[0, 0, :nb] = g
    hist[0, 1, :nb] = h
    hist[0, 2, :nb] = c
    G, H, C = g.sum(), h.sum(), c.sum()
    l2 = 0.5
    rec = best_split(jnp.asarray(hist), jnp.asarray([nb], jnp.int32),
                     jnp.zeros(1, bool), jnp.ones(1, bool),
                     jnp.float32(G), jnp.float32(H), jnp.float32(C),
                     lambda_l2=l2, min_data_in_leaf=1,
                     min_sum_hessian_in_leaf=1e-3)
    # numpy oracle: best threshold by gain formula
    def gain(gg, hh):
        return gg * gg / (hh + l2)
    best_gain, best_t = -np.inf, -1
    for t in range(nb - 1):
        gl, hl = g[:t + 1].sum(), h[:t + 1].sum()
        gr, hr = G - gl, H - hl
        tot = gain(gl, hl) + gain(gr, hr)
        if tot > best_gain:
            best_gain, best_t = tot, t
    assert int(rec.threshold_bin) == best_t
    np.testing.assert_allclose(float(rec.gain),
                               best_gain - gain(G, H), rtol=1e-4)


def test_leaf_output_math():
    # leaf_out = -sign(G)(|G|-l1)/(H+l2)  (feature_histogram.hpp:281-300)
    assert float(leaf_output(3.0, 2.0, 1.0, 1.0)) == pytest.approx(-2.0 / 3.0)
    assert float(leaf_output(-3.0, 2.0, 1.0, 1.0)) == pytest.approx(2.0 / 3.0)
    assert float(leaf_split_gain(4.0, 3.0, 1.0, 1.0)) == pytest.approx(9 / 4)


def test_binary_dataset_cache_roundtrip(tmp_path, binary_example):
    """save_binary → from_file auto-detects the cache and trains
    identically (reference dataset.cpp binary cache + magic token)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.config import config_from_params
    X, y, _, _ = binary_example
    cfg = config_from_params({"objective": "binary", "verbose": -1})
    ds = RawDataset(X, y, config=cfg)
    p = str(tmp_path / "train.bin")
    ds.save_binary(p)
    assert RawDataset._is_binary_file(p)
    ds2 = RawDataset.from_file(p, cfg)
    np.testing.assert_array_equal(ds.bins, ds2.bins)
    np.testing.assert_array_equal(np.asarray(ds.metadata.label),
                                  np.asarray(ds2.metadata.label))
    assert ds2.used_features == ds.used_features


def test_valid_set_uses_train_binning(binary_example):
    from lightgbm_tpu.dataset import Dataset as RawDataset
    from lightgbm_tpu.config import config_from_params
    X, y, Xt, yt = binary_example
    cfg = config_from_params({"max_bin": 63, "verbose": -1})
    train = RawDataset(X, y, config=cfg)
    valid = RawDataset(Xt, yt, config=cfg, reference=train)
    assert valid.max_num_bin == train.max_num_bin
    for mt, mv in zip(train.mappers, valid.mappers):
        assert mt.num_bin == mv.num_bin


def test_numerical_bins_fast_path_matches_general_loop():
    """The no-big-count searchsorted fast path in _numerical_bins must be
    emission-for-emission identical to the general greedy scan (reference
    bin.cpp:109-186 semantics).  The oracle below is the general loop."""
    from lightgbm_tpu.binning import _numerical_bins, _distinct_with_zero

    def oracle(vals, counts, total_sample_cnt, max_bin, min_data_in_bin):
        n_distinct = vals.size
        cnt_in_bin = []
        if min_data_in_bin > 0:
            max_bin = max(1, min(max_bin,
                                 total_sample_cnt // min_data_in_bin))
        mean_bin_size = total_sample_cnt / max_bin
        zero_idx = np.flatnonzero(vals == 0.0)
        zero_cnt = int(counts[zero_idx[0]]) if zero_idx.size else 0
        if zero_cnt > mean_bin_size:
            non_zero_cnt = total_sample_cnt - zero_cnt
            max_bin = min(max_bin,
                          1 + non_zero_cnt // max(min_data_in_bin, 1))
        max_bin = max(int(max_bin), 1)
        is_big = counts >= mean_bin_size
        rest_bin_cnt = max_bin - int(is_big.sum())
        rest_sample_cnt = total_sample_cnt - int(counts[is_big].sum())
        if rest_bin_cnt > 0:
            mean_bin_size = rest_sample_cnt / rest_bin_cnt
        upper, lower, cur, bin_cnt = [], [float(vals[0])], 0, 0
        for i in range(n_distinct - 1):
            if not is_big[i]:
                rest_sample_cnt -= int(counts[i])
            cur += int(counts[i])
            if (is_big[i] or cur >= mean_bin_size or
                    (is_big[i + 1] and cur >= max(1.0,
                                                  mean_bin_size * 0.5))):
                upper.append(float(vals[i]))
                cnt_in_bin.append(cur)
                bin_cnt += 1
                lower.append(float(vals[i + 1]))
                if bin_cnt >= max_bin - 1:
                    break
                cur = 0
                if not is_big[i]:
                    rest_bin_cnt -= 1
                    if rest_bin_cnt > 0:
                        mean_bin_size = rest_sample_cnt / rest_bin_cnt
        cnt_in_bin.append(int(total_sample_cnt - sum(cnt_in_bin)))
        bin_cnt += 1
        ub = np.empty(bin_cnt)
        for i in range(bin_cnt - 1):
            ub[i] = (upper[i] + lower[i + 1]) / 2.0
        ub[bin_cnt - 1] = np.inf
        return ub, cnt_in_bin

    rng = np.random.RandomState(0)
    checked = 0
    for trial in range(120):
        kind = trial % 4
        n = rng.randint(300, 4000)
        if kind == 0:
            x = rng.randn(n)                    # continuous, all distinct
        elif kind == 1:
            x = rng.randn(n).round(2)           # many duplicates
        elif kind == 2:
            x = np.abs(rng.randn(n))
            x[rng.rand(n) < 0.3] = 0.0          # sparse-ish
        else:
            x = rng.exponential(1.0, n).round(1)  # skewed duplicates
        vals, counts = _distinct_with_zero(x[x != 0], n)
        mb = int(rng.choice([15, 63, 255]))
        mdib = int(rng.choice([1, 3, 10]))
        if vals.size <= mb:
            continue
        ub_new, cib_new = _numerical_bins(vals, counts, n, mb, mdib)
        ub_old, cib_old = oracle(vals, counts, n, mb, mdib)
        np.testing.assert_array_equal(ub_new, ub_old)
        assert list(cib_new) == list(cib_old)
        checked += 1
    assert checked > 40
