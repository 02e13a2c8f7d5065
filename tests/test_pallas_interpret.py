"""Pallas TPU kernel logic validated on CPU via interpret mode.

The production backend selection uses these kernels only on real TPU
(learner/*.py pick backend="pallas" there), so without this file the
kernel bodies would never execute in CI.  Interpret mode runs the exact
kernel (grid, BlockSpecs, accumulation across row-chunks) on the CPU
backend and must match the XLA fallback to f32-accumulation-order
tolerance (the two paths sum chunks in different orders, so last-ulp
differences are expected; atol 1e-4 on O(1) values catches any real
indexing/masking bug).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import (hist_pallas, hist_pallas_multileaf,
                                        hist_multileaf_masked,
                                        hist_multileaf_xla, hist_xla)

pytestmark = pytest.mark.quick


def _rand(n, f, b, seed=0):
    rng = np.random.RandomState(seed)
    gb = rng.randint(0, b, size=(f, n)).astype(np.int32)
    return rng, gb


def test_hist_pallas_matches_xla_f32():
    rng, gb = _rand(5000, 11, 250)       # odd F -> feature-group padding,
    B = 256                              # odd C -> row-chunk padding
    vals8 = np.zeros((8, 5000), np.float32)
    vals8[0] = rng.randn(5000)
    vals8[1] = rng.rand(5000)
    vals8[2] = (rng.rand(5000) < 0.8)
    h_pl = hist_pallas(jnp.asarray(gb), jnp.asarray(vals8),
                       num_bins_padded=B, input_dtype="float32",
                       interpret=True)
    h_x = hist_xla(jnp.asarray(gb.T), jnp.asarray(vals8[:3]),
                   num_bins_padded=B, input_dtype="float32")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=0, atol=1e-4)


def test_hist_pallas_multileaf_matches_xla():
    rng, gb = _rand(3000, 8, 60, seed=1)
    B = 128
    M = 24
    vals = rng.randn(M, 3000).astype(np.float32)
    h_pl = hist_pallas_multileaf(jnp.asarray(gb), jnp.asarray(vals),
                                 num_bins_padded=B, input_dtype="float32",
                                 interpret=True)
    h_x = hist_multileaf_xla(jnp.asarray(gb), jnp.asarray(vals),
                             num_bins_padded=B, input_dtype="float32")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=0, atol=1e-4)


def test_hist_multileaf_masked_pallas_matches_xla():
    """The production rounds-learner kernel: in-kernel mask construction
    (leaf ids vs slot table) must equal the XLA-level formulation,
    including empty (-1) slots and padded rows."""
    rng, gb = _rand(4097, 9, 250, seed=2)   # non-multiple-of-chunk C
    B = 256
    K = 7
    lid = rng.randint(0, 12, size=4097).astype(np.int32)
    gh8 = np.zeros((8, 4097), np.float32)
    gh8[0] = rng.randn(4097)
    gh8[1] = rng.rand(4097)
    gh8[2] = (rng.rand(4097) < 0.9)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    sl = np.array([3, 7, -1, 0, 11, -1, 5], np.int32)
    h_pl = hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="pallas",
        input_dtype="float32", interpret=True)
    h_x = hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype="float32")
    assert h_pl.shape == h_x.shape == (K, 9, 3, B)
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=0, atol=1e-4)
    # empty slots produce exactly zero
    assert np.asarray(h_pl)[2].max() == 0.0
    assert np.asarray(h_pl)[5].max() == 0.0


def test_hist_masked_int8_quantized_kernel():
    """The int8 MXU kernel (interpret mode) vs its own XLA emulation:
    identical dequantized histograms, exact counts, and within the
    analytic quantization bound of the f32 truth."""
    rng, gb = _rand(3000, 6, 120, seed=5)
    B = 128
    lid = rng.randint(0, 8, size=3000).astype(np.int32)
    gh8 = np.zeros((8, 3000), np.float32)
    gh8[0] = rng.randn(3000)
    gh8[1] = rng.rand(3000)
    gh8[2] = 1.0
    sl = np.array([0, 3, -1, 7], np.int32)
    args = (jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
            jnp.asarray(sl))
    kw = dict(num_bins_padded=B)
    h_q = hist_multileaf_masked(*args, backend="pallas",
                                input_dtype="int8", interpret=True, **kw)
    h_qx = hist_multileaf_masked(*args, backend="xla",
                                 input_dtype="int8", **kw)
    np.testing.assert_allclose(np.asarray(h_q), np.asarray(h_qx),
                               rtol=0, atol=1e-4)
    h_f = hist_multileaf_masked(*args, backend="xla",
                                input_dtype="float32", **kw)
    # counts exact
    np.testing.assert_array_equal(np.asarray(h_q)[:, :, 2],
                                  np.asarray(h_f)[:, :, 2])
    # grad/hess within n_bin * scale/2 of the f32 truth
    sg = np.abs(gh8[0]).max() / 127.0
    sh = np.abs(gh8[1]).max() / 127.0
    cnt = np.asarray(h_f)[:, :, 2]
    bound_g = cnt * sg / 2 + 1e-4
    bound_h = cnt * sh / 2 + 1e-4
    assert (np.abs(np.asarray(h_q)[:, :, 0] - np.asarray(h_f)[:, :, 0])
            <= bound_g).all()
    assert (np.abs(np.asarray(h_q)[:, :, 1] - np.asarray(h_f)[:, :, 1])
            <= bound_h).all()


@pytest.mark.parametrize("max_nb,exp_pack", [(64, 2), (32, 4), (16, 8),
                                             (33, 2), (65, 1)])
def test_hist_masked_feature_packing(max_nb, exp_pack):
    """Feature packing (<=64-bin features share a 128-lane block,
    docs/GPU-Performance.md:153-156 sweet spot): the packed kernel must
    equal the unpacked XLA path bin for bin, for every sub-block width."""
    from lightgbm_tpu.ops.histogram import packed_bins_layout
    bs, pack = packed_bins_layout(max_nb, 128)
    assert pack == exp_pack
    rng, gb = _rand(2500, 11, max_nb, seed=8)   # odd F: pad feature joins
    B = 128                                     # a pack; must stay zero
    K = 5
    lid = rng.randint(0, 9, size=2500).astype(np.int32)
    gh8 = np.zeros((8, 2500), np.float32)
    gh8[0] = rng.randn(2500)
    gh8[1] = rng.rand(2500)
    gh8[2] = (rng.rand(2500) < 0.9)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    sl = np.array([2, -1, 0, 8, 4], np.int32)
    args = (jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
            jnp.asarray(sl))
    h_pk = hist_multileaf_masked(*args, num_bins_padded=B, backend="pallas",
                                 input_dtype="float32", interpret=True,
                                 max_num_bin=max_nb)
    h_x = hist_multileaf_masked(*args, num_bins_padded=B, backend="xla",
                                input_dtype="float32")
    assert h_pk.shape == h_x.shape == (K, 11, 3, B)
    np.testing.assert_allclose(np.asarray(h_pk), np.asarray(h_x),
                               rtol=0, atol=1e-4)
    if pack > 1:
        # lanes past the sub-block width must be exactly zero
        assert np.asarray(h_pk)[:, :, :, bs:].max() == 0.0


def test_hist_masked_int8_feature_packing():
    rng, gb = _rand(2000, 5, 60, seed=9)
    B = 128
    lid = rng.randint(0, 6, size=2000).astype(np.int32)
    gh8 = np.zeros((8, 2000), np.float32)
    gh8[0] = rng.randn(2000)
    gh8[1] = rng.rand(2000)
    gh8[2] = 1.0
    sl = np.array([1, 4, -1], np.int32)
    args = (jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
            jnp.asarray(sl))
    h_q = hist_multileaf_masked(*args, num_bins_padded=B, backend="pallas",
                                input_dtype="int8", interpret=True,
                                max_num_bin=64)
    h_qx = hist_multileaf_masked(*args, num_bins_padded=B, backend="xla",
                                 input_dtype="int8")
    np.testing.assert_allclose(np.asarray(h_q), np.asarray(h_qx),
                               rtol=0, atol=1e-4)


@pytest.mark.parametrize("K", [1, 84])
@pytest.mark.parametrize("B,max_nb", [(256, 255), (128, 63)])
def test_hist_masked_int8_equals_integer_histogram(K, B, max_nb):
    """The int8 kernel sums exact products in int32, so the order of the
    sum cannot show: its histogram is EQUAL, not close, to a numpy
    integer histogram of the quantized operands — at the root's K=1 and
    the full K=84 launch, at 256 bins and at the packed 63-bin layout
    (two columns a lane block), over two row chunks of which the second
    is mostly padding, with an odd feature count and empty slots."""
    from lightgbm_tpu.ops.histogram import quantize_gh
    C, F = 8192 + 301, 11
    rng, gb = _rand(C, F, max_nb, seed=30)
    lid = rng.randint(0, 2 * K + 1, size=C).astype(np.int32)
    gh8 = np.zeros((8, C), np.float32)
    gh8[2] = (rng.rand(C) < 0.9)
    gh8[0] = rng.randn(C) * gh8[2]
    gh8[1] = rng.rand(C) * gh8[2]
    sl = rng.permutation(2 * K + 1)[:K].astype(np.int32)
    sl[K // 3::7] = -1                      # empty slots (none at K=1)
    h = hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="pallas",
        input_dtype="int8", interpret=True, max_num_bin=max_nb)
    ghq, sg, sh = quantize_gh(jnp.asarray(gh8))
    ghq = np.asarray(ghq)
    assert np.abs(ghq[:2]).max() == 127
    want = np.zeros((K, F, 3, B), np.int64)
    for k in range(K):
        rows = np.flatnonzero(lid == sl[k])
        for f in range(F):
            for c in range(3):
                np.add.at(want[k, f, c], gb[f, rows], ghq[c, rows])
    scale = np.asarray([sg, sh, 1.0], np.float32)[None, None, :, None]
    assert h.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(h), want.astype(np.float32) * scale)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("C", [2 * 8192, 8192 + 301],
                         ids=["aligned_rows", "padded_rows"])
def test_hist_masked_takes_the_trees_quantisation(backend, C):
    """The rounds learner quantises gh8 once a tree and hands every
    launch the triple: the histogram is the one a launch that quantises
    for itself gives, bit for bit — over rows that fill the row chunks
    and over rows the wrapper pads (ghq is padded beside gh8)."""
    from lightgbm_tpu.ops.histogram import quantize_gh
    F, K, B = 8, 8, 128
    rng, gb = _rand(C, F, 63, seed=34)
    lid = jnp.asarray(rng.randint(0, K, size=C).astype(np.int32))
    gh8 = np.zeros((8, C), np.float32)
    gh8[2] = (rng.rand(C) < 0.7)
    gh8[0] = rng.randn(C) * gh8[2]
    gh8[1] = rng.rand(C) * gh8[2]
    gh8 = jnp.asarray(gh8)
    kw = dict(num_bins_padded=B, backend=backend, input_dtype="int8",
              max_num_bin=63, interpret=backend == "pallas")
    sl = jnp.arange(K, dtype=jnp.int32)
    own = hist_multileaf_masked(jnp.asarray(gb), lid, gh8, sl, **kw)
    given = hist_multileaf_masked(jnp.asarray(gb), lid, gh8, sl,
                                  ghq=quantize_gh(gh8), **kw)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(given))
    assert float(np.abs(np.asarray(own)).sum()) > 0


@pytest.mark.parametrize("input_dtype", ["float32", "bfloat16", "int8"])
def test_hist_masked_int8_stored_bins(input_dtype):
    """int8-STORED bins (value-128 HBM layout, the Expo-scale memory fix)
    must histogram identically to int32 storage, through both the f32/bf16
    kernel and the quantized kernel, including the G=32 block regrouping."""
    rng, gb = _rand(3000, 37, 250, seed=12)     # F=37: pads to 64 at G=32
    B = 256
    K = 5
    lid = rng.randint(0, 9, size=3000).astype(np.int32)
    gh8 = np.zeros((8, 3000), np.float32)
    gh8[0] = rng.randn(3000)
    gh8[1] = rng.rand(3000)
    gh8[2] = (rng.rand(3000) < 0.9)
    gh8[0] *= gh8[2]
    gh8[1] *= gh8[2]
    sl = np.array([2, -1, 0, 8, 4], np.int32)
    gb8 = (gb.astype(np.int16) - 128).astype(np.int8)
    h_i8 = hist_multileaf_masked(
        jnp.asarray(gb8), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="pallas",
        input_dtype=input_dtype, interpret=True)
    h_i32 = hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype=input_dtype)
    np.testing.assert_allclose(np.asarray(h_i8), np.asarray(h_i32),
                               rtol=0, atol=1e-4)
    # XLA fallback accepts the int8 storage too
    h_i8x = hist_multileaf_masked(
        jnp.asarray(gb8), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype=input_dtype)
    np.testing.assert_allclose(np.asarray(h_i8x), np.asarray(h_i32),
                               rtol=0, atol=1e-4)


def test_hist_masked_bf16_onehot():
    """The bf16 masked kernel (int32 one-hot compare, bf16 operands):
    the pallas result must match the XLA bf16 formulation bit-for-bit
    in the one-hot and to bf16 summation tolerance in the totals."""
    rng, gb = _rand(2051, 5, 255, seed=9)
    B = 256
    lid = rng.randint(0, 10, size=2051).astype(np.int32)
    gh8 = np.zeros((8, 2051), np.float32)
    gh8[0] = rng.randn(2051)
    gh8[1] = rng.rand(2051)
    gh8[2] = 1.0
    sl = np.array([1, -1, 9, 4], np.int32)
    args = (jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
            jnp.asarray(sl))
    h_pl = hist_multileaf_masked(*args, num_bins_padded=B,
                                 backend="pallas", input_dtype="bfloat16",
                                 interpret=True)
    h_x = hist_multileaf_masked(*args, num_bins_padded=B,
                                backend="xla", input_dtype="bfloat16")
    assert h_pl.shape == (4, 5, 3, B)
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=2e-2, atol=2e-2)
    # counts (bf16 sums of 0/1) agree exactly between the formulations
    np.testing.assert_array_equal(np.asarray(h_pl)[:, :, 2],
                                  np.asarray(h_x)[:, :, 2])
    assert np.asarray(h_pl)[1].max() == 0.0


@pytest.mark.parametrize("input_dtype", ["bfloat16", "int8"])
def test_hist_masked_int8_stored_packed_bins(input_dtype):
    """int8-STORED bins combined with feature packing: the widen,
    un-offset and pack shift (`gb + 128 + s*bins_sub`) all run on the
    value-128 layout inside _packed_onehot — pin it against int32
    storage through XLA."""
    rng, gb = _rand(2500, 33, 60, seed=21)      # 60 bins -> bins_sub=64
    B = 128
    lid = rng.randint(0, 6, size=2500).astype(np.int32)
    gh8 = np.zeros((8, 2500), np.float32)
    gh8[0] = rng.randn(2500)
    gh8[1] = rng.rand(2500)
    gh8[2] = 1.0
    sl = np.array([0, 5, -1, 3], np.int32)
    gb8 = (gb.astype(np.int16) - 128).astype(np.int8)
    h_pl = hist_multileaf_masked(
        jnp.asarray(gb8), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="pallas",
        input_dtype=input_dtype, interpret=True, max_num_bin=60)
    h_x = hist_multileaf_masked(
        jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
        jnp.asarray(sl), num_bins_padded=B, backend="xla",
        input_dtype=input_dtype)
    tol = 2e-2 if input_dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=0, atol=tol)
    np.testing.assert_array_equal(np.asarray(h_pl)[:, :, 2],
                                  np.asarray(h_x)[:, :, 2])
    assert np.asarray(h_pl)[2].max() == 0.0


def test_hist_masked_int8_padded_rows_and_top_leaf():
    """The quantized kernel over a padded row stream: padded rows carry
    lid sentinel -2 and all-zero ghq rows, empty slots carry -1, and
    neither may leak into a live slot.  C > chunk (real padding), a
    slot holding the top leaf id 254, empty -1 slots."""
    rng, gb = _rand(9000, 4, 200, seed=31)      # 9000 > 8192 chunk -> pad
    B = 256
    lid = rng.randint(0, 255, size=9000).astype(np.int32)
    lid[:50] = 254                               # leaf 254 is live
    gh8 = np.zeros((8, 9000), np.float32)
    gh8[0] = rng.randn(9000)
    gh8[1] = rng.rand(9000)
    gh8[2] = 1.0
    sl = np.array([254, -1, 7, 0], np.int32)
    args = (jnp.asarray(gb), jnp.asarray(lid), jnp.asarray(gh8),
            jnp.asarray(sl))
    h_n = hist_multileaf_masked(*args, num_bins_padded=B, backend="pallas",
                                input_dtype="int8", interpret=True)
    h_x = hist_multileaf_masked(*args, num_bins_padded=B, backend="xla",
                                input_dtype="int8")
    np.testing.assert_allclose(np.asarray(h_n), np.asarray(h_x),
                               rtol=0, atol=1e-4)
    # leaf-254 slot counts exactly its rows (aliased pad rows add zero)
    assert np.asarray(h_n)[0, 0, 2].sum() == (lid == 254).sum()
    assert np.asarray(h_n)[1].max() == 0.0


def _pallas_calls(fn, *args):
    """pallas_call equations in the jaxpr of fn(*args), nested ones too."""
    import jax
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("operands", ["int8", "bfloat16"])
@pytest.mark.parametrize("itemsize,Fp,R,nbin,calls", [(1, 32, 13, 255, 1),
                                                      (4, 32, 28, 255, 2),
                                                      (4, 16, 16, 255, 1),
                                                      (4, 24, 5, 255, 1),
                                                      (4, 32, 27, 63, 2)],
                         ids=["int8_13_of_32", "int32_28_of_32",
                              "int32_16_of_16", "int32_5_of_24",
                              "int32_27_of_32_packed"])
def test_hist_masked_skips_the_stores_padded_columns(itemsize, Fp, R, nbin,
                                                     calls, operands, K):
    """A store padded to the feature group, as the rounds learner lays it
    out: `real_columns` leading columns hold data, the rest bin 0 (-128
    stored as int8).  The launch that histograms only the real columns
    gives them the histograms of the launch over all columns, bit for
    bit, and exact zeros for the padding, on the Pallas interpreter and
    on XLA; over two row chunks.  One 32-column block (13 real) is one
    launch; four 8-column blocks whose last holds 4 real columns are two,
    the full blocks' and the tail's; a store with no padding is the one
    launch it was; blocks of padding alone (a store padded to the
    scatter's slices, say) launch nothing.  With 63 bins two features
    share a packed column of 128 bins, and the tail's last real column
    holds one of them."""
    from lightgbm_tpu.ops.histogram import store_alignment
    B = 128 if nbin <= 64 else 256
    col, chunk = store_alignment(itemsize, B, operands, nbin)
    assert Fp % col == 0
    C = 2 * chunk
    rng = np.random.RandomState(40 + R + K)
    gb = np.zeros((Fp, C), np.int32)
    gb[:R] = rng.randint(0, nbin, size=(R, C))
    if itemsize == 1:
        gb = (gb - 128).astype(np.int8)
    lid = rng.randint(0, 2 * K + 1, size=C).astype(np.int32)
    gh8 = np.zeros((8, C), np.float32)
    gh8[2] = rng.rand(C) < 0.9
    gh8[0] = rng.randn(C) * gh8[2]
    gh8[1] = rng.rand(C) * gh8[2]
    sl = rng.permutation(2 * K + 1)[:K].astype(np.int32)
    args = tuple(jnp.asarray(a) for a in (gb, lid, gh8, sl))
    kw = dict(num_bins_padded=B, input_dtype=operands, max_num_bin=nbin)

    def hist(backend, **more):
        return np.asarray(hist_multileaf_masked(
            *args, backend=backend, interpret=backend == "pallas",
            **kw, **more))
    for backend in ("pallas", "xla"):
        every = hist(backend)
        real = hist(backend, real_columns=R)
        assert real.shape == every.shape == (K, Fp, 3, B)
        np.testing.assert_array_equal(real[:, :R], every[:, :R])
        assert not real[:, R:].any()
        if R < Fp:           # the padding's bin 0 held every masked row
            assert every[:, R:, 2].any()
        assert real[:, :R, 2].sum() > 0

    def launch(*a):
        return hist_multileaf_masked(*a, backend="pallas", interpret=True,
                                     real_columns=R, **kw)
    assert _pallas_calls(launch, *args) == calls


def test_hist_pallas_bf16_onehot():
    """Gather-fed kernels with bf16 operands (_simple_onehot): must
    match the XLA bf16 formulation."""
    rng, gb = _rand(3001, 9, 255, seed=33)
    vals8 = np.zeros((8, 3001), np.float32)
    vals8[0] = rng.randn(3001)
    vals8[1] = rng.rand(3001)
    vals8[2] = 1.0
    h_pl = hist_pallas(jnp.asarray(gb), jnp.asarray(vals8),
                       num_bins_padded=256, input_dtype="bfloat16",
                       interpret=True)
    h_x = hist_xla(jnp.asarray(gb.T), jnp.asarray(vals8[:3]),
                   num_bins_padded=256, input_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(h_pl), np.asarray(h_x),
                               rtol=2e-2, atol=2e-2)
    m = rng.randn(16, 3001).astype(np.float32)
    h_ml = hist_pallas_multileaf(jnp.asarray(gb), jnp.asarray(m),
                                 num_bins_padded=256,
                                 input_dtype="bfloat16", interpret=True)
    h_mlx = hist_multileaf_xla(jnp.asarray(gb), jnp.asarray(m),
                               num_bins_padded=256, input_dtype="bfloat16")
    np.testing.assert_allclose(np.asarray(h_ml), np.asarray(h_mlx),
                               rtol=2e-2, atol=2e-2)


def test_gather_chunk_cap_respects_vmem_budget():
    """ADVICE round 5: the 512-row floor let padded B >= 2048 exceed the
    stated 4 MB budget; the floor is now one 128-lane tile."""
    from lightgbm_tpu.ops.histogram import _gather_chunk_cap
    for B in (128, 256, 1024, 2048, 4096):
        ck = _gather_chunk_cap(B, 4)
        assert ck % 128 == 0 and ck >= 128
        if ck > 128:          # above the floor the budget must hold
            assert ck * B * 4 <= int(4e6)


@pytest.mark.parametrize("bins_itemsize,dtype,want", [
    (4, "int8", 8192), (4, "bfloat16", 8192), (4, "float32", 2048),
    (1, "int8", 2048), (1, "float32", 1024)])
def test_masked_chunk_is_the_compile_validated_table(bins_itemsize, dtype,
                                                     want):
    """The masked kernels' row chunk comes from the table that
    tests/test_tpu_compile.py validates against the TPU compiler, and
    shrinks in proportion for value-row blocks taller than K=84's."""
    from lightgbm_tpu.ops.histogram import _masked_chunk
    for Mp in (8, 24, 96, 256):
        assert _masked_chunk(Mp, bins_itemsize, dtype) == want
    tall = _masked_chunk(512, bins_itemsize, dtype)
    assert tall == want // 2 and tall % 128 == 0
