"""The bin store laid out once, at set-up, to the tiles of the two kernels
that read it in every round (learner/rounds.RoundsTreeLearner,
ops/histogram.store_alignment): at the returned multiples neither
`hist_multileaf_masked` nor `_partition_pallas` pads the store, the padded
rows and columns add exact zeros, and `tree/store_copy_rows` says whether
the layout engaged.

The learner picks the Pallas kernels on the chip only, so the parity cases
steer it here: `_kernel_backend` answers "pallas", every `pallas_call`
runs in interpret mode, and the row chunks are forced small (512 rows in
place of 8192), so that 20,011 and 9,001 rows are many blocks and a
multiple of none.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import profiling
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as RawDataset
from lightgbm_tpu.learner import rounds
from lightgbm_tpu.learner.fused import tree_arrays_to_host
from lightgbm_tpu.learner.rounds import RoundsTreeLearner
from lightgbm_tpu.learner.serial import SerialTreeLearner
from lightgbm_tpu.ops import histogram, partition
from lightgbm_tpu.ops.histogram import (_masked_layout,
                                        masked_store_copy_rows,
                                        store_alignment)
from lightgbm_tpu.ops.partition import (_partition_layout,
                                        partition_store_copy_rows)


# ---- (a) the helper at the benchmark cells' shapes --------------------------

@pytest.mark.parametrize("lanes,max_num_bin", [(256, 255), (128, 63)])
@pytest.mark.parametrize("operands", ["int8", "bfloat16", "float32"])
@pytest.mark.parametrize("bins_itemsize", [4, 1], ids=["int32", "int8"])
@pytest.mark.parametrize("F,N", [(28, 10_500_000), (2000, 400_000)],
                         ids=["higgs", "epsilon"])
def test_aligned_store_is_tiled_by_both_kernels(F, N, bins_itemsize,
                                                operands, lanes,
                                                max_num_bin):
    col, row = store_alignment(bins_itemsize, lanes, operands, max_num_bin)
    Fa, Na = F + (-F) % col, N + (-N) % row
    assert 0 <= Fa - F < col and 0 <= Na - N < row
    for K in (1, 8, 32, 84):
        lay = _masked_layout(Fa, Na, K, bins_itemsize, lanes, operands,
                             max_num_bin)
        assert (lay.Fg, lay.Cp) == (Fa, Na), (K, lay)
        assert Na % lay.Ck == 0
        assert masked_store_copy_rows(
            Fa, Na, K, bins_itemsize=bins_itemsize, num_bins_padded=lanes,
            backend="pallas", input_dtype=operands,
            max_num_bin=max_num_bin) == 0
    Fp, Ck, Cp = _partition_layout(Fa, Na, bins_itemsize, 256)
    assert (Fp, Cp) == (Fa, Na) and Na % Ck == 0 and Ck >= 512
    kw = dict(bins_itemsize=bins_itemsize, num_slots=256, backend="pallas",
              num_bins_padded=lanes)
    assert partition_store_copy_rows(Fa, Na, **kw) == 0
    # the store as the dataset has it is copied whole by both
    assert partition_store_copy_rows(F, N, **kw) == N
    assert masked_store_copy_rows(
        F, N, 8, bins_itemsize=bins_itemsize, num_bins_padded=lanes,
        backend="pallas", input_dtype=operands, max_num_bin=max_num_bin) == N


def test_the_cells_padded_shapes():
    """What the two configurations' stores become on the chip."""
    assert store_alignment(4, 256, "int8", 255) == (8, 8192)
    assert (28 + (-28) % 8, 10_500_000 + (-10_500_000) % 8192) == (
        32, 10_502_144)
    assert 400_000 + (-400_000) % 8192 == 401_408
    # the partition tiles them with the chunks it had: 8192 and 512
    assert _partition_layout(32, 10_502_144, 4, 256) == (32, 8192, 10_502_144)
    assert _partition_layout(2000, 401_408, 4, 256) == (2000, 512, 401_408)


def test_partition_chunk_divides_rows_or_falls_back():
    # a width whose VMEM ceiling (2560 rows) does not divide the rows:
    # the longest multiple of 128 under it that does
    Fp, Ck, Cp = _partition_layout(704, 11_000_832, 1, 256)
    assert (Fp, Cp) == (704, 11_000_832) and Ck == 1536
    # rows that no chunk of 512 or more divides: padded to the ceiling
    assert _partition_layout(32, 128 * 1009, 4, 256) == (
        32, 8192, 8192 * 16)
    # under one chunk: one block, as it stands
    assert _partition_layout(8, 5000, 4, 128) == (8, 5000, 5000)
    # the XLA path copies nothing
    assert partition_store_copy_rows(
        28, 20_011, bins_itemsize=4, num_slots=256, backend="xla",
        num_bins_padded=256) == 0


# ---- (c), (d) the learner over rows that are a multiple of no block ---------

CHUNK = 512


@pytest.fixture
def pallas_interpreted(monkeypatch):
    """The learner as on the chip, run by the Pallas interpreter, with
    both kernels' row chunks at CHUNK rows."""
    from jax.experimental import pallas as pl
    real_call = pl.pallas_call

    def interpreted(*args, **kw):
        kw["interpret"] = True
        return real_call(*args, **kw)

    jax.clear_caches()      # the chunk sizes are no part of a jit's key
    monkeypatch.setattr(pl, "pallas_call", interpreted)
    monkeypatch.setattr(rounds, "_kernel_backend", lambda: "pallas")
    monkeypatch.setattr(histogram, "_MASKED_CHUNK",
                        dict.fromkeys(histogram._MASKED_CHUNK, CHUNK))
    monkeypatch.setattr(partition, "_PARTITION_CHUNK", CHUNK)
    yield
    jax.clear_caches()


def _problem(n, f, max_bin, operands, seed):
    """Rows with +-1 gradients and constant hessians, so that every sum
    is exact in float32 and in the int8 operands alike."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.6 * X[:, 1] * X[:, 2] + 0.3 * X[:, 3] > 0
         ).astype(np.float64)
    cfg = config_from_params({
        "objective": "binary", "num_leaves": 13, "max_bin": max_bin,
        "min_data_in_leaf": n // 20, "histogram_dtype": operands,
        "verbose": -1})
    ds = RawDataset(X, y, config=cfg)
    g = jnp.asarray(np.where(y > 0, -1.0, 1.0).astype(np.float32))
    h = jnp.asarray(np.full(n, 0.5, np.float32))
    return ds, cfg, g, h, rng


def _build(learner, g, h, bag=None):
    """One tree from the learner's own program: (TreeArrays, leaf ids
    of the real rows, how far the tree/ counters moved).  The ids come
    back in the learner's row layout, padded rows at the tail."""
    before = profiling.counters("tree/")
    _, lid, arrs = learner.train_device(
        g, h, bag, None if bag is None else len(bag))
    after = profiling.counters("tree/")
    assert lid.shape == (learner.row_layout.num_rows,) == (learner.Np,)
    return arrs, np.asarray(lid)[: learner.N], {
        k: after[k] - before.get(k, 0.0) for k in after}


def _same_arrays(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def _splits(t):
    return sorted(zip(t.split_feature_inner[: t.num_leaves - 1],
                      t.threshold_in_bin[: t.num_leaves - 1]))


@pytest.mark.parametrize("bagged", [False, True], ids=["all_rows", "bagged"])
@pytest.mark.parametrize("n,f,max_bin,operands", [
    (20_011, 28, 63, "int8"), (9_001, 70, 255, "float32")],
    ids=["20011x28_b63_int8", "9001x70_b255_f32"])
def test_aligned_learner_builds_the_fallbacks_tree(pallas_interpreted,
                                                   monkeypatch, n, f,
                                                   max_bin, operands,
                                                   bagged):
    ds, cfg, g, h, rng = _problem(n, f, max_bin, operands, seed=n)
    bag = (jnp.asarray(np.sort(rng.choice(n, size=int(n * 0.6),
                                          replace=False)).astype(np.int32))
           if bagged else None)

    aligned = RoundsTreeLearner(ds, cfg, None)
    col, row = store_alignment(4, aligned.B, operands, max_bin)
    assert (col, row) == (8, CHUNK)
    assert aligned.Fpad == f + (-f) % 8 and aligned.Np == n + (-n) % CHUNK
    assert aligned.bins_dev.shape == (aligned.Fpad, aligned.Np)
    arrs_a, lid_a, moved_a = _build(aligned, g, h, bag)

    # the same learner over the store as the dataset has it: both
    # wrappers pad it in every round and launch
    monkeypatch.setattr(rounds, "store_alignment", lambda *a: (1, 1))
    plain = RoundsTreeLearner(ds, cfg, None)
    assert plain.bins_dev.shape == (f, n)
    arrs_p, lid_p, moved_p = _build(plain, g, h, bag)

    _same_arrays(arrs_a, arrs_p)
    assert lid_a.shape == lid_p.shape == (n,)
    np.testing.assert_array_equal(lid_a, lid_p)

    # (d) the counter: nothing copied from the aligned store; from the
    # plain one all rows in every round's partition and every launch
    assert moved_a["tree/store_copy_rows"] == 0
    rounds_, launches = moved_p["tree/rounds"], moved_p["tree/hist_passes"]
    assert rounds_ >= 3 and launches == rounds_ + 1
    assert moved_p["tree/store_copy_rows"] == (rounds_ + launches) * n
    for name in ("tree/rounds", "tree/hist_passes", "tree/hist_slots",
                 "tree/hist_live_slots"):
        assert moved_a[name] == moved_p[name], name
    # rows are counted as streamed: the padded store's
    assert moved_a["tree/hist_rows_touched"] == launches * aligned.Np
    assert moved_p["tree/hist_rows_touched"] == launches * n

    # and the tree is the exact leaf-wise learner's
    tr = tree_arrays_to_host(arrs_a, ds, cfg.num_leaves)
    ts, lid_s = SerialTreeLearner(ds, cfg).train(
        g, h, bag, None if bag is None else len(bag))
    assert 5 < tr.num_leaves == ts.num_leaves <= 13
    assert _splits(tr) == _splits(ts)
    np.testing.assert_array_equal(np.sort(tr.leaf_count[: tr.num_leaves]),
                                  np.sort(ts.leaf_count[: ts.num_leaves]))
    rows_in = np.arange(n) if bag is None else np.asarray(bag)
    assert tr.leaf_count[: tr.num_leaves].sum() == len(rows_in)
    np.testing.assert_allclose(
        np.asarray(tr.leaf_value)[lid_a[rows_in]],
        np.asarray(ts.leaf_value)[np.asarray(lid_s)[rows_in]], rtol=1e-6)


def test_aligned_learner_on_a_two_shard_mesh(pallas_interpreted, monkeypatch):
    """Each shard's rows are laid out to the row chunk on their own
    (Np = 2 x aligned(ceil(N / 2))), the scattered columns stay whole
    feature groups, and the tree is the one-device learner's."""
    from jax.sharding import Mesh
    n, f = 9_001, 28
    ds, cfg, g, h, _ = _problem(n, f, 63, "int8", seed=5)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))

    sharded = RoundsTreeLearner(ds, cfg, mesh)
    per_shard = -(-n // 2)
    assert sharded.Np == 2 * (per_shard + (-per_shard) % CHUNK)
    assert sharded.Fpad % 8 == 0
    arrs_s, lid_s, moved_s = _build(sharded, g, h)
    assert moved_s["tree/store_copy_rows"] == 0
    assert lid_s.shape == (n,)

    one, lid_1, _ = _build(RoundsTreeLearner(ds, cfg, None), g, h)
    monkeypatch.setattr(rounds, "store_alignment", lambda *a: (1, 1))
    arrs_p, lid_p, moved_p = _build(RoundsTreeLearner(ds, cfg, mesh), g, h)
    assert moved_p["tree/store_copy_rows"] == (
        moved_p["tree/rounds"] + moved_p["tree/hist_passes"]) * 2 * per_shard

    _same_arrays(arrs_s, arrs_p)           # bitwise: the same shard sums
    np.testing.assert_array_equal(lid_s, lid_p)
    # against one device the shards' sums meet in another order: the
    # same splits and rows, leaf values to rounding
    for name in ("split_feature", "threshold_bin", "left_child",
                 "right_child", "leaf_count", "num_leaves"):
        np.testing.assert_array_equal(np.asarray(getattr(arrs_s, name)),
                                      np.asarray(getattr(one, name)), name)
    np.testing.assert_allclose(np.asarray(arrs_s.leaf_value),
                               np.asarray(one.leaf_value), rtol=1e-6)
    np.testing.assert_array_equal(lid_s, lid_1)


def test_a_store_under_one_row_chunk_stays_as_it_is(pallas_interpreted):
    ds, cfg, g, h, _ = _problem(300, 5, 63, "int8", seed=1)
    lrn = RoundsTreeLearner(ds, cfg, None)
    assert lrn.Np == 300 and lrn.Fpad == 8
    _, lid, moved = _build(lrn, g, h)
    assert lid.shape == (300,)
    assert moved["tree/store_copy_rows"] == 0


@pytest.mark.parametrize("f", [28, 32], ids=["padded", "unpadded"])
def test_padded_columns_are_left_out_and_counted(pallas_interpreted,
                                                 monkeypatch, f):
    """The learner hands its launches the store's real column count: the
    tree is the one of launches over every column, bit for bit, and
    tree/hist_pad_columns is the columns left out once a launch, folded
    on the host from tree/hist_passes (registered at 0 where the store
    has no padded column)."""
    ds, cfg, g, h, _ = _problem(3_001, f, 63, "int8", seed=f)
    lrn = RoundsTreeLearner(ds, cfg, None)
    assert (lrn.Cstore, lrn.Fpad) == (f, 32)
    arrs, lid, moved = _build(lrn, g, h)
    assert moved["tree/hist_passes"] >= 3
    assert moved[profiling.HIST_PAD_COLUMNS] == (
        moved["tree/hist_passes"] * (32 - f))

    real = rounds.hist_multileaf_masked
    monkeypatch.setattr(rounds, "hist_multileaf_masked",
                        lambda *a, real_columns=0, **kw: real(*a, **kw))
    arrs_all, lid_all, _ = _build(RoundsTreeLearner(ds, cfg, None), g, h)
    _same_arrays(arrs, arrs_all)
    np.testing.assert_array_equal(lid, lid_all)


def test_off_the_chip_the_store_is_not_padded():
    """The XLA kernels tile nothing, so the CPU learner pads nothing."""
    ds, cfg, g, h, _ = _problem(9_001, 28, 63, "int8", seed=2)
    lrn = RoundsTreeLearner(ds, cfg, None)
    assert lrn.bins_dev.shape == (28, 9_001)
    _, _, moved = _build(lrn, g, h)
    assert moved["tree/store_copy_rows"] == 0
    assert moved["tree/hist_rows_touched"] == moved["tree/hist_passes"] * 9_001
