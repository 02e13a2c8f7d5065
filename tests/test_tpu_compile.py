"""The main-path kernels, compiled for a TPU v5e that is described and not
attached (on-chip-measurement guide, section 2): what Mosaic or XLA:TPU
refuses — a layout it cannot infer, an op the v5e VPU lacks, more scoped
VMEM than 16 MB — fails here, on the CPU tier, before any chip run.

Every shape is the north-star's or a benchmark cell's: F=28 or Epsilon's
2000-wide store, B=256 bins or the packed 63-bin layout, the rounds
learner's K tiers (1 = root, 8, 32, 84), float32 / bfloat16 / int8
operands, int32 and int8-stored bins.  Nothing runs; a compile that passes
is not a chip run.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 1 << 20        # rows; the kernels' grids scale with it, nothing else
B = 256


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return s


def compile_for_chip(fn, *args, kernel=True):
    compiled = jax.jit(fn).lower(*args).compile()
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    return compiled


def masked(dtype, bins=B, max_num_bin=255):
    """hist_multileaf_masked as learner/rounds.py calls it."""
    from lightgbm_tpu.ops.histogram import hist_multileaf_masked

    def f(gb, lid, gh, sl):
        return hist_multileaf_masked(gb, lid, gh, sl, num_bins_padded=bins,
                                     backend="pallas", input_dtype=dtype,
                                     max_num_bin=max_num_bin)
    return f


def masked_args(shape, F, bins_dtype, K, n=N):
    return (shape((F, n), bins_dtype), shape((n,), jnp.int32),
            shape((8, n), jnp.float32), shape((K,), jnp.int32))


@pytest.mark.parametrize("K", [1, 8, 32, 84])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_masked_histogram_every_tier(shape, dtype, K):
    """int32 bins.  float32 is the Config default; K=84 float32 once asked 32.8 MB of
    VMEM, and the tiers are not monotone (K=8 can need more than K=84)."""
    compile_for_chip(masked(dtype), *masked_args(shape, 28, jnp.int32, K))


@pytest.mark.parametrize("dtype,F,bins_dtype,K,bins,max_num_bin", [
    ("bfloat16", 28, jnp.int32, 84, B, 255),
    ("float32", 32, jnp.int8, 84, B, 255), ("int8", 32, jnp.int8, 84, B, 255),
    ("int8", 2000, jnp.int32, 84, B, 255), ("int8", 2000, jnp.int32, 1, B, 255),
    ("int8", 2000, jnp.int32, 84, 128, 63),
    ("int8", 2000, jnp.int32, 1, 128, 63),
    ("int8", 2000, jnp.int32, 8, B, 255), ("int8", 2000, jnp.int32, 32, B, 255),
    ("int8", 2000, jnp.int32, 8, 128, 63),
    ("int8", 2000, jnp.int32, 32, 128, 63)])
def test_masked_histogram_other_layouts(shape, dtype, F, bins_dtype, K, bins,
                                        max_num_bin):
    """bf16 operands and int8-stored bins (G=32 feature blocks, 128-lane
    bin windows) at the full K=84 pass; and every launch of the two
    Epsilon cells: the root (K=1) and the stream's slot tiers
    (K = 8, 32, 84) over a 2000-wide store, at 256 bins and at
    max_bin=63 (two 64-bin columns packed into one 128-lane block)."""
    n = N if F < 100 else N // 8
    compile_for_chip(masked(dtype, bins, max_num_bin),
                     *masked_args(shape, F, bins_dtype, K, n))


@pytest.mark.parametrize("F,bins_dtype", [(28, jnp.int32), (32, jnp.int8),
                                          (2000, jnp.int32)])
def test_fused_partition(shape, F, bins_dtype):
    """partition_rows through the fused VMEM kernel (256 slots)."""
    from lightgbm_tpu.ops.partition import partition_rows

    def f(bins, lid, tbl):
        return partition_rows(bins, lid, tbl, num_slots=256,
                              backend="pallas", num_bins_padded=B)
    n = N if F < 100 else N // 8
    compile_for_chip(f, shape((F, n), bins_dtype), shape((n,), jnp.int32),
                     shape((7, 256), jnp.float32))


def store_copies(compiled, cells: int):
    """Instructions of an optimised program that write an s32 buffer of
    `cells` elements or more — a padded or relaid copy of the bin store
    — as (name, opcode) pairs.  Views (bitcast, get-tuple-element) and
    the parameter itself write nothing."""
    import math
    import re
    out = []
    for m in re.finditer(r"^\s*(?:ROOT )?(%\S+) = s32\[([\d,]+)\]\S* ([\w-]+)\(",
                         compiled.as_text(), re.M):
        name, dims, opcode = m.groups()
        if (math.prod(int(d) for d in dims.split(",")) >= cells
                and opcode not in ("parameter", "bitcast",
                                   "get-tuple-element")):
            out.append((name, opcode))
    return out


def test_higgs_build_program(shape):
    """The whole build step of the benchmark cell `higgs.full`, as
    RoundsTreeLearner jits it on the chip: 10,502,144 rows by 32 columns
    of the int32 store — 10.5M by 28 as the learner lays them out on the
    host, to the histogram kernel's row chunk and feature group — 255
    leaves, int8 operands, the per-leaf histogram cache, the Pallas
    partition, and every launch over all the rows at the slot tier
    (8 / 32 / 84) that holds the round's leaves.  One program of four
    slot chunks a round, whose arguments (1.5 GB) and temporaries
    (0.55 GB) are what the cell holds.

    The store is an argument and nothing else: no instruction writes a
    store-sized buffer (each wrapper's pad was one, in every round and
    launch), and the temporaries stand 1.86 GB under the 2,405,195,776 B
    of the program that padded (PR 33) — both padded copies, 1.34 GB
    each as tiled, less what had shared their space."""
    import functools
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.learner.common import make_split_kw
    from lightgbm_tpu.learner.rounds import build_tree_rounds
    from lightgbm_tpu.ops.histogram import store_alignment
    col, row = store_alignment(4, B, "int8", 255)
    F, n = 28 + (-28) % col, 10_500_000 + (-10_500_000) % row
    assert (F, n) == (32, 10_502_144)
    cfg = config_from_params({"objective": "binary", "num_leaves": 255,
                              "min_data_in_leaf": 1,
                              "min_sum_hessian_in_leaf": 100.0,
                              "histogram_dtype": "int8", "verbose": -1})
    build = functools.partial(
        build_tree_rounds, num_leaves=255, num_bins_padded=B,
        max_num_bin=255, split_kw=make_split_kw(cfg), max_depth=-1,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        backend="pallas", input_dtype=cfg.histogram_dtype,
        cache_parent_hist=True)
    compiled = compile_for_chip(
        build, shape((F, n), jnp.int32), shape((n,), jnp.float32),
        shape((n,), jnp.float32), shape((n,), jnp.float32),
        shape((F,), jnp.int32), shape((F,), jnp.bool_), shape((F,), jnp.bool_))
    assert store_copies(compiled, 28 * 10_500_000) == []
    mem = compiled.memory_analysis()
    # read: 547,270,144 B
    assert mem.temp_size_in_bytes < 2_405_195_776 - 1.34e9 - 4e8, (
        mem.temp_size_in_bytes)
    held = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert held < 2.2e9, held      # an eighth of the chip


def test_allstate_build_program(shape):
    """The whole build step of the benchmark cell `allstate.full`, as
    RoundsTreeLearner jits it on the chip: 4,228 one-hot and numeric
    columns that EFB packs into under 48 store columns (the planner's plan
    of 60,000 generated rows at the full width; the cell's own has the
    same shape), 12,184,290 rows laid out as `[48, 12189696]` int32, 255
    leaves, int8 operands, the per-leaf histogram cache of STORE columns —
    and split search over a chunk's `[84, 48, 3, 256]` store histogram as
    it stands, through the plan's per-cell tables: no array with the F
    original features for an axis is built anywhere.  The store is an
    argument and nothing else (2.34 GB); the temporaries are what a dense
    store of 48 columns has — the per-leaf cache `[255, 48, 3, 256]`, a
    chunk's histograms, the row vectors: under 1.5 GB, where the gather to
    `[84, F, 3, 256]` (1.09 GB each, several alive at once) read 5.05 GB
    (PERF.md section 4)."""
    import functools
    from benchmark.generators import allstate
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset
    from lightgbm_tpu.learner.common import make_split_kw
    from lightgbm_tpu.learner.rounds import build_tree_rounds
    from lightgbm_tpu.ops.histogram import store_alignment
    import scipy.sparse as sps
    cfg = config_from_params({"objective": "binary", "num_leaves": 255,
                              "min_data_in_leaf": 1, "max_bin": 255,
                              "min_sum_hessian_in_leaf": 100.0,
                              "histogram_dtype": "int8", "verbose": -1})
    X, y = allstate.make(60_000, 4228, (0, 0))
    ds = Dataset.from_csc(sps.csr_matrix(X), y, cfg)
    plan, Fo = ds.bundle_plan, ds.num_features
    assert plan is not None and plan.num_columns <= 48 and Fo > 4000
    col, row = store_alignment(4, B, "int8", 255)
    C = plan.num_columns + (-plan.num_columns) % col
    n = 12_184_290 + (-12_184_290) % row
    assert (C, n) == (48, 12_189_696)
    cells = ds.search_tables(B, C)
    assert cells.feat.shape == (C, B)
    assert (cells.feat >= 0).sum() == (ds.num_bins - 1).sum()
    build = functools.partial(
        build_tree_rounds, ftbl=plan.feat_table(), unb=cells,
        num_leaves=255, num_bins_padded=B,
        max_num_bin=255, split_kw=make_split_kw(cfg), max_depth=-1,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        backend="pallas", input_dtype=cfg.histogram_dtype,
        cache_parent_hist=True)
    compiled = compile_for_chip(
        build, shape((C, n), jnp.int32), shape((n,), jnp.float32),
        shape((n,), jnp.float32), shape((n,), jnp.float32),
        shape((Fo,), jnp.int32), shape((Fo,), jnp.bool_),
        shape((Fo,), jnp.bool_))
    assert store_copies(compiled, plan.num_columns * 12_184_290) == []
    text = compiled.as_text()
    assert f"[84,{Fo}," not in text and f"[{Fo * B}," not in text
    assert "f32[84,48,3,256]" in text or "f32[84,48,256,3]" in text
    mem = compiled.memory_analysis()
    assert 2.3e9 < mem.argument_size_in_bytes < 2.6e9, (
        mem.argument_size_in_bytes)
    # read: see PERF.md section 4
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    held = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert held < 4.5e9, held
    print("allstate build: temp", mem.temp_size_in_bytes,
          "args", mem.argument_size_in_bytes)


def test_criteo_tb_build_program_on_four_chips(topo):
    """The whole build step of the benchmark cell `criteo_tb.data4` as
    RoundsTreeLearner jits it on a four-chip host: under shard_map over
    a described v5e:2x2, each chip a shard `[72, 13,500,416]` of the
    int32 store — 54M rows by 67 columns as the learner lays them out
    (columns to the feature group, which the four-way scatter of 18
    columns keeps; each shard's rows to the row chunk) — 255 leaves,
    int8 operands, the psum_scatter exchange and its record all_gather.

    The prediction on memory, checked without a chip: a shard is
    3.89 GB of arguments, no instruction writes a store-sized buffer,
    and the temporaries stay under 1.2 GB (read: see the assert)."""
    import functools
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.learner.common import make_split_kw
    from lightgbm_tpu.learner.fused import TreeArrays
    from lightgbm_tpu.learner.rounds import build_tree_rounds
    from lightgbm_tpu.ops.histogram import store_alignment
    from lightgbm_tpu.sharded.mesh import pad_cols_to_ndev
    col, row = store_alignment(4, B, "int8", 255)
    shard = -(-54_000_000 // 4)
    shard += (-shard) % row
    F = pad_cols_to_ndev(67 + (-67) % col, 4, align=col)
    assert (F, shard) == (72, 13_500_416)
    n = 4 * shard
    cfg = config_from_params({"objective": "binary", "num_leaves": 255,
                              "min_data_in_leaf": 1,
                              "min_sum_hessian_in_leaf": 100.0,
                              "histogram_dtype": "int8", "verbose": -1})
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "feature"))
    build = functools.partial(
        build_tree_rounds, num_leaves=255, num_bins_padded=B,
        max_num_bin=255, split_kw=make_split_kw(cfg), max_depth=-1,
        min_data_in_leaf=cfg.min_data_in_leaf,
        min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
        backend="pallas", input_dtype=cfg.histogram_dtype,
        cache_parent_hist=True, hist_exchange="psum_scatter",
        num_devices=4, data_axis="data")
    out_specs = (jax.tree_util.tree_map(lambda _: P(), TreeArrays(
        *[0] * len(TreeArrays._fields))), P("data"), P())
    step = jax.shard_map(
        build, mesh=mesh, out_specs=out_specs, check_vma=False,
        in_specs=(P(None, "data"), P("data"), P("data"), P("data"),
                  P(), P(), P()))

    def s(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    compiled = compile_for_chip(
        step, s((F, n), jnp.int32, P(None, "data")),
        s((n,), jnp.float32, P("data")), s((n,), jnp.float32, P("data")),
        s((n,), jnp.float32, P("data")), s((F,), jnp.int32, P()),
        s((F,), jnp.bool_, P()), s((F,), jnp.bool_, P()))
    text = compiled.as_text()
    assert "reduce-scatter" in text or "all-reduce" in text
    assert "all-gather" in text
    assert store_copies(compiled, 67 * 13_500_000) == []
    mem = compiled.memory_analysis()           # per device
    assert 3.88e9 < mem.argument_size_in_bytes < 4.2e9, (
        mem.argument_size_in_bytes)
    # read: see PERF.md section 4
    assert mem.temp_size_in_bytes < 1.2e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("F,n,bins,max_num_bin,copies", [
    (32, 10_502_144, B, 255, False), (2000, 401_408, B, 255, False),
    (2000, 401_408, 128, 63, False),
    # the stores as the datasets have them: each wrapper pads
    (28, 10_500_000, B, 255, True), (2000, 400_000, B, 255, True)])
def test_a_round_copies_no_aligned_store(shape, F, n, bins, max_num_bin,
                                         copies):
    """One round's partition and one K = 8 launch over a cell's store:
    laid out to store_alignment's multiples, both kernels read the
    argument through bitcasts and the program's temporaries are the
    launch's operands (a few [8, n] rows); as the dataset has it, each
    wrapper writes a padded copy first."""
    from lightgbm_tpu.ops.partition import partition_rows

    def f(gb, lid, gh, sl, tbl):
        lid2 = partition_rows(gb, lid, tbl, num_slots=256,
                              backend="pallas", num_bins_padded=bins)
        return masked("int8", bins, max_num_bin)(gb, lid2, gh, sl)
    compiled = compile_for_chip(
        f, *masked_args(shape, F, jnp.int32, 8, n),
        shape((7, 256), jnp.float32))
    written = store_copies(compiled, F * n)
    assert bool(written) == copies, written
    temp = compiled.memory_analysis().temp_size_in_bytes
    if copies:
        assert temp > 4 * F * n, temp
    else:
        assert temp < 0.5 * 4 * F * n, temp


def test_table_lookup_kernel(shape):
    from lightgbm_tpu.ops.lookup import _lookup_pallas
    compile_for_chip(_lookup_pallas, shape((7, 256), jnp.float32),
                     shape((N,), jnp.int32))


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_sparse_histogram(shape, dtype):
    """hist_sparse_pallas over slot-segmented entry streams (CTR width:
    8-column windows x 256 bins = 2048 one-hot lanes)."""
    from lightgbm_tpu.ops.histogram import hist_sparse_pallas
    nwin, ew, cols, rows = 256, 4096, 2048, 1 << 19

    def f(e_row, e_flat, e_valid, slot_col, zero_bin, lid, gh, sl):
        return hist_sparse_pallas(e_row, e_flat, e_valid, slot_col,
                                  zero_bin, lid, gh, sl,
                                  num_columns_padded=cols,
                                  num_bins_padded=B, input_dtype=dtype)
    compile_for_chip(
        f, shape((nwin, ew), jnp.int32), shape((nwin, ew), jnp.int32),
        shape((nwin, ew), jnp.float32), shape((nwin * 8,), jnp.int32),
        shape((cols,), jnp.int32), shape((rows,), jnp.int32),
        shape((8, rows), jnp.float32), shape((84,), jnp.int32))


def test_binned_traversal_int16_record(shape):
    """The serving request path on the chip: XLA traversal of a 500-tree,
    255-leaf ensemble whose node record is narrowed to int16 (TPU only,
    ops/predict._maybe_narrow) over a uint8 quantized request buffer."""
    from lightgbm_tpu.ops.predict import (EnsembleMeta, EnsembleStack, _LANES,
                                          _predict_ensemble_quantized_soa)
    stack = EnsembleStack(nodes=shape((500, 254, _LANES), jnp.int16),
                          leaf_value=shape((500, 255), jnp.float32),
                          root=shape((500,), jnp.int32),
                          class_id=shape((500,), jnp.int32))
    meta = EnsembleMeta(depth=16, num_class=1, any_cat=False)

    def f(stack, xb):
        return _predict_ensemble_quantized_soa(stack, xb, meta=meta)
    compile_for_chip(f, stack, shape((4096, 28), jnp.uint8), kernel=False)


def test_score_update_with_row_sharded_leaf_ids(topo, monkeypatch):
    """The data-parallel learner returns leaf ids sharded over its mesh and
    the train score lies in the same row layout, so the update is one
    shard_map over that mesh: each chip runs the Mosaic lookup (which XLA
    cannot partition under plain jit: "cannot be automatically
    partitioned" is what stopped the first four-chip run) over its own
    13.5M ids and adds to its own shard, with no collective.  Compiled at
    `criteo_tb.data4`'s shapes, beside the row-local programs of an
    iteration on the same arrays: the gradients (padded rows masked) and
    the [K, N] view that the closing fetch reads.  Where the ids are
    sharded and the score is not (a learner that reports no layout), the
    XLA lookup still compiles.  The backend question is steered here; the
    program has no option for it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.boosting.score_updater import (
        _add_leaf_to_row_jit, _add_leaf_to_row_sharded)
    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.jaxutil import RowLayout, slice_rows_dev
    from lightgbm_tpu.objectives import create_objective
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    n, n_pad = 54_000_000, 54_001_664
    score = jax.ShapeDtypeStruct((1, n_pad), jnp.float32,
                                 sharding=NamedSharding(mesh, P(None, "data")))
    ids = jax.ShapeDtypeStruct((n_pad,), jnp.int32, sharding=rows)
    values = jax.ShapeDtypeStruct((255,), jnp.float32, sharding=rep)

    def no_collective(text):
        return not any(op in text for op in (
            "all-gather", "all-reduce", "all-to-all", "collective-permute"))

    update = _add_leaf_to_row_sharded(rows, 0).lower(
        score, ids, values).compile()
    text = update.as_text()
    assert "tpu_custom_call" in text and no_collective(text)
    assert "add_leaf_to_row_sharded" in text.split("\n", 1)[0]
    # a shard of the score in, a shard out, and the lookup's [8, rows]
    # block between them: nothing the size of all 54M rows on a chip
    assert update.memory_analysis().temp_size_in_bytes < 8 * 4 * n_pad // 4 * 2

    obj = create_objective(config_from_params({"objective": "binary"}))
    obj.num_data, obj.layout = n, RowLayout(n, n_pad, rows)
    obj.weights = None

    def f(score, label):
        is_p = label[None, :] > 0
        return jnp.where(is_p, score, -score), jnp.abs(score)
    grads = obj._jit_gradients(f).lower(
        score, jax.ShapeDtypeStruct((n_pad,), jnp.float32,
                                    sharding=rows)).compile()
    assert no_collective(grads.as_text())

    slice_rows_dev.lower(score, n=n).compile()     # the [K, N] view

    flat = (jax.ShapeDtypeStruct((1, n), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=NamedSharding(
                mesh, P("data"))), values)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _add_leaf_to_row_jit.lower(*flat, tree_id=0, spmd=False)
    _add_leaf_to_row_jit.lower(*flat, tree_id=0, spmd=True).compile()
