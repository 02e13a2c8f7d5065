"""Compile the learners' whole jitted build-tree step for a TPU v5e that is
described and not attached (on-chip-measurement guide, section 2) — what
to run before a chip call whose compile is long or spans four chips.

tests/test_tpu_compile.py covers the kernels; a whole step cannot be a
test, because the learners ask `jax.default_backend()` and place their
own arrays.  So this script steers them from outside: it answers "tpu"
for the backend, reports a v5e's memory limit, and lets `device_put` to
a described mesh pass through; then it lowers `learner._build` with
shapes that carry described-device shardings.  Nothing runs; a compile
that passes is not a chip run.

    JAX_PLATFORMS=cpu python scripts/compile_step.py [substring ...]

prints one line per step: seconds, the number of `tpu_custom_call`s, the
compiler's memory analysis per device and the collectives it put in.
"""
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ROWS = (1_048_576, 10_500_000)
V5E_BYTES_LIMIT = 15.75e9
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
          "verbose": -1}


def main(want):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    # steer the code that asks the backend (see the module docstring)
    jax.default_backend = lambda: "tpu"
    from lightgbm_tpu.learner import common, fused, rounds
    from lightgbm_tpu.ops.histogram import store_alignment
    common.device_bytes_limit = rounds.device_bytes_limit = \
        lambda: V5E_BYTES_LIMIT
    real_put = jax.device_put

    def put(x, s=None, **kw):
        described = (isinstance(s, NamedSharding)
                     and s.mesh.devices.flat[0].platform == "tpu")
        return x if described else real_put(x, s, **kw)
    jax.device_put = put

    from lightgbm_tpu.config import config_from_params
    from lightgbm_tpu.dataset import Dataset

    rng = np.random.RandomState(0)
    X = rng.randn(4096, 28)
    y = (X[:, 0] > 0).astype(np.float64)

    def lower(name, learner, n, rows_sh, rep_sh, bins_sh, shards=1):
        def s(dims, dtype, sh):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=sh)
        if isinstance(learner, rounds.RoundsTreeLearner):
            # n rows as the learner lays them out: each shard's to the
            # histogram kernel's row chunk (the 4096 rows it was built
            # on are one block and stay as they are)
            _, row = store_alignment(learner.bins_dev.dtype.itemsize,
                                     learner.B, learner.config.histogram_dtype,
                                     int(learner.dataset.max_num_bin))
            n = shards * row * math.ceil(math.ceil(n / shards) / row)
        args = (s((learner.bins_dev.shape[0], n), learner.bins_dev.dtype,
                  bins_sh),
                s((n,), jnp.float32, rows_sh), s((n,), jnp.float32, rows_sh),
                s((n,), jnp.float32, rows_sh),
                s(learner.num_bins_dev.shape, jnp.int32, rep_sh),
                s(learner.is_cat_dev.shape, jnp.bool_, rep_sh),
                s(learner._base_fmask.shape, jnp.bool_, rep_sh))
        t0 = time.time()
        compiled = learner._build.lower(*args).compile()
        text = compiled.as_text()
        ma = compiled.memory_analysis()
        print(f"{name} ({n} rows): {time.time() - t0:.0f}s "
              f"tpu_custom_call={text.count('tpu_custom_call')} "
              f"temp={ma.temp_size_in_bytes / 1e9:.2f}GB "
              f"args={ma.argument_size_in_bytes / 1e9:.2f}GB "
              f"collectives={sorted(c for c in ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all') if c in text)}",
              flush=True)

    for dtype in ("float32", "int8", "bfloat16"):
        cfg = config_from_params(dict(PARAMS, histogram_dtype=dtype))
        ds = Dataset(X, y, config=cfg)
        for n in ROWS:
            name = f"rounds one-chip {dtype} N={n}"
            if want(name):
                lower(name, rounds.RoundsTreeLearner(ds, cfg, None), n,
                      one, one, one)
        name = f"rounds four-chip {dtype} N={ROWS[-1]}"
        if want(name):
            mesh = Mesh(np.asarray(topo.devices).reshape(4, 1),
                        ("data", "feature"))
            lr = rounds.RoundsTreeLearner(ds, cfg, mesh)
            print(f"  hist_exchange={lr.hist_exchange}")
            lower(name, lr, ROWS[-1], NamedSharding(mesh, P("data")),
                  NamedSharding(mesh, P()),
                  NamedSharding(mesh, P(None, "data")), shards=4)
    name = f"fused one-chip float32 N={ROWS[0]}"
    if want(name):
        cfg = config_from_params(PARAMS)
        lower(name, fused.FusedTreeLearner(Dataset(X, y, config=cfg), cfg,
                                           None), ROWS[0], one, one, one)


if __name__ == "__main__":
    picks = sys.argv[1:]
    main(lambda name: not picks or any(p in name for p in picks))
