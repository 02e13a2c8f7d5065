"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Tests never need real TPU hardware; distributed learners are exercised on
XLA's host-platform device simulator (SURVEY.md §4: the analog of the
reference's CPU-OpenCL fake-GPU CI trick, .travis.yml:15-23).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# persistent compilation cache (JAX_COMPILATION_CACHE_DIR if set, else
# <checkout>/.jax_cache): the suite is compile-dominated on a small
# host (the tree builders are large XLA programs), and the programs are
# identical run to run — cache them across processes/runs
from lightgbm_tpu.jaxutil import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import numpy as np
import pytest

REF_EXAMPLES = "/root/reference/examples"

# build the native loader once if a toolchain exists, so its tests run
# instead of skipping (src/native/loader.cpp; ~2 s compile)
_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_natlib = os.path.join(_root, "lightgbm_tpu", "lib", "liblgbt_native.so")
_nat_failed = _natlib + ".build_failed"
if not os.path.exists(_natlib) and not os.path.exists(_nat_failed):
    import shutil
    import subprocess
    if shutil.which("g++"):
        _r = subprocess.run(["bash", os.path.join(_root, "scripts",
                                                  "build_native.sh")],
                            capture_output=True, text=True, timeout=120,
                            check=False)
        if _r.returncode != 0:
            # cache the failure so every session doesn't retry; native
            # tests will skip, and the marker explains why
            os.makedirs(os.path.dirname(_nat_failed), exist_ok=True)
            with open(_nat_failed, "w") as _f:
                _f.write(_r.stderr[-4000:])
            print(f"[conftest] native build failed; see {_nat_failed}")


@pytest.fixture(scope="session")
def binary_example():
    from lightgbm_tpu.dataset import parse_text_file
    X, y, _ = parse_text_file(f"{REF_EXAMPLES}/binary_classification/binary.train")
    Xt, yt, _ = parse_text_file(f"{REF_EXAMPLES}/binary_classification/binary.test")
    return X, y, Xt, yt


@pytest.fixture(scope="session")
def regression_example():
    from lightgbm_tpu.dataset import parse_text_file
    X, y, _ = parse_text_file(f"{REF_EXAMPLES}/regression/regression.train")
    Xt, yt, _ = parse_text_file(f"{REF_EXAMPLES}/regression/regression.test")
    return X, y, Xt, yt


@pytest.fixture(scope="session")
def multiclass_example():
    from lightgbm_tpu.dataset import parse_text_file
    X, y, _ = parse_text_file(
        f"{REF_EXAMPLES}/multiclass_classification/multiclass.train")
    Xt, yt, _ = parse_text_file(
        f"{REF_EXAMPLES}/multiclass_classification/multiclass.test")
    return X, y, Xt, yt


@pytest.fixture(scope="session")
def rank_example():
    from lightgbm_tpu.dataset import parse_text_file
    import numpy as np
    X, y, _ = parse_text_file(f"{REF_EXAMPLES}/lambdarank/rank.train")
    Xt, yt, _ = parse_text_file(f"{REF_EXAMPLES}/lambdarank/rank.test")
    q = np.loadtxt(f"{REF_EXAMPLES}/lambdarank/rank.train.query", dtype=np.int64)
    qt = np.loadtxt(f"{REF_EXAMPLES}/lambdarank/rank.test.query", dtype=np.int64)
    return X, y, q, Xt, yt, qt
