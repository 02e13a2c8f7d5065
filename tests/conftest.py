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


# ---- the example datasets ----------------------------------------------------
# The four fixtures below used to read the reference checkout's
# `examples/` directory, a mount the test image does not have.  They draw
# synthetic data of the same shape and kind from fixed seeds instead
# (rows x columns, label kind and file format of each example; labels
# carry noise, so the tests' metric thresholds still tell a learner that
# learns from one that does not), write it in the examples' own layout
# and file formats, and parse it back through `parse_text_file`, so a
# test that passes a path and a test that takes the arrays see one
# dataset.

def _signal(X):
    """A few linear, interaction and smooth terms over the first columns."""
    return (1.2 * X[:, 0] - 0.9 * X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
            + 0.6 * np.sin(2 * X[:, 4]) + 0.5 * (X[:, 5] ** 2 - 1))


def _write_tsv(path, y, X):
    np.savetxt(path, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")


def _write_libsvm(path, y, X):
    with open(path, "w") as f:
        for label, row in zip(y, X):
            nz = np.flatnonzero(row)
            f.write(" ".join([f"{label:g}"]
                             + [f"{j}:{row[j]:.6g}" for j in nz]) + "\n")


def _binary(rng, n):
    X = rng.randn(n, 28)
    return X, (_signal(X) + 0.7 * rng.logistic(size=n) > 0).astype(float)


def _regression(rng, n):
    X = rng.randn(n, 20)
    return X, 1 / (1 + np.exp(-(_signal(X) + 0.5 * rng.randn(n))))


def _multiclass(rng, n):
    X = rng.randn(n, 28)
    logits = 1.5 * X[:, :5] + 0.8 * X[:, 5:10] * X[:, 10:15]
    return X, np.argmax(logits + rng.gumbel(size=(n, 5)), axis=1).astype(float)


def _rank(rng, sizes):
    """Queries of the given sizes over 300 sparse columns; relevance 0-4,
    mostly 0, from a signal over the first columns."""
    n = int(np.sum(sizes))
    X = rng.randn(n, 300) * (rng.rand(n, 300) < 0.1)
    X[:, :6] = rng.randn(n, 6)
    X[0, -1] = 1.0                  # every file reaches the last column
    s = _signal(X) + 1.0 * rng.randn(n)
    y = np.digitize(s, np.quantile(s, [0.5, 0.75, 0.9, 0.97])).astype(float)
    return X, y


_CONF = """task = train
objective = {objective}
metric = {metric}
data = {stem}.train
valid_data = {stem}.test
num_trees = 100
learning_rate = 0.1
num_leaves = 31
min_data_in_leaf = 50
output_model = LightGBM_model.txt
"""


@pytest.fixture(scope="session")
def examples_dir(tmp_path_factory):
    """A directory laid out as the reference's `examples/`."""
    root = tmp_path_factory.mktemp("examples")
    rng = np.random.RandomState(20240607)
    for sub, stem, draw, objective, metric in (
            ("binary_classification", "binary", _binary, "binary",
             "binary_logloss,auc"),
            ("regression", "regression", _regression, "regression", "l2"),
            ("multiclass_classification", "multiclass", _multiclass,
             "multiclass", "multi_logloss")):
        d = root / sub
        d.mkdir()
        for part, n in (("train", 7000), ("test", 500)):
            X, y = draw(rng, n)
            _write_tsv(d / f"{stem}.{part}", y, X)
            if stem == "binary":
                np.savetxt(d / f"{stem}.{part}.weight",
                           np.where(y > 0, 1.2, 1.0), fmt="%g")
        (d / "train.conf").write_text(_CONF.format(
            objective=objective, metric=metric, stem=stem))
    d = root / "lambdarank"
    d.mkdir()
    for part, queries, rows in (("train", 201, 3005), ("test", 50, 768)):
        sizes = rng.multinomial(rows - 5 * queries,
                                np.ones(queries) / queries) + 5
        X, y = _rank(rng, sizes)
        _write_libsvm(d / f"rank.{part}", y, X)
        np.savetxt(d / f"rank.{part}.query", sizes, fmt="%d")
    return str(root)


def _parsed(examples_dir, sub, stem):
    from lightgbm_tpu.dataset import parse_text_file
    X, y, _ = parse_text_file(f"{examples_dir}/{sub}/{stem}.train")
    Xt, yt, _ = parse_text_file(f"{examples_dir}/{sub}/{stem}.test")
    return X, y, Xt, yt


@pytest.fixture(scope="session")
def binary_example(examples_dir):
    return _parsed(examples_dir, "binary_classification", "binary")


@pytest.fixture(scope="session")
def regression_example(examples_dir):
    return _parsed(examples_dir, "regression", "regression")


@pytest.fixture(scope="session")
def multiclass_example(examples_dir):
    return _parsed(examples_dir, "multiclass_classification", "multiclass")


@pytest.fixture(scope="session")
def rank_example(examples_dir):
    X, y, Xt, yt = _parsed(examples_dir, "lambdarank", "rank")
    q = np.loadtxt(f"{examples_dir}/lambdarank/rank.train.query",
                   dtype=np.int64)
    qt = np.loadtxt(f"{examples_dir}/lambdarank/rank.test.query",
                    dtype=np.int64)
    return X, y, q, Xt, yt, qt
