"""The binned training set of a cell, through the calls a user makes:
`lgb.Dataset(X, y).construct(params)` on a cold checkout, and
`lgb.Dataset("<file>.bin")` (the reference's binary dataset file) once this
checkout has binned these rows.  After `bench.binned_dataset`, but keyed by
what generates the rows — (generator, data_seed, rows, features, max_bin) — so
that a hit skips the synthesis as well as the binning.

The dataset belongs to the configuration, as a public dataset's files would:
the training set and the test split (`test_split`, the rows `valid_auc` is
taken on) are drawn from the configuration's `data_seed`, the same rows in
every run.  `--seed` draws the rows on which the device's predictions are held
against the plain walk (`check_rows`).  NumPy binning costs 27.7 s at Higgs
and about two minutes at Epsilon (shape_sweep_measured.json); a training set
per seed would make every run with a new seed pay that, in every later check,
and would let the seed change the work.  The first run of a cell in a checkout
pays it once, and `dataset_s` with the `dataset` line printed before the
result says which kind a run was.
"""
import importlib
import os
import time

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache")


TRAIN, TEST, CHECK = 0, 1, 2     # second word of a generator's seed: the stream


def generate(config: dict, rows: int, seed):
    """`seed` is what numpy's generators take: here always a pair (number,
    stream), so that no `--seed` can draw the training rows again."""
    gen = importlib.import_module(f"benchmark.generators.{config['generator']}")
    return gen.make(rows, int(config["features"]), seed)


def test_split(config: dict, rows: int):
    """The configuration's test split: the same held-out rows in every run."""
    return generate(config, rows, (int(config["data_seed"]), TEST))


def check_rows(config: dict, rows: int, seed: int):
    """The run's own rows, drawn from `--seed`."""
    return generate(config, rows, (seed, CHECK))


def cache_path(config: dict, params: dict) -> str:
    return os.path.join(CACHE_DIR, "{}_s{}_{}x{}_b{}.bin".format(
        config["generator"], config["data_seed"], config["rows"],
        config["features"], params["max_bin"]))


def binned_train_set(config: dict, params: dict):
    """-> (lgb.Dataset, facts).  `facts["how"]` is "loaded" or "binned"."""
    import lightgbm_tpu as lgb
    path = cache_path(config, params)
    t0 = time.perf_counter()
    if os.path.exists(path):
        try:
            ds = lgb.Dataset(path).construct(params)
            if ds.num_data() == config["rows"]:
                return ds, {"how": "loaded", "file": os.path.basename(path),
                            "seconds": time.perf_counter() - t0}
        except Exception as e:      # unreadable or old format: bin again
            print(f"benchmark: {path} unusable ({e}); binning again",
                  flush=True)
    X, y = generate(config, int(config["rows"]),
                    (int(config["data_seed"]), TRAIN))
    t_synth = time.perf_counter() - t0
    ds = lgb.Dataset(X, y).construct(params)
    # the shell keeps the raw rows alive; the store is all training needs
    ds.data = ds._raw_X = None
    del X
    t_bin = time.perf_counter() - t0 - t_synth
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        ds.save_binary(tmp)
        os.replace(tmp, path)       # atomic: a reader never sees half a file
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return ds, {"how": "binned", "file": os.path.basename(path),
                "synth_s": t_synth, "bin_s": t_bin,
                "seconds": time.perf_counter() - t0}
