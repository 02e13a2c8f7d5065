"""CLI application tests: config files and data in the layout of the
reference's examples (tests/conftest.py `examples_dir`) run through the
application entry point (reference test strategy: examples as
integration tests, SURVEY.md §4)."""
import os
import numpy as np
import pytest

from lightgbm_tpu.application import main, Predictor
import lightgbm_tpu as lgb


def test_train_predict_cycle(tmp_path, binary_example, examples_dir):
    EX = examples_dir
    model = tmp_path / "model.txt"
    out = tmp_path / "preds.txt"
    rc = main([
        f"config={EX}/binary_classification/train.conf",
        f"data={EX}/binary_classification/binary.train",
        f"valid_data={EX}/binary_classification/binary.test",
        "num_trees=5", f"output_model={model}", "verbosity=-1",
    ])
    assert rc == 0 and model.exists()
    rc = main([
        "task=predict",
        f"data={EX}/binary_classification/binary.test",
        f"input_model={model}", f"output_result={out}", "verbosity=-1",
    ])
    assert rc == 0
    preds = np.loadtxt(out)
    X, y, Xt, yt = binary_example
    bst = lgb.Booster(model_file=str(model))
    # CLI predict scores through the serving PredictorRuntime's f32
    # device walk (shared compile cache with task=serve); the in-memory
    # Booster.predict reference uses the host f64 walk for small batches
    np.testing.assert_allclose(preds, bst.predict(Xt), atol=1e-6)
    # weighted training actually used the .weight side file
    assert preds.shape[0] == len(yt)


def test_cli_error_paths(tmp_path):
    assert main([]) == 1
    assert main(["task=predict", "data=/nonexistent"]) == 1
    assert main(["task=banana", "data=x"]) == 1


@pytest.mark.slow
def test_cli_continue_training(tmp_path, regression_example, examples_dir):
    """Regression: input_model must actually load and replay the model
    (create_boosting used to only sniff the first line for the type)."""
    X, y, Xt, yt = regression_example
    m1 = tmp_path / "m1.txt"
    m2 = tmp_path / "m2.txt"
    base = [
        f"data={examples_dir}/regression/regression.train",
        "objective=regression",
        "verbosity=-1", "min_data_in_leaf=20",
    ]
    assert main(base + ["num_trees=5", f"output_model={m1}"]) == 0
    assert main(base + ["num_trees=5", f"input_model={m1}",
                        f"output_model={m2}"]) == 0
    b1 = lgb.Booster(model_file=str(m1))
    b2 = lgb.Booster(model_file=str(m2))
    assert b2.num_trees() > b1.num_trees()
    mse1 = np.mean((b1.predict(Xt) - yt) ** 2)
    mse2 = np.mean((b2.predict(Xt) - yt) ** 2)
    assert mse2 < mse1


def test_regression_example_conf(tmp_path, examples_dir):
    EX = examples_dir
    model = tmp_path / "model.txt"
    rc = main([
        f"config={EX}/regression/train.conf",
        f"data={EX}/regression/regression.train",
        f"valid_data={EX}/regression/regression.test",
        "num_trees=5", f"output_model={model}", "verbosity=-1",
    ])
    assert rc == 0 and model.exists()


def test_predict_file_streaming_chunks_match_oneshot(tmp_path, binary_example):
    """Chunked predict_file (predictor.hpp:80-159 pipelined-reader analog)
    must match a whole-file pass to float32-walk precision."""
    X, y, Xt, yt = binary_example
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "num_leaves": 15}, lgb.Dataset(X, y))
    for _ in range(3):
        bst.update()
    data = tmp_path / "pred.tsv"
    rows = ["\t".join([f"{yt[i]:g}"] + [f"{v:.8g}" for v in Xt[i]])
            for i in range(len(yt))]
    data.write_text("\n".join(rows) + "\n")
    p = Predictor(bst)
    out_small = tmp_path / "preds_small.txt"
    out_big = tmp_path / "preds_big.txt"
    p.predict_file(str(data), str(out_small), chunk_rows=37)
    p.predict_file(str(data), str(out_big), chunk_rows=1 << 20)
    # both pass through the runtime's padded row buckets; tiny f32
    # reduction-order drift across bucket shapes is permitted, but the
    # host-walk reference must agree to serving tolerance (1e-6)
    np.testing.assert_allclose(np.loadtxt(out_small),
                               np.loadtxt(out_big), atol=1e-7)
    np.testing.assert_allclose(np.loadtxt(out_small), bst.predict(Xt),
                               atol=1e-6)
