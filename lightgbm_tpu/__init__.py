"""lightgbm_tpu: a TPU-native gradient boosting framework.

A from-scratch JAX/XLA/Pallas re-design of LightGBM (reference:
/root/reference, v2.0-era): binned leaf-wise histogram GBDT with
LightGBM-compatible parameters, model text format, and Python API —
histograms on the MXU, split scans on the VPU, distributed learners as
XLA collectives over a device mesh.
"""

__version__ = "0.3.0"

from .config import Config, config_from_params, PARAM_ALIASES
from .dataset import Dataset as RawDataset, Metadata
from .tree import Tree
from .boosting.gbdt import GBDT, create_boosting
from .basic import Dataset, Booster, LightGBMError
from .engine import train, cv
from .callback import (early_stopping, print_evaluation, record_evaluation,
                       reset_parameter)
from .sklearn import LGBMModel, LGBMRegressor, LGBMClassifier, LGBMRanker
from .plotting import (plot_importance, plot_metric, plot_tree,
                       create_tree_digraph)

__all__ = [
    "Config", "config_from_params", "PARAM_ALIASES", "Metadata", "Tree",
    "GBDT", "create_boosting", "Dataset", "Booster", "LightGBMError",
    "train", "cv", "early_stopping", "print_evaluation", "record_evaluation",
    "reset_parameter", "LGBMModel", "LGBMRegressor", "LGBMClassifier",
    "LGBMRanker", "plot_importance", "plot_metric", "plot_tree",
    "create_tree_digraph", "serving", "online",
]


def __getattr__(name):
    # the online-prediction and online-learning subsystems are imported
    # on first use so the training/CLI import path stays free of server
    # and daemon machinery
    if name in ("serving", "online"):
        import importlib
        return importlib.import_module("." + name, __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
