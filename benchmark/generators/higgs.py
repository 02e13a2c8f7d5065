"""Synthetic Higgs-shaped rows: a copy of `bench.synth_higgs` (the repo's
generator since PR 1), kept here so that no later PR can change the yardstick.

The labelling function is fixed (weights from seed 0), so the training rows
and the held-out rows share it; only the rows and the label noise vary with
the seed, which is whatever `numpy.random.RandomState` takes.  Values are float32-valued: handing float32 to
`lgb.Dataset` bins bit-identically to the float64 copy `bench.py` hands it.
"""
import numpy as np


def make(rows: int, features: int, seed):
    w = np.random.RandomState(0).randn(features) / np.sqrt(features)
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    logits = (X @ w + 0.5 * np.sin(X[:, 0] * 2.0) * X[:, 1]
              - 0.3 * X[:, 2] * X[:, 3])
    y = (logits + rng.logistic(size=rows) * 0.5 > 0).astype(np.float64)
    return X, y
