"""Synthetic Epsilon-shaped rows: dense standard-normal features and a noisy
linear label, after `scripts/run_shape_sweep.make_epsilon`.

Two departures from that generator.  The label weights come from a fixed seed
(0) and not from the row seed, so the training rows and the held-out rows
share one labelling function, as in `generators/higgs.py`.  And the rows are
drawn as float32 by numpy's `Generator` in blocks (the original draws float64
from `RandomState` and converts, 40 s for 400,000 x 2,000 on the chip's
host), so that the peak host memory is the float32 matrix plus one block.  `seed` is whatever `numpy.random.default_rng` takes.
"""
import numpy as np

BLOCK = 8192            # 64 MB as float64: the block's copy stays one reused buffer


def make(rows: int, features: int, seed):
    w = np.random.RandomState(0).randn(features) / np.sqrt(features)
    rng = np.random.default_rng(seed)
    X = np.empty((rows, features), np.float32)
    logits = np.empty(rows, np.float64)
    for a in range(0, rows, BLOCK):
        b = min(a + BLOCK, rows)
        rng.standard_normal(out=X[a:b], dtype=np.float32)
        logits[a:b] = X[a:b].astype(np.float64) @ w
    y = (logits + 0.3 * rng.logistic(size=rows) > 0).astype(np.float64)
    return X, y
