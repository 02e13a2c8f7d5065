"""The plain reference for one node's split where features may hold a NaN
bin: NumPy float64, no program code.

The reference's rule for a numerical feature of missing type NaN
(Advanced-Topics, "Missing Value Handle"; its FindBestThresholdNumerical
run once a direction): the value thresholds `bin <= t` are scored once
with the NaN bin's sums on the right (t in 0 .. nb - 2, the last of them
"every value left, NaN right") and once with them on the left (t in
0 .. nb - 3), and the better of the two is the split, its `default_left`
the NaN bin's side.  A feature without a NaN bin has the first scan
only; a categorical one is `bin == t` for every bin it may split on, NaN
right.  The gain is the textbook `GL^2/HL + GR^2/HR - G^2/H` under the
two limits a child has to meet.  The histogram is split_reference's:
int64 sums of the int8-quantised gradients and hessians and the row
count per (column, bin).
"""
import numpy as np


def candidates(hist: np.ndarray, split_bins, is_cat, nan_bins,
               min_data: int, min_hess: float, hess_slack: float = 0.0):
    """-> [F, B, 2] float64 gain of every candidate of a [F, 3, B]
    histogram: [..., 0] the NaN bin right (or no NaN bin), [..., 1] the
    NaN bin left; -inf where no such candidate or a child is under a
    limit.  A child's hessian has to reach min_hess * (1 + hess_slack):
    a slack above 0 keeps only candidates that no float32 rounding of
    the sums can take under the limit, one below 0 every candidate that
    such a rounding can take over it."""
    F, _, B = hist.shape
    G, H, C = (float(hist[0, k].sum()) for k in range(3))
    out = np.full((F, B, 2), -np.inf)
    for f in range(F):
        nb, nan = int(split_bins[f]), int(nan_bins[f])
        h = hist[f].astype(np.float64)
        if is_cat[f]:
            sides = [(h[:, :nb], 0)]
        else:
            left = np.cumsum(h[:, :max(nb - 1, 0)], axis=1)
            sides = [(left, 0)]
            if nan >= 0:
                sides.append((left[:, :max(nb - 2, 0)] + h[:, nan:nan + 1],
                              1))
        for (GL, HL, CL), side in sides:
            GR, HR, CR = G - GL, H - HL, C - CL
            least = min_hess * (1.0 + hess_slack)
            ok = ((CL >= min_data) & (CR >= min_data)
                  & (HL >= least) & (HR >= least))
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = np.where(ok, GL * GL / HL + GR * GR / HR - G * G / H,
                                -np.inf)
            out[f, :gain.size, side] = gain
    return out


def best_split(hist: np.ndarray, split_bins, is_cat, nan_bins,
               min_data: int, min_hess: float, hess_slack: float = 0.0):
    """(feature, threshold bin, default_left, gain) of the best split;
    (-1, -1, False, -inf) where no candidate qualifies."""
    gains = candidates(hist, split_bins, is_cat, nan_bins, min_data,
                       min_hess, hess_slack)
    f, t, side = np.unravel_index(int(np.argmax(gains)), gains.shape)
    g = float(gains[f, t, side])
    if not np.isfinite(g):
        return -1, -1, False, -np.inf
    return int(f), int(t), bool(side), g


def histogram(store: np.ndarray, gq: np.ndarray, hq: np.ndarray,
              num_bins_padded: int) -> np.ndarray:
    """[F, 3, B] int64: split_reference.histogram's sums (grad levels,
    hess levels, rows per store column and bin), counted by the groups of
    rows that share one (grad, hess) level pair.  At a root whose score
    is one constant the pair is the label's, so every group but the
    largest is a few rows, and the largest one's counts are a column's
    own less the others'."""
    pairs, inv = np.unique(np.stack([gq, hq], axis=1), axis=0,
                           return_inverse=True)
    inv = inv.reshape(-1)
    big = int(np.argmax(np.bincount(inv)))
    few = [(pairs[j], np.flatnonzero(inv == j))
           for j in range(len(pairs)) if j != big]
    F, B = store.shape[0], num_bins_padded
    out = np.zeros((F, 3, B), np.int64)
    for f in range(F):
        col = store[f]
        out[f, 2] = np.bincount(col, minlength=B)
        rest = out[f, 2].copy()
        for (g, h), rows in few:
            c = np.bincount(col[rows], minlength=B)
            rest -= c
            out[f, 0] += g * c
            out[f, 1] += h * c
        out[f, 0] += pairs[big, 0] * rest
        out[f, 1] += pairs[big, 1] * rest
    return out
