"""The plain reference for one node's split: NumPy, int64 and float64, no
program code.

`histogram` sums, per (column, bin) of a binned store, the int8-quantised
gradients and hessians (`quantize`, the program's symmetric rounding redone
in NumPy float32) and the row count in int64: the sums the histogram kernel
has to reach exactly wherever its operands are int8.  `best_split` takes
the textbook gain `GL^2/HL + GR^2/HR - G^2/H` over every candidate — a
numerical column's `bin <= t` for t below its last bin, a categorical
column's `bin == t` for every bin it may split on — under the two limits a
child has to meet, and keeps the first maximum in (column, bin) order, as
a flat argmax does.
"""
import numpy as np

BLOCK = 1 << 20


def quantize(v: np.ndarray):
    """-> (int64 levels in [-127, 127], float32 scale): `quantize_gh` for
    one row of values."""
    v = np.asarray(v, np.float32)
    scale = np.maximum(np.max(np.abs(v)), np.float32(1e-30)) / np.float32(127)
    return np.round(v / scale).astype(np.int64), np.float32(scale)


def histogram(store: np.ndarray, gq: np.ndarray, hq: np.ndarray,
              num_bins_padded: int) -> np.ndarray:
    """[F, 3, B] int64: per store column and bin the sums of the grad
    levels, the hess levels and the rows, over `store` [F, N] (bins as
    unsigned integers), a block of rows at a time."""
    F, N = store.shape
    B = num_bins_padded
    out = np.zeros((F, 3, B), np.int64)
    for lo in range(0, N, BLOCK):
        hi = min(N, lo + BLOCK)
        # float64 weights hold these integers exactly (sums < 2^53)
        g = gq[lo:hi].astype(np.float64)
        h = hq[lo:hi].astype(np.float64)
        for f in range(F):
            b = store[f, lo:hi]
            out[f, 0] += np.rint(np.bincount(b, g, B)).astype(np.int64)
            out[f, 1] += np.rint(np.bincount(b, h, B)).astype(np.int64)
            out[f, 2] += np.bincount(b, minlength=B)
    return out


def best_split(hist: np.ndarray, split_bins, is_cat, min_data: int,
               min_hess: float):
    """(feature, threshold bin, gain) of the best split of a [F, 3, B]
    float64 histogram; (-1, -1, -inf) where no candidate qualifies.
    `split_bins[f]` is the bins column f may split on: a numerical column
    takes thresholds 0 .. split_bins - 2, a categorical one every bin
    below split_bins by itself."""
    G, H, C = (float(hist[0, k].sum()) for k in range(3))
    best = (-1, -1, -np.inf)
    for f in range(hist.shape[0]):
        nb = int(split_bins[f])
        if is_cat[f]:
            GL, HL, CL = (hist[f, k, :nb].astype(np.float64)
                          for k in range(3))
        else:
            GL, HL, CL = (np.cumsum(hist[f, k, :max(nb - 1, 0)],
                                    dtype=np.float64) for k in range(3))
        if GL.size == 0:
            continue
        GR, HR, CR = G - GL, H - HL, C - CL
        ok = ((CL >= min_data) & (CR >= min_data)
              & (HL >= min_hess) & (HR >= min_hess))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = np.where(ok, GL * GL / HL + GR * GR / HR - G * G / H,
                            -np.inf)
        t = int(np.argmax(gain))
        if gain[t] > best[2]:
            best = (f, t, float(gain[t]))
    return best


def gain_of(hist: np.ndarray, feature: int, threshold: int,
            is_cat: bool) -> float:
    """The float64 gain of one candidate of `hist`."""
    G, H = (float(hist[0, k].sum()) for k in range(2))
    sel = (slice(threshold, threshold + 1) if is_cat
           else slice(0, threshold + 1))
    GL, HL = (float(hist[feature, k, sel].sum()) for k in range(2))
    return GL * GL / HL + (G - GL) ** 2 / (H - HL) - G * G / H
