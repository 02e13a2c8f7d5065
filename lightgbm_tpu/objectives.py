"""Objective functions: jitted elementwise gradient/hessian kernels.

Parity with /root/reference/src/objective/ (factory objective_function.cpp:9-31):
regression (L2), regression_l1, huber, fair, poisson
(regression_objective.hpp), binary (binary_objective.hpp:45-113),
multiclass softmax / multiclassova (multiclass_objective.hpp), lambdarank
(rank_objective.hpp:19-242).

Scores and gradients are `[K, N]` float32 device arrays (K = trees per
iteration; the reference uses a flat class-major buffer, gbdt.cpp:648-656).
The reference's per-row OMP loops become one fused elementwise XLA program;
LambdaRank's per-query pairwise loop becomes a padded `[Q, D, D]` masked
computation chunked over queries (no sigmoid lookup table needed — the VPU
evaluates exp directly; rank_objective.hpp:173-199 is a CPU-ism).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .dataset import Metadata
from .jaxutil import RowLayout


def softmax(x, axis=-1):
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=axis, keepdims=True)


class Objective:
    """Base objective.  get_gradients: [K, N] score -> ([K, N], [K, N]).

    A row-local objective (a row's gradient depends on that row's score,
    label and weight alone: all but lambdarank) can be given the tree
    learner's row layout at init.  It then keeps label and weights in
    that layout — padded to Np rows, sharded like the store's rows on a
    mesh — takes the [K, Np] score where it lies and returns [K, Np]
    gradients there, exactly 0.0 in the padded rows."""

    name = "regression"
    num_tree_per_iteration = 1
    is_constant_hessian = False
    boost_from_average = False
    row_local = True
    layout: Optional[RowLayout] = None

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int,
             layout: Optional[RowLayout] = None) -> None:
        self.num_data = num_data
        self.layout = layout if self.row_local else None
        self.label = self._rows(metadata.label, np.float32)
        self.weights = (None if metadata.weights is None
                        else self._rows(metadata.weights, np.float32))

    def _rows(self, x, dtype) -> jax.Array:
        """A per-row host array on the device: in the layout when there
        is one (a shard straight to each device), else as it is."""
        x = np.asarray(x, dtype)
        return jnp.asarray(x) if self.layout is None else self.layout.place(x)

    def _host_rows(self, x: jax.Array, dtype=np.float64) -> np.ndarray:
        """The real rows of `_rows`' result, back on the host."""
        return jax.device_get(x)[: self.num_data].astype(dtype)

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def convert_output(self, score: np.ndarray) -> np.ndarray:
        """Raw score -> prediction output (reference ConvertOutput)."""
        return score

    def initial_score(self) -> float:
        """boost_from_average seed value (gbdt.cpp:333-355)."""
        return 0.0

    def to_string(self) -> str:
        return self.name

    def _jit_gradients(self, f):
        """jit the gradient function under the objective's name: its XLA
        module reads `jit_gradients_<objective>` in a trace, not
        `jit_f`.  In a layout with padded rows the program also zeroes
        their gradient and hessian (fused, free).  That is not hygiene:
        the learner's int8 quantisation scales by the largest |g| and
        |h| over ALL rows it is handed, so a padded row's sigmoid of 0.5
        against label 0 would move a shard's scale, and with it levels,
        near-ties and trees."""
        n, layout = self.num_data, self.layout
        if layout is not None and layout.num_rows > n:
            grads = f

            def f(score, *rest):
                g, h = grads(score, *rest)
                real = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) < n
                return jnp.where(real, g, 0.0), jnp.where(real, h, 0.0)
        f.__name__ = f.__qualname__ = f"gradients_{self.name}"
        return jax.jit(f)

    def _apply_weights(self, g, h):
        if self.weights is None:
            return g, h
        w = self.weights[None, :]
        return g * w, h * w


class RegressionL2(Objective):
    name = "regression"
    boost_from_average = True

    @property
    def is_constant_hessian(self):
        return self.weights is None

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)

        def f(score, label, weights):
            g = score - label[None, :]
            h = jnp.ones_like(g)
            if weights is not None:
                g = g * weights[None, :]
                h = h * weights[None, :]
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self.label, self.weights)

    def initial_score(self) -> float:
        lab = self._host_rows(self.label)
        if self.weights is not None:
            w = self._host_rows(self.weights)
            return float((lab * w).sum() / w.sum())
        return float(lab.mean())


class RegressionL1(Objective):
    name = "regression_l1"
    boost_from_average = True

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        eta = self.config.gaussian_eta

        def f(score, label, weights):
            lab = label[None, :]
            diff = score - lab
            w = jnp.ones_like(score) if weights is None else weights[None, :]
            g = jnp.where(diff >= 0.0, 1.0, -1.0) * w
            h = w * _gaussian_hessian(score, lab, g, eta, w)
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self.label, self.weights)

    def initial_score(self) -> float:
        return float(np.median(self._host_rows(self.label)))


def _gaussian_hessian(y, t, g, eta, w):
    """Common::ApproximateHessianWithGaussian (common.h:436-445); the
    leading `w` factor is applied by the caller."""
    diff = y - t
    x = jnp.abs(diff)
    a = 2.0 * jnp.abs(g)  # w already folded into g by callers
    c = jnp.maximum((jnp.abs(y) + jnp.abs(t)) * eta, 1.0e-10)
    return jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2 * jnp.pi))


class RegressionHuber(Objective):
    name = "huber"
    boost_from_average = True

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        delta = self.config.huber_delta
        eta = self.config.gaussian_eta

        def f(score, label, weights):
            lab = label[None, :]
            diff = score - lab
            w = jnp.ones_like(score) if weights is None else weights[None, :]
            small = jnp.abs(diff) <= delta
            g = jnp.where(small, diff, jnp.sign(diff) * delta) * w
            h_small = w
            h_big = w * _gaussian_hessian(score, lab, jnp.sign(diff) * delta * w,
                                          eta, w)
            h = jnp.where(small, h_small, h_big)
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self.label, self.weights)

    def initial_score(self) -> float:
        return float(np.mean(self._host_rows(self.label)))


class RegressionFair(Objective):
    name = "fair"
    boost_from_average = True

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        c = self.config.fair_c

        def f(score, label, weights):
            x = score - label[None, :]
            w = jnp.ones_like(score) if weights is None else weights[None, :]
            g = c * x / (jnp.abs(x) + c) * w
            h = c * c / ((jnp.abs(x) + c) ** 2) * w
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self.label, self.weights)

    def initial_score(self) -> float:
        return float(np.mean(self._host_rows(self.label)))


class RegressionPoisson(Objective):
    name = "poisson"
    boost_from_average = True

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        mds = self.config.poisson_max_delta_step

        def f(score, label, weights):
            g = score - label[None, :]
            h = score + mds
            if weights is not None:
                g = g * weights[None, :]
                h = h * weights[None, :]
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self.label, self.weights)

    def initial_score(self) -> float:
        return float(np.mean(self._host_rows(self.label)))


class BinaryLogloss(Objective):
    name = "binary"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        lab = np.asarray(metadata.label)
        is_pos = lab > 0
        cnt_pos, cnt_neg = int(is_pos.sum()), int((~is_pos).sum())
        self.need_train = cnt_pos > 0 and cnt_neg > 0
        w_pos, w_neg = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.config.scale_pos_weight
        sigmoid = self.sigmoid

        def f(score, label, weights):
            is_p = label[None, :] > 0
            lbl = jnp.where(is_p, 1.0, -1.0)
            lw = jnp.where(is_p, w_pos, w_neg)
            response = -lbl * sigmoid / (1.0 + jnp.exp(lbl * sigmoid * score))
            absr = jnp.abs(response)
            g = response * lw
            h = absr * (sigmoid - absr) * lw
            if weights is not None:
                g = g * weights[None, :]
                h = h * weights[None, :]
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        if not self.need_train:
            z = jnp.zeros_like(score)
            return z, z
        return self._f(score, self.label, self.weights)

    def convert_output(self, score):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * score))

    def to_string(self):
        return f"binary sigmoid:{self.sigmoid:g}"


class MulticlassSoftmax(Objective):
    name = "multiclass"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = config.num_class

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        lab = np.asarray(metadata.label).astype(np.int32)
        if lab.min() < 0 or lab.max() >= self.num_class:
            raise ValueError(
                f"Label must be in [0, {self.num_class}) for multiclass")
        self._label_int = self._rows(lab, np.int32)

        def f(score, label_int, weights):
            p = softmax(score, axis=0)                       # [K, N]
            onehot = (jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
                      == label_int[None, :])
            g = p - onehot.astype(p.dtype)
            h = 2.0 * p * (1.0 - p)
            if weights is not None:
                g = g * weights[None, :]
                h = h * weights[None, :]
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self._label_int, self.weights)

    def convert_output(self, score):
        e = np.exp(score - score.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)

    def to_string(self):
        return f"multiclass num_class:{self.num_class}"


class MulticlassOVA(Objective):
    name = "multiclassova"

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = config.num_class
        self.sigmoid = config.sigmoid

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        lab = np.asarray(metadata.label).astype(np.int32)
        self._label_int = self._rows(lab, np.int32)
        sigmoid = self.sigmoid

        def f(score, label_int, weights):
            is_p = (jax.lax.broadcasted_iota(jnp.int32, score.shape, 0)
                    == label_int[None, :])
            lbl = jnp.where(is_p, 1.0, -1.0)
            response = -lbl * sigmoid / (1.0 + jnp.exp(lbl * sigmoid * score))
            absr = jnp.abs(response)
            g = response
            h = absr * (sigmoid - absr)
            if weights is not None:
                g = g * weights[None, :]
                h = h * weights[None, :]
            return g, h
        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self._label_int, self.weights)

    def convert_output(self, score):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * score))

    def to_string(self):
        return f"multiclassova num_class:{self.num_class} sigmoid:{self.sigmoid:g}"


class LambdarankNDCG(Objective):
    name = "lambdarank"
    row_local = False       # gathers scores by doc_idx across a query

    def init(self, metadata, num_data, layout=None):
        super().init(metadata, num_data, layout)
        if metadata.query_boundaries is None:
            raise ValueError("Lambdarank tasks require query information")
        qb = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(qb) - 1
        sizes = np.diff(qb)
        D = int(sizes.max())
        Q = self.num_queries
        # padded doc-index matrix; pad slots point at sentinel N.
        # Vectorized construction — per-query Python loops cost minutes
        # at MS-LTR scale (~31k queries) on a small host
        j = np.arange(D)
        valid = j[None, :] < sizes[:, None]                     # [Q, D]
        doc_idx = np.where(valid, qb[:-1, None] + j[None, :],
                           num_data).astype(np.int32)
        gains = self.config.label_gain
        if not gains:
            gains = tuple(float(2 ** i - 1) for i in range(31))
        label_gain = np.asarray(gains, np.float64)
        lab = np.asarray(metadata.label).astype(np.int32)
        # inverse max DCG per query at max_position (rank_objective.hpp:60-69)
        k = self.config.max_position
        discount = 1.0 / np.log2(2.0 + np.arange(D))
        # sort LABELS descending (not gains): the reference's CalMaxDCG
        # does, and a custom label_gain table need not be monotonic
        lab_pad_np = np.concatenate([lab, [0]])
        lab_mat = np.where(valid, lab_pad_np[doc_idx], -1)
        lab_sorted = -np.sort(-lab_mat, axis=1)[:, :k]          # desc, top-k
        g_sorted = np.where(lab_sorted >= 0,
                            label_gain[np.maximum(lab_sorted, 0)], 0.0)
        md = (g_sorted * discount[None, : g_sorted.shape[1]]).sum(axis=1)
        inv_max_dcg = np.where(md > 0, 1.0 / np.maximum(md, 1e-300), 0.0)
        # chunk queries so the [q, D, D] pairwise block stays ~64MB.
        # Q is padded UP to a chunk multiple with all-sentinel queries
        # (empty mask -> zero lambdas) — requiring qc | Q would
        # degenerate to qc=1 (fully serial scan) whenever Q is prime
        sigmoid = self.config.sigmoid
        N = num_data
        qc = max(1, min(Q, (1 << 24) // max(D * D, 1)))
        Qp = qc * ((Q + qc - 1) // qc)
        if Qp > Q:
            doc_idx = np.pad(doc_idx, ((0, Qp - Q), (0, 0)),
                             constant_values=num_data)
            inv_max_dcg = np.pad(inv_max_dcg, (0, Qp - Q))
        self._doc_idx = jnp.asarray(doc_idx)
        self._mask = jnp.asarray(doc_idx < num_data)
        self._inv_max_dcg = jnp.asarray(inv_max_dcg, jnp.float32)
        self._label_gain = jnp.asarray(label_gain, jnp.float32)
        self._discount = jnp.asarray(discount, jnp.float32)
        self._lab_pad = jnp.asarray(np.concatenate([lab, [0]]).astype(jnp.int32))
        self._q_chunk = qc

        def f(score, lab_pad, doc_idx, mask, inv_max_dcg):
            s1 = score[0]
            s_pad = jnp.concatenate([s1, jnp.zeros(1, s1.dtype)])

            def one_chunk(carry, args):
                didx, msk, imd = args          # [qc, D], [qc, D], [qc]
                sc = s_pad[didx]               # [qc, D]
                lb = lab_pad[didx]             # [qc, D] int
                sc = jnp.where(msk, sc, -jnp.inf)
                order = jnp.argsort(-sc, axis=1)       # rank -> doc slot
                sc_s = jnp.take_along_axis(sc, order, axis=1)
                lb_s = jnp.take_along_axis(lb, order, axis=1)
                msk_s = jnp.take_along_axis(msk, order, axis=1)
                gain_s = self._label_gain[jnp.clip(lb_s, 0, label_gain.size - 1)]
                disc = self._discount[None, : sc_s.shape[1]]
                best = sc_s[:, 0]
                cnt = msk_s.sum(axis=1)
                worst = jnp.take_along_axis(
                    sc_s, jnp.maximum(cnt - 1, 0)[:, None], axis=1)[:, 0]
                # pairwise [qc, D(hi), D(lo)]
                ds = sc_s[:, :, None] - sc_s[:, None, :]
                valid = (msk_s[:, :, None] & msk_s[:, None, :]
                         & (lb_s[:, :, None] > lb_s[:, None, :]))
                dcg_gap = gain_s[:, :, None] - gain_s[:, None, :]
                Dq = sc_s.shape[1]
                paired_disc = jnp.abs(self._discount[None, :Dq, None]
                                      - self._discount[None, None, :Dq])
                delta = dcg_gap * paired_disc * imd[:, None, None]
                norm = jnp.where((best != worst)[:, None, None],
                                 0.01 + jnp.abs(ds), 1.0)
                delta = delta / norm
                p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * sigmoid * ds))
                p_hess = p_lambda * (2.0 - p_lambda)
                p_lambda = jnp.where(valid, -p_lambda * delta, 0.0)
                p_hess = jnp.where(valid, p_hess * 2.0 * delta, 0.0)
                lam_s = p_lambda.sum(axis=2) - p_lambda.sum(axis=1)
                hes_s = p_hess.sum(axis=2) + p_hess.sum(axis=1)
                # unsort then scatter to flat [N]
                g_flat, h_flat = carry
                docs = jnp.take_along_axis(didx, order, axis=1)
                g_flat = g_flat.at[docs.reshape(-1)].add(
                    lam_s.reshape(-1), mode="drop")
                h_flat = h_flat.at[docs.reshape(-1)].add(
                    hes_s.reshape(-1), mode="drop")
                return (g_flat, h_flat), None

            g0 = jnp.zeros(N, s1.dtype)
            h0 = jnp.zeros(N, s1.dtype)
            Qn, D = doc_idx.shape
            nchunk = Qn // qc
            args = (doc_idx.reshape(nchunk, qc, D),
                    mask.reshape(nchunk, qc, D),
                    inv_max_dcg.reshape(nchunk, qc))
            (g, h), _ = jax.lax.scan(one_chunk, (g0, h0), args)
            if self.weights is not None:
                g = g * self.weights
                h = h * self.weights
            return g[None, :], h[None, :]

        self._f = self._jit_gradients(f)

    def get_gradients(self, score):
        return self._f(score, self._lab_pad, self._doc_idx, self._mask,
                       self._inv_max_dcg)


def create_objective(config: Config) -> Objective:
    table = {
        "regression": RegressionL2,
        "regression_l1": RegressionL1,
        "huber": RegressionHuber,
        "fair": RegressionFair,
        "poisson": RegressionPoisson,
        "binary": BinaryLogloss,
        "multiclass": MulticlassSoftmax,
        "multiclassova": MulticlassOVA,
        "lambdarank": LambdarankNDCG,
    }
    if config.objective not in table:
        raise ValueError(f"unknown objective: {config.objective}")
    return table[config.objective](config)


def objective_from_model_string(s: str, config: Config) -> Objective:
    """Recreate an objective from its model-file ToString() form
    (objective_function.cpp:33-57)."""
    toks = s.split()
    name = toks[0]
    kw = {}
    for t in toks[1:]:
        if ":" in t:
            k, v = t.split(":", 1)
            kw[k] = v
    cfg = config
    if "num_class" in kw:
        cfg = cfg.with_updates(num_class=int(kw["num_class"]))
    if "sigmoid" in kw:
        cfg = cfg.with_updates(sigmoid=float(kw["sigmoid"]))
    cfg = cfg.with_updates(objective=name)
    return create_objective(cfg)
