"""GOSS: Gradient-based One-Side Sampling.

Parity with /root/reference/src/boosting/goss.hpp: replaces bagging — keep
the top `top_rate` fraction of rows by |g*h|, sample `other_rate` of the
rest and amplify their gradients/hessians by (1-a)/b (goss.hpp:79-124);
sampling is skipped for the first 1/learning_rate iterations (goss.hpp:129).

TPU mapping: the per-thread ArgMaxAtK partial selection becomes one
`jax.lax.top_k` on |g*h| summed over classes; the amplification is a
masked elementwise multiply.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from .gbdt import GBDT


@functools.partial(jax.jit, static_argnames=("top_k", "other_k", "cap"))
def _goss_select(gradients: jax.Array, hessians: jax.Array, rand_key,
                 *, top_k: int, other_k: int, cap: int):
    """Returns (bag_idx [cap] padded with N, amplified g, h)."""
    K, N = gradients.shape
    score = jnp.sum(jnp.abs(gradients * hessians), axis=0)
    # top_k selection
    _, top_idx = jax.lax.top_k(score, top_k)
    # sample other_k of the rest uniformly: use random keys on the
    # complement via masked scores
    mask_top = jnp.zeros(N, bool).at[top_idx].set(True)
    u = jax.random.uniform(rand_key, (N,))
    u = jnp.where(mask_top, -1.0, u)  # exclude top rows
    _, other_idx = jax.lax.top_k(u, other_k)
    multiply = jnp.ones(N, jnp.float32)
    amp = (1.0 - top_k / N) / max(other_k / N, 1e-30) if N else 1.0
    multiply = multiply.at[other_idx].set(amp)
    sel = jnp.concatenate([top_idx, other_idx]).astype(jnp.int32)
    sel = jnp.sort(sel)
    pad = jnp.full((cap - sel.shape[0],), N, jnp.int32)
    bag = jnp.concatenate([sel, pad])
    g = gradients * multiply[None, :]
    h = hessians * multiply[None, :]
    return bag, g, h


class GOSS(GBDT):
    def __init__(self, config: Config, train_set=None, objective=None):
        if config.bagging_freq > 0 and config.bagging_fraction < 1.0:
            raise ValueError("cannot use bagging in GOSS")
        super().__init__(config, train_set, objective)
        self._goss_key = jax.random.PRNGKey(config.bagging_seed)

    def sub_model_name(self) -> str:
        return "goss"

    def _extra_training_state(self):
        # the raw uint32 key words; jax.random.key_data unwraps typed
        # keys, raw legacy keys pass through np.asarray unchanged
        key = self._goss_key
        try:
            key = jax.random.key_data(key)
        except TypeError:
            pass
        return {"goss_key":
                jax.device_get(key).astype(np.uint32).tolist()}

    def _restore_extra_training_state(self, state):
        if "goss_key" in state:
            self._goss_key = jnp.asarray(
                np.asarray(state["goss_key"], np.uint32))

    def train_one_iter(self, gradient=None, hessian=None,
                       is_eval: bool = False) -> bool:
        self._boost_from_average()
        if gradient is None or hessian is None:
            gradient, hessian = self.boosting_gradients()
        cfg = self.config
        n = self.num_data
        top_k = max(int(n * cfg.top_rate), 1)
        other_k = max(int(n * cfg.other_rate), 1)
        # skip sampling during warmup (goss.hpp:129)
        warmup = int(1.0 / max(cfg.learning_rate, 1e-12))
        if self.iter_ >= warmup and top_k + other_k < n:
            self._goss_key, sub = jax.random.split(self._goss_key)
            cnt = top_k + other_k
            cap = min(1 << max(cnt - 1, 1).bit_length(), n)
            cap = max(cap, cnt)
            # top-k over the real rows only: the [K, N] view of what
            # came in the learner's row layout (the learner pads and
            # places the selection's gradients again)
            view = self.train_score.layout.view
            bag, gradient, hessian = _goss_select(
                view(gradient), view(hessian), sub, top_k=top_k,
                other_k=other_k, cap=cap)
            self.bag_idx = bag
            self.bag_cnt = cnt
            self.need_bagging = True
            self._goss_active = True
        else:
            self.bag_idx = None
            self.bag_cnt = n
            self.need_bagging = False
            self._goss_active = False
        return GBDT.train_one_iter(self, gradient, hessian, is_eval)

    def _bagging(self, iter_):
        return  # bagging replaced by GOSS selection above
