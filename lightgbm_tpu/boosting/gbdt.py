"""GBDT boosting driver.

Parity with /root/reference/src/boosting/gbdt.cpp:
- TrainOneIter (gbdt.cpp:332-451): boost-from-average init tree
  (:333-355, a 2-leaf tree whose both leaves carry the label average),
  gradients from the objective or user-supplied (custom fobj), bagging
  (:232-317, without-replacement subset re-drawn every `bagging_freq`
  iterations), one tree per class, Shrinkage, score update via leaf
  partition + out-of-bag path (:495-518, :319-330).
- RollbackOneIter (:453-470), early stopping over valid metrics
  (:472-578), model text save/load (:694-848), JSON dump (:658-692),
  split-count feature importance (:850-872), Predict* (:874-923).

TPU mapping: gradients/scores live on device as [K, N] float32; the
per-iteration flow is (1) one fused elementwise gradient program,
(2) the tree learner's device split loop, (3) one score-update program.
"""
from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config, default_metric_for_objective
from ..dataset import Dataset
from ..learner.fused import create_tree_learner
from ..metrics import Metric, create_metric
from ..objectives import Objective, create_objective, objective_from_model_string
from ..tree import Tree, NUMERICAL_DECISION
from .score_updater import ScoreUpdater


CHECKPOINT_VERSION = 1

# fields that may legitimately differ between the run that wrote a
# checkpoint and the run resuming it (paths, logging, and the resume
# machinery itself); everything else participates in the fingerprint —
# resuming under a different training recipe is an error, not a merge
_FINGERPRINT_EXCLUDE = frozenset({
    "task", "verbose", "num_threads", "num_iterations", "input_model",
    "output_model", "output_result", "config_file", "output_freq",
    "checkpoint_path", "checkpoint_interval",
    # serving / online-daemon knobs: they configure how a model is
    # SERVED or refreshed, never how it trains — editing serve_port in
    # the config file between crash and resume must not discard the run
    "serve_host", "serve_port", "max_batch_rows", "flush_deadline_ms",
    "model_poll_seconds", "min_bucket_rows", "serve_replicas",
    "max_pending_rows", "serve_request_timeout_ms",
    "replica_failure_threshold",
    "refit_decay_rate", "refit_min_rows", "online_trigger_rows",
    "online_mode",
    # observability knobs: where spans/metrics go never changes what a
    # run trains — pointing telemetry elsewhere between crash and
    # resume must not discard the checkpoint
    "telemetry_path", "metrics_port",
})


def config_fingerprint(config: Config) -> str:
    """Stable digest of every training-relevant Config field."""
    d = dataclasses.asdict(config)
    items = sorted((k, repr(v)) for k, v in d.items()
                   if k not in _FINGERPRINT_EXCLUDE)
    return hashlib.sha1(repr(items).encode()).hexdigest()


def _rng_state_to_json(rng: np.random.RandomState) -> Dict:
    kind, keys, pos, has_gauss, cached = rng.get_state()
    return {"kind": kind, "keys": np.asarray(keys).tolist(), "pos": int(pos),
            "has_gauss": int(has_gauss), "cached": float(cached)}


def _rng_state_from_json(d: Dict) -> Tuple:
    return (str(d["kind"]), np.asarray(d["keys"], np.uint32), int(d["pos"]),
            int(d["has_gauss"]), float(d["cached"]))


def load_checkpoint(path: str) -> Optional[Dict]:
    """Parse a training checkpoint; None when absent or unreadable.

    A torn/corrupt checkpoint (a crash artifact) must not wedge the
    restarted run: it logs a warning and training starts from scratch
    (or from ``input_model``), exactly as if no checkpoint existed.
    """
    from .. import log
    try:
        with open(path) as f:
            state = json.load(f)
    except FileNotFoundError:
        return None
    except OSError as e:
        # an existing-but-unreadable checkpoint (EACCES/EIO) must not
        # look like "no checkpoint": losing the resume silently discards
        # every checkpointed iteration
        log.warning(f"could not read checkpoint {path} "
                    f"({type(e).__name__}: {e}); starting fresh")
        return None
    except ValueError as e:
        log.warning(f"ignoring unreadable checkpoint {path} "
                    f"({type(e).__name__}: {e}); starting fresh")
        return None
    if (not isinstance(state, dict)
            or state.get("version") != CHECKPOINT_VERSION
            or "model" not in state):
        log.warning(f"ignoring incompatible checkpoint {path} "
                    f"(version {state.get('version') if isinstance(state, dict) else '?'}); "
                    "starting fresh")
        return None
    return state


class GBDT:
    """Gradient Boosting Decision Tree driver."""

    def __init__(self, config: Config, train_set: Optional[Dataset] = None,
                 objective: Optional[Objective] = None):
        self.config = config
        self.models: List[Tree] = []
        self.iter_ = 0
        self.num_init_iteration = 0
        self.boost_from_average_used = False
        self.best_msg = ""
        self.train_set = None
        self.objective = objective
        self.shrinkage_rate = config.learning_rate
        self.num_class = config.num_class
        self.K = config.num_tree_per_iteration
        self.train_metrics: List[Metric] = []
        self.valid_sets: List[Tuple[str, Dataset, ScoreUpdater, List[Metric]]] = []
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.max_feature_idx = 0
        self._early_stopping_state: Dict = {}
        self._predict_stack_cache: Dict = {}
        # checkpoint resume forces the SEQUENTIAL per-tree score replay
        # ("walk"): it adds trees in exactly training's accumulation
        # order, so resumed scores are bitwise the uninterrupted run's.
        # The tensorized ensemble replay reassociates the f32 sum —
        # exact on dyadic leaf values, last-ULP different otherwise.
        self._replay_kernel: Optional[str] = None
        if train_set is not None:
            self.reset_training_data(train_set, objective)

    # ------------------------------------------------------------------
    def reset_training_data(self, train_set: Dataset,
                            objective: Optional[Objective] = None) -> None:
        cfg = self.config
        self.train_set = train_set
        self.num_data = train_set.num_data
        self.objective = objective or create_objective(cfg)
        # the learner first: where it keeps its rows (their padded count
        # and, on a mesh, their sharding; None from a learner that lays
        # out none) is where the train score, the label, the weights
        # and the gradients live through an iteration, so that score
        # update and gradients are per-row work on the chip that holds
        # the row.  Everything outside that hot path reads the [K, N]
        # view (ScoreUpdater.score)
        self.learner = create_tree_learner(train_set, cfg)
        layout = getattr(self.learner, "row_layout", None)
        self.objective.init(train_set.metadata, self.num_data, layout)
        self.K = self.objective.num_tree_per_iteration
        # bins_t resolves LAZILY (sparse stores materialize the dense
        # transpose only if a consumer actually walks trees over it)
        self.train_score = ScoreUpdater(
            lambda: self.learner.bins_t, self.num_data, self.K,
            train_set.metadata.init_score,
            feat_tbl=train_set.bundle_feat_table(), layout=layout,
            missing_bins=train_set.missing_bins)
        # continued training (input_model): replay the loaded model onto
        # the fresh training scores (the reference re-scores via a
        # Predictor closure during loading, application.cpp:106-113) —
        # one tensorized binned traversal for the whole model under
        # predict_kernel=tensorized (score_updater.add_trees)
        for t in self.models:
            t.rebin_to_dataset(train_set)
        if self.models:
            self.train_score.add_trees(self.models, self.K,
                                       self._replay_kernel
                                       or cfg.predict_kernel)
        self.feature_names = list(train_set.feature_names)
        self.feature_infos = train_set.feature_infos()
        self.max_feature_idx = train_set.num_total_features - 1
        # metrics
        names = cfg.metric or (default_metric_for_objective(cfg.objective),)
        self.train_metrics = []
        for nm in names:
            m = create_metric(nm, cfg)
            if m is not None:
                m.init(train_set.metadata, self.num_data)
                self.train_metrics.append(m)
        # pipelined-tree state (see _train_one_iter_pipelined)
        self._pending = None
        self._pending_stop = False
        # bagging state
        self.bag_rng = np.random.RandomState(cfg.bagging_seed)
        self.bag_idx = None
        self.bag_cnt = self.num_data
        self.need_bagging = (cfg.bagging_fraction < 1.0 and cfg.bagging_freq > 0)
        # degenerate-class bookkeeping (gbdt.cpp:166-195)
        self.class_need_train = [True] * self.K
        self.class_default_output = [0.0] * self.K
        if self.K > 1 and cfg.objective in ("multiclass", "multiclassova"):
            lab = np.asarray(train_set.metadata.label).astype(np.int64)
            for k in range(self.K):
                cnt = int((lab == k).sum())
                if cnt == 0:
                    self.class_need_train[k] = False
                    self.class_default_output[k] = -np.log(1e10)
                elif cnt == self.num_data:
                    self.class_need_train[k] = False
                    self.class_default_output[k] = -np.log(1e-10)

    def add_valid(self, valid_set: Dataset, name: str) -> None:
        self._flush_pending()
        cfg = self.config
        if valid_set.sparse is not None:
            # sparse valid sets hand the ELL triple: scoring walks the
            # row segments directly (predict_ensemble_binned_sparse /
            # the sparse _walk_step) and never densifies
            bins_t = valid_set.sparse_triple()
        else:
            bins_np = valid_set.bins.astype(np.int32)
            pad = np.zeros((bins_np.shape[0], 1), np.int32)
            bins_t = jnp.asarray(
                np.concatenate([bins_np, pad], axis=1).T.copy())
        su = ScoreUpdater(bins_t, valid_set.num_data, self.K,
                          valid_set.metadata.init_score,
                          feat_tbl=valid_set.bundle_feat_table(),
                          missing_bins=valid_set.missing_bins)
        names = cfg.metric or (default_metric_for_objective(cfg.objective),)
        ms = []
        for nm in names:
            m = create_metric(nm, cfg)
            if m is not None:
                m.init(valid_set.metadata, valid_set.num_data)
                ms.append(m)
        # replay existing model onto the new valid scores (loaded trees
        # first need in-bin thresholds for this dataset's mappers); the
        # tensorized kernel replays the whole model in `depth` passes
        for t in self.models:
            t.rebin_to_dataset(valid_set)
        if self.models:
            su.add_trees(self.models, self.K,
                         self._replay_kernel or cfg.predict_kernel)
        self.valid_sets.append((name, valid_set, su, ms))

    # ------------------------------------------------------------------
    def _boost_from_average(self) -> None:
        cfg = self.config
        if (self.models or not cfg.boost_from_average
                or self.train_score.has_init_score or self.num_class > 1
                or self.objective is None
                or not self.objective.boost_from_average):
            return
        # reference uses the plain label average for all objectives
        lab = np.asarray(self.train_set.metadata.label, np.float64)
        import jax
        if jax.process_count() > 1:
            # every rank must seed the SAME constant or the grown trees
            # diverge — average over the GLOBAL label set (bit-exact f64
            # gather: a f32 round here shifts every leaf value)
            from ..distributed import allgather_f64
            sums = allgather_f64(np.asarray([lab.sum(), float(len(lab))]))
            init_score = float(sums[:, 0].sum() / max(sums[:, 1].sum(), 1.0))
        else:
            init_score = float(lab.mean())
        t = Tree(2)
        t.split(0, 0, NUMERICAL_DECISION, 0, 0, 0.0, init_score, init_score,
                0, self.num_data, 1.0)
        self.train_score.add_constant(init_score, 0)
        for _, _, su, _ in self.valid_sets:
            su.add_constant(init_score, 0)
        self.models.append(t)
        self.boost_from_average_used = True

    def _bagging(self, iter_: int) -> None:
        """Re-draw the bag every bagging_freq iterations (gbdt.cpp:257-317)."""
        if not self.need_bagging or iter_ % self.config.bagging_freq != 0:
            return
        n = self.num_data
        cnt = int(self.config.bagging_fraction * n)
        idx = self.bag_rng.choice(n, size=cnt, replace=False)
        idx.sort()
        cap = 1 << max(cnt - 1, 1).bit_length()
        cap = min(cap, n)
        if cap < cnt:
            cap = cnt
        padded = np.full(cap, n, np.int32)
        padded[:cnt] = idx
        # explicit upload: the bag redraw runs mid-loop under the
        # sanitizer's transfer guard (jnp.asarray would be implicit)
        self.bag_idx = jax.device_put(padded)
        self.bag_cnt = cnt

    def boosting_gradients(self) -> Tuple[jax.Array, jax.Array]:
        """[K, Np] in the learner's row layout from an objective that
        holds its label there; [K, N] from any other (lambdarank, a
        learner with no layout), which `learner.train` pads and
        places."""
        if self.objective.layout is not None:
            return self.objective.get_gradients(self.train_score.rows)
        return self.objective.get_gradients(self.train_score.score)

    def _shrink_dev(self) -> jax.Array:
        """Device-resident shrinkage scalar, re-uploaded (explicitly)
        only when the learning rate changes (reset_parameter callback):
        passing the Python float each iteration was one implicit
        host→device transfer per tree."""
        cached = getattr(self, "_shrink_cache", None)
        if cached is None or cached[0] != self.shrinkage_rate:
            cached = (self.shrinkage_rate,
                      jax.device_put(np.float32(self.shrinkage_rate)))
            self._shrink_cache = cached
        return cached[1]

    # ------------------------------------------------------------------
    def _flush_pending(self) -> None:
        """Materialize the pipelined tree from the previous iteration
        (see train_one_iter: the packed-tree device→host transfer is
        overlapped with the next iteration's work — on remote-attached
        TPUs the fetch round-trip alone costs ~70 ms)."""
        if getattr(self, "_pending", None) is None:
            return
        packed, slot, shrink = self._pending
        self._pending = None
        from .. import profiling
        from ..learner.fused import unpack_tree_arrays, tree_arrays_to_host
        with profiling.phase("collect_tree"):
            # explicit fetch (jax.device_get, not np.asarray): the packed
            # vector was copy_to_host_async'd an iteration ago, and the
            # explicit API keeps the transfer-guarded hot path clean.
            # The device builds one tree at a time, so this is where the
            # host waits for the previous iteration to finish
            with profiling.phase("wait_device"):
                vec = jax.device_get(packed)
            arrs = unpack_tree_arrays(vec, self.config.num_leaves)
            tree = tree_arrays_to_host(arrs, self.train_set,
                                       self.config.num_leaves)
            tree.apply_shrinkage(shrink)
            self.models[slot] = tree
            if tree.num_leaves <= 1:
                self._pending_stop = True

    def _can_pipeline(self) -> bool:
        import jax
        return (self.K == 1
                and hasattr(self.learner, "train_device")
                and self.__class__.__name__ in ("GBDT", "GOSS")
                # multi-process training keeps the sync path: the
                # pipelined device-side score update would need local
                # shard extraction from the global leaf_id
                and jax.process_count() == 1)

    def _train_one_iter_pipelined(self) -> bool:
        """Boosting iteration with a one-iteration-delayed tree fetch: the
        packed tree's device→host transfer overlaps the NEXT iteration's
        gradient/build/score work instead of stalling on the round-trip."""
        from .. import profiling
        self._collect_previous()
        if getattr(self, "_pending_stop", False):
            self._pending_stop = False
            self.models.pop()
            self.iter_ -= 1
            import warnings
            warnings.warn("Stopped training because there are no more "
                          "leaves that meet the split requirements.")
            return True
        self._boost_from_average()
        with profiling.phase("boosting"):
            gradient, hessian = self.boosting_gradients()
        with profiling.phase("bagging"):
            self._bagging(self.iter_)
        bag = (self.bag_idx
               if self.need_bagging and self.bag_cnt < self.num_data
               else None)
        with profiling.phase("tree"):
            # K == 1 here (_can_pipeline): reshape instead of [0] — the
            # eager integer index lowers to dynamic_slice and uploads
            # its start index host→device every iteration
            packed, leaf_id, arrs = self.learner.train_device(
                gradient.reshape(-1), hessian.reshape(-1), bag,
                self.bag_cnt if bag is not None else None)
        with profiling.phase("score"):
            from .score_updater import shrink_clip_leaves
            lv = shrink_clip_leaves(arrs.leaf_value, arrs.num_leaves,
                                    self._shrink_dev())
            self.train_score.add_tree_by_leaf_id_dev(leaf_id, lv, 0)
            # valid sets stay on the fast path too: traverse the device
            # TreeArrays directly (no host tree, no pipeline stall)
            for _, _, su, _ in self.valid_sets:
                su.add_tree_arrays_dev(arrs, lv, 0)
        # the DELIBERATE transfer of the pipelined design: start the
        # packed tree's device→host copy now so next iteration's
        # device_get finds it done.  Marked explicitly allowed so the
        # sanitizer's disallow-guard (diagnostics/sanitize.py) doesn't
        # count the prefetch as an accidental sync on backends that
        # guard device→host.
        with jax.transfer_guard("allow"):
            packed.copy_to_host_async()
        self.models.append(None)      # placeholder until _flush_pending
        self._pending = (packed, len(self.models) - 1, self.shrinkage_rate)
        self.iter_ += 1
        return False

    # -- per-iteration telemetry (docs/Observability.md) ---------------

    def _telemetry_iter_begin(self) -> None:
        """Snapshot host-side accumulators so the end-of-iteration
        record can report deltas.  Costs one cached check when
        telemetry is off; never touches the device either way — the
        pipelined path's zero-sync contract holds with telemetry on.
        Deliberate: iterations that ABORT (no splittable leaves — the
        trees are popped and iter_ rolled back) emit no record; only
        completed iterations exist in the stream, matching the model
        they describe."""
        from .. import telemetry
        if not telemetry.enabled():
            self._telem_t0 = None
            return
        from .. import profiling
        self._telem_t0 = time.perf_counter()
        self._telem_phases = profiling.timings()
        self._telem_ctrs = profiling.counters_nosync("tree/")

    def _telemetry_iter_end(self) -> None:
        t0 = getattr(self, "_telem_t0", None)
        if t0 is None:
            return
        from .. import profiling, telemetry
        dt = time.perf_counter() - t0
        phases = profiling.timings()
        ctrs = profiling.counters_nosync("tree/")
        ph = {}
        for k, v in phases.items():
            d = v - self._telem_phases.get(k, 0.0)
            if d > 1e-9:
                ph[k] = round(d, 6)
        # host-visible deltas only: count_deferred device totals fold
        # in at the next drain (a /metrics scrape or bench read), so on
        # the pipelined path these lag rather than force a sync
        deltas = {k.rsplit("/", 1)[-1]: round(v - self._telem_ctrs.get(k,
                                                                       0.0),
                                              1)
                  for k, v in ctrs.items()}
        telemetry.event("train.iteration", iteration=self.iter_,
                        trees=len(self.models), rows=self.num_data,
                        seconds=round(dt, 6), phases=ph,
                        counters=deltas)

    def _telemetry_eval(self, out: List) -> None:
        """Eval results ride the span stream too — emitted only where
        the caller already materialized them (ONE batched device_get),
        so telemetry never adds a sync of its own."""
        from .. import telemetry
        if out and telemetry.enabled():
            telemetry.event("train.eval", iteration=self.iter_,
                            results=[[s, n, v] for s, n, v, _ in out])

    def train_one_iter(self, gradient: Optional[jax.Array] = None,
                       hessian: Optional[jax.Array] = None,
                       is_eval: bool = False) -> bool:
        """One boosting iteration.  Returns True when training should stop
        (early stopping or no splittable leaves)."""
        from .. import profiling
        self._telemetry_iter_begin()
        if gradient is None and hessian is None and self._can_pipeline():
            if self._train_one_iter_pipelined():
                return True
            stop = (self.eval_and_check_early_stopping() if is_eval
                    else False)
            self._telemetry_iter_end()
            return stop
        self._flush_pending()
        self._boost_from_average()
        if gradient is None or hessian is None:
            with profiling.phase("boosting"):
                gradient, hessian = self.boosting_gradients()
        with profiling.phase("bagging"):
            self._bagging(self.iter_)

        should_continue = False
        bag = self.bag_idx if (self.need_bagging and self.bag_cnt < self.num_data) else None
        from .score_updater import select_class_row
        for k in range(self.K):
            if self.class_need_train[k]:
                with profiling.phase("tree"):
                    tree, leaf_id = self.learner.train(
                        select_class_row(gradient, k=k),
                        select_class_row(hessian, k=k), bag,
                        self.bag_cnt if bag is not None else None)
            else:
                tree = Tree(2)
                leaf_id = None
            if tree.num_leaves > 1:
                should_continue = True
                tree.apply_shrinkage(self.shrinkage_rate)
                with profiling.phase("score"):
                    if leaf_id is not None and (
                            bag is None
                            or getattr(self.learner, "full_leaf_id", False)):
                        self.train_score.add_tree_by_leaf_id(tree, leaf_id, k)
                    else:
                        self.train_score.add_tree(tree, k)
                for _, _, su, _ in self.valid_sets:
                    su.add_tree(tree, k)
            else:
                if (not self.class_need_train[k]
                        and len(self.models) < self.K):
                    out = self.class_default_output[k]
                    tree.leaf_value[0] = out
                    self.train_score.add_constant(out, k)
                    for _, _, su, _ in self.valid_sets:
                        su.add_constant(out, k)
            self.models.append(tree)

        if not should_continue:
            import warnings
            warnings.warn("Stopped training because there are no more leaves "
                          "that meet the split requirements.")
            for _ in range(self.K):
                self.models.pop()
            return True
        self.iter_ += 1
        stop = self.eval_and_check_early_stopping() if is_eval else False
        self._telemetry_iter_end()
        return stop

    def _collect_previous(self) -> None:
        """What a pipelined iteration waits for before it allocates
        anything: the previous tree and, on a mesh, the previous score
        update.

        `_flush_pending` returns when the previous BUILD has finished.
        The score update queued behind it runs a few ms longer and
        holds two row vectors a shard — the leaf ids and the score
        before it — that are freed when it ends: about when the host,
        woken by the tree, allocates this iteration's gradients, leaf
        ids and score.  On a mesh, which comes first is a race of host
        against device, and a process's `peak_bytes_in_use` read one
        row vector more or less from run to run (54 MB of 5.2 GB at
        54M rows over four chips: PERF.md §6).  Waiting for the update
        takes the race away and both vectors off every iteration's
        peak; the update no longer hides the host's dispatches, which
        read as nothing there (the host unpacks the tree meanwhile) and
        as 1.5-2 ms an iteration on one device.  One device keeps the
        overlap: there the update outlasts the dispatches in every
        iteration, and the peak is steady as it is.

        (Defined down here and called on the line where
        `_flush_pending()` stood: the Mosaic kernel bodies carry the
        lines of this file's frames above the build — PERF.md §6.)"""
        self._flush_pending()
        if self.train_score.layout.sharding is not None:
            from .. import profiling
            with profiling.phase("wait_device"):
                jax.block_until_ready(self.train_score.rows)

    def rollback_one_iter(self) -> None:
        self._flush_pending()
        if self.iter_ <= 0:
            return
        for k in range(self.K):
            tree = self.models[-self.K + k]
            tree.apply_shrinkage(-1.0)
            self.train_score.add_tree(tree, k)
            for _, _, su, _ in self.valid_sets:
                su.add_tree(tree, k)
        del self.models[-self.K:]
        self.iter_ -= 1

    # ------------------------------------------------------------------
    def _eval_one_set(self, set_name: str, su: ScoreUpdater,
                      ms: List[Metric], out: List) -> None:
        """Device metric kernels first (lazy device scalars — see
        _materialize_evals); host fallback fetches the score vector at
        most once per dataset."""
        host_score = None
        for m in ms:
            res = m.eval_device(su.score, self.objective)
            if res is None:
                if host_score is None:
                    host_score = su.get()
                res = m.eval(host_score, self.objective)
            for nm, v in res:
                out.append((set_name, nm, v, m.factor_to_bigger_better > 0))

    @staticmethod
    def _materialize_evals(out: List) -> List[Tuple[str, str, float, bool]]:
        """Resolve collected (set, name, value, bigger_better) rows whose
        values may still be 0-d device scalars with ONE batched
        jax.device_get.  The old contract (each metric float()ing its
        own result) cost one blocking device→host round-trip per metric
        per iteration — the per-iteration pipeline stall the sanitizer's
        transfer guard flags; V valid sets × M metrics now cost exactly
        one sync."""
        if not out:
            return out
        vals = jax.device_get([v for _, _, v, _ in out])
        return [(s, n, float(v), b)
                for (s, n, _, b), v in zip(out, vals)]

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        from .. import profiling
        out: List = []
        with profiling.phase("metric"):
            self._eval_one_set("training", self.train_score,
                               self.train_metrics, out)
            out = self._materialize_evals(out)
        self._telemetry_eval(out)
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        from .. import profiling
        out: List = []
        with profiling.phase("metric"):
            for name, _, su, ms in self.valid_sets:
                self._eval_one_set(name, su, ms, out)
            out = self._materialize_evals(out)
        self._telemetry_eval(out)
        return out

    def eval_and_check_early_stopping(self, results=None) -> bool:
        """CLI-path early stopping (gbdt.cpp:472-578): stop when no valid
        metric improved for early_stopping_round iterations.  `results`
        lets a caller that already evaluated (for logging) avoid a second
        full metric pass."""
        esr = self.config.early_stopping_round
        if esr <= 0:
            return False
        res = self.eval_valid() if results is None else results
        if not res:
            return False
        st = self._early_stopping_state
        improved = False
        for name, metric, value, bigger_better in res:
            key = (name, metric)
            cmp = value if bigger_better else -value
            if key not in st or cmp > st[key][0]:
                st[key] = (cmp, self.iter_)
                improved = True
        best_iter = max(v[1] for v in st.values())
        if self.iter_ - best_iter >= esr:
            self._flush_pending()   # materialize before dropping models
            n_drop = (self.iter_ - best_iter) * self.K
            del self.models[-n_drop:]
            self.iter_ = best_iter
            return True
        return False

    # ------------------------------------------------------------------
    @property
    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        extra = 1 if self.boost_from_average_used else 0
        return (len(self.models) - extra) // self.K

    # batch-size/ensemble-size product above which prediction moves to the
    # stacked device walk (ops/predict.py); small calls keep the host f64
    # walk (no jit latency, reference-exact double comparisons)
    _DEVICE_PREDICT_MIN_WORK = 2_000_000
    _PREDICT_CHUNK = 262_144

    def _cache_predict_stack(self, key, value):
        """Bounded-size put: the stack cache never outgrows a few model
        generations (stale generations evict wholesale)."""
        if len(self._predict_stack_cache) >= 4 * max(self.K, 1):
            self._predict_stack_cache.clear()
        self._predict_stack_cache[key] = value
        return value

    def _run_chunked(self, X: np.ndarray, out: np.ndarray, kernel_fn):
        """Shared device-predict chunk loop: full `_PREDICT_CHUNK` slabs
        plus ONE padded remainder, so the jitted kernel only ever sees
        one compiled shape.  `kernel_fn` maps a [chunk, F] f32 slab to
        device values whose LAST axis is rows; rows land in
        ``out[..., a:b]``."""
        import jax.numpy as jnp
        n = X.shape[0]
        CHUNK = self._PREDICT_CHUNK
        for a in range(0, n, CHUNK):
            b = min(a + CHUNK, n)
            chunk = X[a:b]
            if b - a < CHUNK and n > CHUNK:
                chunk = np.pad(chunk, ((0, CHUNK - (b - a)), (0, 0)))
            vals = kernel_fn(jnp.asarray(chunk, jnp.float32))
            out[..., a:b] = jax.device_get(vals)[..., : b - a]

    def _predict_raw_device(self, X: np.ndarray, used: int) -> np.ndarray:
        """Stacked-ensemble device predictor (predictor.hpp:24-159 is the
        reference's parallel batch path; here all trees × all rows advance
        one level per step on device).  f32 feature/threshold compares —
        the same single-precision trade the reference GPU learner makes
        (docs/GPU-Performance.md:130-134).

        ``predict_kernel=tensorized`` (the `auto` resolution) traverses
        ALL classes' trees in one fused program; ``walk`` keeps the
        per-class vmapped walk.
        """
        from ..ops.predict import (stack_trees, predict_trees,
                                   resolve_predict_kernel)
        kernel = resolve_predict_kernel(self.config.predict_kernel)
        if kernel == "tensorized":
            return self._predict_raw_device_tensorized(X, used)
        n = X.shape[0]
        out = np.zeros((self.K, n), np.float64)
        for k in range(self.K):
            key = (used, k, len(self.models))
            cached = self._predict_stack_cache.get(key)
            if cached is None:
                trees = [self.models[i] for i in range(used)
                         if i % self.K == k]
                if not trees:
                    continue
                stack = stack_trees(trees, binned=False)
                depth = max((t.max_depth_grown for t in trees), default=1)
                cached = self._cache_predict_stack(
                    key, (stack, max(depth, 1)))
            stack, depth = cached
            self._run_chunked(
                X, out[k],
                lambda c, _s=stack, _d=depth: predict_trees(_s, c, depth=_d))
        return out[0] if self.K == 1 else out.T

    def _predict_raw_device_tensorized(self, X: np.ndarray,
                                       used: int) -> np.ndarray:
        """One ensemble-wide traversal program for all classes (ops/
        predict.py predict_ensemble_any): `depth` fused steps instead of
        one walk per class."""
        from ..ops.predict import build_ensemble, predict_ensemble_any
        n = X.shape[0]
        key = ("ens", used, len(self.models))
        cached = self._predict_stack_cache.get(key)
        if cached is None:
            trees_by_class = [
                [self.models[i] for i in range(used) if i % self.K == k]
                for k in range(self.K)]
            stack, meta = build_ensemble(trees_by_class, binned=False)
            cached = self._cache_predict_stack(
                key, (jax.device_put(stack), meta))
        stack, meta = cached
        out = np.zeros((self.K, n), np.float64)
        self._run_chunked(
            X, out,
            lambda c: predict_ensemble_any(stack, c, meta=meta))
        return out[0] if self.K == 1 else out.T

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        self._flush_pending()
        """Raw scores for a dense matrix (rows, raw features) -> [N] or [N, K]."""
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        n = X.shape[0]
        used = self._num_used_models(num_iteration)
        force = os.environ.get("LIGHTGBM_TPU_DEVICE_PREDICT", "")
        use_dev = (force != "0"
                   and (force == "1"
                        or n * max(used, 1) >= self._DEVICE_PREDICT_MIN_WORK))
        if use_dev and used > 0:
            return self._predict_raw_device(X, used)
        out = np.zeros((self.K, n), np.float64)
        for i in range(used):
            out[i % self.K] += self.models[i].predict_raw(X)
        return out[0] if self.K == 1 else out.T

    def predict(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration)
        if self.objective is not None:
            return self.objective.convert_output(raw)
        return raw

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = -1
                           ) -> np.ndarray:
        """Leaf index per (row, model) — [N, num_models] int32.

        ``predict_kernel=walk`` is the host per-tree walk (exact f64
        compares); ``tensorized`` routes through the device ensemble
        leaf traversal (ops/predict.predict_ensemble_leaf) under the
        same work gating as predict_raw.  The two return IDENTICAL
        indices (tests/test_online.py leaf-parity suite): the device
        stack is built one-class-per-tree in MODEL order (the class-
        major flatten of the value kernels would silently permute
        multiclass models' columns), and the device categorical compare
        carries the host's explicit finite mask.
        """
        self._flush_pending()
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        used = self._num_used_models(num_iteration)
        from ..ops.predict import resolve_predict_kernel
        kernel = resolve_predict_kernel(self.config.predict_kernel)
        force = os.environ.get("LIGHTGBM_TPU_DEVICE_PREDICT", "")
        n = X.shape[0]
        use_dev = (kernel == "tensorized" and used > 0 and force != "0"
                   and (force == "1"
                        or n * used >= self._DEVICE_PREDICT_MIN_WORK))
        if use_dev:
            return self._predict_leaf_device(X, used)
        return np.stack([self.models[i].predict_leaf_index(X)
                         for i in range(used)], axis=1)

    def _predict_leaf_device(self, X: np.ndarray, used: int) -> np.ndarray:
        """Tensorized leaf routing: ONE ensemble traversal for all
        models (model-order stack, [T, N] leaves), chunked like the
        value kernels."""
        from ..ops.predict import predict_ensemble_leaf, stack_ensemble
        key = ("leaf", used, len(self.models))
        cached = self._predict_stack_cache.get(key)
        if cached is None:
            stack, meta = stack_ensemble(
                [[self.models[i]] for i in range(used)], binned=False)
            cached = self._cache_predict_stack(
                key, (jax.device_put(stack), meta))
        stack, meta = cached
        out = np.zeros((used, X.shape[0]), np.int32)
        self._run_chunked(
            X, out, lambda c: predict_ensemble_leaf(stack, c, meta=meta))
        return np.ascontiguousarray(out.T)

    def _num_used_models(self, num_iteration: int) -> int:
        n = len(self.models)
        if num_iteration > 0:
            ni = num_iteration + (1 if self.boost_from_average_used else 0)
            n = min(ni * self.K, n)
        return n

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split"
                           ) -> Dict[str, float]:
        """Per-feature importance (gbdt.cpp:850-872 split counts; "gain"
        sums split_gain per feature, the reference C API's
        importance_type=1)."""
        self._flush_pending()
        cnt = np.zeros(self.max_feature_idx + 1, np.float64)
        for t in self.models:
            for i in range(t.num_leaves - 1):
                if importance_type == "gain":
                    cnt[t.split_feature[i]] += float(t.split_gain[i])
                else:
                    cnt[t.split_feature[i]] += 1
        pairs = [(float(c), self.feature_names[i]
                  if i < len(self.feature_names) else f"Column_{i}")
                 for i, c in enumerate(cnt) if c > 0]
        pairs.sort(key=lambda p: -p[0])
        if importance_type == "gain":
            return {name: c for c, name in pairs}
        return {name: int(c) for c, name in pairs}

    def sub_model_name(self) -> str:
        return "tree"

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        self._flush_pending()
        """LightGBM-compatible model text (gbdt.cpp:694-738)."""
        buf = io.StringIO()
        buf.write(self.sub_model_name() + "\n")
        buf.write(f"num_class={self.num_class}\n")
        buf.write(f"num_tree_per_iteration={self.K}\n")
        buf.write(f"label_index={self.label_idx}\n")
        buf.write(f"max_feature_idx={self.max_feature_idx}\n")
        if self.objective is not None:
            buf.write(f"objective={self.objective.to_string()}\n")
        if self.boost_from_average_used:
            buf.write("boost_from_average\n")
        buf.write("feature_names=" + " ".join(self.feature_names) + "\n")
        buf.write("feature_infos=" + " ".join(self.feature_infos) + "\n")
        buf.write("\n")
        used = self._num_used_models(num_iteration)
        for i in range(used):
            buf.write(f"Tree={i}\n")
            buf.write(self.models[i].to_string())
            buf.write("\n")
        buf.write("\nfeature importances:\n")
        for name, c in self.feature_importance().items():
            buf.write(f"{name}={c}\n")
        return buf.getvalue()

    def save_model_to_file(self, filename: str, num_iteration: int = -1) -> None:
        with open(filename, "w") as f:
            f.write(self.save_model_to_string(num_iteration))

    def load_model_from_string(self, model_str: str) -> None:
        """gbdt.cpp:752-848."""
        lines = model_str.splitlines()

        def find(prefix):
            for ln in lines:
                if ln.startswith(prefix):
                    return ln[len(prefix):].strip()
            return None

        nc = find("num_class=")
        if nc is not None:
            self.num_class = int(nc)
        k = find("num_tree_per_iteration=")
        self.K = int(k) if k is not None else self.num_class
        li = find("label_index=")
        if li is not None:
            self.label_idx = int(li)
        mf = find("max_feature_idx=")
        if mf is not None:
            self.max_feature_idx = int(mf)
        obj = find("objective=")
        if obj:
            self.objective = objective_from_model_string(obj, self.config)
        self.boost_from_average_used = any(
            ln.strip() == "boost_from_average" for ln in lines)
        fn = find("feature_names=")
        if fn:
            self.feature_names = fn.split()
        fi = find("feature_infos=")
        if fi:
            self.feature_infos = fi.split()
        # trees
        self.models = []
        text = "\n".join(lines)
        parts = text.split("Tree=")
        for p in parts[1:]:
            body = p.split("\n", 1)[1] if "\n" in p else ""
            stop = body.find("\nfeature importances")
            if stop >= 0:
                body = body[:stop]
            self.models.append(Tree.from_string(body))
        extra = 1 if self.boost_from_average_used else 0
        self.num_init_iteration = (len(self.models) - extra) // max(self.K, 1)
        self.iter_ = 0

    def to_json(self) -> Dict:
        """Field-for-field parity with the reference's DumpModel
        (gbdt.cpp:658-692): name, num_class, num_tree_per_iteration,
        label_index, max_feature_idx, feature_names, tree_info with a
        tree_index per entry; per-tree fields from Tree::ToJSON
        (tree.cpp:326-365).  `objective` is an extension (the reference
        omits it from the dump but needs it to reload)."""
        self._flush_pending()
        return {
            "name": self.sub_model_name(),
            "num_class": self.num_class,
            "num_tree_per_iteration": self.K,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": self.objective.to_string() if self.objective else "",
            "feature_names": self.feature_names,
            "tree_info": [dict(tree_index=i, **t.to_json())
                          for i, t in enumerate(self.models)],
        }

    # -- checkpoint / resume (docs/Robustness.md) ----------------------

    def _extra_training_state(self) -> Dict:
        """Subclass hook: sampler/boosting state beyond the base GBDT's
        (GOSS key, DART drop RNG + tree weights)."""
        return {}

    def _restore_extra_training_state(self, state: Dict) -> None:
        pass

    def training_state(self) -> Dict:
        """Everything a resumed run needs to continue BITWISE where this
        one stands: the model text, the iteration/continuation counters,
        the early-stopping bests, and the exact sampler RNG state (a
        re-seeded RNG would re-draw the first bags and fork the run)."""
        self._flush_pending()
        state = {
            "version": CHECKPOINT_VERSION,
            "fingerprint": config_fingerprint(self.config),
            "boosting": self.sub_model_name(),
            "iteration": self.iter_,
            "num_init_iteration": self.num_init_iteration,
            "shrinkage_rate": self.shrinkage_rate,
            "early_stopping": [
                [name, metric, cmp, it]
                for (name, metric), (cmp, it)
                in self._early_stopping_state.items()],
            "bag_rng": _rng_state_to_json(self.bag_rng),
            "model": self.save_model_to_string(),
        }
        state.update(self._extra_training_state())
        return state

    def save_checkpoint(self, path: str,
                        extra: Optional[Dict] = None) -> None:
        """Atomic snapshot: tmp + os.replace, so a crash mid-write
        leaves the PREVIOUS checkpoint intact, never a torn one.
        ``extra`` rides along in the state dict (the CLI records a
        ``finished`` marker so reruns of a completed command no-op)."""
        from .. import log, telemetry
        from ..diagnostics import faults
        state = self.training_state()
        if extra:
            state.update(extra)
        with telemetry.span("train.checkpoint", path=path,
                            iteration=self.iter_,
                            trees=len(self.models)):
            payload = json.dumps(state)
            faults.torn_write("train.checkpoint", path, payload)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(payload)
            os.replace(tmp, path)
        log.debug(f"checkpoint saved to {path} (iteration {self.iter_}, "
                  f"{len(self.models)} trees)")
        faults.check("train.after_checkpoint")

    def restore_training_state(self, state: Dict) -> None:
        """Apply a checkpoint's counters + RNG state.  Call AFTER
        ``load_model_from_string(state['model'])`` + ``reset_training_data``
        (which replays the restored trees onto the training/valid
        scores) — this restores what the replay cannot."""
        from ..log import LightGBMError
        fp = config_fingerprint(self.config)
        if state.get("fingerprint") != fp:
            raise LightGBMError(
                "checkpoint was written under a different training "
                "config (fingerprint mismatch); resuming would silently "
                "mix recipes — delete the checkpoint to start fresh, or "
                "restore the original parameters")
        if state.get("boosting") != self.sub_model_name():
            raise LightGBMError(
                f"checkpoint holds a {state.get('boosting')!r} model, "
                f"this run is {self.sub_model_name()!r}")
        self.iter_ = int(state["iteration"])
        self.num_init_iteration = int(state.get("num_init_iteration", 0))
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self._early_stopping_state = {
            (name, metric): (float(cmp), int(it))
            for name, metric, cmp, it in state.get("early_stopping", [])}
        if state.get("bag_rng"):
            self.bag_rng.set_state(_rng_state_from_json(state["bag_rng"]))
        self._restore_extra_training_state(state)

    def resume_from_checkpoint(self, state: Dict, train_set: Dataset,
                               objective: Optional[Objective] = None) -> int:
        """One-call resume: load the checkpoint model, replay it onto
        fresh training scores, restore counters/RNG.  Returns the
        iteration to continue from.  Valid sets added AFTER this call
        replay the restored model automatically (add_valid does)."""
        from .. import telemetry
        with telemetry.span(
                "train.resume",
                checkpoint_iteration=int(state.get("iteration", 0))) as sp:
            self.load_model_from_string(state["model"])
            self._replay_kernel = "walk"  # order-exact replay (__init__)
            self.reset_training_data(train_set, objective)
            self.restore_training_state(state)
            sp.set(trees=len(self.models))
        return self.iter_


def create_boosting(config: Config, model_file: str = "") -> "GBDT":
    """Factory (boosting.cpp:29-71): gbdt | dart | goss, with model-file
    resume reading the first line as the submodel type."""
    from .dart import DART
    from .goss import GOSS
    table = {"gbdt": GBDT, "tree": GBDT, "dart": DART, "goss": GOSS}
    btype = config.boosting_type
    model_str = ""
    if model_file:
        with open(model_file) as f:
            model_str = f.read()
        first = model_str.split("\n", 1)[0].strip()
        if first in table:
            btype = first
    if btype not in table:
        raise ValueError(f"unknown boosting type: {btype}")
    gbdt = table[btype](config)
    if model_str:
        gbdt.load_model_from_string(model_str)
    return gbdt
