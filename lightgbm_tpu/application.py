"""CLI application: `python -m lightgbm_tpu key=value… [config=train.conf]`.

Mirrors the reference Application (/root/reference/src/application/
application.cpp:46-248, main.cpp): parse key=value argv + config file,
task=train → load data/valid sets, boost with per-iteration metric output
and wall-clock logging, save model; task=predict → batch-score a data file
to output_result; task=serve → online JSON-lines HTTP scoring
(lightgbm_tpu/serving/).  The reference examples' train.conf/predict.conf
run unmodified.
"""
from __future__ import annotations

import sys
import time
from typing import List, Optional

import numpy as np

from . import log
from .basic import Booster, Dataset, LightGBMError
from .boosting.gbdt import create_boosting
from .config import (Config, check_param_conflict, config_from_params,
                     parse_cli_args)
from .dataset import Dataset as RawDataset, parse_text_file


def _log(cfg: Config, msg: str) -> None:
    log.info(msg)


def _label_idx(cfg: Config) -> int:
    """label_column → column index (dataset_loader.cpp:22-157 semantics:
    a bare index, or `name:<col>` which needs a header)."""
    if not cfg.label_column:
        return 0
    if cfg.label_column.startswith("name:"):
        raise LightGBMError(
            "label_column=name:<col> requires has_header=true data; "
            "name-based selection is not supported for prediction input")
    try:
        return int(cfg.label_column)
    except ValueError:
        raise LightGBMError(
            f"invalid label_column: {cfg.label_column!r}") from None


class Application:
    def __init__(self, argv: List[str]):
        params = parse_cli_args(argv)
        if not params:
            raise LightGBMError(
                "no parameters given; usage: python -m lightgbm_tpu "
                "config=train.conf [key=value ...]")
        self.params = params
        self.config = config_from_params(params)
        check_param_conflict(self.config)

    def run(self) -> None:
        cfg = self.config
        from . import telemetry
        # the task IS the process role: spans from a trainer, a daemon
        # and a serving fleet sharing one telemetry_path stay
        # distinguishable (and land in separate chrome-trace pid lanes)
        telemetry.set_process(cfg.task)
        # standalone Prometheus /metrics for roles without their own
        # HTTP server; task=serve and task=route mount the same payload
        # on their own endpoints instead (serving/server.py,
        # router/server.py)
        metrics_srv = None
        if cfg.metrics_port and cfg.task not in ("serve", "serving",
                                                 "route", "router"):
            metrics_srv = telemetry.start_metrics_server(
                cfg.metrics_port, host=cfg.serve_host)
        try:
            if cfg.task == "train":
                self._train()
            elif cfg.task in ("predict", "prediction", "test"):
                self._predict()
            elif cfg.task in ("serve", "serving"):
                self._serve()
            elif cfg.task in ("route", "router"):
                self._route()
            elif cfg.task in ("online", "online_train"):
                self._online()
            elif cfg.task in ("refit", "refit_tree"):
                self._refit()
            else:
                raise LightGBMError(f"unknown task: {cfg.task}")
        finally:
            if metrics_srv is not None:
                metrics_srv.close()

    # ------------------------------------------------------------------
    def _train(self) -> None:
        cfg = self.config
        if not cfg.data:
            raise LightGBMError("no training data: set data=<file>")
        # multi-host bootstrap BEFORE any device use — the analog of the
        # reference's Network::Init at InitTrain (application.cpp:185-197)
        if cfg.num_machines > 1:
            from .distributed import maybe_init_from_config
            if maybe_init_from_config(cfg):
                import jax
                _log(cfg, f"initialized {cfg.num_machines}-process world, "
                          f"{len(jax.devices())} global devices")
        t0 = time.time()
        train_raw = RawDataset.from_file(cfg.data, cfg)
        if cfg.is_save_binary_file and not RawDataset._is_binary_file(
                cfg.data):
            train_raw.save_binary(cfg.data + ".bin")
            _log(cfg, f"saved binary dataset cache to {cfg.data}.bin")
        _log(cfg, f"finished loading data in {time.time() - t0:.6f} seconds")
        _log(cfg, f"number of data: {train_raw.num_data}, number of "
                  f"features: {train_raw.num_features}")

        # checkpoint resume: a prior run's snapshot replaces input_model
        # (its trees INCLUDE whatever input_model seeded that run with)
        from .boosting.gbdt import load_checkpoint
        resume = (load_checkpoint(cfg.checkpoint_path)
                  if cfg.checkpoint_path else None)
        gbdt = create_boosting(cfg, "" if resume else cfg.input_model)
        from .objectives import create_objective
        objective = create_objective(cfg)
        start_it = 0
        if resume is not None:
            start_it = gbdt.resume_from_checkpoint(resume, train_raw,
                                                   objective)
            _log(cfg, f"resumed from checkpoint {cfg.checkpoint_path}: "
                      f"iteration {start_it}, {gbdt.num_trees} trees")
        else:
            gbdt.reset_training_data(train_raw, objective)
        for i, vpath in enumerate(cfg.valid_data):
            vraw = RawDataset.from_file(vpath, cfg, reference=train_raw)
            gbdt.add_valid(vraw, f"valid_{i + 1}")

        checkpointing = bool(cfg.checkpoint_path
                             and cfg.checkpoint_interval > 0)
        # an early-stopped run already rolled back past its best
        # iteration; resuming its loop would just retrain the dropped
        # tail until early stopping fires again — and the marker must
        # survive a no-op rerun, or the rerun-after-that retrains it
        resumed_early_stop = (resume is not None
                              and resume.get("finished") == "early_stop")
        if resumed_early_stop:
            start_it = cfg.num_iterations
        stopped_early = resumed_early_stop
        start = time.time()
        for it in range(start_it, cfg.num_iterations):
            stop = gbdt.train_one_iter(None, None, is_eval=False)
            printing = (cfg.verbose >= 1 and cfg.metric_freq > 0
                        and (it + 1) % cfg.metric_freq == 0)
            valid_res = (gbdt.eval_valid()
                         if printing or cfg.early_stopping_round > 0 else [])
            if cfg.early_stopping_round > 0:
                stop = stop or gbdt.eval_and_check_early_stopping(valid_res)
            if printing:
                for name, metric_name, val, _ in (
                        gbdt.eval_train() if cfg.is_training_metric else []):
                    _log(cfg, f"Iteration:{it + 1}, {name} {metric_name} : "
                              f"{val:g}")
                for name, metric_name, val, _ in valid_res:
                    _log(cfg, f"Iteration:{it + 1}, {name} {metric_name} : "
                              f"{val:g}")
            _log(cfg, f"{time.time() - start:.6f} seconds elapsed, finished "
                      f"iteration {it + 1}")
            if checkpointing and (it + 1) % cfg.checkpoint_interval == 0:
                gbdt.save_checkpoint(cfg.checkpoint_path)
            if stop:
                _log(cfg, "early stopping")
                stopped_early = True
                break
        if checkpointing:
            # final snapshot so a rerun after completion is a no-op
            # resume instead of re-training the tail after the last
            # periodic snapshot (early_stop marks the rolled-back run)
            gbdt.save_checkpoint(cfg.checkpoint_path, extra={
                "finished": "early_stop" if stopped_early else "complete"})
        gbdt.save_model_to_file(cfg.output_model)
        _log(cfg, f"finished training, model saved to {cfg.output_model}")
        if cfg.serve_quantize != "raw":
            # ship the frozen-mapper sidecar beside the model so the
            # serving registry (and the online daemon, which adopts it)
            # can quantize requests against the model's OWN training
            # mappers — the refbin contract behind serve_quantize=binned
            try:
                train_raw.save_refbin(cfg.output_model + ".refbin")
                _log(cfg, "frozen bin mappers saved to "
                          f"{cfg.output_model}.refbin")
            except OSError as e:
                log.warning(f"could not save the refbin sidecar "
                            f"({type(e).__name__}: {e}); binned serving "
                            "of this model will fall back to raw")

    # ------------------------------------------------------------------
    def _predict(self) -> None:
        cfg = self.config
        if not cfg.data:
            raise LightGBMError("no prediction data: set data=<file>")
        if not cfg.input_model:
            raise LightGBMError("no model: set input_model=<file>")
        # one Booster + one compiled-predictor runtime for the whole
        # task: every file/chunk shares the stacked trees and the warm
        # executables instead of rebuilding the TreeStack per call
        bst = Booster(model_file=cfg.input_model)
        predictor = Predictor(bst, raw_score=cfg.is_predict_raw_score,
                              leaf_index=cfg.is_predict_leaf_index,
                              num_iteration=cfg.num_iteration_predict,
                              predict_kernel=cfg.predict_kernel,
                              serve_quantize=cfg.serve_quantize,
                              refbin=cfg.input_model + ".refbin")
        predictor.predict_file(cfg.data, cfg.output_result,
                               has_header=cfg.has_header,
                               label_idx=_label_idx(cfg))
        _log(cfg, f"finished prediction, results saved to "
                  f"{cfg.output_result}")

    # ------------------------------------------------------------------
    def _serve(self) -> None:
        from .serving.server import serve_from_config
        serve_from_config(self.config)

    # ------------------------------------------------------------------
    def _route(self) -> None:
        """task=route: the stdlib-only router tier fronting M backend
        task=serve processes (lightgbm_tpu/router/, docs/Router.md) —
        consistent-hash tenant placement, per-backend circuit breakers,
        fleet-aggregated /stats + /metrics."""
        from .router import route_from_config
        route_from_config(self.config)

    # ------------------------------------------------------------------
    def _online(self) -> None:
        """task=online: the continuous refresh daemon (online/trainer.py)
        — watch a labeled-traffic JSONL, refit/continue on trigger,
        publish generations to the registry path.  With `serve_models`
        set, one daemon per catalog tenant shares the traffic tail
        (keyed rows, keyed publish paths — docs/serving.md
        "Multi-tenant catalog")."""
        from .online.trainer import OnlineFleet, OnlineTrainer
        if self.config.serve_models:
            OnlineFleet.from_config(self.config).run_forever()
        else:
            OnlineTrainer.from_config(self.config).run_forever()

    # ------------------------------------------------------------------
    def _refit(self) -> None:
        """task=refit (reference task=refit_tree): one-shot leaf-value
        refit of input_model on a labeled data file, saved to
        output_model."""
        cfg = self.config
        if not cfg.data:
            raise LightGBMError("no refit data: set data=<file>")
        if not cfg.input_model:
            raise LightGBMError("no model: set input_model=<file>")
        from .online.refit import refit_gbdt
        ds = RawDataset.from_file(cfg.data, cfg)
        gbdt = create_boosting(cfg, cfg.input_model)
        # plain text files re-parse cheaply, so route on the RAW
        # feature values (exact, Booster.refit parity); binary stores
        # and selector-remapped files keep the binned fallback
        leaf = None
        if (not RawDataset._is_binary_file(cfg.data)
                and not cfg.use_two_round_loading
                and not (cfg.weight_column or cfg.group_column
                         or cfg.ignore_column)):
            label_idx = (int(cfg.label_column) if cfg.label_column
                         and not cfg.label_column.startswith("name:")
                         else 0)
            X, _, _ = parse_text_file(cfg.data, cfg.has_header, label_idx)
            if len(X) == ds.num_data:
                leaf = gbdt.predict_leaf_index(X)
        stats = refit_gbdt(gbdt, ds, leaf_idx=leaf)
        gbdt.save_model_to_file(cfg.output_model)
        _log(cfg, f"refit {stats['trees_refit']} of {stats['trees']} "
                  f"trees on {stats['rows']} rows "
                  f"(decay {stats['decay_rate']:g}); model saved to "
                  f"{cfg.output_model}")


class Predictor:
    """Batch file prediction (reference predictor.hpp:24-159): parse the
    input file, score every row, write one prediction per line.

    Value/raw scoring runs through a shared `serving.PredictorRuntime`,
    so the CLI batch path and the online server hit the same compiled-
    executable cache: chunks are padded to power-of-two row buckets and
    never retrace on a leftover shape.  Leaf-index output keeps the host
    walk (exact int semantics, no device analog yet)."""

    def __init__(self, booster: Booster, raw_score: bool = False,
                 leaf_index: bool = False, num_iteration: int = -1,
                 runtime=None, predict_kernel=None,
                 serve_quantize: str = "raw", refbin=None):
        self.booster = booster
        self.raw_score = raw_score
        self.leaf_index = leaf_index
        self.num_iteration = num_iteration
        gbdt = getattr(booster, "_gbdt", booster)
        gbdt._flush_pending()
        if runtime is None and not leaf_index and gbdt.models:
            # zero-tree models keep the host path: Booster.predict
            # returns the baseline score, nothing to compile.  Batch
            # prediction shares the serving runtime, so it shares the
            # serve_quantize dial too (resolve_runtime owns the
            # auto/binned/raw policy): binned requires the model's
            # .refbin mapper sidecar, auto falls back to raw without one
            from .serving.runtime import resolve_runtime
            runtime = resolve_runtime(
                booster, serve_quantize=serve_quantize, refbin=refbin,
                num_iteration=num_iteration, max_batch_rows=262_144,
                predict_kernel=predict_kernel)
        self.runtime = runtime

    def predict(self, X: np.ndarray) -> np.ndarray:
        if self.leaf_index:
            return self.booster.predict(X, num_iteration=self.num_iteration,
                                        pred_leaf=True)
        if self.runtime is not None:
            return self.runtime.predict(
                X, kind="raw" if self.raw_score else "value")
        return self.booster.predict(X, num_iteration=self.num_iteration,
                                    raw_score=self.raw_score)

    def predict_file(self, data_path: str, out_path: str,
                     has_header: bool = False, label_idx: int = 0,
                     chunk_rows: int = 262_144) -> None:
        """Streaming file prediction: CSV/TSV inputs are read in chunks
        and scored chunk-by-chunk through the fixed-shape device
        predictor, so the full float64 matrix never exists — the analog
        of the reference's pipelined double-buffered reader
        (predictor.hpp:80-159, pipeline_reader.h).  Peak host memory is
        one chunk (~60 MB at 28 features) instead of ~2.4 GB for an
        11M-row file.  LibSVM keeps the one-shot parse (same trade as
        training-side ingestion, dataset.load_file_two_round)."""
        with open(out_path, "w") as f:
            for X in _iter_predict_chunks(data_path, has_header, label_idx,
                                          chunk_rows):
                preds = self.predict(X)
                if preds.ndim == 1:
                    f.writelines(f"{v:.17g}\n" for v in preds)
                else:
                    f.writelines(
                        "\t".join(f"{v:.17g}" for v in row) + "\n"
                        for row in preds)


def _iter_predict_chunks(data_path: str, has_header: bool, label_idx: int,
                         chunk_rows: int):
    """Yield [chunk, F] float64 feature blocks from a prediction file.
    CSV/TSV stream through pandas chunked reads; LibSVM (ragged, rare at
    predict-file scale) falls back to the one-shot parser."""
    from .dataset import _detect_format

    with open(data_path, "r") as f:
        first = f.readline()
        if not first:
            raise ValueError(f"empty data file: {data_path}")
        if has_header:
            first = f.readline() or first
    fmt = _detect_format(first)
    if fmt == "libsvm":
        X, _, _ = parse_text_file(data_path, has_header, label_idx)
        yield X
        return
    import pandas as pd
    # same fmt->sep mapping and '#'-comment handling as the one-shot
    # np.loadtxt parser (dataset.py parse_text_file)
    sep = "," if fmt == "csv" else r"\s+"
    for ch in pd.read_csv(data_path, sep=sep, comment="#",
                          header=0 if has_header else None,
                          chunksize=chunk_rows, dtype=np.float64):
        arr = ch.to_numpy(dtype=np.float64)
        yield np.delete(arr, label_idx, axis=1)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from .jaxutil import enable_compile_cache
    enable_compile_cache()
    try:
        Application(argv).run()
    except LightGBMError as e:
        print(f"[LightGBM-TPU] [Fatal] {e}", file=sys.stderr)
        return 1
    return 0
