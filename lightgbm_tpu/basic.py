"""User-facing Dataset / Booster API.

Mirrors the reference python package (/root/reference/python-package/
lightgbm/basic.py): `Dataset` with lazy construction, reference-alignment
for validation data, pandas & categorical handling (basic.py:536-1159);
`Booster` with update/eval/predict/save (basic.py:1160-1781).  There is no
ctypes/C-API hop: the "engine" underneath is the in-process JAX GBDT.
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config, apply_aliases, config_from_params
from .dataset import Dataset as _InnerDataset, Metadata
from .boosting.gbdt import GBDT, create_boosting
from .log import LightGBMError  # noqa: F401  (canonical error type)


_sparse_densify_warned = False


def _warn_sparse_densify(shape, chunk_rows: int = 0) -> None:
    """One-time warning when a scipy-sparse matrix is materialized dense
    (training avoids this via Dataset.from_csc; the prediction paths
    densify bounded row chunks).  Reports the estimated dense bytes —
    the whole matrix, and the actual per-chunk peak when the caller
    densifies in row slabs."""
    global _sparse_densify_warned
    if _sparse_densify_warned:
        return
    _sparse_densify_warned = True
    from . import log
    est = int(shape[0]) * int(shape[1]) * 8
    if chunk_rows and chunk_rows < shape[0]:
        peak = int(chunk_rows) * int(shape[1]) * 8
        log.warning(
            f"densifying a scipy sparse matrix of shape {tuple(shape)} "
            f"in {chunk_rows}-row chunks (~{peak / 1e6:.1f} MB peak per "
            f"chunk; {est / 1e6:.1f} MB = {est} bytes if whole, as "
            "float64); pass training data as-is to Dataset so the "
            "binner streams CSC columns instead")
        return
    log.warning(
        f"densifying a scipy sparse matrix of shape {tuple(shape)} "
        f"(~{est / 1e6:.1f} MB = {est} bytes as float64); pass training "
        "data as-is to Dataset so the binner streams CSC columns "
        "instead")


def _is_scipy_sparse(data) -> bool:
    return hasattr(data, "toarray") and hasattr(data, "tocsc")


def _to_numpy(data) -> np.ndarray:
    if hasattr(data, "values"):  # pandas DataFrame/Series
        return np.asarray(data.values, dtype=np.float64)
    if isinstance(data, (list, tuple)):
        return np.asarray(data, dtype=np.float64)
    if _is_scipy_sparse(data):
        _warn_sparse_densify(data.shape)
        return np.asarray(data.toarray(), dtype=np.float64)
    return np.asarray(data, dtype=np.float64)


def _read_last_line(path: str) -> str:
    """The final line of a file, scanning backwards in 1 MB chunks — the
    pandas_categorical trailer is exactly one line and can be arbitrarily
    large (high-cardinality categories), so no fixed tail cap is safe."""
    with open(path, "rb") as f:
        f.seek(0, 2)
        end = f.tell()
        buf = b""
        pos = end
        while pos > 0:
            step = min(1 << 20, pos)
            pos -= step
            f.seek(pos)
            buf = f.read(step) + buf
            stripped = buf.rstrip(b"\n")
            nl = stripped.rfind(b"\n")
            if nl >= 0:
                return stripped[nl + 1:].decode(errors="replace")
        return buf.rstrip(b"\n").decode(errors="replace")


def _load_pandas_categorical(model_tail: str):
    """Read the `pandas_categorical:<json>` trailer the save path appends
    (the reference stores the same trailer, basic.py save_model).
    `model_tail` may be just the end of the model text."""
    import json
    marker = "pandas_categorical:"
    pos = model_tail.rfind("\n" + marker)
    if pos < 0:
        if not model_tail.startswith(marker):
            return None
        pos = -1
    line = model_tail[pos + 1:].splitlines()[0]
    try:
        return json.loads(line[len(marker):])
    except json.JSONDecodeError:
        from . import log
        log.warning("model file has a corrupt pandas_categorical trailer; "
                    "categorical DataFrame prediction will be unavailable")
        return None


def _apply_pandas_categorical(data, pandas_categorical):
    """Map a prediction DataFrame's category columns to the TRAINING
    category codes (reference basic.py predict-time pandas handling):
    category order may differ between frames, so codes are re-derived
    from the stored training category lists; unseen categories map to -1
    like pandas' own missing-code convention."""
    if not (hasattr(data, "dtypes") and hasattr(data, "columns")):
        return data
    cat_cols = [c for c in data.columns
                if str(data[c].dtype) == "category"]
    if not cat_cols:
        return data
    if not pandas_categorical or len(cat_cols) != len(pandas_categorical):
        raise ValueError(
            "prediction data has pandas categorical columns but the "
            "model carries no matching training category lists")
    df = data.copy()
    for col, cats in zip(cat_cols, pandas_categorical):
        df[col] = df[col].cat.set_categories(cats).cat.codes.astype(
            np.float64)
    return df


def _resolve_categorical(data, categorical_feature, feature_name, params=None):
    """pandas categorical columns -> codes + column index list
    (reference basic.py:192-260 pandas handling)."""
    cat_cols: List[int] = _params_categorical(params, data, feature_name)
    pandas_categorical = None
    if hasattr(data, "dtypes") and hasattr(data, "columns"):
        import pandas as pd  # type: ignore
        df = data.copy()
        pandas_categorical = []
        for i, col in enumerate(df.columns):
            if str(df[col].dtype) == "category":
                pandas_categorical.append(list(df[col].cat.categories))
                df[col] = df[col].cat.codes.astype(np.float64)
                cat_cols.append(i)
        data = df
    if categorical_feature not in (None, "auto"):
        names = feature_name if feature_name not in (None, "auto") else None
        for c in categorical_feature:
            if isinstance(c, str) and names:
                cat_cols.append(names.index(c))
            elif isinstance(c, int):
                cat_cols.append(c)
    return data, sorted(set(cat_cols)), pandas_categorical


class Dataset:
    """Training/validation dataset with lazy construction."""

    def __init__(self, data, label=None, max_bin=None, reference=None,
                 weight=None, group=None, init_score=None, silent=False,
                 feature_name="auto", categorical_feature="auto", params=None,
                 free_raw_data=False):
        self.params: Dict[str, Any] = dict(params or {})
        if max_bin is not None:
            self.params.setdefault("max_bin", max_bin)
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self.pandas_categorical = None
        self._inner: Optional[_InnerDataset] = None
        self._raw_X: Optional[np.ndarray] = None

    # -- construction -------------------------------------------------------

    def construct(self, extra_params: Optional[Dict[str, Any]] = None
                  ) -> "Dataset":
        if self._inner is not None:
            return self
        merged = dict(self.params)
        if extra_params:
            for k, v in extra_params.items():
                merged.setdefault(k, v)
        cfg = config_from_params(merged)
        if isinstance(self.data, str):
            ref_inner = (self.reference.construct()._inner
                         if self.reference is not None else None)
            self._inner = _InnerDataset.from_file(self.data, cfg,
                                                  reference=ref_inner)
            self._raw_X = None
        else:
            data, cat_cols, self.pandas_categorical = _resolve_categorical(
                self.data, self.categorical_feature, self.feature_name, merged)
            y = None if self.label is None else _to_numpy(self.label).reshape(-1)
            md = Metadata()
            if self.weight is not None:
                md.weights = _to_numpy(self.weight).reshape(-1).astype(np.float32)
            if self.group is not None:
                md.set_query_from_sizes(_to_numpy(self.group).reshape(-1)
                                        .astype(np.int64))
            if self.init_score is not None:
                md.init_score = _to_numpy(self.init_score).reshape(-1)
            names = None
            if self.feature_name not in (None, "auto"):
                names = list(self.feature_name)
            elif hasattr(self.data, "columns"):
                names = [str(c) for c in self.data.columns]
            ref_inner = (self.reference.construct()._inner
                         if self.reference is not None else None)
            if _is_scipy_sparse(data):
                # stream CSC columns into the binner — the full dense
                # matrix never materializes (one-time warning covers the
                # remaining densifying call sites, e.g. predict)
                self._inner = _InnerDataset.from_csc(
                    data, y, cfg, metadata=md, feature_names=names,
                    categorical_feature=cat_cols, reference=ref_inner)
                self._raw_X = data if not self.free_raw_data else None
                return self
            X = _to_numpy(data)
            if X.ndim == 1:
                X = X.reshape(-1, 1)
            self._inner = _InnerDataset(
                X, y, cfg, reference=ref_inner, metadata=md,
                feature_names=names, categorical_feature=cat_cols)
            self._raw_X = X if not self.free_raw_data else None
        return self

    # -- reference-style helpers -------------------------------------------

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params or self.params)

    def set_label(self, label) -> None:
        self.label = label
        if self._inner is not None:
            self._inner.metadata.label = _to_numpy(label).astype(np.float32)

    def set_weight(self, weight) -> None:
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.weights = (
                None if weight is None
                else _to_numpy(weight).reshape(-1).astype(np.float32))

    def set_group(self, group) -> None:
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_query_from_sizes(
                _to_numpy(group).reshape(-1).astype(np.int64))

    def set_init_score(self, init_score) -> None:
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.init_score = (
                None if init_score is None
                else _to_numpy(init_score).reshape(-1))

    def get_label(self):
        self.construct()
        return np.asarray(self._inner.metadata.label)

    def get_weight(self):
        self.construct()
        return self._inner.metadata.weights

    def get_group(self):
        self.construct()
        qb = self._inner.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        self.construct()
        return self._inner.metadata.init_score

    def save_binary(self, filename: str) -> "Dataset":
        """Serialize the constructed binned dataset (reference
        basic.py save_binary → LGBM_DatasetSaveBinary)."""
        self.construct()
        self._inner.save_binary(filename)
        return self

    def save_refbin(self, filename: str) -> "Dataset":
        """Persist only the frozen bin-mapper set — the serving
        registry's ``.refbin`` sidecar for ``serve_quantize=binned``
        with offline-trained models (docs/serving.md)."""
        self.construct()
        self._inner.save_refbin(filename)
        return self

    def num_data(self) -> int:
        self.construct()
        return self._inner.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._inner.feature_names)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        """Row-subset dataset (reference Dataset.subset) — used by cv()."""
        self.construct()
        idx = np.asarray(used_indices, np.int64)
        if self._raw_X is None and not isinstance(self.data, str):
            raise LightGBMError("cannot subset when raw data was freed")
        if isinstance(self.data, str):
            raise LightGBMError("subset of file-backed Dataset not supported")
        sub = Dataset(self._raw_X[idx],
                      label=np.asarray(self.get_label())[idx],
                      reference=self, params=params or self.params)
        w = self.get_weight()
        if w is not None:
            sub.weight = np.asarray(w)[idx]
        return sub


class Booster:
    """The boosting model driver (reference basic.py:1160+)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False):
        params = dict(params or {})
        self.params = params
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._valid_names: List[str] = []
        self._valid_data: List["Dataset"] = []
        self.pandas_categorical = None
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set should be Dataset instance")
            train_set.construct(params)
            cfg = config_from_params(params)
            self._gbdt = create_boosting(cfg)
            self._gbdt.reset_training_data(train_set._inner)
            self.train_set = train_set
            self.pandas_categorical = train_set.pandas_categorical
        elif model_file is not None:
            cfg = config_from_params(params)
            self._gbdt = create_boosting(cfg, model_file)  # loads the model
            self.train_set = None
            self.pandas_categorical = _load_pandas_categorical(
                _read_last_line(model_file))
        elif model_str is not None:
            cfg = config_from_params(params)
            self._gbdt = GBDT(cfg)
            self._gbdt.load_model_from_string(model_str)
            self.train_set = None
            self.pandas_categorical = _load_pandas_categorical(model_str)
        else:
            raise TypeError("need at least one of train_set, model_file, model_str")

    # -- training -----------------------------------------------------------

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct(self.params)
        self._gbdt.add_valid(data._inner, name)
        self._valid_names.append(name)
        self._valid_data.append(data)
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj: Optional[Callable] = None) -> bool:
        """One boosting iteration; returns True if no further splits."""
        from . import profiling
        with profiling.phase("update", iteration=self._gbdt.iter_):
            if train_set is not None and train_set is not self.train_set:
                train_set.construct(self.params)
                self._gbdt.reset_training_data(train_set._inner)
                self.train_set = train_set
            if fobj is None:
                return self._gbdt.train_one_iter(None, None, False)
            preds = self.__inner_raw_score()
            grad, hess = fobj(preds, self.train_set)
            return self.__boost(grad, hess)

    def __inner_raw_score(self) -> np.ndarray:
        sc = self._gbdt.train_score.get()
        return sc.reshape(-1)  # class-major flat, like the reference

    def __boost(self, grad, hess) -> bool:
        import jax.numpy as jnp
        K = self._gbdt.K
        n = self._gbdt.num_data
        g = np.asarray(grad, np.float32).reshape(K, n)
        h = np.asarray(hess, np.float32).reshape(K, n)
        return self._gbdt.train_one_iter(jnp.asarray(g), jnp.asarray(h), False)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._gbdt.current_iteration()

    def num_trees(self) -> int:
        return self._gbdt.num_trees

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        new_cfg = config_from_params(self.params)
        self._gbdt.config = new_cfg
        self._gbdt.shrinkage_rate = new_cfg.learning_rate
        if self._gbdt.train_set is not None:
            self._gbdt.learner.config = new_cfg
        return self

    # -- evaluation ---------------------------------------------------------

    def eval_train(self, feval=None):
        return self.__eval("training", self._gbdt.eval_train(), feval,
                           is_train=True)

    def eval_valid(self, feval=None):
        return self.__eval(None, self._gbdt.eval_valid(), feval,
                           is_train=False)

    def eval(self, data: Dataset, name: str, feval=None):
        if data is self.train_set:
            return self.eval_train(feval)
        return [r for r in self.eval_valid(feval) if r[0] == name]

    def __eval(self, name, results, feval, is_train):
        out = [(nm, metric, val, hib) for nm, metric, val, hib in results]
        if feval is None:
            return out

        def apply(ds_name, raw, dataset):
            ret = feval(raw, dataset)
            if ret is None:
                return
            if isinstance(ret, tuple):
                ret = [ret]
            for fname, val, hib in ret:
                out.append((ds_name, fname, val, hib))

        if is_train and self.train_set is not None:
            apply("training", self.__inner_raw_score(), self.train_set)
        elif not is_train:
            for vname, vdata, (gname, _, su, _) in zip(
                    self._valid_names, self._valid_data,
                    self._gbdt.valid_sets):
                apply(vname, np.asarray(su.get()).reshape(-1), vdata)
        return out

    # -- refit (upstream Booster.refit parity) ------------------------------

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              **kwargs) -> "Booster":
        """Refit the existing model's LEAF VALUES on new data (tree
        structures unchanged) and return the refitted Booster; `self`
        is untouched (upstream ``Booster.refit(data, label,
        decay_rate)`` contract).

        new_leaf = decay_rate * old + (1 - decay_rate) * newton_output
        — the online-learning refit kernel (lightgbm_tpu/online/refit.py):
        one binned ensemble traversal routes every row, one jitted scan
        recomputes every tree's leaves.  kwargs become dataset/refit
        params (e.g. ``refit_min_rows``).
        """
        if label is None:
            raise ValueError("refit needs labels")
        params = dict(self.params)
        params.update(kwargs)
        new = Booster(params=params, model_str=self.model_to_string())
        data = _apply_pandas_categorical(data, self.pandas_categorical)
        X = _to_numpy(data)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        md = Metadata()
        if weight is not None:
            md.weights = _to_numpy(weight).reshape(-1).astype(np.float32)
        inner = _InnerDataset(X, _to_numpy(label).reshape(-1),
                              config_from_params(params), metadata=md)
        from .online.refit import refit_gbdt
        # route on the RAW feature values (upstream refit = pred_leaf
        # then LGBM_BoosterRefit): exact, where the binned router would
        # quantize thresholds falling inside this data's own bins
        leaf = new._gbdt.predict_leaf_index(X)
        refit_gbdt(new._gbdt, inner, decay_rate=decay_rate, leaf_idx=leaf)
        return new

    # -- prediction ---------------------------------------------------------

    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, data_has_header: bool = False,
                is_reshape: bool = True) -> np.ndarray:
        if isinstance(data, str):
            from .dataset import parse_text_file
            X, _, _ = parse_text_file(data, data_has_header)
        else:
            data = _apply_pandas_categorical(data, self.pandas_categorical)
            X = _to_numpy(data)
            if X.ndim == 1:
                X = X.reshape(1, -1)
        if pred_leaf:
            return self._gbdt.predict_leaf_index(X, num_iteration)
        if raw_score:
            return self._gbdt.predict_raw(X, num_iteration)
        return self._gbdt.predict(X, num_iteration)

    # -- model io -----------------------------------------------------------

    def _pandas_categorical_trailer(self) -> str:
        import json
        if not self.pandas_categorical:
            return ""
        def _reject(o):
            # stringifying (e.g. Timestamps) would silently break the
            # save/load round trip: the reloaded strings no longer match
            # the frame's category values.  Refuse loudly instead (the
            # reference raises on unserializable categories too).
            raise LightGBMError(
                "categorical column categories must be JSON-native "
                f"(str/int/float/bool) to save the model; got {type(o)}")
        return ("pandas_categorical:"
                + json.dumps(self.pandas_categorical, default=_reject)
                + "\n")

    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        self._gbdt.save_model_to_file(filename, num_iteration)
        trailer = self._pandas_categorical_trailer()
        if trailer:
            with open(filename, "a") as f:
                f.write(trailer)
        return self

    def model_to_string(self, num_iteration: int = -1) -> str:
        return (self._gbdt.save_model_to_string(num_iteration)
                + self._pandas_categorical_trailer())

    def dump_model(self, num_iteration: int = -1) -> Dict:
        return self._gbdt.to_json()

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        if importance_type not in ("split", "gain"):
            raise ValueError(
                f"unknown importance_type {importance_type!r}; "
                "use 'split' or 'gain'")
        imp = self._gbdt.feature_importance(importance_type)
        names = self.feature_name()
        # split importance is int32 in the reference C API (int* out)
        dt = np.float64 if importance_type == "gain" else np.int32
        return np.array([imp.get(n, 0) for n in names], dt)

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names)

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx + 1

    def free_dataset(self) -> "Booster":
        self.train_set = None
        return self

    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        cfg = config_from_params(self.params)
        self._gbdt = GBDT(cfg)
        self._gbdt.load_model_from_string(state["model_str"])
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self.train_set = None
        self._valid_names = []
        self._valid_data = []
        # category lists travel inside the model text trailer
        self.pandas_categorical = _load_pandas_categorical(
            state["model_str"])


def _params_categorical(params, data, feature_name) -> List[int]:
    """The columns that params name as categorical (`categorical_feature`
    or an alias): indices, or `name:` and feature names, as the file
    loader reads them; a list of indices or names as the constructor's
    argument takes them.  (Defined down here: the lines of the Booster
    methods above are frames that the device programs' kernels record.)"""
    spec = apply_aliases(dict(params or {})).get("categorical_column")
    if spec is None or (isinstance(spec, str) and not spec.strip()):
        return []
    names = None
    if feature_name not in (None, "auto"):
        names = list(feature_name)
    elif hasattr(data, "columns"):
        names = [str(c) for c in data.columns]
    if isinstance(spec, (list, tuple)):
        return _resolve_categorical(None, spec, names)[1]
    from .dataset import _parse_categorical_column
    shape = np.shape(data)
    return _parse_categorical_column(str(spec), names,
                                     shape[1] if len(shape) > 1 else 1)
