"""End-to-end reordering-algorithm selector — the paper's deliverable.

``ReorderSelector`` = feature extraction → scaler → classifier → algorithm
name. ``fit_from_dataset`` trains it from a :class:`LabeledDataset`;
``select``/``predict_matrix`` run the trained pipeline on a new matrix
(the ~16 ms path of the paper's Table 5).

``select_batch`` is the serving path: many matrices at once, either through
the host featurizer or the CSR-native device featurizer
(`extract_features_batch_jnp`); for every zoo member with a ``forward_jnp``
(the JAX models *and* the tree/forest family, via
:mod:`repro.core.ml.forest_jnp`) the scaler transform and classifier
forward also run on device inside one jit.
"""
from __future__ import annotations

import pickle
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.features import pad_csr_batch
from repro.core.labeling import LabeledDataset
from repro.core.ml import MODEL_ZOO, BaseClassifier, accuracy_score
from repro.core.model_selection import GridSearchCV, train_test_split
from repro.core.reqctx import span
from repro.core.scaling import SCALERS
from repro.engine.registry import FeatureSet, get_feature_set
from repro.sparse.csr import CSRMatrix

__all__ = ["ReorderSelector", "DEFAULT_GRIDS", "train_selector",
           "scaler_transform_jnp"]


def scaler_transform_jnp(scaler, x):
    """Device twin of ``scaler.transform`` — reads the fitted state and
    applies the affine map in jnp so it fuses into the inference jit."""
    import jax.numpy as jnp

    st = scaler.state()
    if "mean" in st:
        return ((x - jnp.asarray(st["mean"], jnp.float32))
                / jnp.asarray(st["std"], jnp.float32))
    if "min" in st:
        return ((x - jnp.asarray(st["min"], jnp.float32))
                / jnp.asarray(st["scale"], jnp.float32))
    return x


# Hyperparameter grids per model family (paper §3.4: "candidate values are
# usually given by empirical methods").
DEFAULT_GRIDS: Dict[str, Dict[str, Sequence]] = {
    "random_forest": {
        "criterion": ["gini"],
        "min_samples_leaf": [1, 2],
        "min_samples_split": [2, 5],
        "n_estimators": [50, 100],
    },
    "decision_tree": {
        "criterion": ["gini", "entropy"],
        "max_depth": [None, 8, 16],
        "min_samples_leaf": [1, 2, 5],
    },
    "logistic_regression": {"C": [0.1, 1.0, 10.0], "steps": [500]},
    "naive_bayes": {"var_smoothing": [1e-9, 1e-6]},
    "svm": {"C": [1.0, 10.0], "gamma": [0.1, 0.5], "kernel": ["rbf"]},
    "mlp": {"hidden_layer_sizes": [(64, 32), (128,)], "lr": [0.01]},
    "knn": {"n_neighbors": [3, 5, 9], "weights": ["uniform", "distance"]},
}

# Smaller grids for smoke-speed runs.
FAST_GRIDS: Dict[str, Dict[str, Sequence]] = {
    k: {p: v[:1] for p, v in g.items()} for k, g in DEFAULT_GRIDS.items()
}


class ReorderSelector:
    def __init__(self, model: BaseClassifier, scaler, algorithms: List[str],
                 feature_set: str = "paper12"):
        self.model = model
        self.scaler = scaler
        self.algorithms = algorithms
        # registry name of the feature schema this selector was trained on
        # (resolved lazily; bundles persist and validate it)
        self.feature_set = feature_set

    def _fs(self) -> FeatureSet:
        # getattr: pre-feature-set pickles lack the attribute
        return get_feature_set(getattr(self, "feature_set", "paper12"))

    # -- inference -----------------------------------------------------------
    def predict_features(self, feats: np.ndarray) -> np.ndarray:
        feats = np.atleast_2d(feats)
        return self.model.predict(self.scaler.transform(feats))

    def select(self, a: CSRMatrix) -> Tuple[str, float]:
        """Returns (algorithm name, prediction seconds) — Table 5's columns."""
        t0 = time.perf_counter()
        feats = self._fs().extract(a)
        idx = int(self.predict_features(feats)[0])
        return self.algorithms[idx], time.perf_counter() - t0

    # -- batched serving path --------------------------------------------------
    def select_batch(self, mats: Sequence[CSRMatrix], *, path: str = "host",
                     use_pallas: bool = False
                     ) -> Tuple[List[str], float]:
        """Select for a whole batch at once; returns (names, total seconds).

        ``path='host'`` runs the per-matrix numpy featurizer; ``'device'``
        packs the batch into padded CSR buffers and runs the segment-reduction
        featurizer (optionally through the Pallas csr_stats kernels). JAX
        classifiers then consume the feature batch without leaving device.
        """
        assert path in ("host", "device"), path
        t0 = time.perf_counter()
        fs = self._fs()
        if path == "device" and fs.extract_batch_jnp is not None:
            # device featurizers consume the padded-CSR wire format
            with span(None, "select.pack"):
                packed = pad_csr_batch(mats, bucket=True)
            feats = fs.extract_batch_jnp(packed, use_pallas=use_pallas)
            idx = self._predict_device(feats)
        else:  # host path, or a feature set with no device extractor
            idx = self.predict_features(fs.batch(mats))
        names = [self.algorithms[int(i)] for i in idx]
        return names, time.perf_counter() - t0

    def _fit_version(self) -> tuple:
        """Identity of the fitted state the device jit bakes in as constants.

        Refitting model or scaler assigns fresh objects, so the leaves of
        the fitted attributes change identity and the cached trace is
        invalidated. The version holds strong *references* (compared with
        ``is``), never bare ``id()``s: a freed-and-reallocated object could
        reuse an address and alias a stale trace."""
        import jax

        fitted = {k: v for k, v in vars(self.model).items()
                  if k.endswith("_")}
        leaves = jax.tree_util.tree_leaves(fitted)
        leaves += list(self.scaler.state().values())
        return tuple(leaves)

    @staticmethod
    def _same_version(a, b) -> bool:
        return (a is not None and b is not None and len(a) == len(b)
                and all(x is y for x, y in zip(a, b)))

    def _predict_device(self, feats) -> np.ndarray:
        """Label indices for an on-device (B, 12) feature batch.

        Zoo members exposing ``forward_jnp`` stay on device — scaler +
        forward + argmax shard_mapped over the active serving mesh's batch
        axis in one cached jit (rebuilt if the model, scaler, or mesh
        changes). The fitted state closes over the shard_map body as
        replicated constants, so every shard classifies its B/ndev slice
        locally and the padded feature batch never gathers onto one device;
        a 1-device mesh is the degenerate case of the same trace. That
        includes decision trees and random forests via the flattened-node
        traversal of :mod:`repro.core.ml.forest_jnp`, so the paper's
        winning model serves without a host round-trip; only KNN/NB fall
        back to host inference on transferred features.
        """
        if hasattr(self.model, "forward_jnp"):
            from repro.distributed.meshctx import get_serving_mesh

            sm = get_serving_mesh()
            version = self._fit_version()
            fn = getattr(self, "_device_fn", None)
            if (fn is None or getattr(self, "_device_fn_mesh", None) != sm
                    or not self._same_version(
                        getattr(self, "_device_fn_version", None), version)):
                import jax
                import jax.numpy as jnp

                from repro.distributed.compat import shard_map

                def infer(x):
                    z = scaler_transform_jnp(self.scaler, x)
                    return jnp.argmax(self.model.forward_jnp(z), axis=1)

                spec = sm.spec()
                mapped = shard_map(infer, mesh=sm.mesh, in_specs=(spec,),
                                   out_specs=spec, check_vma=False)
                nd = sm.num_devices

                def infer_sharded(x):
                    b = x.shape[0]
                    pad = (-b) % nd
                    if pad:  # ragged batch: filler rows, sliced off below
                        x = jnp.concatenate(
                            [x, jnp.repeat(x[:1], pad, axis=0)])
                    return mapped(x)[:b]

                fn = self._device_fn = jax.jit(infer_sharded)
                self._device_fn_version = version
                self._device_fn_mesh = sm
            return np.asarray(fn(feats))
        return self.model.predict(self.scaler.transform(np.asarray(feats)))

    def accuracy(self, feats: np.ndarray, labels: np.ndarray) -> float:
        return accuracy_score(labels, self.predict_features(feats))

    # -- persistence -----------------------------------------------------------
    def __getstate__(self):
        # jitted device closures are not picklable; rebuilt lazily on load
        return {k: v for k, v in self.__dict__.items()
                if not k.startswith("_")}

    def save(self, path: str) -> None:
        """Deprecated raw-pickle persistence — prefer the versioned,
        validated :class:`repro.engine.SelectorBundle` (which
        ``SolverEngine.save`` writes). Kept as a shim for old callers."""
        warnings.warn(
            "ReorderSelector.save/load raw pickles are deprecated; use "
            "SolverEngine.save / SelectorBundle.from_selector instead",
            DeprecationWarning, stacklevel=2)
        with open(path, "wb") as f:
            pickle.dump(self, f)

    @staticmethod
    def load(path: str) -> "ReorderSelector":
        """Deprecated twin of :meth:`save`; loads either a raw pickle or a
        SelectorBundle file (so callers migrate one side at a time)."""
        warnings.warn(
            "ReorderSelector.save/load raw pickles are deprecated; use "
            "SolverEngine.load / SelectorBundle.load instead",
            DeprecationWarning, stacklevel=2)
        with open(path, "rb") as f:
            obj = pickle.load(f)
        if isinstance(obj, ReorderSelector):
            return obj
        from repro.engine.bundle import SelectorBundle, _MAGIC

        if isinstance(obj, dict) and obj.get("magic") == _MAGIC:
            return SelectorBundle.from_envelope(obj).to_selector()
        raise TypeError(f"{path} holds {type(obj).__name__}, not a "
                        "ReorderSelector or SelectorBundle")


def train_selector(
    ds: LabeledDataset,
    model_name: str = "random_forest",
    scaling: str = "standard",
    test_size: float = 0.2,
    seed: int = 0,
    cv: int = 5,
    grid: Optional[Dict[str, Sequence]] = None,
    fast: bool = False,
    feature_set: Optional[str] = None,
):
    """Grid-search + refit a selector; returns (selector, report dict).

    ``model_name``/``scaling``/``feature_set`` are registry names (unknown
    ones raise :class:`repro.engine.RegistryLookupError` with suggestions).
    ``feature_set`` defaults to the set the dataset was featurized with.
    The report carries everything the paper's evaluation needs: test
    accuracy, indices of the split, per-scenario totals (AMD / predicted /
    ideal — Table 6), and the mean speedup vs AMD (the 1.45× claim).
    """
    fs_name = feature_set or getattr(ds, "feature_set", None) or "paper12"
    fs = get_feature_set(fs_name)
    x, y = ds.features, ds.labels
    if x.shape[1] != fs.dim:
        raise ValueError(
            f"dataset features have dim {x.shape[1]} but feature set "
            f"{fs_name!r} has {fs.dim} ({list(fs.names)})")
    xtr, xte, ytr, yte, itr, ite = train_test_split(x, y, test_size, seed)
    scaler = SCALERS[scaling]().fit(xtr)
    grids = FAST_GRIDS if fast else DEFAULT_GRIDS
    gs = GridSearchCV(MODEL_ZOO[model_name](),
                      grid or grids.get(model_name, {}), cv=cv, seed=seed)
    gs.fit(scaler.transform(xtr), ytr)
    sel = ReorderSelector(gs.best_model_, scaler, list(ds.algorithms),
                          feature_set=fs_name)

    pred = sel.predict_features(xte)
    acc = accuracy_score(yte, pred)

    # training-report card (persisted into SelectorBundle schema v2):
    # confusion matrix over the held-out split + per-algorithm recall
    k = len(ds.algorithms)
    confusion = np.zeros((k, k), dtype=np.int64)
    for t, q in zip(yte, pred):
        confusion[int(t), int(q)] += 1
    support = confusion.sum(axis=1)
    per_algorithm_recall = {
        alg: (float(confusion[i, i] / support[i]) if support[i] else None)
        for i, alg in enumerate(ds.algorithms)}

    amd_idx = ds.algorithms.index("amd")
    t_amd = ds.times[ite, amd_idx].sum()
    t_pred = ds.times[ite, pred].sum()
    t_ideal = ds.times[ite].min(axis=1).sum()
    speedups = ds.times[ite, amd_idx] / np.maximum(ds.times[ite, pred], 1e-12)

    report = dict(
        model=model_name, scaling=scaling,
        best_params=gs.best_params_, cv_score=gs.best_score_,
        test_accuracy=acc,
        confusion=confusion,
        per_algorithm_recall=per_algorithm_recall,
        test_support={alg: int(s) for alg, s in zip(ds.algorithms, support)},
        test_idx=ite, train_idx=itr, predictions=pred,
        time_amd=float(t_amd), time_predicted=float(t_pred),
        time_ideal=float(t_ideal),
        reduction_vs_amd=float(1.0 - t_pred / t_amd) if t_amd > 0 else 0.0,
        excess_vs_ideal=float(t_pred / t_ideal - 1.0) if t_ideal > 0 else 0.0,
        mean_speedup_vs_amd=float(speedups.mean()),
        max_speedup_vs_amd=float(speedups.max()),
    )
    return sel, report
