"""Classification model stages (XLA-trained).

Reference wrappers (core/.../impl/classification/): OpLogisticRegression
(OpLogisticRegression.scala:46), OpLinearSVC (:47), OpNaiveBayes (:46),
OpMultilayerPerceptronClassifier (:48).  Tree/boosted models live in
``models.trees``.

Each estimator takes (label RealNN, features OPVector) and yields a fitted
``PredictorModel`` producing a ``Prediction`` column — same contract as the
reference's OpPredictorWrapper pipeline.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types.columns import ColumnarDataset, FeatureColumn
from .linear import (
    fit_linear_svc, fit_logistic_regression, fit_multinomial_logreg,
    fit_naive_bayes, logreg_predict_proba, naive_bayes_predict_log_proba,
    softmax_predict_proba, svc_decision,
)
from .prediction import PredictionBatch, PredictorEstimator, PredictorModel

__all__ = [
    "OpLogisticRegression", "LogisticRegressionModel",
    "OpLinearSVC", "LinearSVCModel",
    "OpNaiveBayes", "NaiveBayesModel",
]


def _extract_xy(label_col: FeatureColumn, features_col: FeatureColumn):
    X = np.asarray(features_col.values, dtype=np.float32)
    y = np.asarray(label_col.values, dtype=np.float32)
    return X, np.nan_to_num(y)


@jax.jit
def _device_sigmoid_score(X, coef, intercept):
    return jax.nn.sigmoid(X @ coef + intercept)


@jax.jit
def _device_standardize(X, mu, sigma):
    return (X - mu) / sigma


@jax.jit
def _device_standardize_stats(X, w=None):
    """Weighted column mean/std on device, matching ``_standardize_stats``
    (sigma floored to 1.0 below 1e-12)."""
    if w is None:
        mu = X.mean(axis=0)
        sigma = X.std(axis=0)
    else:
        ws = jnp.maximum(w.sum(), 1e-12)
        mu = (w[:, None] * X).sum(axis=0) / ws
        sigma = jnp.sqrt((w[:, None] * (X - mu) ** 2).sum(axis=0) / ws)
    return mu, jnp.where(sigma < 1e-12, 1.0, sigma)


@jax.jit
def _device_std_sigmoid_score(X, mu, sigma, coef, intercept):
    return jax.nn.sigmoid(((X - mu) / sigma) @ coef + intercept)


# -- AOT-exportable scoring programs (serving/aot.py) ------------------------
# Pure jax functions of (X, *params) with static shapes: the serving plane
# lowers one executable per (model digest, shape bucket) and persists it in
# the AOT store, so a fresh replica cold-starts without tracing or
# compiling.  Everything stays float32 regardless of the x64 flag so the
# same program (and the same persisted executable) serves tests and prod.

def _aot_logreg_binary(X, coef, intercept):
    z = X @ coef + intercept
    p1 = jax.nn.sigmoid(z)
    raw = jnp.stack([-z, z], axis=1)
    proba = jnp.stack([jnp.float32(1.0) - p1, p1], axis=1)
    pred = (p1 >= jnp.float32(0.5)).astype(jnp.float32)
    return pred, raw, proba


def _aot_softmax(X, coef, intercept):
    Z = X @ coef.T + intercept
    e = jnp.exp(Z - Z.max(axis=1, keepdims=True))
    proba = e / e.sum(axis=1, keepdims=True)
    pred = proba.argmax(axis=1).astype(jnp.float32)
    return pred, Z, proba


def _aot_svc(X, coef, intercept):
    z = X @ coef + intercept
    raw = jnp.stack([-z, z], axis=1)
    pred = (z >= jnp.float32(0.0)).astype(jnp.float32)
    return pred, raw


def _aot_naive_bayes(X, log_prior, log_lik):
    Xc = jnp.maximum(X, jnp.float32(0.0))
    joint = Xc @ log_lik.T + log_prior
    m = joint.max(axis=1, keepdims=True)
    logp = joint - (m + jnp.log(
        jnp.exp(joint - m).sum(axis=1, keepdims=True)))
    proba = jnp.exp(logp)
    pred = proba.argmax(axis=1).astype(jnp.float32)
    return pred, logp, proba


class OpLogisticRegression(PredictorEstimator):
    """L2/elastic-net logistic regression trained by jitted Newton-IRLS.

    Param names follow Spark's (regParam, elasticNetParam, maxIter, tol,
    fitIntercept) so default grids transfer verbatim
    (DefaultSelectorParams.scala:36-75).
    """

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 50, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 sample_weight_col: Optional[str] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name="logreg", uid=uid)
        self.reg_param = reg_param
        self.elastic_net_param = elastic_net_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization
        self.sample_weight_col = sample_weight_col
        self.mesh = None

    def with_mesh(self, mesh) -> "OpLogisticRegression":
        """Multi-chip fit: rows shard over the mesh's data axis and GSPMD
        psums the per-iteration IRLS Gram products over ICI
        (parallel/sharded.fit_logreg_sharded).  Binary only — the
        multinomial path stays single-device."""
        self.mesh = mesh
        return self

    def fit_columns(self, data: ColumnarDataset, label_col, features_col):
        X, y = _extract_xy(label_col, features_col)
        w = None
        if self.sample_weight_col and self.sample_weight_col in data:
            w = np.asarray(data[self.sample_weight_col].values, np.float32)
        return self.fit_raw(X, y, w)

    def fit_device(self, X, y, w, problem_type: str):
        """Sweep path: Newton-IRLS fit and sigmoid scores stay on device
        (binary only) — no coefficient fetch per candidate, and the feature
        matrix uploads ONCE (content-memoized); per-fold standardization is
        a device elementwise op, not a fresh host matrix + upload."""
        if problem_type != "binary" or (len(y) and np.nanmax(y) > 1):
            return None
        from .trees import _dev_f32

        fit, mu, sigma = self._fit_binary_on_device(X, y, w)

        def score(Xe):
            Xe_dev = _dev_f32(Xe)
            if mu is None:
                return _device_sigmoid_score(Xe_dev, fit.coef, fit.intercept)
            return _device_std_sigmoid_score(
                Xe_dev, mu, sigma, fit.coef, fit.intercept)
        return score

    #: past this element count the refit standardizes + fits on device from
    #: the (memoized) uploaded matrix — host mean/std/copy passes over a
    #: multi-GB matrix cost tens of seconds on a 1-core host
    _DEVICE_FIT_ELEMS = 1 << 24

    def _fit_binary_on_device(self, X, y, w):
        """Memoized upload + device standardization stats + IRLS fit —
        the ONE binary device-fit path shared by the CV sweep
        (``fit_device``) and the big-matrix refit, so the two cannot
        diverge.  Stats on DEVICE: a host mean/std pass over a 2 GB matrix
        costs ~17 s per candidate on a 1-core host; on device it is two
        fused reductions over the already-resident matrix."""
        from .trees import _dev_f32

        X_dev = _dev_f32(X)
        if self.standardization:
            mu, sigma = _device_standardize_stats(
                X_dev, None if w is None else jnp.asarray(w, jnp.float32))
            Xs = _device_standardize(X_dev, mu, sigma)
        else:
            mu = sigma = None
            Xs = X_dev
        fit = fit_logistic_regression(
            Xs, y, sample_weight=w, reg_param=self.reg_param,
            elastic_net_param=self.elastic_net_param,
            max_iter=self.max_iter, tol=self.tol,
            fit_intercept=self.fit_intercept)
        return fit, mu, sigma

    def fit_raw(self, X: np.ndarray, y: np.ndarray,
                w: Optional[np.ndarray] = None):
        classes = np.unique(y[~np.isnan(y)]).astype(int)
        n_classes = max(int(classes.max()) + 1 if len(classes) else 2, 2)
        if (n_classes <= 2 and self.mesh is None
                and np.size(X) > self._DEVICE_FIT_ELEMS):
            fit, mu_d, sigma_d = self._fit_binary_on_device(X, y, w)
            mu = None if mu_d is None else np.asarray(mu_d)
            sigma = None if sigma_d is None else np.asarray(sigma_d)
            coef, intercept = _unstandardize(
                np.asarray(fit.coef), float(np.asarray(fit.intercept)),
                mu, sigma)
            return LogisticRegressionModel(
                coef=coef.tolist(), intercept=float(intercept))
        mu, sigma = _standardize_stats(X, w) if self.standardization else (None, None)
        Xs = _apply_standardize(X, mu, sigma)
        if n_classes <= 2:
            if self.mesh is not None:
                from ..parallel.sharded import fit_logreg_sharded

                fit = fit_logreg_sharded(
                    np.asarray(Xs, np.float32), y, self.mesh, w,
                    reg_param=self.reg_param,
                    elastic_net_param=self.elastic_net_param,
                    max_iter=self.max_iter, tol=self.tol,
                    fit_intercept=self.fit_intercept)
            else:
                fit = fit_logistic_regression(
                    Xs, y, sample_weight=w, reg_param=self.reg_param,
                    elastic_net_param=self.elastic_net_param,
                    max_iter=self.max_iter, tol=self.tol,
                    fit_intercept=self.fit_intercept)
            coef, intercept = _unstandardize(
                np.asarray(fit.coef), float(np.asarray(fit.intercept)), mu, sigma)
            return LogisticRegressionModel(
                coef=coef.tolist(), intercept=float(intercept))
        fit = fit_multinomial_logreg(
            Xs, y.astype(np.int32), n_classes=n_classes, sample_weight=w,
            reg_param=self.reg_param, elastic_net_param=self.elastic_net_param,
            max_iter=self.max_iter, tol=self.tol,
            fit_intercept=self.fit_intercept)
        coefs, intercepts = [], []
        for k in range(n_classes):
            c, i = _unstandardize(np.asarray(fit.coef)[k],
                                  float(np.asarray(fit.intercept)[k]), mu, sigma)
            coefs.append(c.tolist())
            intercepts.append(float(i))
        return LogisticRegressionModel(coef=coefs, intercept=intercepts)


def _standardize_stats(X, w):
    if w is None:
        mu = X.mean(axis=0)
        sigma = X.std(axis=0)
    else:
        ws = max(w.sum(), 1e-12)
        mu = (w[:, None] * X).sum(axis=0) / ws
        sigma = np.sqrt((w[:, None] * (X - mu) ** 2).sum(axis=0) / ws)
    sigma = np.where(sigma < 1e-12, 1.0, sigma)
    return mu.astype(np.float32), sigma.astype(np.float32)


def _apply_standardize(X, mu, sigma):
    if mu is None:
        return X
    return (X - mu) / sigma


def _unstandardize(coef, intercept, mu, sigma):
    """Map standardized-space coefficients back to raw feature space."""
    if mu is None:
        return coef, intercept
    raw = coef / sigma
    return raw, intercept - float(np.dot(raw, mu))


class LogisticRegressionModel(PredictorModel):
    """Binary: coef (D,); multinomial: coef (K, D) + intercept list."""

    def __init__(self, coef, intercept, uid: Optional[str] = None):
        super().__init__(operation_name="logreg", uid=uid)
        self.coef = coef
        self.intercept = intercept

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        from .. import native
        coef = np.asarray(self.coef, np.float32)
        if isinstance(X, np.ndarray):
            # host path: a host-resident matrix is scored with host BLAS
            # (a dot + sigmoid) rather than uploaded just to predict;
            # device scoring is for device-resident inputs
            if coef.ndim == 1:
                if native.AVAILABLE and len(X) <= 4096:
                    beta = np.append(coef, np.float32(self.intercept))
                    z = native.linear_margin(np.asarray(X, np.float32), beta)
                else:
                    z = np.asarray(X, np.float32) @ coef + np.float32(
                        self.intercept)
                with np.errstate(over="ignore"):
                    p1 = 1.0 / (1.0 + np.exp(-z))
                proba = np.stack([1.0 - p1, p1], axis=1)
                return PredictionBatch(
                    prediction=(p1 >= 0.5).astype(np.float64),
                    raw_prediction=np.stack([-z, z], axis=1),
                    probability=proba)
            Z = (np.asarray(X, np.float32) @ coef.T
                 + np.asarray(self.intercept, np.float32))
            e = np.exp(Z - Z.max(axis=1, keepdims=True))
            proba = e / e.sum(axis=1, keepdims=True)
            return PredictionBatch(
                prediction=proba.argmax(axis=1).astype(np.float64),
                raw_prediction=Z, probability=proba)
        if coef.ndim == 1:
            proba, raw = logreg_predict_proba(
                jnp.asarray(coef), jnp.float32(self.intercept), X)
            proba = np.asarray(proba)
            return PredictionBatch(
                prediction=(proba[:, 1] >= 0.5).astype(np.float64),
                raw_prediction=np.asarray(raw),
                probability=proba)
        proba, raw = softmax_predict_proba(
            jnp.asarray(coef), jnp.asarray(self.intercept, jnp.float32), X)
        proba = np.asarray(proba)
        return PredictionBatch(
            prediction=proba.argmax(axis=1).astype(np.float64),
            raw_prediction=np.asarray(raw),
            probability=proba)

    def aot_scoring_spec(self):
        from .prediction import AOTScoringSpec
        coef = np.asarray(self.coef, np.float32)
        if coef.ndim == 1:
            return AOTScoringSpec(
                name="logreg.binary", fn=_aot_logreg_binary,
                params=(coef, np.float32(self.intercept)),
                outputs=("prediction", "rawPrediction", "probability"),
                n_features=int(coef.shape[-1]))
        return AOTScoringSpec(
            name="logreg.softmax", fn=_aot_softmax,
            params=(coef, np.asarray(self.intercept, np.float32)),
            outputs=("prediction", "rawPrediction", "probability"),
            n_features=int(coef.shape[-1]))


class OpLinearSVC(PredictorEstimator):
    """Squared-hinge linear SVM via jitted Newton (OpLinearSVC parity)."""

    def __init__(self, reg_param: float = 1e-4, max_iter: int = 100,
                 tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="linsvc", uid=uid)
        self.reg_param = reg_param
        self.max_iter = max_iter
        self.tol = tol
        self.fit_intercept = fit_intercept
        self.standardization = standardization

    def fit_columns(self, data: ColumnarDataset, label_col, features_col):
        X, y = _extract_xy(label_col, features_col)
        return self.fit_raw(X, y)

    def fit_raw(self, X: np.ndarray, y: np.ndarray,
                w: Optional[np.ndarray] = None):
        mu, sigma = _standardize_stats(X, w) if self.standardization else (None, None)
        fit = fit_linear_svc(
            _apply_standardize(X, mu, sigma), y, sample_weight=w,
            reg_param=self.reg_param,
            max_iter=self.max_iter, tol=self.tol,
            fit_intercept=self.fit_intercept)
        coef, intercept = _unstandardize(
            np.asarray(fit.coef), float(np.asarray(fit.intercept)), mu, sigma)
        return LinearSVCModel(coef=coef.tolist(), intercept=float(intercept))


class LinearSVCModel(PredictorModel):
    def __init__(self, coef: List[float], intercept: float,
                 uid: Optional[str] = None):
        super().__init__(operation_name="linsvc", uid=uid)
        self.coef = coef
        self.intercept = intercept

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        from .. import native
        if native.AVAILABLE and len(X) <= 4096:
            beta = np.append(np.asarray(self.coef, np.float32),
                             np.float32(self.intercept))
            z = native.linear_margin(np.asarray(X, np.float32), beta)
        else:
            z = np.asarray(svc_decision(jnp.asarray(self.coef, jnp.float32),
                                        jnp.float32(self.intercept), X))
        raw = np.stack([-z, z], axis=1)
        return PredictionBatch(prediction=(z >= 0).astype(np.float64),
                               raw_prediction=raw)

    def aot_scoring_spec(self):
        from .prediction import AOTScoringSpec
        coef = np.asarray(self.coef, np.float32)
        return AOTScoringSpec(
            name="linsvc", fn=_aot_svc,
            params=(coef, np.float32(self.intercept)),
            outputs=("prediction", "rawPrediction"),
            n_features=int(coef.shape[-1]))


class OpNaiveBayes(PredictorEstimator):
    """Multinomial naive Bayes (OpNaiveBayes parity, smoothing=1.0)."""

    def __init__(self, smoothing: float = 1.0, uid: Optional[str] = None):
        super().__init__(operation_name="naivebayes", uid=uid)
        self.smoothing = smoothing

    def fit_columns(self, data: ColumnarDataset, label_col, features_col):
        X, y = _extract_xy(label_col, features_col)
        return self.fit_raw(X, y)

    def fit_raw(self, X: np.ndarray, y: np.ndarray,
                w: Optional[np.ndarray] = None):
        classes = np.unique(y)
        n_classes = max(int(classes.max()) + 1 if len(classes) else 2, 2)
        log_prior, log_lik = fit_naive_bayes(
            X, y.astype(np.int32), n_classes=n_classes, sample_weight=w,
            smoothing=self.smoothing)
        return NaiveBayesModel(log_prior=np.asarray(log_prior).tolist(),
                               log_lik=np.asarray(log_lik).tolist())

    # -- streaming fit: per-class (count, feature-sum) is a plain monoid ----
    # Multinomial NB's sufficient statistics are exactly class counts and
    # per-class feature sums — the fit streams whole, so a chunked train
    # never materializes the feature matrix for this model (tolerance vs
    # in-core: chunked float64 sums vs the device's float32 one-hot matmul,
    # ~1e-5 on the log-likelihoods).

    supports_streaming_fit = True

    def begin_fit(self):
        return {}  # class value -> [count, feat_sum (D,) float64]

    def update_chunk(self, state, data, label_col, features_col):
        X, y = _extract_xy(label_col, features_col)
        Xc = np.maximum(X, 0.0)  # fit_naive_bayes clips negatives
        for uv in np.unique(y):
            mask = (y == uv)
            # one sgemv per class instead of a row gather: indicator sums
            # stay exact in float32 below 2^24 rows, real-valued slots land
            # within the documented 1e-4 log-likelihood tolerance
            sums = (mask.astype(np.float32) @ Xc).astype(np.float64)
            cnt = int(mask.sum())
            ent = state.get(float(uv))
            if ent is None:
                state[float(uv)] = [cnt, sums]
            else:
                ent[0] += cnt
                ent[1] = ent[1] + sums
        return state

    def merge_states(self, a, b):
        for k, (cnt, sums) in b.items():
            ent = a.get(k)
            if ent is None:
                a[k] = [cnt, sums]
            else:
                ent[0] += cnt
                ent[1] = ent[1] + sums
        return a

    def finish_fit(self, state):
        if not state:
            raise ValueError("NaiveBayes streaming fit saw no rows")
        n_classes = max(int(max(state)) + 1, 2)
        d = len(next(iter(state.values()))[1])
        class_count = np.zeros(n_classes, np.float64)
        feat_count = np.zeros((n_classes, d), np.float64)
        for k, (cnt, sums) in state.items():
            class_count[int(k)] = cnt
            feat_count[int(k)] = sums
        log_prior = (np.log(class_count + 1e-12)
                     - np.log(max(class_count.sum(), 1e-12)))
        log_lik = (np.log(feat_count + self.smoothing)
                   - np.log(feat_count.sum(axis=1, keepdims=True)
                            + self.smoothing * d))
        return NaiveBayesModel(
            log_prior=np.asarray(log_prior, np.float32).tolist(),
            log_lik=np.asarray(log_lik, np.float32).tolist())


class NaiveBayesModel(PredictorModel):
    def __init__(self, log_prior, log_lik, uid: Optional[str] = None):
        super().__init__(operation_name="naivebayes", uid=uid)
        self.log_prior = log_prior
        self.log_lik = log_lik

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        # host numpy: the predict is one slim GEMV-like product and the
        # eager jnp op chain ratcheted the CPU client's buffer pool by
        # ~5 MB per call — block-wise scoring (serving, the out-of-core
        # assemble) paid that as a permanent RSS high-water.  Same
        # max-shifted logsumexp as jax.scipy's.
        lp = np.asarray(self.log_prior, np.float32)
        ll = np.asarray(self.log_lik, np.float32)
        Xc = np.maximum(np.asarray(X, np.float32), 0.0)
        joint = Xc @ ll.T + lp                       # (N, K)
        m = joint.max(axis=1, keepdims=True)
        logp = joint - (m + np.log(
            np.exp(joint - m).sum(axis=1, keepdims=True)))
        proba = np.exp(logp)
        return PredictionBatch(prediction=proba.argmax(axis=1).astype(np.float64),
                               raw_prediction=logp, probability=proba)

    def aot_scoring_spec(self):
        from .prediction import AOTScoringSpec
        log_lik = np.asarray(self.log_lik, np.float32)
        return AOTScoringSpec(
            name="naivebayes", fn=_aot_naive_bayes,
            params=(np.asarray(self.log_prior, np.float32), log_lik),
            outputs=("prediction", "rawPrediction", "probability"),
            n_features=int(log_lik.shape[-1]))
