"""Prediction column batch + shared predictor stage bases.

Reference: the ``Prediction`` feature type (features/types/Maps.scala:339-394)
and ``OpPredictorWrapper``/``OpProbabilisticClassifierModel``
(core/.../sparkwrappers/specific/OpPredictorWrapper.scala:71,121).

A ``PredictionBatch`` stores the whole batch's predictions as arrays
(columnar, device-friendly) while presenting the reference's per-row
``Map[String, Double]`` view for local scoring and tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from ..features.feature import Feature
from ..stages.base import BinaryEstimator, BinaryModel
from ..types.columns import FeatureColumn
from ..types.feature_types import OPNumeric, OPVector, Prediction

__all__ = ["PredictionBatch", "prediction_column", "PredictorEstimator",
           "PredictorModel", "AOTScoringSpec"]


@dataclasses.dataclass(frozen=True)
class AOTScoringSpec:
    """A model's pure device scoring program, in AOT-exportable form.

    ``fn(X, *params)`` must be a pure jax function of a fixed-shape
    ``(N, D) float32`` matrix plus the model's parameter arrays, returning
    a tuple of arrays named by ``outputs`` (a subset/order of
    ``("prediction", "rawPrediction", "probability")``).  Parameters are
    RUNTIME arguments (not baked constants) so the serialized executable's
    shape is exactly ``(bucket, D)`` + the param shapes — the serving AOT
    cache (serving/aot.py) content-addresses entries on a digest of the
    params anyway, so a changed model can never reuse a stale program.
    """

    name: str                 # program family, e.g. "logreg.binary"
    fn: Any                   # callable (X, *params) -> tuple of arrays
    params: tuple             # numpy arrays / np scalars, fixed order
    outputs: tuple            # names for fn's returned tuple, in order
    #: width D of the (N, D) input matrix.  Explicit because it is NOT
    #: inferrable from the params in general (NaiveBayes' params[0] is the
    #: (K,) class prior, not the (K, D) likelihood matrix).
    n_features: Optional[int] = None


@dataclasses.dataclass
class PredictionBatch:
    """Columnar predictions: prediction (N,), optional raw/proba (N, K)."""

    prediction: np.ndarray
    raw_prediction: Optional[np.ndarray] = None
    probability: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.prediction)

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            return self.row(int(idx))
        return PredictionBatch(
            self.prediction[idx],
            None if self.raw_prediction is None else self.raw_prediction[idx],
            None if self.probability is None else self.probability[idx],
        )

    def row(self, i: int) -> Dict[str, float]:
        out = {"prediction": float(self.prediction[i])}
        if self.raw_prediction is not None:
            for k, v in enumerate(np.atleast_1d(self.raw_prediction[i])):
                out[f"rawPrediction_{k}"] = float(v)
        if self.probability is not None:
            for k, v in enumerate(np.atleast_1d(self.probability[i])):
                out[f"probability_{k}"] = float(v)
        return out

    def __iter__(self) -> Iterator[Dict[str, float]]:
        for i in range(len(self)):
            yield self.row(i)


def prediction_column(prediction, raw_prediction=None, probability=None) -> FeatureColumn:
    batch = PredictionBatch(
        np.asarray(prediction),
        None if raw_prediction is None else np.asarray(raw_prediction),
        None if probability is None else np.asarray(probability),
    )
    return FeatureColumn(Prediction, batch)


class PredictorEstimator(BinaryEstimator):
    """Base for model estimators: inputs (response RealNN, features OPVector)."""

    # model fits dispatch XLA programs: the execution plan (workflow/plan.py)
    # serializes these in stable layer order instead of pooling them
    device_heavy = True

    # input schema (SchemaError at wiring, TM004 statically); position 0 is
    # the label slot for the leakage lint (TM006)
    input_types = (OPNumeric, OPVector)
    label_input_positions = (0,)

    def __init__(self, operation_name: str, uid: Optional[str] = None):
        super().__init__(operation_name=operation_name, output_type=Prediction,
                         uid=uid)

    def output_is_response(self) -> bool:
        return False  # Prediction output is never the workflow response

    @property
    def label_feature(self) -> Feature:
        return self.input_features[0]

    @property
    def features_feature(self) -> Feature:
        return self.input_features[1]

    def fit_device(self, X: np.ndarray, y: np.ndarray, w,
                   problem_type: str):
        """Device-resident fit for validation sweeps.

        Returns ``score(X_eval) -> jax.Array`` (the validation score vector,
        see ``PredictorModel.score_device``) or None to fall back to
        ``fit_raw`` + host scoring.  Implementations must not materialize
        device values on host (each sync stalls the sweep's dispatch queue).
        """
        return None


class PredictorModel(BinaryModel):
    """Base for fitted predictors; subclasses implement predict(X)."""

    device_heavy = True  # batch predicts are jitted device programs

    input_types = (OPNumeric, OPVector)
    label_input_positions = (0,)

    def __init__(self, operation_name: str, uid: Optional[str] = None):
        super().__init__(operation_name=operation_name, output_type=Prediction,
                         uid=uid)

    def output_is_response(self) -> bool:
        return False

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        raise NotImplementedError

    def aot_scoring_spec(self) -> Optional[AOTScoringSpec]:
        """The model's scoring program as an :class:`AOTScoringSpec`, or
        None when the family has no single-program device form (trees,
        isotonic) — serving then keeps the host ``predict_batch`` path.
        """
        return None

    def score_device(self, X: np.ndarray, problem_type: str):
        """Validation score vector as a DEVICE array, or None if unsupported.

        binary -> P(class 1); regression/multiclass -> prediction.  Sweeps
        use this to keep fit→score→metric on device: every host
        materialization is a sync that stalls the dispatch queue, so the
        selector fetches one stacked metric array per sweep instead of one
        score vector per candidate×fold (see OpValidator's thread-pool
        analogue, OpCrossValidation.scala:113-138).
        """
        return None

    def transform_columns(self, label_col, features_col) -> FeatureColumn:
        X = np.asarray(features_col.values, dtype=np.float32)
        # serving device path: when a BucketedExecutor has installed AOT/
        # JIT-compiled per-bucket scoring programs on this model AND the
        # calling thread is inside the device scoring context (set by the
        # executor, never by the breaker's host-fallback path), route
        # through the compiled program for this batch shape.  Unknown
        # shapes return None and fall through to the host predict.
        programs = getattr(self, "_serving_programs", None)
        if programs is not None:
            from ..serving.aot import device_scoring_active

            if device_scoring_active():
                batch = programs.predict(X)
                if batch is not None:
                    return FeatureColumn(Prediction, batch)
        batch = self.predict_batch(X)
        return FeatureColumn(Prediction, batch)
