"""AOT scoring programs — serialized executables for millisecond cold starts.

The serving plane's per-process warm-up is dominated by tracing + XLA
compilation: every shape bucket of every served model is a distinct
program (the Titanic-shaped DAG compiles ~28 programs), paid again by
every fresh replica.  Following the TPU
serving-comparison playbook (PAPERS.md), this module lowers each
``(model digest, shape bucket)`` scoring program AHEAD OF TIME and
persists the compiled executable in a content-addressed on-disk store
(``utils/compile_cache.AOTStore``), so a cold replica *loads* its warm
programs instead of compiling them:

  * key = digest(model scoring params, bucket, backend, jax version,
    x64 flag, format version) — a changed model, different backend, or
    jax upgrade misses and falls back to JIT (which writes the fresh
    entry through);
  * payload = ``jax.experimental.serialize_executable`` bytes; the call
    pytrees are RECONSTRUCTED from the spec's arity at load time (never
    pickled jax internals), and the sidecar meta carries a sha256 so a
    truncated/corrupted entry reads as a miss, never as a program;
  * parity: a deserialized executable is the same compiled artifact the
    in-process JIT produces, so AOT-path scores are byte-identical to
    JIT-path scores (test-asserted; the tier1 SERVING_COLDSTART gate
    also compares output digests across fresh subprocesses).

The device path is OPT-IN per server (``device_programs=True``): the
default executor keeps the host ``predict_batch`` path byte-identical to
PR 1, and the circuit breaker's host fallback never enters the device
scoring context, so an open breaker cannot touch these programs at all.
"""
from __future__ import annotations

import hashlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import compile_cache
from ..utils.compile_cache import AOT_FORMAT_VERSION, AOTStore

__all__ = ["ScoringProgramSet", "scoring_digest", "device_scoring",
           "device_scoring_active", "AOTStore"]


# ---------------------------------------------------------------------------
# device-scoring context — who may use installed programs
# ---------------------------------------------------------------------------

_tls = threading.local()


class device_scoring:
    """Context manager marking the current thread as the device scoring
    path.  ``PredictorModel.transform_columns`` consults this so ONLY the
    bucketed executor routes through compiled programs — the breaker's
    host fallback and offline scoring stay on the host path."""

    def __enter__(self):
        self._prev = getattr(_tls, "active", False)
        _tls.active = True
        return self

    def __exit__(self, *exc):
        _tls.active = self._prev
        return False


def device_scoring_active() -> bool:
    return getattr(_tls, "active", False)


# ---------------------------------------------------------------------------
# content addressing
# ---------------------------------------------------------------------------

def _jax_version() -> str:
    import jax

    return jax.__version__


def _x64_enabled() -> bool:
    import jax

    return bool(jax.config.jax_enable_x64)


def model_params_digest(spec) -> str:
    """Digest of the scoring program identity: family name + parameter
    bytes/shapes/dtypes.  Two models with identical fitted parameters
    share executables; any parameter change changes every key."""
    h = hashlib.sha256()
    h.update(spec.name.encode())
    for p in spec.params:
        arr = np.asarray(p)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()[:20]


def scoring_digest(spec, bucket: int, backend: str) -> str:
    """The store key for one ``(model, bucket)`` executable."""
    h = hashlib.sha256()
    h.update(model_params_digest(spec).encode())
    h.update(f"|bucket={bucket}|backend={backend}".encode())
    h.update(f"|jax={_jax_version()}|x64={_x64_enabled()}".encode())
    h.update(f"|fmt={AOT_FORMAT_VERSION}".encode())
    return f"{spec.name.replace('.', '_')}-b{bucket}-{h.hexdigest()[:24]}"


# ---------------------------------------------------------------------------
# program set
# ---------------------------------------------------------------------------

class ScoringProgramSet:
    """Per-model set of compiled per-bucket scoring programs.

    ``ensure_bucket`` populates one bucket either by LOADING a serialized
    executable from the AOT store (milliseconds; recorded as an
    ``aotLoad``) or by JIT-compiling it (recorded as a ``compile``) and
    writing the serialized executable through to the store so the next
    replica loads it.  ``predict`` runs the program for an exact-shape
    batch; unknown shapes return None (caller falls back to the host
    ``predict_batch``).
    """

    def __init__(self, model, store: Optional[AOTStore] = None,
                 cache_key_prefix: str = "serving"):
        spec = model.aot_scoring_spec() if hasattr(
            model, "aot_scoring_spec") else None
        if spec is None:
            raise ValueError(
                f"{type(model).__name__} has no AOT scoring spec")
        self.model = model
        self.spec = spec
        self.store = store
        self.cache_key_prefix = cache_key_prefix
        from ..utils.profiling import backend_name

        self.backend = backend_name()
        # the spec carries D explicitly; infer from params[0] only for
        # legacy specs where params[0] happens to be (…, D)-shaped
        self.n_features = (int(spec.n_features)
                           if getattr(spec, "n_features", None) is not None
                           else int(np.asarray(spec.params[0]).shape[-1]))
        self._programs: Dict[int, Any] = {}
        self._modes: Dict[int, str] = {}  # bucket -> "aot" | "jit"
        self._lock = threading.Lock()
        #: jnp-ready parameter arrays (uploaded once, reused every call)
        self._params = tuple(np.asarray(p) for p in spec.params)

    # -- introspection ------------------------------------------------------

    @property
    def buckets(self) -> List[int]:
        with self._lock:
            return sorted(self._programs)

    @property
    def modes(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._modes)

    def cached_in_store(self, bucket: int) -> bool:
        """True when the AOT store already holds a valid entry for this
        (model, bucket) — the warmup skip probe."""
        if self.store is None:
            return False
        return self.store.contains(
            scoring_digest(self.spec, bucket, self.backend),
            expect=self._expect())

    def _expect(self) -> Dict[str, Any]:
        return {"backend": self.backend, "jaxVersion": _jax_version(),
                "program": self.spec.name,
                "outputs": list(self.spec.outputs)}

    # -- build / load -------------------------------------------------------

    def _arg_specs(self, bucket: int):
        import jax
        import jax.numpy as jnp

        specs = [jax.ShapeDtypeStruct((bucket, self.n_features),
                                      jnp.float32)]
        for p in self._params:
            specs.append(jax.ShapeDtypeStruct(np.shape(p), np.asarray(
                p).dtype))
        return tuple(specs)

    def _call_trees(self):
        import jax

        n_args = 1 + len(self._params)
        in_tree = jax.tree_util.tree_structure(((0,) * n_args, {}))
        out_tree = jax.tree_util.tree_structure((0,) * len(
            self.spec.outputs))
        return in_tree, out_tree

    def ensure_bucket(self, bucket: int, allow_load: bool = True) -> str:
        """Make ``bucket``'s program runnable; returns "aot" (loaded) or
        "jit" (compiled).  Corrupted / version-mismatched store entries
        fall back to JIT and are replaced by the write-through."""
        with self._lock:
            mode = self._modes.get(bucket)
            if mode is not None:
                return mode
        from ..obs.flight import record_event

        key = scoring_digest(self.spec, bucket, self.backend)
        ledger_key = f"{self.cache_key_prefix}.aot.bucket{bucket}"
        program = None
        mode = "jit"
        if allow_load and self.store is not None:
            got = self.store.get(key, expect=self._expect())
            if got is not None:
                payload, _meta = got
                try:
                    program = self._load(payload)
                    mode = "aot"
                    compile_cache.record_aot_load(ledger_key)
                    record_event("serve.aot_load", key=key, bucket=bucket)
                except Exception:
                    # undeserializable payload (e.g. foreign runtime):
                    # treat exactly like corruption — drop + recompile
                    self.store.invalidate(key)
                    program = None
            if program is None:
                compile_cache.record_aot_miss(ledger_key)
                record_event("serve.aot_miss", key=key, bucket=bucket)
        if program is None:
            program = self._compile(bucket)
            compile_cache.record_compile(ledger_key)
            record_event("serve.aot_compile", key=key, bucket=bucket)
            if self.store is not None:
                try:
                    payload = self._serialize(program)
                    self.store.put(key, payload, self._expect())
                except Exception:  # store is an optimization, never fatal
                    pass
        with self._lock:
            self._programs[bucket] = program
            self._modes[bucket] = mode
        return mode

    def _compile(self, bucket: int):
        import jax

        return jax.jit(self.spec.fn).lower(
            *self._arg_specs(bucket)).compile()

    def _serialize(self, program) -> bytes:
        from jax.experimental import serialize_executable as se

        payload, _in_tree, _out_tree = se.serialize(program)
        return payload

    def _load(self, payload: bytes):
        import jax
        from jax.experimental import serialize_executable as se

        in_tree, out_tree = self._call_trees()
        # load onto the ONE device ``_compile`` targets (the default
        # device): left to its default, deserialize_and_load spreads the
        # executable over every local device, and a single-device program
        # then fails at its first call on any multi-chip host
        return se.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=jax.devices()[:1])

    # -- execution ----------------------------------------------------------

    def predict(self, X: np.ndarray):
        """Run the compiled program for this exact batch shape; None when
        no program covers ``X`` (caller uses the host path)."""
        from ..models.prediction import PredictionBatch

        if X.ndim != 2 or X.shape[1] != self.n_features:
            return None
        bucket = int(X.shape[0])
        with self._lock:
            program = self._programs.get(bucket)
        if program is None:
            return None
        outs = program(np.ascontiguousarray(X, np.float32), *self._params)
        named = dict(zip(self.spec.outputs, outs))
        pred = np.asarray(named["prediction"]).astype(np.float64)
        raw = named.get("rawPrediction")
        proba = named.get("probability")
        return PredictionBatch(
            prediction=pred,
            raw_prediction=None if raw is None else np.asarray(raw),
            probability=None if proba is None else np.asarray(proba))


def find_predictor(workflow_model):
    """The AOT-relevant stage of a persisted workflow model: the LAST
    predictor stage in its scoring DAG (the one whose device program the
    serving hot path actually runs per batch)."""
    from ..models.prediction import PredictorModel

    found = None
    for stage in getattr(workflow_model, "stages", []) or []:
        if isinstance(stage, PredictorModel):
            found = stage
    return found


def program_set_for(model, store: Optional[AOTStore] = None,
                    cache_key_prefix: str = "serving"
                    ) -> Optional[ScoringProgramSet]:
    """Build + INSTALL a program set for a workflow model (or a bare
    predictor), or None when no stage has an AOT-exportable scoring
    program (serving stays on the host path — correct, just without the
    cold-start win).  Installation sets ``_serving_programs`` on the
    predictor stage; the programs only ever run inside the
    :class:`device_scoring` context."""
    predictor = None
    spec_fn = getattr(model, "aot_scoring_spec", None)
    if callable(spec_fn) and spec_fn() is not None:
        predictor = model
    else:
        cand = find_predictor(model)
        if cand is not None and cand.aot_scoring_spec() is not None:
            predictor = cand
    if predictor is None:
        return None
    ps = ScoringProgramSet(predictor, store=store,
                           cache_key_prefix=cache_key_prefix)
    predictor._serving_programs = ps
    return ps
