"""Shared bench-JSON metadata — one helper instead of N hand-rolled copies.

Every ``examples/bench_*.py`` script used to assemble its own backend /
rss / timestamp fields for ``benchmarks/*_latest.json``; the shapes had
drifted (some recorded rss, some not; none carried a run id).  This
helper gives every bench JSON an identical ``meta`` block — including the
active trace id when the run was traced, so a bench artifact links back
to its span tree and flight recording.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

__all__ = ["bench_meta", "estimate_disabled_overhead_s"]


def _rss_mb() -> Optional[float]:
    try:
        import resource

        return round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)
    except Exception:  # pragma: no cover - non-POSIX
        return None


def bench_meta(wall_s: Optional[float] = None) -> Dict[str, Any]:
    """The standard metadata block every bench JSON carries: backend and
    the device as JAX reports it (platform, kind, count — a number without
    them cannot be told from a CPU run), jax version, peak RSS, pid, unix
    time, a fresh run id, and the active trace id (None when the run was
    untraced)."""
    import jax

    from ..utils.profiling import backend_name
    from ..utils.uid import uid_for
    from .trace import current_tracer

    tracer = current_tracer()
    devices = jax.devices()
    meta: Dict[str, Any] = {
        "backend": backend_name(),
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
        "deviceCount": len(devices),
        "jax": jax.__version__,
        "rssMb": _rss_mb(),
        "at": int(time.time()),
        "pid": os.getpid(),
        "runId": uid_for("Bench"),
        "traceId": tracer.trace_id if tracer is not None else None,
    }
    if wall_s is not None:
        meta["wallSecs"] = round(float(wall_s), 3)
    return meta


def estimate_disabled_overhead_s(n_hooks: int,
                                 samples: int = 50_000) -> float:
    """Measured cost of ``n_hooks`` disabled tracing hooks.

    Times ``samples`` begin/end span pairs plus flight-event checks with
    tracing OFF (the steady production state) and scales to ``n_hooks`` —
    the ``lint_wall_s``-style fraction bench_pipeline emits to prove the
    instrumentation stays off-path when disabled.  Callers must invoke
    this with no tracer installed; it raises otherwise rather than
    reporting an enabled-path number as the disabled overhead."""
    from .flight import current_recorder, record_event
    from .trace import begin_span, current_tracer, end_span

    if current_tracer() is not None or current_recorder() is not None:
        raise RuntimeError(
            "estimate_disabled_overhead_s must run with tracing disabled")
    t0 = time.perf_counter()
    for _ in range(samples):
        sp = begin_span("x", cat="bench")
        record_event("x")
        end_span(sp)
    per_hook = (time.perf_counter() - t0) / samples
    return per_hook * max(int(n_hooks), 0)
