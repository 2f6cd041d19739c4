"""Elastic sweep execution — survive device loss, degrade, keep finishing.

Real TPU fleets are preemptible and resize under you (cf. the TPU
serving/fine-tuning comparison in PAPERS.md): a chip drops mid-sweep, the
backend restarts, or a preempted pod comes back smaller.  Before this
module the pod-scale selector sweep (parallel/mesh.py + selector/
validators.py) answered every one of those with an aborted train.
This module holds the pieces that turn "restartable" into "finishes
anyway":

* :func:`is_device_loss` / :func:`classify_sweep_error` — the shared
  classifier for backend/XLA runtime errors: every sweep-unit exception
  handler routes through ONE list of needles (the TM046 lint pins this:
  a broad ``except Exception`` around sweep-unit execution that does not
  consult the classifier is a static error).
* :class:`ElasticCounters` — retries / mesh shrinks / quarantined units /
  watchdog fires / device losses, mirrored into the global
  ``utils.profiling`` run counters so bench JSON and selector metadata
  report the same numbers.
* :class:`ElasticContext` — the per-sweep policy object the
  ``SweepWorkQueue`` consults: bounded per-unit retry on device loss
  (shrinking the mesh between attempts, ultimately to the single-device
  CPU path), the opt-in straggler watchdog (per-unit deadlines at
  ``factor x CostModel.predict``, escalating timeout -> degraded re-run
  -> quarantine), and the checkpoint flush that makes completed work
  durable before a risky retry.
* :func:`shrink_mesh` — rebuild a smaller ("data", "grid") sweep mesh
  from the devices that still answer; ``None`` means "no mesh left, fit
  single-device".

Testability: ``utils.faults`` gained the ``device_loss`` action and the
``unit.slow`` / ``device.loss`` injection points (fired at the top of
every sweep-unit attempt), so the whole escalation matrix is
seed-deterministically exercised in tests/test_elastic.py without ever
needing a chip to actually die.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "is_device_loss", "classify_sweep_error", "DEVICE_LOSS_NEEDLES",
    "ElasticCounters", "ElasticContext", "shrink_mesh",
    "run_with_deadline",
]

#: message fragments that say the accelerator BACKEND is missing/broken —
#: as opposed to a workload failure (a diverging candidate, a shape
#: error): backend-init failures, the runtime device-loss shapes XLA
#: raises mid-execution, and the fault harness's injected form.
DEVICE_LOSS_NEEDLES = (
    "Unable to initialize backend",
    "backend setup/compile error",
    "No visible TPU",
    "failed to connect to all addresses",
    "UNAVAILABLE: TPU",
    "DEVICE_LOST",
    "device is lost",
    "Device or resource busy",
    "injected device loss",
)


def is_device_loss(e: BaseException) -> bool:
    """True when ``e`` says a device/backend died — the recoverable-by-
    degrading class — rather than the workload itself failing."""
    from ..utils.faults import DeviceLossError

    if isinstance(e, DeviceLossError):
        return True
    msg = f"{type(e).__name__}: {e}"
    return any(s in msg for s in DEVICE_LOSS_NEEDLES)


def classify_sweep_error(e: BaseException) -> str:
    """``"device_loss"`` | ``"workload"`` — the routing decision every
    sweep-unit exception handler must make (lint rule TM046)."""
    return "device_loss" if is_device_loss(e) else "workload"


def surviving_devices():
    """Devices that still answer, or ``[]`` when the backend itself is
    gone (at which point the caller falls back to single-device CPU —
    jax re-inits lazily on the next host-path fit)."""
    try:
        import jax

        return list(jax.devices())
    except Exception:
        return []


def shrink_mesh(mesh, queue_width: int = 1):
    """The next smaller ("data", "grid") sweep mesh from the surviving
    devices, or ``None`` when one (or zero) device remains — the signal
    to drop to the single-device fit path.

    The returned mesh is pure data-parallel (grid axis 1): after a loss
    the grid groups are stripped anyway (their compiled programs target
    the dead mesh), so the degraded mode is sequential mesh-sharded fits.
    """
    from .mesh import make_sweep_mesh

    prev = 1
    if mesh is not None:
        prev = 1
        for name in mesh.axis_names:
            prev *= int(mesh.shape[name])
    devs = surviving_devices()
    n = min(len(devs), max(prev // 2, 1))
    # largest power of two <= n keeps the data axis tiling trivial
    p = 1
    while p * 2 <= n:
        p *= 2
    if p <= 1:
        return None
    return make_sweep_mesh(queue_width, n_devices=p, grid_parallelism=1)


def mesh_device_count(mesh) -> int:
    """Devices a mesh spans (1 for ``None`` — the single-chip path)."""
    if mesh is None:
        return 1
    n = 1
    for name in mesh.axis_names:
        n *= int(mesh.shape[name])
    return n


@dataclass
class ElasticCounters:
    """The elastic-execution scoreboard for one sweep.

    Mirrored increment-by-increment into the global profiling counters
    (``utils.profiling.count_elastic``) so ``benchmarks/*_latest.json``
    and ``model_selector_summary`` metadata agree without plumbing.
    """

    retries: int = 0            # unit re-runs (device loss or watchdog)
    mesh_shrinks: int = 0       # mesh rebuilt smaller (incl. resume-time)
    mesh_repacks: int = 0       # resume re-batched onto a DIFFERENT mesh
    quarantined: int = 0        # units given up on after the retry budget
    watchdog_fires: int = 0     # per-unit deadline overruns
    device_losses: int = 0      # classified device-loss exceptions seen

    def count(self, kind: str, n: int = 1) -> None:
        setattr(self, kind, getattr(self, kind) + n)
        from ..obs.flight import record_event
        from ..utils.profiling import count_elastic

        count_elastic(kind, n)
        # every elastic transition is a flight-recorder event — counting
        # at the single shared site keeps the causal order (loss →
        # shrink → retry → quarantine) exactly as the ladder executed it
        record_event(f"elastic.{kind}")

    def to_json(self) -> Dict[str, int]:
        return {"retries": self.retries,
                "meshShrinks": self.mesh_shrinks,
                "meshRepacks": self.mesh_repacks,
                "quarantined": self.quarantined,
                "watchdogFires": self.watchdog_fires,
                "deviceLosses": self.device_losses}


class ElasticContext:
    """Per-sweep elastic policy, consulted by ``SweepWorkQueue``.

    ``shrink`` is the owner's degrade hook (the ModelSelector rebuilds a
    smaller mesh from surviving devices and re-points its live ``mesh``
    attribute — the unit fitters read it per fit, so the NEXT attempt
    lands on the shrunk mesh with no queue surgery); it returns True when
    something actually changed.  ``unit_deadline_s`` arms the straggler
    watchdog (None = off; the ModelSelector only arms it when the cost
    model's tier is warm — a cold tier would produce garbage deadlines).
    """

    def __init__(self,
                 shrink: Optional[Callable[[], bool]] = None,
                 max_unit_retries: int = 2,
                 unit_deadline_s: Optional[float] = None,
                 max_watchdog_retries: int = 1,
                 counters: Optional[ElasticCounters] = None):
        self.shrink_cb = shrink
        self.max_unit_retries = int(max_unit_retries)
        self.unit_deadline_s = unit_deadline_s
        self.max_watchdog_retries = int(max_watchdog_retries)
        self.counters = counters or ElasticCounters()
        #: set by run_all so a risky retry can flush completed units first
        self.checkpoint: Any = None
        #: flips True after a shrink: remaining grid-group blocks target
        #: the dead mesh and must be stripped to sequential fits
        self.groups_invalid = False
        #: watchdog-abandoned worker threads (an in-flight XLA program
        #: cannot be interrupted); drained at sweep end so a finishing
        #: straggler never runs into interpreter teardown
        self.abandoned: list = []

    # -- shared classifier ---------------------------------------------------

    @staticmethod
    def classify(e: BaseException) -> bool:
        return is_device_loss(e)

    # -- plumbing ------------------------------------------------------------

    def _shrink_once(self) -> bool:
        if self.shrink_cb is None:
            return False
        try:
            changed = bool(self.shrink_cb())
        except Exception:   # a failing degrade hook must not mask the loss
            changed = False
        if changed:
            self.counters.count("mesh_shrinks")
            self.groups_invalid = True
        return changed

    def _flush_checkpoint(self) -> None:
        ck = self.checkpoint
        if ck is not None:
            try:
                ck.flush()
            except Exception:   # durability is best-effort mid-recovery
                pass

    # -- escalation hooks ----------------------------------------------------

    def on_device_loss(self, unit_index: int, err: BaseException,
                       attempt: int) -> bool:
        """A classified device loss inside unit ``unit_index`` on retry
        ``attempt``.  True = shrink happened (or was attempted) and the
        unit should re-run; False = budget exhausted, quarantine it."""
        self.counters.count("device_losses")
        self._flush_checkpoint()
        if attempt >= self.max_unit_retries:
            self.counters.count("quarantined")
            return False
        self._shrink_once()
        self.counters.count("retries")
        return True

    def on_group_device_loss(self, err: BaseException) -> None:
        """A device loss inside a batched grid-group program: shrink and
        let the queue strip the group to sequential fits (which then land
        on the shrunk mesh).  The strip IS the retry — every member
        re-runs — so it lands on the retry counter like a unit re-run."""
        self.counters.count("device_losses")
        self._flush_checkpoint()
        self._shrink_once()
        self.counters.count("retries")

    def on_watchdog_timeout(self, unit_index: int, attempt: int) -> bool:
        """Unit ``unit_index`` blew its deadline.  True = degrade and
        re-run (the deadline doubles per attempt); False = quarantine."""
        self.counters.count("watchdog_fires")
        self._flush_checkpoint()
        if attempt >= self.max_watchdog_retries:
            self.counters.count("quarantined")
            return False
        self._shrink_once()
        self.counters.count("retries")
        return True

    def drain(self, per_thread_timeout_s: float = 30.0) -> int:
        """Join watchdog-abandoned workers (bounded per thread) at sweep
        end: their results are already discarded, but letting them run
        into interpreter teardown crashes the XLA runtime.  A thread
        still alive past the cap is left as a daemon (a truly hung
        program must not hang the sweep's exit too).  Returns how many
        were still alive when drain started."""
        alive = [t for t in self.abandoned if t.is_alive()]
        for t in alive:
            t.join(per_thread_timeout_s)
        self.abandoned = [t for t in self.abandoned if t.is_alive()]
        return len(alive)

    def note_resumed_mesh(self, saved_mesh: Optional[Dict[str, Any]],
                          current_mesh: Optional[Dict[str, Any]]) -> None:
        """A checkpoint written under ``saved_mesh`` resumed under
        ``current_mesh`` (advisory records, ``checkpoint.mesh_record``).
        Counts the re-pack, and a shrink when the device count dropped —
        the ELASTIC_SMOKE gate asserts this is visible in the JSON."""
        if saved_mesh == current_mesh:
            return
        self.counters.count("mesh_repacks")
        saved_n = int((saved_mesh or {}).get("devices", 1))
        cur_n = int((current_mesh or {}).get("devices", 1))
        if cur_n < saved_n:
            self.counters.count("mesh_shrinks")


def run_with_deadline(fn: Callable[[], Any], deadline_s: float,
                      abandoned: Optional[list] = None) -> Tuple[Any, bool]:
    """Run ``fn`` in a daemon worker with a join deadline.

    Returns ``(value, timed_out)``.  On timeout the worker keeps running
    (an in-flight XLA program cannot be interrupted) but the sweep moves
    on — the abandoned thread's result is discarded, and the thread is
    appended to ``abandoned`` so the sweep can :meth:`ElasticContext.
    drain` it before exiting.  Exceptions raised by ``fn`` re-raise
    here, so the caller's device-loss routing sees them exactly as in
    the undecorated path.
    """
    box: Dict[str, Any] = {}

    def work():
        try:
            box["val"] = fn()
        except BaseException as e:  # noqa: BLE001 - re-raised to caller
            box["err"] = e

    t = threading.Thread(target=work, name="sweep-unit-watchdog",
                         daemon=True)
    t.start()
    t.join(max(float(deadline_s), 1e-3))
    if t.is_alive():
        if abandoned is not None:
            abandoned.append(t)
        return None, True
    if "err" in box:
        raise box["err"]
    return box.get("val"), False
