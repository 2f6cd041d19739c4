"""Pod runtime — ``jax.distributed`` bootstrap + host-level collectives.

Reference mapping: the reference's multi-machine story is a Spark
cluster — a driver plus executors, with ``treeAggregate`` merging
partition statistics across the wire.  The TPU-native equivalent is a
JAX POD: N OS processes, each owning a slice of the global device set,
booted through ``jax.distributed.initialize`` so device collectives
(psum/allgather) span processes.  "Large Scale Distributed Linear
Algebra With TPUs" (PAPERS.md) is the kernel-side template; this module
is the process-side substrate.

Two layers live here:

* :class:`PodContext` — who am I (``process_index`` / ``process_count``
  / coordinator address), what do I own (``local_devices`` vs the global
  addressable set), plus the HOST-LEVEL collectives the streaming-fit
  protocol needs: ``allgather_obj`` (pickle over a padded uint8
  ``process_allgather``), ``broadcast_obj``, and ``barrier``.  Mergeable
  fit states are host objects, so cross-process merges ride these
  instead of hand-rolled device programs.
* bootstrap — ``TMOG_POD_*`` env handshake (:func:`init_pod_from_env`),
  and :func:`launch_local_pod`, which forks N local CPU processes with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` so a whole pod
  is testable on ONE CI host (the ``tmog pod`` CLI and
  ``examples/launch_pod.py`` are thin wrappers).

Env handshake (set by the launcher, read by ``init_pod_from_env``)::

  TMOG_POD_COORDINATOR     host:port of process 0's coordinator service
  TMOG_POD_NUM_PROCESSES   pod size
  TMOG_POD_PROCESS_ID      this process's index
  TMOG_POD_LOCAL_DEVICES   forced host-platform device count (CPU pods)

CPU pods additionally need the gloo collectives backend
(``jax_cpu_collectives_implementation``) selected BEFORE
``jax.distributed.initialize`` — the stock CPU client raises
"Multiprocess computations aren't implemented" on the first
cross-process program otherwise.
"""
from __future__ import annotations

import os
import pickle
import signal
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["PodContext", "PodTimeoutError", "current_pod",
           "init_pod_from_env", "launch_local_pod", "pick_free_port",
           "pod_env", "ENV_COORDINATOR", "ENV_NUM_PROCESSES",
           "ENV_PROCESS_ID", "ENV_LOCAL_DEVICES"]

ENV_COORDINATOR = "TMOG_POD_COORDINATOR"
ENV_NUM_PROCESSES = "TMOG_POD_NUM_PROCESSES"
ENV_PROCESS_ID = "TMOG_POD_PROCESS_ID"
ENV_LOCAL_DEVICES = "TMOG_POD_LOCAL_DEVICES"


class PodTimeoutError(RuntimeError):
    """A pod child did not come up (or a peer died mid-collective)."""


class PodContext:
    """One process's view of the pod.

    ``active`` is False for the inert single-process context
    (``process_count == 1`` with no distributed runtime) — every
    collective then degenerates to the identity, so pod-aware code paths
    never need a separate single-process branch.
    """

    def __init__(self, process_index: int = 0, process_count: int = 1,
                 coordinator_address: Optional[str] = None,
                 initialized: bool = False, declared: bool = False):
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.coordinator_address = coordinator_address
        self.initialized = initialized
        #: True when the TMOG_POD_* env named a pod — including a POD OF
        #: ONE, which runs the full pod train protocol (entry-structured
        #: passes, pod checkpoints) with every collective degenerate;
        #: that is how a 2-process checkpoint resumes on 1 process
        self.declared = declared
        #: cross-host-count resumes observed by this process's trains
        self.repacks = 0
        self._step = 0

    # -- identity ------------------------------------------------------------

    @property
    def active(self) -> bool:
        """True when collectives actually cross processes."""
        return self.process_count > 1

    def is_coordinator(self) -> bool:
        """True for process 0 — the ONLY process that performs durable
        side effects (checkpoints, benchmarks/*.json, cost-history
        appends, quarantine sidecars); lint rule TM047 pins the
        convention."""
        return self.process_index == 0

    def local_devices(self) -> List[Any]:
        import jax

        return list(jax.local_devices())

    def addressable_device_count(self) -> int:
        return len(self.local_devices())

    def global_device_count(self) -> int:
        import jax

        return len(jax.devices())

    def describe(self) -> Dict[str, Any]:
        """The ADVISORY pod record a checkpoint carries (never compared
        on resume — host counts are elastic, the exact analogue of the
        PR 9 mesh record)."""
        return {"processCount": self.process_count,
                "processIndex": self.process_index}

    # -- host-level collectives ---------------------------------------------
    #
    # Under TMOG_CHECK=1 every collective records (seq, kind, site) into
    # the per-process CollectiveLedger (analysis/contracts.py) and
    # carries that header INSIDE its payload, so two processes whose
    # collective sequences split fail with both sites named (TM074)
    # instead of hanging the transport; TMOG_COLLECTIVE_TIMEOUT arms a
    # watchdog around every blocking exchange (TM073).

    def _exchange(self, obj: Any) -> List[Any]:
        """The raw padded-pickle allgather every host collective rides."""
        from jax.experimental import multihost_utils

        raw = np.frombuffer(pickle.dumps(obj), np.uint8)
        lens = multihost_utils.process_allgather(
            np.array([len(raw)], np.int64)).ravel()
        # bucket the padded length to the next power of two: every
        # distinct shape jit-compiles a fresh allgather program, and a
        # long train exchanges dozens of distinct payload sizes —
        # bucketing keeps the executable cache to O(log max_payload)
        need = max(int(lens.max()), 1)
        size = 1024
        while size < need:
            size <<= 1
        buf = np.zeros(size, np.uint8)
        buf[:len(raw)] = raw
        rows = multihost_utils.process_allgather(buf)
        rows = np.atleast_2d(rows)
        return [pickle.loads(rows[i, :int(lens[i])].tobytes())
                for i in range(self.process_count)]

    def _ledger_exchange(self, entry, obj: Any) -> List[Any]:
        """Header-verified exchange: every payload carries its ledger
        entry; a peer at a different seq/kind is named (TM074)."""
        from ..analysis.contracts import (CollectiveWatchdog,
                                          verify_collective_headers)
        from ..analysis.diagnostics import ContractViolation, Diagnostic

        with CollectiveWatchdog(entry[1], entry[2]):
            rows = self._exchange({"h": entry, "o": obj})
        headers = []
        for i, r in enumerate(rows):
            if not (isinstance(r, dict) and "h" in r and "o" in r):
                raise ContractViolation(Diagnostic(
                    rule="TM074",
                    message=(f"collective-ledger divergence: this "
                             f"process paired {entry[1]} (ledger seq "
                             f"{entry[0]}, {entry[2]}) with an unledgered "
                             f"payload from process {i} — the peer is "
                             f"executing a different exchange"),
                    location=str(entry[2])))
            headers.append(tuple(r["h"]))
        verify_collective_headers(headers)
        return [r["o"] for r in rows]

    def barrier(self, name: str) -> None:
        """All processes rendezvous; returns once every peer arrived."""
        if not self.active:
            return
        from ..analysis.contracts import record_collective
        from ..utils.faults import FaultSkip, fire

        try:
            fire("pod.barrier", tag=name)
        except FaultSkip:
            return
        self._step += 1
        entry = record_collective("barrier", name)
        # TMOG_CHECK is pod-uniform (launch_local_pod inherits the env),
        # so every process takes the same transport branch
        if entry is not None:  # tmog: disable=TM071
            # ledger mode: the rendezvous doubles as a header check, so
            # a peer arriving with a DIFFERENT collective is attributed
            self._ledger_exchange(entry, None)
            return
        from jax.experimental import multihost_utils

        from ..analysis.contracts import CollectiveWatchdog

        label = f"tmog.{name}.{self._step}"
        with CollectiveWatchdog(f"barrier({name})", label):
            multihost_utils.sync_global_devices(label)

    def allgather_obj(self, obj: Any,
                      _kind: str = "allgather_obj") -> List[Any]:
        """Every process contributes one picklable object; every process
        receives the full list ORDERED BY PROCESS INDEX — the merge-order
        anchor of the streaming-fit exchange (states merge host 0 first,
        matching a single process's sequential chunk order)."""
        if not self.active:
            return [obj]
        from ..analysis.contracts import (CollectiveWatchdog,
                                          record_collective)

        entry = record_collective(_kind)
        # same pod-uniform TMOG_CHECK dispatch as barrier above
        if entry is not None:  # tmog: disable=TM071
            return self._ledger_exchange(entry, obj)
        with CollectiveWatchdog(_kind, "<ledger off>"):
            return self._exchange(obj)

    def broadcast_obj(self, obj: Any, kind: str = "broadcast_obj") -> Any:
        """Coordinator's object lands on every process (others pass any
        placeholder, conventionally None).  ``kind`` labels the exchange
        in the collective ledger — the serving control channel passes
        ``"fabric.control"`` so a divergent fleet-control message is
        attributed as such rather than as a generic broadcast."""
        if not self.active:
            return obj
        # one exchange both directions keeps the protocol lockstep-simple;
        # pod payloads here are small (decisions, counters, cursors)
        return self.allgather_obj(obj, _kind=kind)[0]

    def allsum(self, arr: np.ndarray) -> np.ndarray:
        """Elementwise sum of a host float array across processes."""
        if not self.active:
            return np.asarray(arr)
        parts = self.allgather_obj(np.asarray(arr), _kind="allsum")
        out = parts[0].astype(np.float64, copy=True)
        for p in parts[1:]:
            out += p
        return out.astype(np.asarray(arr).dtype, copy=False)


#: process-wide pod context; inert singleton until init_pod_from_env runs
_POD = PodContext()


def current_pod() -> PodContext:
    return _POD


def init_pod_from_env(local_devices: Optional[int] = None) -> PodContext:
    """Initialize the distributed runtime from the ``TMOG_POD_*``
    handshake; a no-op (returning the inert context) when the env does
    not describe a pod.  Must run BEFORE the first jax device use.
    Idempotent per process."""
    global _POD
    if _POD.initialized:
        return _POD
    raw_n = os.environ.get(ENV_NUM_PROCESSES)
    n = int(raw_n or 1)
    if raw_n is None:
        return _POD
    if n == 1:
        # a DECLARED pod of one: no distributed runtime to boot, but the
        # pod train protocol engages (cross-host-count resume rides it)
        _POD = PodContext(process_index=0, process_count=1,
                          initialized=True, declared=True)
        return _POD
    coord = os.environ.get(ENV_COORDINATOR)
    idx = int(os.environ.get(ENV_PROCESS_ID, "0") or 0)
    if not coord:
        raise ValueError(
            f"{ENV_NUM_PROCESSES}={n} but {ENV_COORDINATOR} is unset — "
            f"launch pod processes via launch_local_pod / `tmog pod` (or "
            f"export the coordinator address yourself)")
    ndev = local_devices if local_devices is not None else int(
        os.environ.get(ENV_LOCAL_DEVICES, "0") or 0)
    if ndev and "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={ndev}").strip()
    import jax

    # the stock CPU client has no cross-process collectives; gloo does.
    # Selected unconditionally (it only affects the CPU client) and
    # WITHOUT consulting jax.default_backend() — that call would
    # initialize the backend, after which distributed.initialize refuses
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=n, process_id=idx)
    _POD = PodContext(process_index=idx, process_count=n,
                      coordinator_address=coord, initialized=True,
                      declared=True)
    from ..obs.trace import set_global_attrs

    set_global_attrs(process=idx)
    from ..obs.flight import record_event

    record_event("pod.init", process=idx, processes=n, coordinator=coord,
                 local_devices=len(jax.local_devices()))
    return _POD


def _set_pod(pod: PodContext) -> PodContext:
    """Test seam: install a context without booting jax.distributed."""
    global _POD
    _POD = pod
    return pod


# ---------------------------------------------------------------------------
# local pod launcher — N processes on ONE host, testable in CI
# ---------------------------------------------------------------------------

def pick_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pod_env(process_id: int, num_processes: int, coordinator: str,
            local_devices: int = 2,
            base_env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The child environment for one pod process: the ``TMOG_POD_*``
    handshake plus the forced host-platform device count.  The parent's
    env (``TMOG_FAULTS`` included — fault schedules are INHERITED, so a
    seeded plan is process-deterministic across the pod) passes through
    unless overridden."""
    env = dict(os.environ if base_env is None else base_env)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_NUM_PROCESSES] = str(int(num_processes))
    env[ENV_PROCESS_ID] = str(int(process_id))
    env[ENV_LOCAL_DEVICES] = str(int(local_devices))
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def launch_local_pod(num_processes: int, argv: Sequence[str],
                     local_devices: int = 2,
                     base_env: Optional[Dict[str, str]] = None,
                     timeout: float = 600.0,
                     kill_grace_s: float = 20.0,
                     cwd: Optional[str] = None) -> List[Dict[str, Any]]:
    """Fork ``argv`` as an N-process local pod and wait for all of them.

    Each child gets the :func:`pod_env` handshake with a freshly picked
    coordinator port.  If any child dies (non-zero exit or a SIGKILL
    from an armed fault plan), the survivors — which may be blocked in a
    collective waiting for the corpse — are terminated after
    ``kill_grace_s`` so a crash test can never deadlock the harness.

    Returns one record per process: ``{"returncode", "stdout",
    "stderr"}`` in process order.
    """
    coord = f"127.0.0.1:{pick_free_port()}"
    procs = []
    for i in range(int(num_processes)):
        env = pod_env(i, num_processes, coord, local_devices=local_devices,
                      base_env=base_env)
        procs.append(subprocess.Popen(
            list(argv), env=env, cwd=cwd,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    deadline = time.time() + timeout
    first_death: Optional[float] = None
    while True:
        states = [p.poll() for p in procs]
        if all(s is not None for s in states):
            break
        dead_bad = any(s is not None and s != 0 for s in states)
        now = time.time()
        if dead_bad and first_death is None:
            first_death = now
        if ((first_death is not None and now - first_death > kill_grace_s)
                or now > deadline):
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            time.sleep(1.0)
            for p in procs:
                if p.poll() is None:
                    p.kill()
            if now > deadline and first_death is None:
                for p in procs:
                    p.wait()
                raise PodTimeoutError(
                    f"pod of {num_processes} did not finish within "
                    f"{timeout:.0f}s")
            break
        time.sleep(0.05)
    out = []
    for p in procs:
        stdout, stderr = p.communicate()
        out.append({"returncode": p.returncode, "stdout": stdout,
                    "stderr": stderr})
    return out


def main_pod_cli(args) -> int:
    """`tmog pod -n N [--devices K] -- cmd ...` — run a command as an
    N-process local pod (each child sees the TMOG_POD_* handshake and
    calls ``init_pod_from_env`` itself)."""
    results = launch_local_pod(args.num_processes, args.cmd,
                               local_devices=args.devices,
                               timeout=args.timeout)
    rc = 0
    for i, r in enumerate(results):
        sys.stdout.write(f"--- pod process {i} (rc={r['returncode']}) ---\n")
        sys.stdout.write(r["stdout"])
        if r["returncode"] != 0:
            sys.stderr.write(r["stderr"])
            rc = r["returncode"] or 1
    return rc
