"""Persistent XLA compilation cache enablement.

A selector sweep compiles tens of XLA programs, and a cold process pays
for every one of them again.  JAX's persistent compilation cache removes
that on every run after the first, provided every process points at the
same directory (a directory that moves never hits).  The contract:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself;
  ``enable_persistent_cache`` sets NO directory in code.
* not set: the directory is the fixed ``<checkout>/.jax_cache``
  (git-ignored) — never a temp name, a pid or a timestamp.

Either way the minimum-compile-time threshold is lowered so the sweep's
many sub-second programs are cached too.  A cache that cannot be enabled
raises: a silently disabled cache looks exactly like a slow machine.
Spark-analogue: the reference has no equivalent (the JVM JITs per
process); this is XLA-specific plumbing.
"""
from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["enable_persistent_cache", "record_compile", "record_hit",
           "record_aot_load", "record_aot_miss",
           "cache_stats", "reset_cache_stats",
           "AOTStore", "AOT_FORMAT_VERSION", "default_aot_dir"]

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")

_enabled = False


def enable_persistent_cache(cache_dir: Optional[str] = None,
                            min_compile_secs: float = 0.15) -> str:
    """Turn on the persistent compilation cache and return its directory;
    safe to call repeatedly.  Call before the first compilation for full
    effect; programs compiled earlier in the process are not retroactively
    cached.  ``cache_dir`` overrides both the environment and the default
    (a test seam)."""
    global _enabled
    import jax

    if _enabled:
        return jax.config.jax_compilation_cache_dir
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    path = jax.config.jax_compilation_cache_dir
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    _enabled = True
    return path


# ---------------------------------------------------------------------------
# in-process compile accounting
# ---------------------------------------------------------------------------
#
# The persistent cache above removes *cross-process* recompiles; serving
# additionally needs to PROVE that its steady state never compiles at all
# (docs/performance.md: a cold XLA compile is multi-second — two orders of
# magnitude over a serving deadline).  These counters are the ledger: every
# warm-program site (the serving executor's shape buckets) records a
# ``compile`` when it builds/first-executes a program for a key and a
# ``hit`` when it reuses one, so tests can assert "N requests, zero new
# compiles after warmup" instead of trusting timing.

_stats_lock = threading.Lock()
_compiles: Dict[str, int] = {}
_hits: Dict[str, int] = {}
_aot_loads: Dict[str, int] = {}
_aot_misses: Dict[str, int] = {}


def record_compile(key: str, n: int = 1) -> None:
    """Count a program build (first execution at a new shape) for ``key``."""
    with _stats_lock:
        _compiles[key] = _compiles.get(key, 0) + n


def record_hit(key: str, n: int = 1) -> None:
    """Count a warm reuse of the already-compiled program for ``key``."""
    with _stats_lock:
        _hits[key] = _hits.get(key, 0) + n


def record_aot_load(key: str, n: int = 1) -> None:
    """Count a serialized executable loaded from the AOT store (a warm
    cold-start: no trace, no XLA compile)."""
    with _stats_lock:
        _aot_loads[key] = _aot_loads.get(key, 0) + n


def record_aot_miss(key: str, n: int = 1) -> None:
    """Count an AOT-store lookup that fell back to a JIT compile (absent,
    corrupted, or version-mismatched entry)."""
    with _stats_lock:
        _aot_misses[key] = _aot_misses.get(key, 0) + n


def cache_stats() -> Dict[str, Dict[str, int]]:
    """Snapshot: {'compiles': {key: n}, 'hits': {key: n}, 'totals': ...}."""
    with _stats_lock:
        compiles = dict(_compiles)
        hits = dict(_hits)
        aot_loads = dict(_aot_loads)
        aot_misses = dict(_aot_misses)
    return {
        "compiles": compiles,
        "hits": hits,
        "aotLoads": aot_loads,
        "aotMisses": aot_misses,
        "totals": {"compiles": sum(compiles.values()),
                   "hits": sum(hits.values()),
                   "aotLoads": sum(aot_loads.values()),
                   "aotMisses": sum(aot_misses.values())},
    }


def reset_cache_stats() -> None:
    with _stats_lock:
        _compiles.clear()
        _hits.clear()
        _aot_loads.clear()
        _aot_misses.clear()


# ---------------------------------------------------------------------------
# AOT executable store — content-addressed serialized XLA executables
# ---------------------------------------------------------------------------
#
# The persistent compilation cache above shortcuts the XLA *compile*; the
# AOT store goes further and persists the COMPILED EXECUTABLE itself
# (``jax.experimental.serialize_executable``), so a fresh serving process
# skips tracing, lowering AND compilation — cold start to first scored
# request drops from the seconds a Titanic-shaped DAG's ~28 program
# compiles take to milliseconds of deserialization.
#
# Entries are content-addressed: the key is a digest over the model's
# scoring parameters + shape bucket + backend + jax version + format
# version, so a changed model, a different backend, or a jax upgrade can
# NEVER load a stale executable — they simply miss and fall back to JIT
# (which writes the fresh entry through).  Writes are atomic (tmp +
# ``os.replace``, the utils/jsonio pattern) and every payload carries a
# sha256 checksum in its sidecar meta; a corrupted or truncated entry
# reads as a miss and is deleted, never served.

#: bump to invalidate every persisted executable (layout/semantic change)
AOT_FORMAT_VERSION = 1

_DEFAULT_AOT_DIR = os.path.join(_DEFAULT_DIR, "aot")


def default_aot_dir() -> str:
    """Resolve the AOT store root: ``TMOG_AOT_CACHE_DIR`` or
    ``<repo>/.jax_cache/aot``."""
    return os.environ.get("TMOG_AOT_CACHE_DIR", _DEFAULT_AOT_DIR)


class AOTStore:
    """On-disk content-addressed store of serialized XLA executables.

    One entry = ``<key>.bin`` (the serialized executable payload) +
    ``<key>.json`` (sidecar meta: checksum, backend, jax version, format
    version, output arity — everything a loader needs to validate the
    entry and rebuild the call trees without tracing).

    The store is a FLEET-shared artifact cache, not a per-process one:
    keys are content digests of (model, bucket, backend, jax version), a
    write is atomic tmp+fsync+``os.replace``, and ``get`` validates the
    checksummed sidecar before trusting a payload — so N serving hosts
    (or a host and its replacement) can safely point at one shared
    directory (``TMOG_AOT_CACHE_DIR``, e.g. on NFS).  The first host to
    compile a bucket warms every later cold start: a fresh replica loads
    the serialized executable byte-identically instead of compiling
    (bench_serving's shared-cache leg gates ``compiles == 0`` on the
    second process).  Concurrent writers of the same key race benignly —
    content addressing makes both payloads identical.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = root or default_aot_dir()

    # -- paths --------------------------------------------------------------

    def _paths(self, key: str) -> Tuple[str, str]:
        return (os.path.join(self.root, f"{key}.bin"),
                os.path.join(self.root, f"{key}.json"))

    # -- write --------------------------------------------------------------

    def put(self, key: str, payload: bytes, meta: Dict[str, Any]) -> None:
        """Persist one executable atomically.  ``meta`` is augmented with
        the payload checksum + size and the format version; a crashed
        writer leaves either the previous complete entry or none."""
        from .jsonio import write_json_atomic

        os.makedirs(self.root, exist_ok=True)
        bin_path, meta_path = self._paths(key)
        tmp = bin_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, bin_path)
        doc = dict(meta)
        doc["sha256"] = hashlib.sha256(payload).hexdigest()
        doc["bytes"] = len(payload)
        doc["formatVersion"] = AOT_FORMAT_VERSION
        write_json_atomic(meta_path, doc)

    # -- read ---------------------------------------------------------------

    def get(self, key: str,
            expect: Optional[Dict[str, Any]] = None
            ) -> Optional[Tuple[bytes, Dict[str, Any]]]:
        """Load + validate one entry; None on ANY problem (absent,
        truncated, checksum mismatch, format/field mismatch vs ``expect``)
        — the caller falls back to JIT.  Invalid entries are deleted so
        the write-through replaces them instead of tripping forever."""
        from .jsonio import read_json_tolerant

        bin_path, meta_path = self._paths(key)
        meta = read_json_tolerant(meta_path, default={})
        if not meta:
            return None
        try:
            with open(bin_path, "rb") as f:
                payload = f.read()
        except OSError:
            return None
        ok = (meta.get("formatVersion") == AOT_FORMAT_VERSION
              and meta.get("bytes") == len(payload)
              and meta.get("sha256")
              == hashlib.sha256(payload).hexdigest())
        if ok and expect:
            ok = all(meta.get(k) == v for k, v in expect.items())
        if not ok:
            self.invalidate(key)
            return None
        return payload, meta

    def contains(self, key: str,
                 expect: Optional[Dict[str, Any]] = None) -> bool:
        """Cheap validity probe (meta-only: checksum is verified at
        ``get`` time, field/version match here)."""
        from .jsonio import read_json_tolerant

        bin_path, meta_path = self._paths(key)
        if not os.path.exists(bin_path):
            return False
        meta = read_json_tolerant(meta_path, default={})
        if not meta or meta.get("formatVersion") != AOT_FORMAT_VERSION:
            return False
        if expect and any(meta.get(k) != v for k, v in expect.items()):
            return False
        return True

    def invalidate(self, key: str) -> None:
        for p in self._paths(key):
            try:
                os.unlink(p)
            except OSError:
                pass

    def keys(self) -> List[str]:
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(n[:-4] for n in names if n.endswith(".bin"))

    def stats(self) -> Dict[str, Any]:
        """Fleet-operator view of the shared cache directory: entry count
        + payload bytes (the answer to "is the shared cache actually
        warming cold starts, and how big has it grown")."""
        entries = self.keys()
        payload_bytes = 0
        for k in entries:
            try:
                payload_bytes += os.path.getsize(self._paths(k)[0])
            except OSError:
                pass
        return {"root": self.root, "entries": len(entries),
                "payloadBytes": payload_bytes}
