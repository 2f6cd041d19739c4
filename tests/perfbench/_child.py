"""Child-process plumbing shared by the perfbench tests: the platform, x64
and the compile-cache directory all latch at first jax use, so every run of
``perfbench/run.py`` is a process of its own (the pattern of
``tests/test_chip_smoke.py``); and what a tiny run of a cell is given and
has to print, which follows from the cell's configuration alone."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = ["--rows", "3000", "--cols", "32"]
#: the generator's own oracle, by label kind (``perfbench/checks.py``); a
#: binary generator without one is scored by ``X @ beta``
OWN_ORACLE = {"binary": "oracle_score", "regression": "oracle_predict"}


def child_env(cache_dir, devices: int = 1, **extra) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["TMOG_COST_HISTORY"] = ""
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the chip path is 32-bit; conftest's x64 must not leak into the child
    env.pop("JAX_ENABLE_X64", None)
    env.pop("PYTHONPATH", None)
    env.update(extra)
    return env


def run_cell(workload: str, *flags, root: str = ROOT, cache_dir,
             devices: int = 1, seconds: str = "2", trace: str = "0"):
    """``perfbench/run.py`` for one cell from ``root``; returns the
    completed process and the parsed last line (``None`` if it is no JSON
    object)."""
    out = subprocess.run(
        [sys.executable, os.path.join("perfbench", "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", seconds,
         "--trace", trace, *flags],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=child_env(cache_dir, devices))
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return out, last


def tiny_run(cell: str, root: str = ROOT) -> dict:
    """A tiny run of the one-chip ``cell`` under ``root``: its ``flags`` (a
    schema that lists its columns keeps them), and what its last line and
    log have to show by the cell's label kind (``checks.LABEL_KINDS``) and
    generator: ``holdout``, the quality metric; ``oracle_key``, the metric's
    comparison with the oracle in ``compared``; ``oracle_from``."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import checks, spec

    config = spec.load_cell(cell, root=root)["config"]
    kind = checks.label_kind(config)
    generator = spec.load_module("generators", config["generator"]["name"],
                                 root=root)
    own = OWN_ORACLE[config["problem"]]
    listed = "columns" in config["schema"]["predictors"]
    return {"flags": TINY[:2] if listed else TINY, "holdout": kind.holdout,
            "oracle_key": kind.metric.lower() + "_over_oracle",
            "oracle_from": own if hasattr(generator, own) else "X @ beta"}
