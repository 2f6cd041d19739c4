"""BENCHMARK.json and the data files it names, loaded and checked.

The harness finds everything by name: a cell names its configuration and
its traffic mix, a configuration names its file, a traffic mix is
``traffic/<name>.json``, a mode is ``modes/<name>.py``, a generator is
``generators/<name>.py`` and a per-layer metric is ``metrics/<name>.py``.
Nothing here lists cells, so a later PR adds files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_module(kind: str, name: str, root: str = ROOT):
    """``perfbench/<kind>/<name>.py`` as a module, found by file name."""
    if not NAME.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = os.path.join(root, "perfbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration file, its
    traffic file, and the metrics it reports."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = _json(os.path.join(root, entry["file"]))
    traffic = _json(os.path.join(root, "perfbench", "traffic",
                                 cell["traffic"] + ".json"))

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "config_entry": entry,
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)],
            "run_seconds": bench["run_seconds"]}
