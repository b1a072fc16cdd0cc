"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``, a data file); the mix's ``kind`` names
its runner, ``kinds/<kind>.py``; a per-layer metric is read by
``metrics/<name>.py``, or, for a quantity split by cell
(``device.idle_share.gossip``), by the file of its stem
(``metrics/device.idle_share.py``).  Adding a cell, a mix, a kind or a
metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload named {name!r}")


def config(bench: dict, root: Path, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise SystemExit(f"benchmark: no configuration named {name!r}")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def end_to_end(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics whose ``workloads`` list ``cell``; every
    per-layer entry has to carry that list."""
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise SystemExit(f"benchmark: per-layer metric {m['name']!r} "
                             "lists no workloads")
    return [m for m in bench["per_layer"] if cell in m["workloads"]]


@functools.cache
def _module(path: Path, name: str):
    """The module at ``path``, loaded once per process (a kind keeps what
    it compiled for a later run in the same process)."""
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def kind(name: str):
    """The ``run(run) -> Outcome`` function of ``kinds/<name>.py``."""
    return _module(BENCH / "kinds" / f"{name}.py",
                   f"benchmark_kind_{name}").run


def reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``, or of the file
    of the longest stem of ``name`` (split at dots) that has one."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = BENCH / "metrics" / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return _module(path, f"benchmark_metric_{name}").read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{BENCH / 'metrics'}")
