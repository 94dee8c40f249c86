"""Find the benchmark's pieces by name.

``BENCHMARK.json`` at the root of the checkout names the cells, each with
a configuration and a traffic mix.  Each name maps to one file:

- ``configs/<config>.json``   the deployment (grid, order, layout, ...);
- ``traffic/<traffic>.json``  the mix: a driver name and its parameters;
- ``cells/<cell>.json``       the limits of the comparison that decides
  ``correct``;
- ``drivers/<driver>.py``, ``equations/<equation>.py``,
  ``e2e/<metric>.py``, ``layers/<metric>.py``: code, loaded by path.
  A metric named ``<base>.<part>`` is read by ``<base>.py``: the part
  after the dot splits one quantity over cells that report different
  end-to-end metrics (``gpts.serve`` would be ``gpts`` in serving cells).

A name outside the benchmark's alphabet, or one with no file, raises
:class:`UnknownName`: nothing is looked up anywhere else.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CODE_KINDS = ("drivers", "equations", "e2e", "layers")
DATA_KINDS = ("configs", "traffic", "cells")


class UnknownName(LookupError):
    """A cell, configuration, traffic mix, driver or metric with no file."""


def _path(kind: str, name: str, suffix: str) -> Path:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise UnknownName(f"{kind}: {name!r} is not a benchmark name")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise UnknownName(f"{kind}: no {name!r} ({path.relative_to(ROOT)})")
    return path


def spec(root: Path = ROOT) -> dict:
    """The parsed ``BENCHMARK.json``."""
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(name: str, benchmark: dict) -> dict:
    """The ``workloads`` entry called ``name``."""
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise UnknownName(f"workloads: no {name!r} in BENCHMARK.json")


def data(kind: str, name: str) -> dict:
    if kind not in DATA_KINDS:
        raise UnknownName(f"no data kind {kind!r}")
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def code(kind: str, name: str) -> ModuleType:
    if kind not in CODE_KINDS:
        raise UnknownName(f"no code kind {kind!r}")
    path = _path(kind, name, ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def reader(kind: str, metric: str) -> ModuleType:
    """The module that reads ``metric`` (its name up to the first dot)."""
    return code(kind, metric.split(".", 1)[0])


def metrics_for(cell: str, entries: list) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that apply to
    ``cell``: those with no ``workloads`` key, and those that list it."""
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]
