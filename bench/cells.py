"""Cells and their parts, found by name.

A cell is ``BENCHMARK.json``'s workload entry.  It names a configuration
(``bench/configs/<config>.json``, the deployment's sizes and guarantees)
and a mix (``bench/traffic/<traffic>.json``, a data file of parameters).
The mix names the code that runs it, each a module of its own:

* ``generator``: ``bench/generators/<name>.py``, whose ``Traffic`` makes the
  batches from the seed;
* ``service``: ``bench/services/<name>.py``, whose ``build`` makes the
  system under test and hands it each batch;
* a generator may name further modules, such as a key distribution
  (``bench/distributions/<name>.py``).

Each per-layer and end-to-end metric is read by ``bench/metrics/<name>.py``.
A later cell, mix, configuration or metric is a new file, never an edit.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict                # the mix's parameters
    chips: int
    per_layer: tuple             # the per_layer entries this cell reports
    end_to_end: tuple            # the end_to_end entries this cell reports


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@functools.cache
def module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def reports(metrics, cell: str) -> tuple:
    return tuple(m for m in metrics if cell in m.get("workloads", [cell]))


def spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    s = spec(root)
    cells = {w["name"]: w for w in s["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    return Cell(name=name, config=config(w["config"]),
                traffic=traffic(w["traffic"]), chips=int(w["chips"]),
                per_layer=reports(s["per_layer"], name),
                end_to_end=reports(s["end_to_end"], name))
