"""Cells as data: ``BENCHMARK.json`` and the files it names.

A cell is one entry of ``workloads``: a configuration (a deployment of
the store, ``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``). The configuration's ``deployment.kind`` is
the module ``deployments/<kind>.py``, which defines ``Deployment(config,
seed, root)``; the mix's ``op`` is the module ``ops/<op>.py``, which
defines ``Op(deployment, mix, seed)`` (``chipbench/traffic.py``). The
metrics a cell reports are the ``end_to_end`` and ``per_layer`` entries
whose ``workloads`` list names it, or that list none. Each metric is read
by a file of its own, ``end_to_end/<name>.py`` or ``metrics/<name>.py``,
which defines ``read(run) -> float | None``. Everything here is found by
name, so a cell, a configuration, a deployment, a mix, an op or a metric
is added by adding files and entries, never by editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

#: a name: letters, digits, ``_``, ``.``, ``-``; starts with a letter,
#: digit or ``_``; at most 64 characters
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")

#: the benchmark's own directory (``benchmarks/chip``)
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CellError(ValueError):
    """A cell, or a file it needs, is missing or malformed."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: object            # the module that defines read(run)
    moves: str | None = None  # per-layer metrics: the end-to-end one


@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]
    deployment: object = None   # deployments/<kind>.py: Deployment
    op: object = None           # ops/<op>.py: Op


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise CellError(f"{what} {name!r} is not a valid name")
    return name


def _load_json(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise CellError(f"{what}: cannot read {path}: {e}") from e


def load_module(directory: str, name: str, attr: str, what: str):
    """The module ``<directory>/<name>.py``, loaded by path (names carry
    dots); it must define ``attr``."""
    path = os.path.join(directory, check_name(name, what) + ".py")
    if not os.path.isfile(path):
        raise CellError(f"{what} {name}: no module at {path}")
    safe = re.sub(r"[^A-Za-z0-9_]", "_", name)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{what}_{safe}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, attr, None)):
        raise CellError(f"{what} {name}: {path} defines no {attr}")
    return mod


def load_reader(directory: str, name: str):
    """The metric reader ``<directory>/<name>.py``: ``read(run)``."""
    return load_module(directory, name, "read", "metric")


def _metrics(entries: list, workload: str, directory: str,
             per_layer: bool) -> list[Metric]:
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and workload not in cells:
            continue
        if not UNIT_RE.match(m.get("unit", "")):
            raise CellError(f"metric {m.get('name')}: bad unit {m.get('unit')!r}")
        out.append(Metric(name=m["name"], unit=m["unit"],
                          reader=load_reader(directory, m["name"]),
                          moves=m.get("moves") if per_layer else None))
    return out


def load_cell(root: str, workload: str, *, here: str = HERE) -> Cell:
    """Read ``<root>/BENCHMARK.json`` and everything the cell
    ``workload`` names, from the benchmark directory ``here``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json")
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    cfg_entry = configs.get(w["config"])
    if cfg_entry is None:
        raise CellError(f"workload {workload}: no config {w['config']!r}")
    traffic = check_name(w["traffic"], "traffic")
    cell = Cell(
        workload=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"]),
                          f"config {w['config']}"),
        mix=_load_json(os.path.join(here, "traffic", traffic + ".json"),
                       f"traffic {traffic}"),
        end_to_end=_metrics(bench.get("end_to_end", []), workload,
                            os.path.join(here, "end_to_end"), False),
        per_layer=_metrics(bench.get("per_layer", []), workload,
                           os.path.join(here, "metrics"), True))
    kind = cell.config.get("deployment", {}).get("kind")
    cell.deployment = load_module(os.path.join(here, "deployments"), kind,
                                  "Deployment", "deployment").Deployment
    cell.op = load_module(os.path.join(here, "ops"), cell.mix.get("op"),
                          "Op", "op").Op
    if not cell.end_to_end or not cell.per_layer:
        raise CellError(f"workload {workload}: needs end-to-end and "
                        "per-layer metrics")
    return cell
