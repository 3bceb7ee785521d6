"""Find a cell's configuration, traffic, limits and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found from the names in
``BENCHMARK.json``:

* ``configs/<config>.json``   the cut ArchConfig (``arch``) with its source,
  ``reduced``, ``assumed`` and deployment; ``reference`` names the plain
  model in ``references/<reference>.py``;
* ``references/<reference>.py``  that model's ``loss`` and
  ``flops_per_token`` (``references/__init__.py``);
* ``traffic/<traffic>.json``  agents, graph, H, K, optimizer, batch, seq,
  gossip path and fusion, and the size of the token pool;
* ``limits/<workload>.json``  the limit of each number that decides
  ``correct``;
* ``metrics/<metric>.py``     a ``read(ctx)`` that returns the metric, or
  None where it finds nothing to read.

So a configuration of any architecture the program registers brings its
``configs/`` file, a ``references/`` file where none there computes its
model yet, a ``limits/`` file for each of its cells, and a ``traffic/``
or ``metrics/`` file only for a new mix or metric, with their entries in
``BENCHMARK.json``.  Its ``arch`` holds the ``ArchConfig`` fields: the
nested configs (``moe``, ``mla``, ``ssm``) as objects of their fields,
tuples (``block_pattern``) as lists, dtypes by name.  Its weights come
from ``weights.py``'s rules by leaf name.  No file here names a
configuration, a cell or an architecture.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, get_args, get_type_hints

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

__all__ = ["HERE", "ROOT", "Cell", "load_benchmark", "load_cell",
           "arch_config", "load_module"]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # limits/<workload>.json: {number: limit}
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", (workload,))


def load_cell(workload: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=_read_json(ROOT / configs[w["config"]]["file"]),
        traffic=_read_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(HERE / "limits" / f"{workload}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, workload)))


def _nested_type(hint) -> type:
    """The dataclass of a field typed ``SomeConfig | None``."""
    return next(t for t in get_args(hint) or (hint,)
                if dataclasses.is_dataclass(t))


def _fields(cls: type, values: dict) -> dict:
    """``values`` of a JSON object as ``cls``'s fields: nested objects as
    the dataclasses their fields are typed with, lists as tuples."""
    hints = get_type_hints(cls)
    out = {}
    for key, value in values.items():
        if isinstance(value, dict):
            nested = _nested_type(hints[key])
            value = nested(**_fields(nested, value))
        elif isinstance(value, list):
            value = tuple(value)
        out[key] = value
    return out


def arch_config(config: dict, **overrides: Any):
    """The program's ArchConfig for a configuration file's ``arch``."""
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig

    kw = _fields(ArchConfig, dict(config["arch"], **overrides))
    for key in ("param_dtype", "compute_dtype"):
        kw[key] = jnp.dtype(kw[key])
    return ArchConfig(source=config["source"], **kw)


def load_module(path: Path) -> ModuleType:
    """Import a file by its path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
