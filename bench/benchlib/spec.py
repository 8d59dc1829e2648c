"""Finds each piece of a cell by its name.

``BENCHMARK.json`` (at the checkout's root) names every cell, with its
configuration and traffic mix; each of those is a file of its own:

* ``configs/<config>.json``  — expression, formats, schedule, operands,
  and the names of its ``engine``, ``work`` and ``reference`` files;
  ``tiny`` holds its size for the tests on the CPU: ``dims``, and per
  operand its ``shape`` and, where it has one, ``nnz``;
* ``engines/<engine>.py``    — the engine and its requests: ``build(config)``
  returns the engine ``SamServer`` serves, ``arrays(operands)`` maps
  drawn operands to what a ``Request`` carries, ``request(config,
  arrays)`` returns the ``Request``, and ``answer(result)`` the served
  result as the array the reference returns (``load.py``);
* ``generators/<kind>.py``   — ``draw(spec, gen)``: one operand of that
  ``kind`` (``operands.py``);
* ``traffic/<traffic>.json`` — clients and the server's ``max_batch``;
* ``metrics/<metric>.py``    — one per-layer metric's reader;
* ``work/<name>.py``         — FLOPs and minimum bytes of one request;
* ``reference/<name>.py``    — the float64 reference and its control;
* ``peaks.json``             — the chip's peaks, keyed by device kind.

A later cell adds files and entries; none of these functions changes.
A module file is loaded once per process and path.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(Path(root) / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: Path = BENCH

    def module(self, kind: str, name: str) -> ModuleType:
        return module(kind, name, self.bench)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, spec: Optional[Dict[str, Any]] = None,
         bench: Path = BENCH) -> Cell:
    """The cell ``name`` of ``spec`` (``BENCHMARK.json`` by default), with
    its configuration and traffic files read from ``bench``."""
    spec = benchmark() if spec is None else spec
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    bench = Path(bench)
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=load_json(bench / "configs" / f"{entry['config']}.json"),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        bench=bench)


def module(kind: str, name: str, bench: Path = BENCH) -> ModuleType:
    """``<bench>/<kind>/<name>.py`` as a module (a name may hold dots)."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path}")
    return _load(path, kind, name)


@functools.lru_cache(maxsize=None)
def _load(path: Path, kind: str, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, bench: Path = BENCH) -> Dict[str, Any]:
    """The peaks of ``device_kind``; a kind not in the table is an error,
    never a default."""
    table = load_json(Path(bench) / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]
