"""Tiny copies of the benchmark's configurations, for the tests on the
CPU: the same expressions, formats, schedules, engines and references at
the size each configuration's ``tiny`` entry gives (``dims``, and per
operand ``shape`` and, where it has one, ``nnz``), and a short traffic
mix. A configuration added as a file is cut and tested like the rest."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict, List

from . import spec


def configs(bench: Path = spec.BENCH) -> List[str]:
    """The name of every configuration file under ``bench``, sorted."""
    return sorted(p.stem for p in (Path(bench) / "configs").glob("*.json"))


def cut(config: Dict[str, Any]) -> Dict[str, Any]:
    """``config`` at its ``tiny`` size."""
    size = config["tiny"]
    return dict(config, dims=size["dims"], operands={
        name: dict(op, **size["operands"][name])
        for name, op in config["operands"].items()})


def tiny_bench(dst: Path, clients: int = 4, max_batch: int = 4) -> Path:
    """A copy of the benchmark under ``dst`` with every configuration cut
    to its tiny size, and a traffic mix ``tiny`` of ``clients``."""
    dst = Path(dst)
    shutil.copytree(spec.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    for name in configs(dst):
        path = dst / "configs" / f"{name}.json"
        path.write_text(json.dumps(cut(json.loads(path.read_text()))))
    (dst / "traffic" / "tiny.json").write_text(
        json.dumps({"clients": clients, "max_batch": max_batch}))
    return dst


def tiny_cell(bench: Path, config: str) -> spec.Cell:
    base = spec.benchmark()
    name = f"{config}.tiny"
    return spec.cell(name, dict(base, workloads=[
        {"name": name, "config": config, "traffic": "tiny", "chips": 1,
         "why": "test"}]), bench)
