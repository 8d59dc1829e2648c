"""Tiny copies of the benchmark's configurations, for the tests on the
CPU: the same expressions, formats, schedules and references at a few
dozen nonzeros, and a short traffic mix."""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Dict

from . import spec

SIZES: Dict[str, Dict] = {
    "spmv-rail507": {"dims": {"i": 8, "j": 48},
                     "operands": {"B": ([8, 48], 40), "c": ([48], None)}},
    "spmm-g42": {"dims": {"i": 24, "k": 24, "j": 24},
                 "operands": {"B": ([24, 24], 40), "C": ([24, 24], 40)}},
}


def tiny_bench(dst: Path, clients: int = 4, max_batch: int = 4) -> Path:
    """A copy of the benchmark under ``dst`` with every configuration cut
    to its tiny size, and a traffic mix ``tiny`` of ``clients``."""
    dst = Path(dst)
    shutil.copytree(spec.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    for name, size in SIZES.items():
        path = dst / "configs" / f"{name}.json"
        config = json.loads(path.read_text())
        config["dims"] = size["dims"]
        for op, (shape, nnz) in size["operands"].items():
            config["operands"][op]["shape"] = shape
            if nnz is not None:
                config["operands"][op]["nnz"] = nnz
        path.write_text(json.dumps(config))
    (dst / "traffic" / "tiny.json").write_text(
        json.dumps({"clients": clients, "max_batch": max_batch}))
    return dst


def tiny_cell(bench: Path, config: str) -> spec.Cell:
    base = spec.benchmark()
    name = f"{config}.tiny"
    return spec.cell(name, dict(base, workloads=[
        {"name": name, "config": config, "traffic": "tiny", "chips": 1,
         "why": "test"}]), bench)
