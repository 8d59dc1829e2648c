"""Operands drawn from the seed, as a configuration's ``operands`` state.

Every draw has a stream of its own, ``(seed, *stream)``, so the same seed
gives the same operands whatever order the client threads run in:

* ``SHARED``: ``(seed, 0, operand index)`` — one object for the whole run;
* ``WARM``:   ``(0, 1, ...)`` — set-up's warm-up operands, the same for
  every seed: the program's capacity pass sizes some of its programs
  from the data, so warm-up data from the seed would compile anew in
  every run;
* ``FRESH``:  ``(seed, 2, client, request, operand index)`` — one per
  request, so no operand object or content is seen twice in a run;
* ``SAMPLE``: ``(seed, 3)`` — which answers are compared, where a
  configuration compares a sample.

A ``sparse`` operand has ``nnz`` nonzeros at distinct uniform random
positions with values uniform in [-1, 1] (never 0); a ``dense`` one is
uniform in [-1, 1] everywhere.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

SHARED, WARM, FRESH, SAMPLE = 0, 1, 2, 3


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


@dataclasses.dataclass
class Operand:
    """One operand: its dense float32 array (what the serving API takes)
    and, for a sparse one, its COO triplets (what the reference reads)."""
    shape: Tuple[int, ...]
    dense: Optional[np.ndarray]
    coo: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None

    def to_dense(self) -> np.ndarray:
        if self.dense is not None:
            return self.dense
        rows, cols, vals = self.coo
        out = np.zeros(self.shape, np.float32)
        out[rows, cols] = vals
        return out

    def drop_dense(self) -> None:
        """Free a sparse operand's dense array; the COO stays."""
        if self.coo is not None:
            self.dense = None


def draw(spec: Dict[str, Any], gen: np.random.Generator) -> Operand:
    shape = tuple(int(d) for d in spec["shape"])
    dtype = np.dtype(spec.get("dtype", "float32"))
    if spec["kind"] == "dense":
        return Operand(shape, gen.uniform(-1.0, 1.0, shape).astype(dtype))
    if spec["kind"] != "sparse" or len(shape) != 2:
        raise ValueError(f"operand kind {spec['kind']!r} of rank "
                         f"{len(shape)} is not drawn here")
    n_rows, n_cols = shape
    flat = np.sort(gen.choice(n_rows * n_cols, size=int(spec["nnz"]),
                              replace=False))
    vals = gen.uniform(-1.0, 1.0, flat.size).astype(dtype)
    vals[vals == 0] = 1
    rows, cols = np.divmod(flat, n_cols)
    op = Operand(shape, None, (rows, cols, vals))
    op.dense = op.to_dense()
    return op


def draw_all(config: Dict[str, Any], share: str, seed: int,
             *stream: int) -> Dict[str, Operand]:
    """Every operand of ``config`` whose ``share`` is ``share``, each from
    stream ``(seed, *stream, its index in the configuration)``."""
    return {name: draw(spec, rng(seed, *stream, i))
            for i, (name, spec) in enumerate(config["operands"].items())
            if spec["share"] == share}
