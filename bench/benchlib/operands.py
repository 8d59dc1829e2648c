"""Operands drawn from the seed, as a configuration's ``operands`` state.

Each operand names its ``kind``, and ``generators/<kind>.py`` draws it:
a module with ``draw(spec, gen) -> Operand``, where ``spec`` is the
operand's entry in the configuration (``shape``, ``dtype``, ``nnz`` and
whatever else its kind reads) and ``gen`` a ``numpy.random.Generator``
of the operand's own stream. A new kind is a new file. A generator of a
sparse kind returns its COO and never a dense array: the engine module
asks ``Operand.to_dense`` for one where its requests carry it.

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
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import numpy as np

from . import spec as specs

SHARED, WARM, FRESH, SAMPLE = 0, 1, 2, 3


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


@dataclasses.dataclass
class Operand:
    """One operand: a dense one's array, or a sparse one's COO (one
    coordinate array per dimension, then the values), which is what the
    reference reads."""
    shape: Tuple[int, ...]
    dense: Optional[np.ndarray]
    coo: Optional[Tuple[np.ndarray, ...]] = None

    def to_dense(self) -> np.ndarray:
        """The dense array; a sparse operand's is built anew on each
        call, and not kept."""
        if self.dense is not None:
            return self.dense
        *coords, vals = self.coo
        out = np.zeros(self.shape, vals.dtype)
        out[tuple(coords)] = vals
        return out


def draw(spec: Dict[str, Any], gen: np.random.Generator,
         bench: Path = specs.BENCH) -> Operand:
    """One operand of ``spec``, drawn by ``generators/<kind>.py``."""
    return specs.module("generators", spec["kind"], bench).draw(spec, gen)


def draw_all(config: Dict[str, Any], share: str, seed: int,
             *stream: int, bench: Path = specs.BENCH) -> Dict[str, Operand]:
    """Every operand of ``config`` whose ``share`` is ``share``, each from
    stream ``(seed, *stream, its index in the configuration)``."""
    return {name: draw(spec, rng(seed, *stream, i), bench)
            for i, (name, spec) in enumerate(config["operands"].items())
            if spec["share"] == share}
