"""The comparison that decides ``correct``.

Each served answer is set against the configuration's float64 reference
for its own request: the error is ``max |got - ref| / max |ref|`` (inf
on a shape mismatch or a NaN). A run is correct when every request it
submitted came back without an error and every answer's error is at most
the configuration's ``limits.max_rel_err``. The numbers compared are
returned with their limits, for the result line and standard error.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import math
import sys

import numpy as np


def rel_error(got, ref: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    err = float(np.max(np.abs(got - ref), initial=0.0))
    scale = float(np.max(np.abs(ref), initial=0.0))
    err = err / scale if scale > 0 else err
    return float("inf") if np.isnan(err) else err


def max_error(pairs: Iterable) -> Optional[float]:
    """The largest ``rel_error`` over ``(got, ref)`` pairs (None if none)."""
    errs = [rel_error(g, r) for g, r in pairs]
    return max(errs) if errs else None


def checks(max_err: Optional[float], failed: int,
           limit: float) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit. A run that compared no
    answer, or an answer of the wrong shape or with a NaN, reads the
    largest float (JSON has no infinity)."""
    if max_err is None or not math.isfinite(max_err):
        max_err = sys.float_info.max
    return {
        "max_rel_err": {"value": max_err, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }


def passed(found: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in found.values())


def lines(found: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {name} {c['value']!r} limit {c['limit']!r}"
            for name, c in found.items()]
