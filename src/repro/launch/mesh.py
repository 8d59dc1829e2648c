"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single-pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips (pod x data x model) — the pod axis extends
the DP/FSDP group across the ICI/DCN boundary. Every axis is ``Auto``:
the model code places ``with_sharding_constraint`` hints, which only
Auto axes accept.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over the actually-present devices (tests, examples)."""
    n = len(jax.devices())
    return jax.make_mesh((max(n // model_parallel, 1), model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)
