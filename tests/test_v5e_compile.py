"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse (unaligned slices, more
VMEM than a kernel may use). Each test compiles one kernel at the widths
the engine serves — rail507's 409,856 stored values, a 507-row output,
the 4,096-slot dense-workspace guard — and asserts the kernel survived
into the compiled program as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
The program runs with 64-bit mode on (``core/coord_ops`` enables it when
imported), so these compiles do too.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.coo_levels import coo_to_levels_pallas
from repro.kernels.scatter_workspace import scatter_workspace
from repro.kernels.segment_reduce import segment_reduce

ROWS = 409_856                      # rail507's stored values (Table 3)
GUARD = kops._PALLAS_WORKSPACE_MAX_SLOTS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_compiles_run_in_x64_mode():
    assert jax.config.jax_enable_x64


@pytest.mark.parametrize("mul_pair", [False, True],
                         ids=["plain", "mul_pair"])
@pytest.mark.parametrize("slots", [507, GUARD])
def test_scatter_workspace_compiles(one_chip, slots, mul_pair):
    cols = 3 if mul_pair else 2
    text = _compiled_text(
        lambda ids, c: scatter_workspace(ids, c, num_slots=slots,
                                         mul_pair=mul_pair),
        jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((ROWS, cols), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_vmapped_scatter_workspace_compiles(one_chip):
    text = _compiled_text(
        jax.vmap(lambda ids, c: scatter_workspace(ids, c, num_slots=507)),
        jax.ShapeDtypeStruct((8, ROWS), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((8, ROWS, 2), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_segment_reduce_compiles(one_chip):
    text = _compiled_text(
        lambda v, s: segment_reduce(v, s, num_segments=GUARD),
        jax.ShapeDtypeStruct((ROWS, 1), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_coo_to_levels_compiles_at_guard(one_chip):
    n = 4 * GUARD
    text = _compiled_text(
        lambda k, v: coo_to_levels_pallas(k, v, (GUARD, GUARD),
                                          (GUARD, GUARD)),
        jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    assert "tpu_custom_call" in text
