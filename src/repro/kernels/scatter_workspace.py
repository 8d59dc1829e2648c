"""Dense-workspace scatter-add kernel: keyed merge as a one-hot MXU matmul.

The §4.4 lane/term merge (and every dense-workspace Gustavson reduce)
needs "sum rows with equal key" over a bounded key space. Scatter-add has
no efficient TPU primitive; the TPU-native schedule is the same one-hot
matmul as ``segment_reduce``, generalized to C payload columns so ONE
kernel pass produces every per-slot aggregate a merge needs:

  for an id tile ``s (T,)`` and payload tile ``V (T, C)``, the
  contribution to the workspace is ``onehot(s)^T @ V`` — an
  (S, T) x (T, C) MXU product accumulated in a VMEM-resident (S, C)
  scratch across tiles.

``keyed_union_reduce`` uses C=2 (``[value, hit]``: sums and appearance
counts in one pass), the fused multiply-reduce uses C=2 with the product
formed in-kernel from two value columns, and the ``coo_to_levels``
compaction uses C=2 (``[crd, parent]`` moved to their compacted slots).
Ids equal to ``num_slots`` land in one extra padding row, dropped on
return — the same convention as ``segment_reduce``.

Layout:
  ids  : (N,) int32 in [0, num_slots]   (num_slots == dropped pad slot)
  cols : (N, C) float32
  out  : (num_slots, C) float32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, cols_ref, o_ref, acc_ref, *, n_slots, t, mul_pair):
    nt = pl.program_id(0)

    @pl.when(nt == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[0]                                        # (T,)
    cols = cols_ref[...].astype(jnp.float32)                # (T, C)
    if mul_pair:
        # columns 0/1 are the two multiplicands, column 2 the hit mask:
        # form [a*b, hit] in registers — the product stream never exists
        # outside this kernel. The mask gates the product so garbage at
        # padded/invalid rows (which may be inf/nan) cannot poison the
        # accumulator through 0 * nan.
        mask = cols[:, 2:3] > 0.0
        prod = jnp.where(mask, cols[:, 0:1] * cols[:, 1:2], 0.0)
        cols = jnp.concatenate([prod, mask.astype(jnp.float32)], axis=1)
    seg_iota = jax.lax.broadcasted_iota(jnp.int32, (n_slots, t), 0)
    onehot = (seg_iota == ids[None, :]).astype(jnp.float32)  # (S, T)
    # HIGHEST: the payload must cross the MXU at full f32 width — a
    # single bf16 pass would round every value (and every packed
    # coordinate of the coo_to_levels move) to 8 mantissa bits
    acc_ref[...] += jnp.dot(onehot, cols,
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(nt == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "t_tile", "mul_pair",
                                    "interpret"))
def scatter_workspace(ids: jnp.ndarray, cols: jnp.ndarray, *,
                      num_slots: int, t_tile: int = 1024,
                      mul_pair: bool = False,
                      interpret: bool = False) -> jnp.ndarray:
    """out[s, c] = sum over i with ids[i] == s of cols[i, c].

    ``mul_pair=True`` treats ``cols`` as ``[a, b, hit]`` and accumulates
    ``[a*b*hit, hit]`` instead (the fused multiply-reduce payload).
    See module docstring for the layout contract.
    """
    n, c = cols.shape
    pad_n = (-n) % t_tile
    if pad_n:
        cols = jnp.pad(cols, ((0, pad_n), (0, 0)))
        ids = jnp.pad(ids, (0, pad_n), constant_values=num_slots)
    n_p = cols.shape[0]
    s_p = num_slots + 1                  # extra slot swallows padding rows
    ids2d = ids.astype(jnp.int32).reshape(1, n_p)
    c_out = 2 if mul_pair else c

    # block indices are int32: under 64-bit mode a bare 0 in an index
    # map lowers as i64, which Mosaic cannot legalize
    out = pl.pallas_call(
        functools.partial(_kernel, n_slots=s_p, t=t_tile,
                          mul_pair=mul_pair),
        grid=(n_p // t_tile,),
        in_specs=[
            pl.BlockSpec((1, t_tile), lambda nt: (jnp.int32(0), nt)),
            pl.BlockSpec((t_tile, c), lambda nt: (nt, jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((s_p, c_out),
                               lambda nt: (jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((s_p, c_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((s_p, c_out), jnp.float32)],
        interpret=interpret,
    )(ids2d, cols)
    return out[:num_slots]
