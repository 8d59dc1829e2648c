"""Segment-reduce kernel: the SAM Reducer (Def 3.7) as a tiled MXU matmul.

Scatter-add has no efficient TPU primitive; the TPU-native schedule for
"sum rows with equal segment id" is a one-hot matmul: for a value tile
``V (T, D)`` and its segment ids ``s (T,)``, the contribution to the output
is ``onehot(s)^T @ V`` — an (S, T) x (T, D) MXU product. The output block
stays resident in VMEM and accumulates across value tiles.

This is the hot path of the SAM-lowered MoE combine and of the embedding
gradient (union+reduce of repeated coordinates). S (number of segments) is
bounded by the expert count / vocab tile, so the (S, D_tile) accumulator
fits VMEM comfortably.

Layout:
  vals : (N, D) float    seg_ids : (N,) int32 in [0, S)   (need not be sorted)
  out  : (S, D)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, vals_ref, o_ref, acc_ref, *, n_seg, t):
    nt = pl.program_id(1)

    @pl.when(nt == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[0]                          # (T,)
    seg_iota = jax.lax.broadcasted_iota(jnp.int32, (n_seg, t), 0)
    onehot = (seg_iota == ids[None, :]).astype(jnp.float32)   # (S, T)
    # HIGHEST: full-f32 MXU passes, so the segment sums are f32-exact
    acc_ref[...] += jnp.dot(onehot, vals_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(nt == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "t_tile", "d_tile",
                                    "interpret"))
def segment_reduce(vals: jnp.ndarray, seg_ids: jnp.ndarray, *,
                   num_segments: int, t_tile: int = 512, d_tile: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    n, d = vals.shape
    pad_n = (-n) % t_tile
    if pad_n:
        vals = jnp.pad(vals, ((0, pad_n), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, pad_n),
                          constant_values=num_segments)  # masked out
    pad_d = (-d) % d_tile
    if pad_d:
        vals = jnp.pad(vals, ((0, 0), (0, pad_d)))
    n_p, d_p = vals.shape
    # one extra segment swallows padding rows; dropped on return
    s_p = num_segments + 1
    ids2d = seg_ids.astype(jnp.int32).reshape(1, n_p)

    grid = (d_p // d_tile, n_p // t_tile)
    # block indices are int32: under 64-bit mode a bare 0 in an index
    # map lowers as i64, which Mosaic cannot legalize
    out = pl.pallas_call(
        functools.partial(_kernel, n_seg=s_p, t=t_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_tile), lambda dj, nt: (jnp.int32(0), nt)),
            pl.BlockSpec((t_tile, d_tile), lambda dj, nt: (nt, dj)),
        ],
        out_specs=pl.BlockSpec((s_p, d_tile),
                               lambda dj, nt: (jnp.int32(0), dj)),
        out_shape=jax.ShapeDtypeStruct((s_p, d_p), vals.dtype),
        scratch_shapes=[pltpu.VMEM((s_p, d_tile), jnp.float32)],
        interpret=interpret,
    )(ids2d, vals)
    return out[:num_segments, :d]
