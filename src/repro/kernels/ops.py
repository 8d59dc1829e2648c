"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to auto: Python-interpreted on CPU (this container),
compiled Mosaic on real TPU. All wrappers accept/return standard jnp arrays
and handle BSR bookkeeping (building padded slot maps from COO block
coordinates, sentinel padding, causal local masks).
"""
from __future__ import annotations

import contextlib
import functools
from collections import Counter
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bsr_attention import bsr_flash_attention as _bsr_attn
from .segment_reduce import segment_reduce as _segment_reduce
from .sddmm_bsr import sddmm_bsr as _sddmm
from .spmm_bsr import spmm_bsr as _spmm


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def bsr_from_block_coords(rows: np.ndarray, cols: np.ndarray,
                          blocks: np.ndarray, n_brow: int
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """COO block coordinates -> padded per-row slot maps for spmm_bsr.

    Returns (blk_map, col_idx, blocks_padded); pad slots point at the
    appended all-zero block.
    """
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    nnzb = len(rows)
    counts = np.bincount(rows, minlength=n_brow)
    max_nnz = max(int(counts.max(initial=0)), 1)
    blk_map = np.full((n_brow, max_nnz), nnzb, dtype=np.int32)
    col_idx = np.zeros((n_brow, max_nnz), dtype=np.int32)
    if nnzb:
        # slot of block b = its rank within its row, in input order: a
        # stable sort by row groups the blocks, and position-minus-
        # row-start inside the sorted order is the rank — one vectorized
        # scatter instead of the O(nnzb) Python loop
        order = np.argsort(rows, kind="stable")
        row_start = np.zeros(n_brow, dtype=np.int64)
        row_start[1:] = np.cumsum(counts)[:-1]
        slot = np.empty(nnzb, dtype=np.int64)
        slot[order] = np.arange(nnzb) - row_start[rows[order]]
        blk_map[rows, slot] = np.arange(nnzb)
        col_idx[rows, slot] = cols
    zeros = np.zeros((1,) + blocks.shape[1:], blocks.dtype)
    return blk_map, col_idx, np.concatenate([blocks, zeros], axis=0)


def spmm_bsr(blk_map, col_idx, blocks, c, *, n_tile: int = 128,
             interpret: Optional[bool] = None):
    return _spmm(jnp.asarray(blk_map), jnp.asarray(col_idx),
                 jnp.asarray(blocks), jnp.asarray(c), n_tile=n_tile,
                 interpret=_auto_interpret(interpret))


def sddmm_bsr(rows, cols, a, b, bs: int = 128, *, k_tile: int = 128,
              interpret: Optional[bool] = None):
    return _sddmm(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(a),
                  jnp.asarray(b), bs, k_tile=k_tile,
                  interpret=_auto_interpret(interpret))


def bsr_flash_attention(q, k, v, kv_idx, *, bq: int = 128, bkv: int = 128,
                        scale: Optional[float] = None, causal: bool = False,
                        interpret: Optional[bool] = None):
    return _bsr_attn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                     jnp.asarray(kv_idx), bq=bq, bkv=bkv, scale=scale,
                     causal=causal, interpret=_auto_interpret(interpret))


def segment_reduce(vals, seg_ids, *, num_segments: int, t_tile: int = 512,
                   d_tile: int = 128, interpret: Optional[bool] = None):
    return _segment_reduce(jnp.asarray(vals), jnp.asarray(seg_ids),
                           num_segments=num_segments, t_tile=t_tile,
                           d_tile=d_tile,
                           interpret=_auto_interpret(interpret))


# ---------------------------------------------------------------------------
# SAM-primitive dispatch table (compiled-engine hot paths)
# ---------------------------------------------------------------------------
# The compiled JAX backend routes its hot primitives through this table:
#   keyed_segment_sum   — the inner sum of coord_ops.keyed_union_reduce (the
#       fused Gustavson merge). On TPU it lowers to the Pallas
#       ``segment_reduce`` one-hot MXU matmul; elsewhere the plain
#       jax.ops.segment_sum fallback wins.
#   sorted_intersect    — sorted-key stream intersection. The searchsorted
#       fallback in coord_ops is already the data-parallel two-finger merge;
#       a dedicated Pallas kernel can be slotted in here without touching
#       core/.
#   keyed_union_reduce  — the §4.4 lane/term/tile merge stage: sums every
#       (term, lane) partial COO at equal result keys. On TPU with a small
#       declared key bound it runs the ``scatter_workspace`` dense-workspace
#       kernel (one pass produces sums AND appearance counts); otherwise the
#       coord_ops sort-merge fallback.
#   mul_reduce          — a mul-ALU product folded into the final keyed
#       reduce (``CompiledExpr``'s collapse): the product stream is formed
#       inside the workspace kernel, never materialized.
#   intersect_mul_reduce — the whole Gustavson inner loop (sorted intersect
#       × gather × multiply × reduce) as ONE kernel
#       (``fused_stream.fused_imr_workspace``).
#   coo_to_levels       — the program-fusion COO→levels handoff with the
#       per-level compaction on the workspace kernel.
# ``sam_primitive(name)`` picks the implementation for the active backend;
# every TPU entry guards its crossover threshold and falls back to the
# coord_ops implementation outside it, so dispatch is always safe.
# ``TRACED`` counts which side of that guard each TPU entry took, and the
# entry runs that side under a ``kops.<primitive>.<pallas|fallback>``
# named scope; ``plan_primitive`` gives a fallback resolved directly the
# same ``kops.<primitive>.fallback`` scope, so every device op a compiled
# plan runs through this table names its primitive in the trace.

from ..core import coord_ops as _co
from .coo_levels import MAX_EXACT_COORD as _MAX_EXACT_COORD
from .coo_levels import coo_to_levels_pallas as _coo_to_levels_kernel
from .fused_stream import fused_imr_workspace as _fused_imr_workspace
from .scatter_workspace import scatter_workspace as _scatter_workspace

# VMEM budget: the Pallas segment_reduce keeps an (S+1, 128) f32 accumulator
# resident; beyond this segment count the fallback is the better schedule.
_PALLAS_SEGSUM_MAX_SEGMENTS = 4096
# the dense-workspace merge kernels keep a (key_bound+1, 2) accumulator in
# VMEM and build (key_bound+1, T) one-hot tiles; beyond this bound the
# sort-merge fallback is the better schedule (same crossover shape as the
# segsum guard above)
_PALLAS_WORKSPACE_MAX_SLOTS = 4096
# one-hot moves ride the f32 MXU: only dtypes the (exact) f32 accumulator
# can represent round-trip losslessly take the Pallas path — f64/int fall
# back rather than silently narrowing through float32
_PALLAS_EXACT_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)

# (primitive, "pallas" | "fallback") -> how many times a TPU entry was
# traced into a program on that side of its guard (tracing happens once
# per compiled plan, so this says which implementation each plan runs)
TRACED: Counter = Counter()


@contextlib.contextmanager
def _took(name: str, pallas: bool):
    """Count which side of its guard a TPU entry took, and run that side
    under the ``kops.<name>.<side>`` named scope; yields ``pallas``."""
    side = "pallas" if pallas else "fallback"
    TRACED[(name, side)] += 1
    with jax.named_scope(f"kops.{name}.{side}"):
        yield pallas


def _keyed_segment_sum_pallas(vals, seg_ids, num_segments: int):
    """1-D keyed segment-sum via the tiled MXU segment_reduce kernel.

    Dtype preservation: the kernel accumulates in float32 scratch, which
    is exact for f32/bf16/f16 inputs but would silently narrow f64 (and
    round large ints), so those dtypes route to the fallback.
    """
    with _took("keyed_segment_sum",
               num_segments <= _PALLAS_SEGSUM_MAX_SEGMENTS
               and vals.dtype in _PALLAS_EXACT_DTYPES) as pallas:
        if not pallas:
            return _co.default_segment_sum(vals, seg_ids, num_segments)
        out = segment_reduce(vals[:, None], seg_ids,
                             num_segments=num_segments)
        return out[:, 0]


def _dense_workspace_finalize(sums, hits, cap: int):
    """Compact a (num_slots,) dense workspace exactly like the dense
    branch of ``coord_ops.keyed_union_reduce`` — shared by every
    workspace-kernel wrapper so their results are bit-identical to the
    fallback's."""
    nseg = sums.shape[0]
    appeared = hits > 0
    (uk, uv), count = _co.compact(
        appeared, (jnp.arange(nseg, dtype=jnp.int64), sums), cap, fill=0)
    out_valid = jnp.arange(cap) < count
    return (jnp.where(out_valid, uk, _co.PAD_KEY),
            jnp.where(out_valid, uv, 0.0), out_valid, count)


def _workspace_ok(vals, key_bound) -> bool:
    return (key_bound is not None
            and int(key_bound) <= _PALLAS_WORKSPACE_MAX_SLOTS
            and vals.dtype in _PALLAS_EXACT_DTYPES)


def _keyed_union_reduce_pallas(keys, vals, valid, cap: int,
                               segment_sum_impl=None, key_bound=None):
    """Dense-workspace keyed merge on the ``scatter_workspace`` kernel.

    One kernel pass scatters ``[value, hit]`` into a ``key_bound``-slot
    accumulator — the sums and the appearance counts the union semantics
    need (a live key with sum 0 keeps its slot) come out together.
    Unknown/large key bounds and non-f32 values keep the coord_ops
    sort-merge fallback.
    """
    with _took("keyed_union_reduce", _workspace_ok(vals, key_bound)) \
            as pallas:
        if not pallas:
            return _co.keyed_union_reduce(keys, vals, valid, cap,
                                          segment_sum_impl,
                                          key_bound=key_bound)
        nseg = max(int(key_bound), 1)
        ids = jnp.where(valid, keys, nseg).astype(jnp.int32)
        v0 = jnp.where(valid, vals, jnp.zeros((), vals.dtype))
        cols = jnp.stack([v0.astype(jnp.float32),
                          valid.astype(jnp.float32)], axis=1)
        ws = _scatter_workspace(ids, cols, num_slots=nseg,
                                interpret=_auto_interpret(None))
        return _dense_workspace_finalize(ws[:, 0], ws[:, 1], cap)


def _mul_reduce_pallas(keys, a_vals, b_vals, valid, cap: int, *,
                       key_bound=None, segment_sum_impl=None):
    """Fused multiply × keyed reduce: the product is formed inside the
    workspace kernel (``mul_pair`` payload), so the engine's deferred
    mul-ALU never materializes a product stream."""
    with _took("mul_reduce", _workspace_ok(a_vals, key_bound)) as pallas:
        if not pallas:
            return _co.mul_reduce(keys, a_vals, b_vals, valid, cap,
                                  key_bound=key_bound,
                                  segment_sum_impl=segment_sum_impl)
        nseg = max(int(key_bound), 1)
        ids = jnp.where(valid, keys, nseg).astype(jnp.int32)
        cols = jnp.stack([a_vals.astype(jnp.float32),
                          b_vals.astype(jnp.float32),
                          valid.astype(jnp.float32)], axis=1)
        ws = _scatter_workspace(ids, cols, num_slots=nseg, mul_pair=True,
                                interpret=_auto_interpret(None))
        return _dense_workspace_finalize(ws[:, 0], ws[:, 1], cap)


def _fused_imr_pallas(a_key, a_valid, a_vals, b_key, b_valid, b_vals,
                      out_key, cap: int, *, key_bound=None,
                      segment_sum_impl=None):
    """The whole Gustavson inner loop as one Pallas kernel (see
    ``fused_stream``). Falls back outside the dense-workspace guard; the
    kernel's stream contract (int32 keys, strictly-increasing valid keys,
    prefix-valid b) is the level-scanner shape the engine produces."""
    with _took("intersect_mul_reduce", _workspace_ok(a_vals, key_bound)) \
            as pallas:
        if not pallas:
            return _co.fused_intersect_mul_reduce(
                a_key, a_valid, a_vals, b_key, b_valid, b_vals, out_key,
                cap, key_bound=key_bound, segment_sum_impl=segment_sum_impl)
        sent = jnp.iinfo(jnp.int32).max
        nseg = max(int(key_bound), 1)
        ak = jnp.where(a_valid & (a_key != _co.PAD_KEY), a_key, sent)
        bk = jnp.where(b_valid & (b_key != _co.PAD_KEY), b_key, sent)
        bv = jnp.where(b_valid, b_vals, jnp.zeros((), b_vals.dtype))
        ws = _fused_imr_workspace(ak, a_vals,
                                  jnp.clip(out_key, 0, nseg - 1),
                                  bk, bv, num_slots=nseg,
                                  interpret=_auto_interpret(None))
        return _dense_workspace_finalize(ws[:, 0], ws[:, 1], cap)


def _coo_to_levels_pallas(keys, valid, dims_list, caps):
    """Pallas-compacted COO→levels; the guard keeps every coordinate and
    capacity inside the exact-f32 horizon and the workspace VMEM budget."""
    with _took("coo_to_levels",
               all(c <= _PALLAS_WORKSPACE_MAX_SLOTS for c in caps)
               and all(d < _MAX_EXACT_COORD for d in dims_list)
               and all(c < _MAX_EXACT_COORD for c in caps)) as pallas:
        if not pallas:
            return _co.coo_to_levels(keys, valid, dims_list, caps)
        return _coo_to_levels_kernel(keys, valid, dims_list, caps,
                                     interpret=_auto_interpret(None))


SAM_PRIMITIVES = {
    "keyed_segment_sum": {
        "tpu": _keyed_segment_sum_pallas,
        "fallback": _co.default_segment_sum,
    },
    "sorted_intersect": {
        "fallback": _co.intersect_keys,
    },
    "keyed_union_reduce": {
        "tpu": _keyed_union_reduce_pallas,
        "fallback": _co.keyed_union_reduce,
    },
    "mul_reduce": {
        "tpu": _mul_reduce_pallas,
        "fallback": _co.mul_reduce,
    },
    "intersect_mul_reduce": {
        "tpu": _fused_imr_pallas,
        "fallback": _co.fused_intersect_mul_reduce,
    },
    "coo_to_levels": {
        "tpu": _coo_to_levels_pallas,
        "fallback": _co.coo_to_levels,
    },
}


def sam_primitive(name: str, backend: Optional[str] = None):
    """Resolve a SAM primitive to the best implementation for ``backend``
    (default: the active JAX backend)."""
    impls = SAM_PRIMITIVES[name]
    backend = backend or jax.default_backend()
    return impls.get(backend, impls["fallback"])


def plan_primitive(name: str):
    """``sam_primitive(name)`` as a compiled plan calls it: a TPU entry
    names the side of its guard itself; a fallback resolved directly runs
    under the ``kops.<name>.fallback`` named scope."""
    impl = sam_primitive(name)
    if impl is not SAM_PRIMITIVES[name]["fallback"]:
        return impl

    @functools.wraps(impl)
    def scoped(*args, **kwargs):
        with jax.named_scope(f"kops.{name}.fallback"):
            return impl(*args, **kwargs)
    return scoped


def register_primitive(name: str, backend: str, impl) -> None:
    """Register (or override) one implementation of a SAM primitive.

    The extension point docs/KERNELS.md documents: a new backend's kernel
    slots into the dispatch table without touching ``core/``. The entry
    must match the fallback's calling convention exactly and should guard
    its own crossover thresholds (returning the fallback's result outside
    them), so ``sam_primitive`` resolution stays always-safe.
    """
    if backend != "fallback" and "fallback" not in SAM_PRIMITIVES.get(
            name, {}):
        raise ValueError(f"primitive {name!r} needs a fallback "
                         f"implementation before backend entries")
    SAM_PRIMITIVES.setdefault(name, {})[backend] = impl


def sliding_window_kv_idx(n_qblk: int, n_kvblk: int, window_blocks: int,
                          causal: bool = True) -> np.ndarray:
    """BCSR mask for sliding-window attention: each q block attends to the
    ``window_blocks`` kv blocks at/before it (the sub-quadratic long-context
    path). Padded with the out-of-range sentinel ``n_kvblk``."""
    idx = np.full((n_qblk, window_blocks), n_kvblk, dtype=np.int32)
    for qi in range(n_qblk):
        hi = qi if causal else min(qi + window_blocks // 2, n_kvblk - 1)
        lo = max(0, hi - window_blocks + 1)
        w = list(range(lo, hi + 1))
        idx[qi, :len(w)] = w
    return idx
