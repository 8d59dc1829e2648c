"""Coordinate-array primitives: the TPU-native realization of SAM blocks.

Each SAM stream becomes a fixed-capacity coordinate/value array plus a
validity mask and a ``parent`` index array that encodes the hierarchical
stop-token structure (element i's fiber is identified by ``parent[i]``).
Every op below is shape-static and jit-compatible:

  scan_level      — Def 3.1 level scanner: expand (seg, crd) fibers of the
                    selected parent references (vectorized ragged expand)
  intersect_keys  — Def 3.2 intersecter: sorted-key membership via
                    searchsorted (the data-parallel two-finger merge; the
                    binary probe is also exactly §4.2's coordinate skipping)
  union_keys      — Def 3.3 unioner: merge + dedup with per-side hole masks
  repeat is a gather:  out = ref[parent_idx]  (Def 3.4; no op needed)
  segment_sum     — Def 3.7 reducer (n=0): jax segment-sum over fibers
  sorted_segment_reduce — Def 3.7 reducer (n>=1): sort-by-key + boundary
                    detection + segment-sum + compaction (Gustavson merge)
  compact         — level writer / final construction (Def 3.8)
  locate_keys     — Def 4.1 locator: direct searchsorted probe

Coordinate droppers (Def 3.9) need no op at all: on TPU they are predication
— the validity mask is ANDed instead of tokens being removed.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax

# Flattened iteration-space keys need 64-bit headroom (key = fiber-chain
# index product). Models/kernels are explicit about their dtypes, so this
# only widens the coordinate machinery.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
I64 = jnp.int64
PAD_KEY = jnp.iinfo(jnp.int64).max  # sorts after every real key

# keyed_union_reduce switches from sort-merge to a dense scatter-add
# workspace when the caller-declared key space fits this many slots
# (a 4 MB f32 accumulator at the limit)
DENSE_REDUCE_BOUND = 1 << 20
# keys below this bound sort as int32 (padding maps onto the bound itself,
# so it still sorts after every live key)
I32_SORT_BOUND = np.iinfo(np.int32).max


def exclusive_cumsum(x):
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def compact(mask: jnp.ndarray, arrays: Tuple[jnp.ndarray, ...], cap: int,
            fill=0) -> Tuple[Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Stable compaction of ``arrays`` rows where ``mask`` — jit-static cap.

    Returns (compacted arrays, count). Rows beyond ``count`` hold ``fill``.

    Implemented gather-side: output slot ``i`` binary-searches the mask's
    running count for the ``i+1``-th marked row. XLA:CPU serializes
    scatters, so the older scatter formulation cost ~10x more wall time
    on large buffers (the fused-chain splice runs this over the full
    pre-reduction emission capacity — see DESIGN.md §6).
    """
    if mask.shape[0] == 0:
        outs = tuple(jnp.full((cap,) + a.shape[1:], fill, dtype=a.dtype)
                     for a in arrays)
        return outs, jnp.zeros((), I32)
    csum = jnp.cumsum(mask.astype(I64))
    count = csum[-1]
    src = jnp.searchsorted(csum, jnp.arange(1, cap + 1, dtype=csum.dtype))
    src = jnp.clip(src, 0, mask.shape[0] - 1)
    live = jnp.arange(cap) < count
    outs = []
    for a in arrays:
        lv = live.reshape((cap,) + (1,) * (a.ndim - 1))
        outs.append(jnp.where(lv, a[src], jnp.asarray(fill, a.dtype)))
    return tuple(outs), count.astype(I32)


def scan_level(seg: jnp.ndarray, crd: jnp.ndarray,
               parent_ref: jnp.ndarray, parent_valid: jnp.ndarray,
               cap: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                  jnp.ndarray]:
    """Expand the fibers addressed by ``parent_ref`` into a child stream.

    Returns (crd, ref, parent_idx, valid) arrays of length ``cap``.
    ``parent_ref < 0`` (holes from unions) scan as empty fibers.
    """
    if crd.shape[0] == 0:  # tensor level with no stored coordinates
        z = jnp.zeros((cap,), I32)
        return z, z, z, jnp.zeros((cap,), bool)
    pr = jnp.clip(parent_ref, 0, seg.shape[0] - 2)
    ok = parent_valid & (parent_ref >= 0)
    lengths = jnp.where(ok, seg[pr + 1] - seg[pr], 0)
    starts = exclusive_cumsum(lengths)
    total = starts[-1] + lengths[-1] if lengths.shape[0] else jnp.zeros((), I32)
    # segment id of each output slot: number of starts <= position
    pos = jnp.arange(cap, dtype=starts.dtype)
    sid = jnp.searchsorted(starts, pos, side="right") - 1
    sid = jnp.clip(sid, 0, lengths.shape[0] - 1)
    intra = pos - starts[sid]
    valid = pos < total
    ref = jnp.where(valid, seg[pr[sid]] + intra, 0)
    out_crd = jnp.where(valid, crd[jnp.clip(ref, 0, crd.shape[0] - 1)], 0)
    return out_crd.astype(I32), ref.astype(I32), sid.astype(I32), valid


def intersect_keys(a_key, a_valid, b_key, b_valid):
    """Sorted-key intersection. Returns (mask over a, b positions).

    ``a_key``/``b_key`` must be sorted with invalid rows keyed PAD_KEY.
    A surviving element keeps its position in *a*; its reference in *b*
    is the searchsorted probe — which is both the two-finger merge and
    the §4.2 gallop, collapsed into one data-parallel primitive.
    """
    idx = jnp.searchsorted(b_key, a_key)
    idxc = jnp.clip(idx, 0, b_key.shape[0] - 1)
    hit = (b_key[idxc] == a_key) & a_valid & (a_key != PAD_KEY)
    hit = hit & b_valid[idxc]
    return hit, idxc


def union_keys(a_key, a_valid, b_key, b_valid, cap: int):
    """Sorted-key union with per-side presence masks.

    Returns (keys, in_a, a_pos, in_b, b_pos, valid) of length ``cap``.
    """
    a_key = jnp.where(a_valid, a_key, PAD_KEY)
    b_key = jnp.where(b_valid, b_key, PAD_KEY)
    allk = jnp.sort(jnp.concatenate([a_key, b_key]))
    first = jnp.concatenate([jnp.ones((1,), bool), allk[1:] != allk[:-1]])
    keep = first & (allk != PAD_KEY)
    (keys,), count = compact(keep, (allk,), cap, fill=PAD_KEY)
    valid = jnp.arange(cap) < count
    ia = jnp.searchsorted(a_key, keys)
    iac = jnp.clip(ia, 0, a_key.shape[0] - 1)
    in_a = (a_key[iac] == keys) & valid
    ib = jnp.searchsorted(b_key, keys)
    ibc = jnp.clip(ib, 0, b_key.shape[0] - 1)
    in_b = (b_key[ibc] == keys) & valid
    return keys, in_a, iac, in_b, ibc, valid


def locate_keys(level_seg, level_crd, parent_ref, probe_crd, valid):
    """Def 4.1 locator: find ``probe_crd`` inside the fiber at parent_ref.

    Returns (found mask, refs).
    """
    pr = jnp.clip(parent_ref, 0, level_seg.shape[0] - 2)
    lo, hi = level_seg[pr], level_seg[pr + 1]
    # searchsorted within [lo, hi) via global probe on keyed coordinates
    n = level_crd.shape[0]

    def probe_one(l, h, c):
        i = jnp.searchsorted(level_crd, c, side="left")
        # clamp into fiber range: gallop from lo
        i = jnp.clip(i, l, jnp.maximum(h - 1, l))
        hitc = level_crd[jnp.clip(i, 0, n - 1)]
        return i, (hitc == c) & (i >= l) & (i < h)

    idx, found = jax.vmap(probe_one)(lo, hi, probe_crd)
    found = found & valid & (parent_ref >= 0) & (hi > lo)
    return found, jnp.where(found, idx, 0).astype(I32)


def default_segment_sum(vals, seg_ids, num_segments: int):
    """Plain-jnp keyed segment-sum; the dispatch-table fallback impl."""
    return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)


def keyed_union_reduce(keys, vals, valid, cap: int, segment_sum_impl=None,
                       key_bound=None):
    """Def 3.7 reducer for n>=1 / multi-term union: sum ``vals`` at equal
    ``keys``.

    Keys encode (accumulation group, coordinate point). Returns
    (unique_keys, summed_vals, valid, count) of length ``cap``; ``count`` is
    the number of distinct live keys, so a caller with a statically chosen
    ``cap`` can detect overflow (``count > cap`` means truncation). The
    inner segment-sum is pluggable: ``kernels.ops`` routes it to the Pallas
    ``segment_reduce`` MXU kernel on TPU.

    ``key_bound`` is a static exclusive upper bound on live key values
    when the caller knows one (the product of the result extents). A
    bound up to ``DENSE_REDUCE_BOUND`` selects the dense-workspace merge:
    one scatter-add over a ``key_bound``-slot accumulator replaces the
    O(n log n) sort — the classic dense-accumulator Gustavson schedule,
    and the dominant cost of every reduce on sort-weak backends. Larger
    (or unknown) bounds keep the sort-based merge.
    """
    segsum = segment_sum_impl or default_segment_sum
    if key_bound is not None and int(key_bound) <= DENSE_REDUCE_BOUND:
        nseg = max(int(key_bound), 1)
        k = jnp.where(valid, keys, 0).astype(I32)
        v0 = jnp.where(valid, vals, jnp.zeros((), vals.dtype))
        sums = segsum(v0, k, nseg)
        hits = segsum(valid.astype(v0.dtype), k, nseg)
        appeared = hits > 0          # a live key with sum 0 stays a slot
        (uk, uv), count = compact(
            appeared, (jnp.arange(nseg, dtype=I64), sums), cap, fill=0)
        out_valid = jnp.arange(cap) < count
        return (jnp.where(out_valid, uk, PAD_KEY),
                jnp.where(out_valid, uv, 0.0), out_valid, count)
    keys = jnp.where(valid, keys, PAD_KEY)
    if key_bound is not None and int(key_bound) < I32_SORT_BOUND:
        # the same stable permutation from 32-bit keys: a 64-bit sort is
        # emulated on TPU and compiles ~10x slower (minutes at 512k keys)
        k32 = jnp.where(valid, keys, I32_SORT_BOUND).astype(I32)
        _, order = jax.lax.sort((k32, jax.lax.iota(I32, keys.shape[0])),
                                num_keys=1, is_stable=True)
    else:
        order = jnp.argsort(keys)
    sk = keys[order]
    sv = jnp.where(valid[order], vals[order], 0.0)
    first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    seg_id = jnp.cumsum(first) - 1
    sums = segsum(sv, seg_id, keys.shape[0])
    keep = first & (sk != PAD_KEY)
    (uk,), count = compact(keep, (sk,), cap, fill=PAD_KEY)
    uv = sums[: cap] if cap <= keys.shape[0] else jnp.pad(
        sums, (0, cap - keys.shape[0]))
    # sums are indexed by seg_id order == compacted order
    out_valid = jnp.arange(cap) < count
    return uk, jnp.where(out_valid, uv, 0.0), out_valid, count


def mul_reduce(keys, a_vals, b_vals, valid, cap: int, *, key_bound=None,
               segment_sum_impl=None):
    """Fused multiply × keyed reduce: sum ``a_vals * b_vals`` at equal
    ``keys``.

    The reduce stage of the Gustavson inner loop with the ALU product
    folded in: the compiled engine defers a ``mul`` ALU's product into
    its final collapse so the product stream is never materialized
    separately from the reduction (``kernels/ops.py`` lowers this to one
    Pallas workspace kernel on TPU). This fallback is the exact unfused
    composition, so routing through it is bit-identical to computing the
    product eagerly. Returns ``(keys, vals, valid, count)`` like
    ``keyed_union_reduce``.
    """
    return keyed_union_reduce(keys, a_vals * b_vals, valid, cap,
                              segment_sum_impl, key_bound=key_bound)


def fused_intersect_mul_reduce(a_key, a_valid, a_vals, b_key, b_valid,
                               b_vals, out_key, cap: int, *, key_bound=None,
                               segment_sum_impl=None):
    """The Gustavson inner loop as ONE primitive: sorted intersection of
    ``b`` into ``a`` × value gather × multiply × keyed segment-reduce.

    ``a_key``/``b_key`` are sorted stream keys (invalid rows keyed
    ``PAD_KEY``); ``a_vals``/``out_key`` are aligned to *a* positions and
    ``b_vals`` to *b* positions — no intersected, gathered, or product
    stream is ever an input, which is exactly what the fused Pallas
    kernel (``kernels/fused_stream.py``) exploits: on TPU the whole
    composition runs as one kernel with no intermediate streams in HBM.
    This fallback is the composition of ``intersect_keys`` + gather +
    multiply + ``keyed_union_reduce`` and therefore bit-identical to the
    unfused pipeline by construction. Returns ``(keys, vals, valid,
    count)`` like ``keyed_union_reduce``.
    """
    hit, idx = intersect_keys(a_key, a_valid, b_key, b_valid)
    prod = a_vals * b_vals[idx]
    return keyed_union_reduce(out_key, prod, hit, cap, segment_sum_impl,
                              key_bound=key_bound)


def accumulate_coo(acc_keys, acc_vals, keys, vals, key_bound=None,
                   segment_sum_impl=None, union_reduce_impl=None):
    """Merge a new keyed COO partial into a running accumulator.

    The out-of-core tile driver's merge step (``jax_backend.TiledExpr``,
    DESIGN.md §7): after each tile executes, its live ``(keys, vals)``
    partial — shifted into the GLOBAL coordinate space — folds into the
    running result with ONE ``keyed_union_reduce``. Contraction-tiled
    partials overlap (a reduce-merge); result-tiled partials are disjoint
    (a concat-merge comes out of the same primitive for free). Peak
    memory of the merge is the running result plus one tile's partial —
    never all tiles at once.

    Inputs/outputs are host (numpy) arrays of live entries only; returns
    ``(keys, vals)`` sorted by key, unique. ``union_reduce_impl`` routes
    the merge through a dispatch-table implementation (the Pallas
    dense-workspace kernel on TPU); None keeps this module's fallback.

    The merge is one jitted call over inputs padded to a power-of-two
    bucket (padding rows are invalid), so a tile stream compiles once per
    bucket instead of once per partial size for every op of the reduce.
    """
    n_acc, n = len(acc_keys), len(acc_keys) + len(keys)
    if n == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.float32))
    cap = max(8, 1 << (n - 1).bit_length())
    k = np.full(cap, PAD_KEY, np.int64)
    k[:n_acc], k[n_acc:n] = acc_keys, keys
    v = np.zeros(cap, np.float32)
    v[:n_acc], v[n_acc:n] = acc_vals, vals
    uk, uv, count = _merge_bucket(
        k, v, np.arange(cap) < n, cap=cap, key_bound=key_bound,
        segment_sum_impl=segment_sum_impl,
        union_reduce_impl=union_reduce_impl)
    n_out = int(count)
    return np.asarray(uk[:n_out]), np.asarray(uv[:n_out])


@functools.partial(jax.jit, static_argnames=(
    "cap", "key_bound", "segment_sum_impl", "union_reduce_impl"))
def _merge_bucket(k, v, valid, *, cap, key_bound, segment_sum_impl,
                  union_reduce_impl):
    union_reduce = union_reduce_impl or keyed_union_reduce
    uk, uv, _, count = union_reduce(k, v, valid, cap, segment_sum_impl,
                                    key_bound=key_bound)
    return uk, uv, count


def convert_level(level, num_parents: int):
    """Canonicalize ONE fibertree level to engine-native (seg, crd) storage.

    The per-level half of the format-conversion path (DESIGN.md §13): the
    compiled engine only scans dense and compressed levels, so hashed and
    bitmap/bitvector levels are re-laid on ingest — without touching the
    tensor's value array, because their backing storage already lists
    children in canonical sorted order:

      * ``hashed``            — the slot table is an iteration-order view
                                over sorted (seg, crd) backing arrays;
                                conversion just drops the view.
      * ``bitmap``/``bitvector`` — packed words expand to (seg, crd) in
                                ascending bit order (= popcount ref order).
      * ``dense``/``compressed`` — already native; returned unchanged.

    Non-unique (``singleton``) levels cannot convert level-locally — a
    merged duplicate renumbers every descendant — so they raise here;
    ``fibertree.canonical_tree`` routes such trees through the whole-tree
    ``FiberTree.convert`` rebuild instead.
    """
    from .fibertree import (BITMAP, BITVECTOR, BV_WIDTH, COMPRESSED, DENSE,
                            HASHED, SINGLETON, Level)
    if level.format in (DENSE, COMPRESSED):
        return level
    if level.format == HASHED:
        return Level(format=COMPRESSED, dim=level.dim, seg=level.seg,
                     crd=level.crd)
    if level.format in (BITVECTOR, BITMAP):
        segs = [0]
        crds: list = []
        for p in range(int(num_parents)):
            for wi, w in enumerate(level.words[p]):
                w = int(w)
                b = 0
                while w >> b:
                    if (w >> b) & 1:
                        crds.append(wi * BV_WIDTH + b)
                    b += 1
            segs.append(len(crds))
        return Level(format=COMPRESSED, dim=level.dim,
                     seg=np.asarray(segs, dtype=np.int64),
                     crd=np.asarray(crds, dtype=np.int64))
    if level.format == SINGLETON:
        raise ValueError("singleton levels convert tree-wide "
                         "(FiberTree.convert), not level-locally")
    raise ValueError(level.format)


def sorted_segment_reduce(keys, vals, valid, cap: int):
    """Back-compat 3-tuple wrapper around ``keyed_union_reduce``."""
    uk, uv, out_valid, _ = keyed_union_reduce(keys, vals, valid, cap)
    return uk, uv, out_valid


def segment_sum(vals, parent_idx, valid, num_parents: int):
    """Def 3.7 scalar reducer (n=0): one sum per parent fiber (zero-mode)."""
    v = jnp.where(valid, vals, 0.0)
    return jax.ops.segment_sum(v, parent_idx, num_segments=num_parents)


def coo_to_levels(keys, valid, dims_list, caps):
    """Sorted unique COO keys -> compressed fibertree levels, on device.

    The producer→consumer fusion primitive (DESIGN.md §6): a stage's keyed
    COO result (sorted ascending, unique, invalid rows keyed ``PAD_KEY``)
    becomes the ``(seg, crd)`` arrays the next stage's level scanners read,
    without ever leaving the accelerator. ``dims_list`` is the per-level
    extent (outer -> inner); ``caps[l]`` is the static capacity of level
    ``l``'s coordinate array (the parent count of level ``l+1``).

    Returns ``(segs, crds, counts)``: ``segs[l]`` has length
    ``caps[l-1] + 1`` (1 + 1 for the root level), ``crds[l]`` has length
    ``caps[l]``, and ``counts[l]`` is the traced number of live entries at
    level ``l`` so a caller with static caps can detect overflow.
    """
    n = len(dims_list)
    pref = [None] * n
    cur = jnp.where(valid, keys, PAD_KEY)
    for l in range(n - 1, -1, -1):
        pref[l] = cur
        if l:
            cur = jnp.where(valid, cur // dims_list[l], PAD_KEY)
    segs, crds, counts = [], [], []
    parent_cap = 1
    # rank of each element's enclosing level-(l-1) fiber (root: fiber 0)
    parent_rank = jnp.zeros(keys.shape[0], dtype=I64)
    for l in range(n):
        first = jnp.concatenate(
            [jnp.ones((1,), bool), pref[l][1:] != pref[l][:-1]]) & valid
        cnt = jnp.sum(first.astype(I64))
        (crd_l, par_l), _ = compact(
            first, (pref[l] % dims_list[l], parent_rank), caps[l], fill=0)
        # padding rows must sort AFTER every live parent so the seg
        # boundaries below count only live entries
        live = jnp.arange(caps[l]) < cnt
        par_l = jnp.where(live, par_l, parent_cap)
        # entries are key-sorted, so parents are non-decreasing:
        # seg[p] = first entry whose parent >= p
        seg_l = jnp.searchsorted(par_l, jnp.arange(parent_cap + 1)
                                 ).astype(I32)
        segs.append(seg_l)
        crds.append(jnp.where(live, crd_l, 0).astype(I32))
        counts.append(cnt)
        parent_rank = jnp.cumsum(first.astype(I64)) - 1
        parent_cap = caps[l]
    return segs, crds, counts
