"""JAX backend: binds SAM graphs to TPU-native coordinate-array execution.

This is the deployable engine (the simulator keeps the paper's wire-level
timing model). A Custard-produced SAM graph is walked in topological order
— the same automatic binding the paper does for its simulator — but each
block lowers to the data-parallel primitive from ``coord_ops``:

  level scanner  -> ragged fiber expansion (scan_level)
  intersecter    -> sorted-key searchsorted membership (predication mask)
  locator        -> direct fiber probe
  repeater       -> a gather:  ref[child.parent]
  array/ALU      -> gathers / elementwise arithmetic
  reducer n=0    -> per-fiber segment_sum (zero-mode comes for free)
  reducer n>=1   -> ONE fused keyed segment-reduce over the final result
                    coordinates. On TPU, cascading merge hardware is the
                    wrong schedule — a single sort+segment-sum keyed by the
                    result coordinates is the native Gustavson merge. All
                    remaining reductions collapse into it (sums commute);
                    this scheduling substitution is documented in DESIGN.md.
  crd dropper    -> predication: nothing to do — ineffectual coordinates
                    never reach the output COO (masks instead of token
                    removal; the TPU has no token streams to clean).
  level writer   -> final compaction into an output FiberTree.

Streams carry a ``parent`` index array instead of stop tokens: element i of
a level belongs to the fiber of element ``parent[i]`` one level up — the
array encoding of the hierarchical control tokens of §3.2.

Two execution modes share the block handlers:

* **Eager** (``execute_graph`` / the legacy ``execute_expr`` fallback):
  capacities are measured from the concrete data per call, which re-traces
  every invocation. Kept as the reference path and as the capacity-recording
  pass of the compiled engine.
* **Compiled** (``compile_expr`` -> ``CompiledExpr``): the whole expression
  — every term plus the cross-term combination — lowers ONCE into a single
  ``jax.jit``-ed callable with static, bucketed capacities. The jit cache is
  keyed on (term-graph structural hashes, format/dims, input-size bucket,
  capacity bucket); repeat executions of the same expression hit the cache
  with zero re-tracing. Multi-term expressions fuse into one keyed
  union/segment-reduce instead of a per-term Python loop, and
  ``CompiledExpr.execute_batch`` vmaps the same callable over many
  same-format operands per dispatch (the ``launch/serve.py`` path).
  Schedules with ``split``/``parallelize`` (§4.1/§4.4) lower through
  ``custard.lower``: each parallelized term executes as N lanes over a
  dynamic lane-id axis — ``jax.vmap`` on one device, ``shard_map`` over
  the device mesh when several are present — and every (term, lane)
  partial COO merges through the same fused keyed union/segment-reduce.
  The full compile/cache/batch/shard pipeline is documented in DESIGN.md.

A third mode rides on top of the compiled engine: **tiled out-of-core
execution** (``TiledExpr``; DESIGN.md §7, docs/TILING.md). A schedule
carrying ``tile={var: n}`` — written by hand or forced by
``compile_expr(..., mem_budget=...)`` when the untiled allocation
estimate exceeds the budget — streams coordinate-space tiles
sequentially through ONE shared per-tile ``CompiledExpr`` (every tile
after the first hits the plan cache) and folds each tile's partial COO
into the running result with ``coord_ops.accumulate_coo``.

**Tracing.** Host work runs under ``jax.profiler.TraceAnnotation`` spans
named ``sam.*`` (encode: ``sam.encode.build``/``sam.encode.pack``;
execute: ``sam.plan.miss``, ``sam.caps``, ``sam.execute.launch``/
``sam.execute.sync``/``sam.execute.regrow``; decode:
``sam.decode.fetch``/``sam.decode.assemble``). Inside a compiled plan
every graph node runs under a ``jax.named_scope`` ``sam.<kind>.n<id>``
(the final keyed reduce under ``sam.collapse``, the lane/term union
under ``sam.merge``), so device ops carry the node they compute; the
eager capacity pass runs with no scope. ``stats["caps_passes"]`` and
``stats["caps_s"]`` count the capacity passes and their seconds.

**Encode reuse.** ``CompiledExpr`` keeps the raw levels of every live
operand array it has encoded into a compressed level, and serves that
same object again from them when an exact check (under
``sam.encode.reuse``) shows its contents unchanged; see ``_Encoded``.
``stats["encode_reuse_hits"]``/``["encode_reuse_misses"]`` count it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels import ops as kops
from . import coord_ops as co
from . import graph as g
from .custard import expr_cache_key, lower
from .einsum import Assignment, parse
from .fibertree import BITVECTOR, COMPRESSED, DENSE, FiberTree, canonical_tree
from .schedule import Format, Schedule, input_accesses

PAD = co.PAD_KEY


@dataclasses.dataclass
class JLevel:
    seg: jnp.ndarray
    crd: jnp.ndarray
    dim: int


def _engine_tree(ft: FiberTree) -> FiberTree:
    """Canonicalize a tensor for engine ingest.

    The compiled kernels iterate (seg, crd) levels in ascending coordinate
    order, so singleton/hashed/bitmap storage is converted to its d/c
    canonical form here (bit-identical values; see
    ``fibertree.canonical_tree``). The graph's CONVERT nodes then become
    pass-throughs: the conversion they model in the token-level simulator
    has already happened at the array level. Explicit ``b`` (bitvector)
    storage stays simulator-only, as documented in fibertree.
    """
    for lv in ft.levels:
        if lv.format == BITVECTOR:
            raise NotImplementedError(
                f"JAX backend supports d/c levels, not {lv.format}")
    return canonical_tree(ft)


@dataclasses.dataclass
class JTensor:
    levels: List[JLevel]
    vals: jnp.ndarray

    @staticmethod
    def from_fibertree(ft: FiberTree) -> "JTensor":
        ft = _engine_tree(ft)
        levels = []
        num_parents = 1
        for lv in ft.levels:
            if lv.format == COMPRESSED:
                levels.append(JLevel(jnp.asarray(lv.seg, jnp.int32),
                                     jnp.asarray(lv.crd, jnp.int32), lv.dim))
                num_parents = len(lv.crd)
            elif lv.format == DENSE:
                # densified: fiber r is [0, dim) with refs r*dim + c
                seg = jnp.arange(num_parents + 1, dtype=jnp.int32) * lv.dim
                crd = jnp.tile(jnp.arange(lv.dim, dtype=jnp.int32),
                               num_parents)
                levels.append(JLevel(seg, crd, lv.dim))
                num_parents *= lv.dim
            else:
                raise NotImplementedError(
                    f"JAX backend supports d/c levels, not {lv.format}")
        return JTensor(levels, jnp.asarray(ft.vals, jnp.float32))


@dataclasses.dataclass
class CanonStream:
    """Canonical iteration stream at one level (parent-indexed coords)."""

    var: str
    crd: jnp.ndarray
    parent_idx: jnp.ndarray
    valid: jnp.ndarray
    dim: int
    parent: Optional["CanonStream"]
    _key: Optional[jnp.ndarray] = None

    @property
    def size(self) -> int:
        return self.crd.shape[0]

    def key(self) -> jnp.ndarray:
        if self._key is None:
            if self.parent is None:
                base = jnp.zeros_like(self.crd, dtype=jnp.int64)
            else:
                pk = self.parent.key()
                base = pk[jnp.clip(self.parent_idx, 0, pk.shape[0] - 1)]
            k = base * self.dim + self.crd.astype(jnp.int64)
            self._key = jnp.where(
                self.valid & (base != PAD), k, PAD)
        return self._key

    def ancestors(self) -> List["CanonStream"]:
        out, s = [], self
        while s is not None:
            out.append(s)
            s = s.parent
        return out  # innermost first


@dataclasses.dataclass
class RefStream:
    stream: Optional[CanonStream]        # None => scalar/root alignment
    ref: jnp.ndarray
    valid: jnp.ndarray


@dataclasses.dataclass
class ValStream:
    stream: Optional[CanonStream]
    vals: jnp.ndarray
    valid: jnp.ndarray
    # provenance of a multiply: ``(a_vals, b_vals)`` with
    # ``vals == a_vals * b_vals``. Advisory — ``vals`` is always the eager
    # product — but lets the final collapse hand the un-multiplied streams
    # to a fused multiply-reduce kernel (the product then never exists as
    # a separate HBM stream on that path).
    pair: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None


@dataclasses.dataclass
class COOResult:
    keys: jnp.ndarray
    vals: jnp.ndarray
    valid: jnp.ndarray
    strides: List[Tuple[str, int]]       # (var, dim) outer->inner


def _val_writer_node(graph_: g.Graph) -> g.Node:
    for n in graph_.of_kind(g.LEVEL_WRITE):
        if n.params.get("var") == "vals":
            return n
    raise ValueError(f"graph {graph_.name} has no value writer")


def decode_live_coo(keys, vals, valid, strides):
    """Host-side decode of a keyed COO result: drop padding and explicit
    zeros, then unflatten keys into per-level coordinates (one column per
    stride, outer->inner)."""
    keys = np.asarray(keys)
    vals = np.asarray(vals)
    live = np.asarray(valid) & (vals != 0.0)
    keys, vals = keys[live], vals[live]
    coords = np.zeros((len(keys), len(strides)), dtype=np.int64)
    rem = keys
    for col in range(len(strides) - 1, -1, -1):
        dim = strides[col][1]
        coords[:, col] = rem % dim
        rem = rem // dim
    return coords, vals


def coo_to_fibertree(keys, vals, valid, strides, shape, fmt_str,
                     mode_order) -> FiberTree:
    """Host-side decode of a keyed COO result into an output FiberTree."""
    coords, vals = decode_live_coo(keys, vals, valid, strides)
    ft = FiberTree.from_coords(shape, coords, vals, fmt_str)
    if mode_order is not None:
        ft.mode_order = tuple(mode_order)
    return ft


class JaxBackend:
    """Executes a single-term SAM graph on coordinate arrays.

    Eager mode (default): stream capacities are measured from the data per
    call (and recorded in ``caps_record`` for the compiled engine's
    capacity-bucketing pass). Static mode (``scan_caps``/``out_cap`` given):
    every shape is fixed up front so the whole walk jits/vmaps; the actually
    needed sizes come back as traced scalars in ``required`` so the caller
    can detect capacity overflow and re-bucket.
    """

    def __init__(self, graph_: g.Graph, tensors: Dict[str, JTensor],
                 dims: Dict[str, int], result_vars: List[str], *,
                 scan_caps: Optional[Dict[int, int]] = None,
                 out_cap: Optional[int] = None,
                 segsum: Optional[Callable] = None,
                 intersect: Optional[Callable] = None,
                 mul_reduce: Optional[Callable] = None,
                 lane: Optional[Any] = None):
        self.g = graph_
        self.t = tensors
        self.dims = dims
        self.result_vars = result_vars
        # §4.4 parallel lane: ``chunk_n``-marked scanners restrict to this
        # lane's coordinate chunk. May be a concrete int (capacity-record
        # pass) or a traced scalar (the vmapped/shard_mapped lane axis);
        # None executes the full iteration space.
        self.lane = lane
        self.env: Dict[Tuple[int, str], Any] = {}
        self.final: Optional[COOResult] = None
        self.scan_caps = scan_caps
        self.out_cap = out_cap
        self.segsum = segsum                       # keyed segment-sum impl
        self.intersect_impl = intersect or co.intersect_keys
        # fused multiply × keyed-reduce impl for the final collapse; None
        # keeps the classic path (reduce the already-multiplied stream)
        self.mul_reduce_impl = mul_reduce
        self.caps_record: Dict[str, int] = {}      # eager: exact sizes used
        self.required: Dict[str, jnp.ndarray] = {}  # static: traced needs

    # -- helpers -------------------------------------------------------
    def _ins(self, node):
        return {e.dst_port: self.env[(e.src, e.src_port)]
                for e in self.g.in_edges(node)}

    @staticmethod
    def _cap(n: int) -> int:
        return max(8, int(np.ceil(n / 8)) * 8)

    def _scope(self, name: str):
        """``jax.named_scope(name)`` inside a compiled plan (static mode);
        none in the eager capacity pass, whose small programs keep their
        compile-cache keys."""
        if self.scan_caps is None:
            return contextlib.nullcontext()
        return jax.named_scope(name)

    # -- handlers -------------------------------------------------------
    def _root(self, node, ins):
        return {"ref": RefStream(None, jnp.zeros((1,), jnp.int32),
                                 jnp.ones((1,), bool))}

    def _level_scan(self, node, ins):
        t = self.t[node.params["tensor"]]
        lv = t.levels[node.params["mode"]]
        r: RefStream = ins["ref"]
        pr = jnp.clip(r.ref, 0, lv.seg.shape[0] - 2)
        lengths = jnp.where(r.valid & (r.ref >= 0), lv.seg[pr + 1] - lv.seg[pr], 0)
        if self.scan_caps is None:
            need = int(jnp.sum(lengths))
            cap = self._cap(need)
            self.caps_record[f"s{node.id}"] = need
        else:
            cap = self.scan_caps[node.id]
            self.required[f"s{node.id}"] = jnp.sum(lengths)
        crd, ref, sid, valid = co.scan_level(lv.seg, lv.crd, r.ref, r.valid, cap)
        ref_valid = valid
        chunk_n = node.params.get("chunk_n")
        if chunk_n and self.lane is not None:
            # split-level scanning: predicate this lane's REFERENCE stream
            # to its contiguous coordinate chunk. The crd/key stream stays
            # fully valid — sorted-key intersection/locate probes rely on
            # monotone keys, which a mid-stream PAD would break — while the
            # dead references zero out-of-chunk subtrees and collapse their
            # downstream fiber expansions, so per-lane sizes truly shrink.
            csz = -(-lv.dim // chunk_n)
            lo = jnp.asarray(self.lane, jnp.int32) * csz
            ref_valid = valid & (crd >= lo) & (crd < lo + csz)
        cs = CanonStream(var=node.params["var"], crd=crd, parent_idx=sid,
                         valid=valid, dim=lv.dim, parent=r.stream)
        out = {"crd": cs, "ref": RefStream(cs, ref, ref_valid)}
        if node.params.get("bv"):
            # word-packed graphs label this edge "bv"; canonical execution
            # publishes the same coordinate stream under both port names
            out["bv"] = cs
        return out

    def _intersect(self, node, ins):
        m = node.params.get("arity", 2)
        crds: List[CanonStream] = [
            ins[f"crd{i}"] if f"crd{i}" in ins else ins[f"bv{i}"]
            for i in range(m)]
        refs: List[RefStream] = [ins[f"ref{i}"] for i in range(m)]
        base = crds[0]
        hit = base.valid
        out_refs = [refs[0].ref]
        out_refs_valid = [refs[0].valid]
        akey = base.key()
        for i in range(1, m):
            bkey = crds[i].key()
            h, idx = self.intersect_impl(akey, hit, bkey, crds[i].valid)
            hit = h
            out_refs.append(refs[i].ref[idx])
            out_refs_valid.append(refs[i].valid[idx])
        cs = CanonStream(var=base.var, crd=base.crd, parent_idx=base.parent_idx,
                         valid=hit, dim=base.dim, parent=base.parent)
        out = {"crd": cs}
        for i in range(m):
            out[f"ref{i}"] = RefStream(cs, out_refs[i],
                                       hit & out_refs_valid[i])
        return out

    def _locate(self, node, ins):
        t = self.t[node.params["tensor"]]
        lv = t.levels[node.params["mode"]]
        cs: CanonStream = ins["crd"]
        pref: RefStream = ins["ref"]
        # parent refs of the located tensor, gathered to element positions
        if pref.stream is None:
            par_ref = jnp.broadcast_to(pref.ref[0], cs.crd.shape)
            par_ok = jnp.broadcast_to(pref.valid[0], cs.crd.shape)
        else:
            par_ref = pref.ref[cs.parent_idx]
            par_ok = pref.valid[cs.parent_idx]
        found, idx = co.locate_keys(lv.seg, lv.crd, par_ref, cs.crd,
                                    cs.valid & par_ok)
        return {"crd": cs, "ref": RefStream(cs, idx, found),
                "ref_in": pref}

    def _repeat(self, node, ins):
        r: RefStream = ins["ref"]
        cs: CanonStream = ins["crd"]
        if r.stream is None:
            ref = jnp.broadcast_to(r.ref[0], cs.crd.shape)
            ok = jnp.broadcast_to(r.valid[0], cs.crd.shape) & cs.valid
        else:
            ref = r.ref[cs.parent_idx]
            ok = r.valid[cs.parent_idx] & cs.valid
        return {"ref": RefStream(cs, ref, ok)}

    def _array(self, node, ins):
        t = self.t[node.params["tensor"]]
        r: RefStream = ins["ref"]
        if t.vals.shape[0] == 0:   # tensor with no stored values
            vals = jnp.zeros(r.ref.shape, jnp.float32)
            return {"val": ValStream(r.stream, vals, r.valid)}
        idx = jnp.clip(r.ref, 0, t.vals.shape[0] - 1)
        vals = jnp.where(r.valid, t.vals[idx], 0.0)
        return {"val": ValStream(r.stream, vals, r.valid)}

    def _alu(self, node, ins):
        a: ValStream = ins["a"]
        b: ValStream = ins["b"]
        op = node.params["op"]
        f = {"mul": jnp.multiply, "add": jnp.add, "sub": jnp.subtract}[op]
        if a.vals.shape != b.vals.shape:
            raise ValueError("ALU operands misaligned in JAX backend")
        pair = (a.vals, b.vals) if op == "mul" else None
        return {"val": ValStream(a.stream, f(a.vals, b.vals),
                                 a.valid | b.valid, pair=pair)}

    def _reduce(self, node, ins):
        v: ValStream = ins["val"]
        if self.final is not None:      # already collapsed into final reduce
            return {"val": v, **{f"crd{k}": ins[f"crd{k}"]
                                 for k in range(int(node.params.get("n", 0)))
                                 if f"crd{k}" in ins}}
        n = int(node.params.get("n", 0))
        cs = v.stream
        if n == 0:
            parent = cs.parent
            num = parent.size if parent is not None else 1
            sums = co.segment_sum(v.vals, cs.parent_idx, v.valid & cs.valid, num)
            pvalid = parent.valid if parent is not None else jnp.ones((1,), bool)
            return {"val": ValStream(parent, sums, pvalid)}
        # n >= 1: fuse every remaining reduction into one keyed reduce over
        # the final result coordinates.
        coo = self._collapse_to_result(v)
        self.final = coo
        out = {"val": coo}
        for k in range(n):
            if f"crd{k}" in ins:
                out[f"crd{k}"] = coo
        return out

    def _collapse_to_result(self, v: ValStream) -> COOResult:
        with self._scope("sam.collapse"):
            return self._collapse(v)

    def _collapse(self, v: ValStream) -> COOResult:
        cs = v.stream
        chain = cs.ancestors()           # innermost first
        strides: List[Tuple[str, int]] = []
        key = jnp.zeros(cs.size, dtype=jnp.int64)
        mult = 1
        idx = jnp.arange(cs.size)
        valid = v.valid & cs.valid
        for s in chain:
            if s.var in self.result_vars:
                key = key + s.crd[idx].astype(jnp.int64) * mult
                strides.append((s.var, self.dims[s.var]))
                mult *= self.dims[s.var]
            valid = valid & s.valid[idx]
            if s.parent is not None:
                idx = s.parent_idx[idx]
        strides.reverse()                # outer -> inner
        if self.out_cap is None:
            need = int(jnp.sum(valid))
            cap = self._cap(need)
            self.caps_record["out"] = need
        else:
            cap = self.out_cap
        if v.pair is not None and self.mul_reduce_impl is not None:
            # the stream is a multiply: hand the un-multiplied operand
            # streams to the fused multiply-reduce primitive (on CPU this
            # resolves to ``co.mul_reduce`` — literally the composition
            # below, so results are bit-identical; on TPU it is one Pallas
            # workspace kernel and the product stream never hits HBM).
            pa, pb = v.pair
            uk, uv, uvalid, count = self.mul_reduce_impl(
                key, pa, pb, valid, cap, key_bound=mult,
                segment_sum_impl=self.segsum)
        else:
            uk, uv, uvalid, count = co.keyed_union_reduce(
                key, v.vals, valid, cap, self.segsum, key_bound=mult)
        if self.out_cap is not None:
            self.required["out"] = count
        return COOResult(uk, uv, uvalid, strides)

    def _crd_drop(self, node, ins):
        # predication: masks already guarantee ineffectual coordinates never
        # reach the output; explicit zeros are filtered at assembly.
        out = {}
        if "outer" in ins:
            out["outer"] = ins["outer"]
        if "inner" in ins:
            out["inner"] = ins["inner"]
        for k in ins:
            if k.startswith("pass"):
                out[k] = ins[k]
        return out

    def _level_write(self, node, ins):
        return dict(ins)

    def _convert(self, node, ins):
        # format-conversion nodes are pass-throughs on the engine: operands
        # were canonicalized to d/c order at ingest (``_engine_tree``), so
        # the sort/tree reorderings they model are already applied. Ports
        # forward unchanged (sort: crd+ref; tree: ref).
        return dict(ins)

    def run_nodes(self) -> None:
        handlers = {
            g.ROOT: self._root, g.LEVEL_SCAN: self._level_scan,
            g.INTERSECT: self._intersect, g.UNION: self._union_unsupported,
            g.REPEAT: self._repeat, g.ARRAY: self._array, g.ALU: self._alu,
            g.REDUCE: self._reduce, g.CRD_DROP: self._crd_drop,
            g.LOCATE: self._locate, g.LEVEL_WRITE: self._level_write,
            g.CONVERT: self._convert,
        }
        for node in self.g.topo_order():
            with self._scope(f"sam.{node.kind}.n{node.id}"):
                outs = handlers[node.kind](node, self._ins(node))
            for port, val in outs.items():
                self.env[(node.id, port)] = val

    def run_streams(self):
        """Execute the graph; return the value-writer stream in final form:
        a ``COOResult`` over the result coordinates, or a traced scalar."""
        self.run_nodes()
        n = _val_writer_node(self.g)
        v = self.env[(n.id, "val")]
        if isinstance(v, COOResult):
            return v
        if isinstance(v, ValStream):
            if v.stream is None:     # scalar result
                return jnp.sum(jnp.where(v.valid, v.vals, 0.0))
            return self._collapse_to_result(v)
        raise TypeError(type(v))

    def run(self) -> Dict[str, FiberTree]:
        v = self.run_streams()
        n = _val_writer_node(self.g)
        tname = n.params["tensor"]
        if not isinstance(v, COOResult):           # scalar result
            return {tname: FiberTree.from_dense(
                np.asarray(float(v)), "")}
        fmt = n.params.get("format", "c" * len(v.strides)) or ""
        return {tname: coo_to_fibertree(
            v.keys, v.vals, v.valid, v.strides, n.params.get("shape", ()),
            fmt, n.params.get("mode_order"))}

    def _union_unsupported(self, node, ins):
        raise NotImplementedError(
            "multi-term graphs: compile per term (see CompiledExpr) and "
            "combine with the fused keyed union")


# ---------------------------------------------------------------------------
# compiled engine
# ---------------------------------------------------------------------------

def _bucket(n: int) -> int:
    """Static-capacity bucket: next power of two, floor 8. Bucketing keeps
    the number of distinct jit signatures logarithmic in the data size."""
    return 8 if n <= 8 else 1 << (n - 1).bit_length()


def _bucket_cap(n: int) -> int:
    """Bucket an intermediate-stream capacity with 25% headroom so sizes
    recorded just under a power of two don't regrow on the next call."""
    return _bucket(int(n * 1.25))


def _bucket_batch(b: int) -> int:
    return 1 if b <= 1 else 1 << (b - 1).bit_length()


def _pad_end(a: np.ndarray, n: int, fill) -> np.ndarray:
    # Host-side numpy on purpose: padding with jnp ops would compile one
    # tiny XLA program per novel concrete shape, which dominates encode
    # cost under serving traffic (every request has a fresh nnz).
    if a.shape[0] >= n:
        return a
    pad = np.full((n - a.shape[0],), fill, a.dtype)
    return np.concatenate([a, pad])


@dataclasses.dataclass
class _Plan:
    """One jitted executable: static capacities + the callable."""
    caps: Dict[str, int]
    fn: Callable


@dataclasses.dataclass
class EncodedBatch:
    """A host-encoded batched dispatch, ready for the device stage.

    Produced by ``CompiledExpr.encode_batch`` (host encode), consumed by
    ``execute_encoded`` (device execute) and ``decode_batch`` (host
    decode) — the three-stage split lets a serving pipeline overlap the
    encode of dispatch N+1 with the execute of dispatch N."""
    stacked: Any                 # batch-stacked padded operand pytree
    sig: Tuple                   # shared input signature (plan-cache key)
    b: int                       # live batch members
    b_pad: int                   # power-of-two padded batch width
    flats: List                  # live members, unstacked (cap recording)
    rep: int = 0                 # index of the largest-nnz member


def _run_with_growth(plan: _Plan, flat, stats: Dict[str, int],
                     reinstall: Callable[[Dict[str, int]], _Plan]):
    """Run a plan, growing bucketed capacities on overflow and retrying.

    Each retry can reveal larger downstream needs (truncation hid
    elements), so loop to a fixpoint. The required sizes come back in ONE
    device_get (per-key blocking transfers would serialize a sync per
    capacity). Shared by the expression engine and the program chain —
    ``reinstall`` builds the replacement plan for the grown caps.
    """
    for _ in range(32):
        with TraceAnnotation("sam.execute.launch"):
            out, required = plan.fn(flat)
        with TraceAnnotation("sam.execute.sync"):
            required = jax.device_get(required)
        grow = {}
        for k, r in required.items():
            need = int(np.max(r))
            if need > plan.caps[k]:
                grow[k] = _bucket_cap(need)
        if not grow:
            return out
        stats["overflow_retries"] += 1
        with TraceAnnotation("sam.execute.regrow"):
            plan = reinstall({**plan.caps, **grow})
    raise RuntimeError("compiled SAM capacity growth did not converge")


def _capacity_pass(record_caps: Callable) -> Callable:
    """Wraps an engine's ``_record_caps``: the eager capacity pass runs
    under the ``sam.caps`` span and counts in the engine's ``stats``
    (``caps_passes`` calls, ``caps_s`` seconds)."""
    @functools.wraps(record_caps)
    def counted(self, *args):
        t = time.perf_counter()
        try:
            with TraceAnnotation("sam.caps"):
                return record_caps(self, *args)
        finally:
            self.stats["caps_passes"] += 1
            self.stats["caps_s"] += time.perf_counter() - t
    return counted


def _raw_flat_of(ft: FiberTree) -> Dict[str, Any]:
    """Raw per-level arrays of one operand fibertree, as NUMPY.

    Only compressed seg/crd and the value array feed ``_pad_flat_arrays``
    (dense expansions are rebuilt there from level metadata), so dense
    levels get zero-length placeholders — cheaper than
    ``JTensor.from_fibertree``, which both materialises the dense
    expansion and converts every level through jnp (a device upload plus
    a tiny-op compile per novel shape)."""
    segs, crds = [], []
    empty = np.zeros(0, np.int32)
    for lv in ft.levels:
        if lv.format == COMPRESSED:
            segs.append(np.asarray(lv.seg, np.int32))
            crds.append(np.asarray(lv.crd, np.int32))
        elif lv.format == DENSE:
            segs.append(empty)
            crds.append(empty)
        else:
            raise NotImplementedError(
                f"JAX backend supports d/c levels, not {lv.format}")
    return {"segs": tuple(segs), "crds": tuple(crds),
            "vals": np.asarray(ft.vals, np.float32)}


def _reusable(arr) -> bool:
    """An operand whose contents ``_Encoded.holds`` can check exactly: a
    numpy array of a bool, integer or float dtype of 1, 2, 4 or 8 bytes."""
    return (isinstance(arr, np.ndarray) and arr.dtype.kind in "biuf"
            and arr.dtype.itemsize in (1, 2, 4, 8))


def _bits(arr: np.ndarray) -> np.ndarray:
    """A C-contiguous array's elements, flat, as integers of their own
    width: two elements are equal bit for bit exactly when these are."""
    return arr.reshape(-1).view(np.dtype(f"i{arr.dtype.itemsize}"))


def _nonzero_bits(arr: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Flat positions of a C-contiguous array's nonzero bit patterns and
    those patterns, read-only."""
    bits = _bits(arr)
    pos = np.flatnonzero(bits)
    at = bits[pos]
    pos.setflags(write=False)
    at.setflags(write=False)
    return pos, at


@dataclasses.dataclass(frozen=True)
class _Encoded:
    """The raw levels (``_raw_flat_of``) of one operand array object that
    an engine has encoded, kept while the array lives.

    From the object's second encode on, the entry also holds the flat
    positions of its nonzero bit patterns and those patterns (the values
    in the array's own dtype, compared as integers of their width), and
    ``holds`` can prove that the array is still, bit for bit, what the
    raw levels were built from: the same object, shape and dtype, as
    many nonzero bit patterns as stored, and the stored ones at the
    stored positions. Encoding reads nothing but the array's contents,
    shape and dtype, so the raw levels are then what a rebuild would
    give. Any write that changes a bit (a ``-0.0`` or a ``NaN`` too)
    fails the check, and the operand is rebuilt."""
    ref: weakref.ref
    shape: Tuple[int, ...]
    dtype: np.dtype
    raw: Dict[str, Any]                   # read-only arrays
    pos: Optional[np.ndarray] = None
    at: Optional[np.ndarray] = None

    def holds(self, arr: np.ndarray) -> bool:
        if (self.pos is None or self.ref() is not arr
                or arr.shape != self.shape or arr.dtype != self.dtype
                or not arr.flags.c_contiguous):
            return False
        bits = _bits(arr)
        return (np.count_nonzero(bits) == self.pos.shape[0]
                and np.array_equal(bits[self.pos], self.at))


def _pad_flat_arrays(raw, level_meta, hints=None):
    """Pad raw operand arrays to power-of-two buckets (shared by the
    expression engine and the program chain engine).

    Only compressed-level coordinate counts are bucketed independently;
    segment lengths (parents+1), dense-level expansions, and the value
    array length all DERIVE from the parent-level bucket, so the jit
    signature depends on nothing but per-level nnz buckets (a size
    sitting on a parents+1 boundary cannot flip the signature).

    The padded pytree leaves are NUMPY arrays: jit converts them at the
    call boundary in one upload, whereas building them with jnp ops
    would trace/compile a tiny XLA program per novel concrete shape —
    under serving traffic (fresh nnz per request) those compiles
    dominate the encode stage.
    """
    flat, sig = {}, []
    for name in sorted(raw):
        e = raw[name]
        segs, crds, lsig = [], [], []
        num_parents = 1
        for i, (fmt_l, dim) in enumerate(level_meta[name]):
            ns = num_parents + 1
            if fmt_l == DENSE:
                nc = num_parents * dim
                segs.append(np.arange(ns, dtype=np.int32) * dim)
                crds.append(np.tile(np.arange(dim, dtype=np.int32),
                                    num_parents))
            else:
                c = e["crds"][i]
                nc = (hints[name][i] if hints
                      else _bucket(c.shape[0]))
                s = e["segs"][i]
                segs.append(_pad_end(s, ns, s[-1]))
                crds.append(_pad_end(c, nc, 0))
            lsig.append((ns, nc))
            num_parents = nc
        vals = _pad_end(e["vals"], num_parents, 0.0)
        flat[name] = {"segs": tuple(segs), "crds": tuple(crds),
                      "vals": vals}
        sig.append((name, tuple(lsig), vals.shape[0]))
    return flat, tuple(sig)


def _tensors_from_flat_arrays(flat, level_meta) -> Dict[str, JTensor]:
    # jnp.asarray: flat leaves are host numpy (see _pad_flat_arrays), but
    # stream ops index these arrays with tracers during the eager
    # capacity-record pass — numpy refuses tracer indices. No-op under
    # jit (leaves are already tracers) and off the per-call hot path.
    out = {}
    for name, e in flat.items():
        out[name] = JTensor(
            [JLevel(jnp.asarray(s), jnp.asarray(c), d)
             for s, c, (_, d) in zip(e["segs"], e["crds"],
                                     level_meta[name])],
            jnp.asarray(e["vals"]))
    return out


_COMPILED: Dict[Tuple[str, bool], "CompiledExpr"] = {}


def lane_mesh_size(par_n: int, bound: Optional[int] = None) -> int:
    """Largest device count that can host the lane mesh: the biggest
    divisor of ``par_n`` no larger than the available devices (and the
    caller's ``bound``, e.g. serve's --devices). 1 means no useful mesh."""
    limit = min(jax.device_count(), par_n, bound or jax.device_count())
    return max((d for d in range(1, limit + 1) if par_n % d == 0),
               default=1)


def _resolve_shard_lanes(shard_lanes, par_n: int) -> int:
    """One resolver for the lane-mesh size (it is part of the engine cache
    key, so it must be computed identically everywhere). ``shard_lanes``:
    None auto-shards whenever a >1-device mesh fits; False forces serial
    vmap; True (or an int device bound) REQUIRES a mesh and raises when
    none fits. Returns the mesh size (1 = plain vmap)."""
    if shard_lanes is None or shard_lanes is False:
        if shard_lanes is False or par_n <= 1:
            return 1
        return lane_mesh_size(par_n)
    bound = None if shard_lanes is True else int(shard_lanes)
    m = lane_mesh_size(par_n, bound)
    if m < 2:
        raise ValueError(
            f"cannot shard {par_n} lane(s) over {jax.device_count()} "
            f"device(s)" + (f" with --devices {bound}" if bound else ""))
    return m


class CompiledExpr:
    """A Custard expression lowered once into jit-cached JAX callables.

    Lifecycle per call:

    1. operands -> concordant fibertrees -> coordinate arrays, padded to
       power-of-two **input buckets** (the jit signature stays stable while
       nnz wobbles inside a bucket);
    2. plan lookup by input signature. A miss runs the eager backend once as
       a **capacity-recording pass**, buckets every intermediate stream
       capacity, and jits the full multi-term executable (shared module-wide
       via the (graph hash, dims, bucket, caps) key);
    3. the jitted callable runs every term and fuses them with one keyed
       union/segment-reduce; it also returns the true required sizes, so a
       **capacity overflow** (data needs more than the bucketed caps) grows
       the plan and re-runs — results are never silently truncated;
    4. the COO result is decoded host-side into an output FiberTree.

    ``execute_batch`` vmaps the same core over stacked same-format operands
    (one dispatch for B expressions), padding the batch to a power of two.
    """

    def __init__(self, expr, fmt: Format, schedule: Schedule,
                 dims: Dict[str, int], *, use_kernels: bool = True,
                 shard_lanes: Optional[bool] = None):
        self.assign: Assignment = parse(expr) if isinstance(expr, str) else expr
        self.fmt = fmt
        self.schedule = schedule
        self.dims = dict(dims)
        self.cache_key = expr_cache_key(self.assign, fmt, schedule, self.dims)
        low = lower(self.assign, fmt, schedule, self.dims)
        self.low = low
        terms = low.require_terms()
        self.signs = [t.sign for t in terms]
        self.graphs = [t.graph for t in terms]
        self.lane_ns = [t.lane_n for t in terms]
        self.par_n = low.par_n
        self.graph_hashes = tuple(G.structural_hash() for G in self.graphs)
        self.rvars = low.result_vars           # post-split, loop order
        self._scalar = not self.rvars
        writer = _val_writer_node(self.graphs[0])
        self._out_shape = writer.params.get("shape", ())
        self._out_fmt = (writer.params.get("format")
                         or "c" * len(self.rvars))
        self._mode_order = writer.params.get("mode_order")
        self._strides = [(v, low.dims[v]) for v in self.rvars]
        # results come back in the ORIGINAL coordinate space: split result
        # levels (vo, vi) are re-merged during output assembly
        self._out_merge = self._build_out_merge()
        # sharded lane dispatch: shard_map over a device mesh when one fits
        # the lane count; vmap on one device. ``shard_lanes``: None = auto,
        # False = never, True/int = require a mesh (of at most that many
        # devices) or fail loudly.
        self._lane_mesh = _resolve_shard_lanes(shard_lanes, self.par_n)
        self._shard_lanes = self._lane_mesh > 1
        self._segsum = None
        self._intersect = None
        self._union_reduce = None
        self._mul_reduce = None
        if use_kernels:
            self._segsum = kops.plan_primitive("keyed_segment_sum")
            self._intersect = kops.plan_primitive("sorted_intersect")
            self._union_reduce = kops.plan_primitive("keyed_union_reduce")
            self._mul_reduce = kops.plan_primitive("mul_reduce")
        self._inputs = tuple(input_accesses(low.assign))
        self._level_meta: Dict[str, List[Tuple[str, int]]] = {}
        self._plans: Dict[Tuple, _Plan] = {}
        self._batch_plans: Dict[Tuple, _Plan] = {}
        self._jit_cache: Dict[Tuple, Callable] = {}
        # Sticky per-level bucket high-water for batched encodes: under
        # serving traffic each request's nnz jitters across power-of-two
        # buckets, and without stickiness every batch whose member max
        # lands in a new bucket combination pays a fresh vmapped XLA
        # compile. Monotone hints pin the batch signature after warmup.
        self._hint_highwater: Dict[str, List[int]] = {}
        # Raw levels of the live operand arrays encoded so far, one entry
        # per (tensor name, array object); an entry leaves with its array
        # (weakref callback). Only single dict operations touch it, which
        # keeps concurrent callers safe without a lock.
        self._encoded: Dict[Tuple[str, int], _Encoded] = {}
        self.stats = {"traces": 0, "plan_hits": 0, "plan_misses": 0,
                      "overflow_retries": 0, "calls": 0, "batch_calls": 0,
                      "lane_dispatches": 0, "sharded_dispatches": 0,
                      "caps_passes": 0, "caps_s": 0.0,
                      "encode_reuse_hits": 0, "encode_reuse_misses": 0}

    @property
    def lane_devices(self) -> List[Any]:
        """The devices the parallel lanes shard over (empty when the
        lanes vmap on one device)."""
        return jax.devices()[:self._lane_mesh] if self._shard_lanes else []

    def _build_out_merge(self):
        """Decode plan for split result levels: [(orig var, o-col, i-col or
        None, inner chunk)] over the post-split stride columns."""
        split_of = self.low.split_of
        if not any(v in split_of for v in self.low.orig_result_vars):
            return None
        merge, i = [], 0
        while i < len(self.rvars):
            v = self.rvars[i]
            if (v.endswith("o") and v[:-1] in split_of
                    and i + 1 < len(self.rvars)
                    and self.rvars[i + 1] == v[:-1] + "i"):
                merge.append((v[:-1], i, i + 1, self.low.dims[v[:-1] + "i"]))
                i += 2
            else:
                merge.append((v, i, None, None))
                i += 1
        return merge

    # -- operand flattening ------------------------------------------------
    def _raw_flat(self, arrays: Dict[str, np.ndarray],
                  memo: Optional[Dict] = None) -> Dict[str, Any]:
        """Raw levels of every input tensor. ``memo`` is shared by the
        members of one dispatch: members that pass the same array object
        share one build or one check."""
        memo = {} if memo is None else memo
        return {name: self._raw_tensor(name, arrays, memo)
                for name in self._inputs}

    def _build_raw(self, name: str, arrays) -> Dict[str, Any]:
        ft = _engine_tree(self.low.build_input(name, arrays))  # s/h/m -> d/c
        self._level_meta.setdefault(
            name, [(lv.format, lv.dim) for lv in ft.levels])
        return _raw_flat_of(ft)

    def _raw_tensor(self, name: str, arrays, memo: Dict) -> Dict[str, Any]:
        """Raw levels of one tensor, reused where the engine has encoded
        this array object before and ``_Encoded.holds`` proves it
        unchanged. Only numpy operands with a compressed level take part
        (``encode_reuse_hits``/``_misses`` count them per member); others
        are built as they come."""
        arr = arrays[name]
        meta = self._level_meta.get(name)
        if not _reusable(arr) or (
                meta is not None and COMPRESSED not in (f for f, _ in meta)):
            return self._build_raw(name, arrays)
        key = (name, id(arr))
        raw = memo.get(key)
        entry = self._encoded.get(key) if raw is None else None
        if entry is not None and entry.pos is not None:
            with TraceAnnotation("sam.encode.reuse", tensor=name):
                if entry.holds(arr):
                    raw = entry.raw
        if raw is not None:
            self.stats["encode_reuse_hits"] += 1
            memo[key] = raw
            return raw
        # seen before: take the positions the next check needs (a fresh
        # object pays for none)
        seen = (entry is not None and entry.ref() is arr
                and arr.flags.c_contiguous)
        pos, at = _nonzero_bits(arr) if seen else (None, None)
        raw = self._build_raw(name, arrays)
        if COMPRESSED not in (f for f, _ in self._level_meta[name]):
            return raw
        self.stats["encode_reuse_misses"] += 1
        for a in (*raw["segs"], *raw["crds"], raw["vals"]):
            a.setflags(write=False)     # shared from here on
        memo[key] = raw
        if arr.flags.c_contiguous:
            table = self._encoded
            ref = (entry.ref if seen else weakref.ref(
                arr, lambda _, k=key: table.pop(k, None)))
            table[key] = _Encoded(ref, arr.shape, arr.dtype, raw, pos, at)
        return raw

    def _pad_flat(self, raw, hints=None):
        """Pad operand arrays to power-of-two buckets (see
        ``_pad_flat_arrays``)."""
        return _pad_flat_arrays(raw, self._level_meta, hints)

    def _tensors_from_flat(self, flat) -> Dict[str, JTensor]:
        return _tensors_from_flat_arrays(flat, self._level_meta)

    # -- plan construction -------------------------------------------------
    def _lanes_of(self, ti: int):
        n = self.lane_ns[ti]
        return range(n) if n > 1 else [None]

    def _needs_fused(self) -> bool:
        return (not self._scalar
                and (len(self.graphs) > 1
                     or any(n > 1 for n in self.lane_ns)))

    @_capacity_pass
    def _record_caps(self, flats: Sequence[Dict]) -> Dict[str, int]:
        """Eager capacity-recording pass over one (or, batched, every)
        concrete padded operand set; returns bucketed static capacities.
        Parallel lanes run with concrete lane ids; a laned term's caps are
        the max over its lanes (the vmapped executable is shape-uniform)."""
        caps: Dict[str, int] = {}
        fused_need = 0
        for flat in flats:
            tensors = self._tensors_from_flat(flat)
            call_fused = 0
            for ti, G in enumerate(self.graphs):
                for lane in self._lanes_of(ti):
                    be = JaxBackend(G, tensors, self.low.dims, self.rvars,
                                    lane=lane)
                    v = be.run_streams()
                    for k, n in be.caps_record.items():
                        key = f"t{ti}.{k}"
                        caps[key] = max(caps.get(key, 0), n)
                    if isinstance(v, COOResult):
                        call_fused += int(jnp.sum(v.valid))
            fused_need = max(fused_need, call_fused)
        caps = {k: _bucket_cap(n) for k, n in caps.items()}
        if self._needs_fused():
            caps["fused"] = _bucket_cap(fused_need)
        return caps

    def _lane_map(self, fn, shard: bool) -> Callable:
        """Vectorize ``fn`` over the lane-id axis: one vmapped dispatch on a
        single device; shard_map over a 1-D ``lanes`` mesh of the largest
        device subset dividing the lane count (each device vmaps its local
        lanes)."""
        vm = jax.vmap(fn)
        if not shard:
            return vm
        mesh = Mesh(np.asarray(self.lane_devices), ("lanes",))
        return _shard_map(vm, mesh=mesh, in_specs=P("lanes"),
                          out_specs=P("lanes"), check_vma=False)

    def _build_core(self, caps: Dict[str, int], batch: bool) -> Callable:
        # the single and the vmapped batch path run the same resolved
        # primitives: Pallas kernels on TPU (pallas_call batches by adding
        # a grid axis), the coord_ops fallbacks elsewhere
        segsum = self._segsum
        intersect = self._intersect
        mul_reduce = self._mul_reduce
        union_reduce = self._union_reduce or co.keyed_union_reduce
        scan_caps = [
            {n.id: caps[f"t{ti}.s{n.id}"] for n in G.of_kind(g.LEVEL_SCAN)}
            for ti, G in enumerate(self.graphs)]
        out_caps = [caps.get(f"t{ti}.out") for ti in range(len(self.graphs))]
        signs = self.signs
        # the batch path nests inside an outer vmap; keep lanes vmapped there
        shard = self._shard_lanes and not batch

        def run_term(ti, tensors, lane):
            be = JaxBackend(self.graphs[ti], tensors, self.low.dims,
                            self.rvars, scan_caps=scan_caps[ti],
                            out_cap=out_caps[ti], segsum=segsum,
                            intersect=intersect, mul_reduce=mul_reduce,
                            lane=lane)
            return be.run_streams(), be.required

        def core(flat):
            self.stats["traces"] += 1      # runs only while jax traces
            tensors = self._tensors_from_flat(flat)
            required: Dict[str, jnp.ndarray] = {}
            outs = []                      # per (term): COOResult or scalar
            for ti in range(len(self.graphs)):
                n = self.lane_ns[ti]
                if n == 1:
                    v, req = run_term(ti, tensors, None)
                    for k, r in req.items():
                        required[f"t{ti}.{k}"] = r
                    outs.append(v)
                    continue
                # §4.4 sharded dispatch: all lanes of this term execute as
                # ONE vectorized call over the lane-id axis
                def one_lane(lane, _ti=ti):
                    v, req = run_term(_ti, tensors, lane)
                    if self._scalar:
                        return v, req
                    return (v.keys, v.vals, v.valid), req
                out, req = self._lane_map(one_lane, shard)(
                    jnp.arange(n, dtype=jnp.int32))
                for k, r in req.items():
                    required[f"t{ti}.{k}"] = jnp.max(r)
                if self._scalar:
                    outs.append(jnp.sum(out))
                else:
                    keys, vals, valid = out          # (n, cap) each
                    outs.append(COOResult(keys.reshape(-1), vals.reshape(-1),
                                          valid.reshape(-1),
                                          list(self._strides)))
            if self._scalar:
                total = signs[0] * outs[0]
                for s, v in zip(signs[1:], outs[1:]):
                    total = total + s * v
                return {"scalar": total}, required
            if len(outs) == 1 and self.lane_ns[0] == 1:
                coo = outs[0]
                vals = coo.vals if signs[0] == 1 else signs[0] * coo.vals
                return {"keys": coo.keys, "vals": vals,
                        "valid": coo.valid}, required
            # lane/term merge stage: ONE keyed union/segment-reduce combines
            # every (term, lane) partial result (sums commute; signs fold
            # into the values; disjoint concat-merges come out for free)
            keys = jnp.concatenate([c.keys for c in outs])
            vals = jnp.concatenate(
                [c.vals if s == 1 else s * c.vals
                 for s, c in zip(signs, outs)])
            valid = jnp.concatenate([c.valid for c in outs])
            bound = 1
            for _, d in self._strides:
                bound *= d
            with jax.named_scope("sam.merge"):
                uk, uv, uvalid, count = union_reduce(
                    keys, vals, valid, caps["fused"], segsum,
                    key_bound=bound)
            required["fused"] = count
            return {"keys": uk, "vals": uv, "valid": uvalid}, required

        return core

    def _install_plan(self, sig, caps: Dict[str, int], *, batch: bool,
                      b_pad: Optional[int] = None) -> _Plan:
        # Per-engine jit cache (engines themselves are deduplicated
        # process-wide by canonical key via compile_expr): the graph hashes
        # in the key tie each executable to the exact lowering it runs.
        jit_key = (self.graph_hashes,
                   tuple(sorted(self.dims.items())), tuple(self.rvars),
                   sig, tuple(sorted(caps.items())), batch, b_pad,
                   self._segsum is not None, self._mul_reduce is not None,
                   tuple(self.lane_ns), self._shard_lanes)
        fn = self._jit_cache.get(jit_key)
        if fn is None:
            core = self._build_core(caps, batch)
            fn = jax.jit(jax.vmap(core)) if batch else jax.jit(core)
            self._jit_cache[jit_key] = fn
        plan = _Plan(caps=caps, fn=fn)
        if batch:
            self._batch_plans[(sig, b_pad)] = plan
        else:
            self._plans[sig] = plan
        return plan

    def _run_plan(self, plan: _Plan, sig, flat, *, batch: bool,
                  b_pad: Optional[int] = None):
        return _run_with_growth(
            plan, flat, self.stats,
            lambda caps: self._install_plan(sig, caps, batch=batch,
                                            b_pad=b_pad))

    # -- output assembly ---------------------------------------------------
    def _assemble_out(self, out, b: Optional[int] = None) -> FiberTree:
        if "scalar" in out:
            v = out["scalar"] if b is None else out["scalar"][b]
            return FiberTree.from_dense(np.asarray(float(v)), "")
        sel = (lambda a: a) if b is None else (lambda a: a[b])
        if self._out_merge is None:
            return coo_to_fibertree(sel(out["keys"]), sel(out["vals"]),
                                    sel(out["valid"]), self._strides,
                                    self._out_shape, self._out_fmt,
                                    self._mode_order)
        return self._assemble_unsplit(sel(out["keys"]), sel(out["vals"]),
                                      sel(out["valid"]))

    @property
    def orig_result_order(self) -> List[str]:
        """The ORIGINAL result variables in storage (loop) order — the
        column order of ``execute_coo`` coordinates."""
        if self._out_merge is not None:
            return [m[0] for m in self._out_merge]
        return list(self.rvars)

    def _live_coords(self, out) -> Tuple[np.ndarray, np.ndarray]:
        """(coords, vals) of the live result in the ORIGINAL coordinate
        space; one coordinate column per ``orig_result_order`` var (split
        result levels re-merged, padding/zeros dropped)."""
        cols, vals = decode_live_coo(out["keys"], out["vals"], out["valid"],
                                     self._strides)
        if self._out_merge is None:
            return cols, vals
        coords = np.zeros((len(cols), len(self._out_merge)), dtype=np.int64)
        for k, (v, io, ii, chunk) in enumerate(self._out_merge):
            coords[:, k] = (cols[:, io] if ii is None
                            else cols[:, io] * chunk + cols[:, ii])
        return coords, vals

    def _assemble_unsplit(self, keys, vals, valid) -> FiberTree:
        """Decode a split-space COO result back into the ORIGINAL
        coordinate space: each (vo, vi) level pair merges to vo*chunk+vi.
        Split padding carries only explicit zeros, which are filtered."""
        coords, vals = self._live_coords(
            {"keys": keys, "vals": vals, "valid": valid})
        orig_vars = self.orig_result_order
        shape = tuple(self.low.orig_dims[v] for v in orig_vars)
        lhs = self.low.orig_assign.lhs
        ft = FiberTree.from_coords(
            shape, coords, vals,
            self.fmt.of(lhs.tensor, len(orig_vars)) or "c" * len(orig_vars))
        ft.mode_order = tuple(lhs.vars.index(v) for v in orig_vars)
        return ft

    # -- public execution --------------------------------------------------
    def execute(self, arrays: Dict[str, np.ndarray]) -> FiberTree:
        """Execute one operand set through the jit-cached plan.

        Args:
            arrays: dense numpy array per input tensor name (concordant
                fibertrees are built internally per the schedule).

        Returns:
            The result ``FiberTree`` in the ORIGINAL coordinate space
            (split levels re-merged, padding trimmed).

        The first call with a new input-size signature pays the
        capacity-record + trace cost; repeats hit the plan cache
        (``self.stats`` records hits/misses/retraces). Equivalent to
        calling the engine: ``eng(arrays)``.

        >>> import numpy as np
        >>> from repro.core.schedule import Format, Schedule
        >>> eng = compile_expr("x(i) = B(i,j) * c(j)",
        ...                    Format({"B": "cc", "c": "c"}),
        ...                    Schedule(loop_order=("i", "j")),
        ...                    {"i": 2, "j": 3})
        >>> B = np.array([[1., 0., 2.], [0., 3., 0.]])
        >>> eng.execute({"B": B, "c": np.ones(3)}).to_dense()
        array([3., 3.])
        """
        return self(arrays)

    def _shared_hints(self, raws: Sequence[Dict]) -> Dict[str, List[int]]:
        """Common bucket per compressed level: max over the operand sets,
        so every member pads to ONE input signature."""
        return {name: [
            max(_bucket(r[name]["crds"][i].shape[0]) for r in raws)
            for i in range(len(raws[0][name]["crds"]))]
            for name in raws[0]}

    def _sticky_hints(self, raws: Sequence[Dict]) -> Dict[str, List[int]]:
        """Shared hints merged with the engine's running per-level
        high-water, so the batch input signature is monotone over the
        engine's lifetime: a stream of dispatches with jittering nnz
        settles on ONE signature (and one XLA executable) after warmup
        instead of recompiling per bucket combination."""
        hints = self._shared_hints(raws)
        for name, hs in hints.items():
            prev = self._hint_highwater.get(name)
            if prev is not None:
                hs = [max(a, b) for a, b in zip(hs, prev)]
                hints[name] = hs
            self._hint_highwater[name] = list(hs)
        return hints

    def _dispatch_out(self, flat, sig):
        """One plan-cached execution; returns the raw keyed-COO ``out``."""
        self.stats["calls"] += 1
        if any(n > 1 for n in self.lane_ns):
            self.stats["lane_dispatches"] += 1
            if self._shard_lanes:
                self.stats["sharded_dispatches"] += 1
        plan = self._plans.get(sig)
        if plan is None:
            self.stats["plan_misses"] += 1
            with TraceAnnotation("sam.plan.miss"):
                caps = self._record_caps([flat])
                plan = self._install_plan(sig, caps, batch=False)
        else:
            self.stats["plan_hits"] += 1
        return self._run_plan(plan, sig, flat, batch=False)

    def _dispatch_single(self, flat, sig) -> FiberTree:
        return self._assemble_out(self._dispatch_out(flat, sig))

    def __call__(self, arrays: Dict[str, np.ndarray]) -> FiberTree:
        flat, sig = self._pad_flat(self._raw_flat(arrays))
        return self._dispatch_single(flat, sig)

    def execute_coo(self, arrays: Dict[str, np.ndarray], *, hints=None
                    ) -> Tuple[Optional[np.ndarray], Any]:
        """Execute one operand set, returning the live result as a COO.

        Returns ``(coords, vals)``: ``coords`` is ``(nnz, k)`` int64 in
        the ORIGINAL coordinate space with one column per
        ``orig_result_order`` variable; scalar expressions return
        ``(None, float)``. This is the tile driver's per-tile entry
        (``TiledExpr``) — the partial never round-trips through a
        ``FiberTree``. ``hints`` overrides the per-level input buckets
        (``_shared_hints`` form) so callers dispatching many related
        operand sets — the tile stream — share ONE input signature and
        therefore one plan."""
        flat, sig = self._pad_flat(self._raw_flat(arrays), hints)
        out = self._dispatch_out(flat, sig)
        if "scalar" in out:
            return None, float(out["scalar"])
        return self._live_coords(out)

    def execute_many(self, arrays_list: Sequence[Dict[str, np.ndarray]]
                     ) -> List[FiberTree]:
        """Dispatch several operand sets as INDIVIDUAL calls sharing one
        input signature (buckets maxed over the set, like execute_batch's
        hints). This is the sharded-lane serving path: each call's lanes
        spread over the device mesh — shard_map cannot nest inside the
        batch vmap — while the shared signature keeps warm traffic on a
        single plan instead of re-tracing per request."""
        if not arrays_list:
            return []
        memo: Dict = {}
        raws = [self._raw_flat(a, memo) for a in arrays_list]
        hints = self._sticky_hints(raws)
        out = []
        for raw in raws:
            flat, sig = self._pad_flat(raw, hints)
            out.append(self._dispatch_single(flat, sig))
        return out

    # -- staged batch execution (host encode / device execute / host
    # decode split out so a serving pipeline can overlap the stages of
    # consecutive dispatches; ``core.serving`` is the consumer) ----------
    def encode_batch(self, arrays_list: Sequence[Dict[str, np.ndarray]]
                     ) -> "EncodedBatch":
        """Host-side stage 1 of a batched dispatch: build the concordant
        fibertrees, pad every member to ONE shared input signature, pad
        the batch axis to a power of two, and stack. The result feeds
        ``execute_encoded``; no device compute beyond the array uploads
        happens here."""
        raws, memo = [], {}
        for a in arrays_list:
            with TraceAnnotation("sam.encode.build"):
                raws.append(self._raw_flat(a, memo))
        with TraceAnnotation("sam.encode.pack"):
            hints = self._sticky_hints(raws)
            # largest-nnz member, recorded pre-padding: capacity recording
            # interprets just this one member eagerly (an O(batch) eager
            # sweep would dominate plan installs at serving widths) and
            # the growth loop heals any residual undershoot from the
            # other members
            rep = max(range(len(raws)),
                      key=lambda i: sum(int(e["vals"].shape[0])
                                        for e in raws[i].values()))
            flats_sigs = [self._pad_flat(r, hints) for r in raws]
            flats = [f for f, _ in flats_sigs]
            sig = flats_sigs[0][1]
            b = len(flats)
            b_pad = _bucket_batch(b)
            padded = flats
            if b_pad > b:      # pad the dispatch with empty operand sets
                filler = jax.tree_util.tree_map(np.zeros_like, flats[0])
                padded = flats + [filler] * (b_pad - b)
            # numpy stack: the ONE host->device upload happens at the jit
            # call boundary in execute_encoded, keeping this stage pure
            # host work that pipeline threads can overlap with device
            # execution
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                             *padded)
        return EncodedBatch(stacked=stacked, sig=sig, b=b, b_pad=b_pad,
                            flats=flats, rep=rep)

    def execute_encoded(self, enc: "EncodedBatch"):
        """Device stage 2: one vmapped plan-cached dispatch of an encoded
        batch. Returns the raw keyed-COO ``out`` for ``decode_batch``."""
        self.stats["batch_calls"] += 1
        if any(n > 1 for n in self.lane_ns):
            self.stats["lane_dispatches"] += 1
        plan = self._batch_plans.get((enc.sig, enc.b_pad))
        if plan is None:
            self.stats["plan_misses"] += 1
            with TraceAnnotation("sam.plan.miss"):
                caps = self._record_caps([enc.flats[enc.rep]])
                plan = self._install_plan(enc.sig, caps, batch=True,
                                          b_pad=enc.b_pad)
        else:
            self.stats["plan_hits"] += 1
        return self._run_plan(plan, enc.sig, enc.stacked, batch=True,
                              b_pad=enc.b_pad)

    def batch_plan_text(self, enc: "EncodedBatch") -> str:
        """Compiled program text of the batch plan that ``enc`` dispatches
        to (``execute_encoded`` installs it): shows which kernels the
        served plan runs — a Pallas kernel is a ``tpu_custom_call``."""
        plan = self._batch_plans[(enc.sig, enc.b_pad)]
        return plan.fn.lower(enc.stacked).compile().as_text()

    def decode_batch(self, enc: "EncodedBatch", out) -> List[FiberTree]:
        """Host-side stage 3: assemble one ``FiberTree`` per live batch
        member (batch-axis padding dropped).

        The whole ``out`` tree transfers in ONE ``device_get`` before the
        per-member loop: slicing device arrays member-by-member would pay
        a device op plus a blocking transfer per member, which dominates
        decode at serving batch widths."""
        with TraceAnnotation("sam.decode.fetch"):
            host = jax.device_get(out)
        with TraceAnnotation("sam.decode.assemble"):
            return [self._assemble_out(host, b=i) for i in range(enc.b)]

    def execute_batch(self, arrays_list: Sequence[Dict[str, np.ndarray]]
                      ) -> List[FiberTree]:
        """Execute many same-format operand sets in ONE vmapped dispatch.

        Args:
            arrays_list: operand sets (each as in ``execute``); all must
                share the expression's tensor names and dims. The batch
                pads to a power of two with empty operand sets and every
                member pads to ONE shared input signature.

        Returns:
            One result ``FiberTree`` per operand set, in order.

        >>> import numpy as np
        >>> from repro.core.schedule import Format, Schedule
        >>> eng = compile_expr("x(i) = B(i,j) * c(j)",
        ...                    Format({"B": "cc", "c": "c"}),
        ...                    Schedule(loop_order=("i", "j")),
        ...                    {"i": 2, "j": 3})
        >>> B = np.array([[1., 0., 2.], [0., 3., 0.]])
        >>> outs = eng.execute_batch([{"B": B, "c": np.ones(3)},
        ...                           {"B": 2 * B, "c": np.ones(3)}])
        >>> [o.to_dense().tolist() for o in outs]
        [[3.0, 3.0], [6.0, 6.0]]
        """
        if not arrays_list:
            return []
        enc = self.encode_batch(arrays_list)
        out = self.execute_encoded(enc)
        return self.decode_batch(enc, out)


# ---------------------------------------------------------------------------
# tiled out-of-core execution (DESIGN.md §7, docs/TILING.md)
# ---------------------------------------------------------------------------

class TiledExpr:
    """Out-of-core driver: stream coordinate-space tiles through ONE
    jit-cached per-tile engine, accumulating the partial COOs.

    An expression whose untiled device allocation exceeds the memory
    budget executes as a grid of coordinate tiles (``Schedule.tile``,
    ``{var: n_tiles}``): every tiled variable's coordinate space
    partitions into ``n`` contiguous chunks, and each grid cell runs the
    SAME expression over zero-padded operand slices with the tiled
    extents shrunk to one chunk (``tiling.slice_operands``). Because
    every tile shares the expression, formats, schedule, and (padded)
    extents, all tiles resolve to ONE process-wide ``CompiledExpr`` —
    the first tile pays the capacity-record + trace cost and every
    later tile hits the plan cache. Tile partials merge through
    ``coord_ops.accumulate_coo`` (one ``keyed_union_reduce`` per tile):
    contraction-tiled partials overlap (reduce-merge), result-tiled
    partials are disjoint (concat-merge) — the same primitive serves
    both. Peak device allocation is one tile's working set plus the
    running result COO, never the untiled expression.

    Built by ``compile_expr`` whenever the schedule carries ``tile`` or
    a ``mem_budget`` forces one; quacks like ``CompiledExpr`` for the
    serving paths (``__call__``/``execute``/``execute_batch``/
    ``execute_many``/``stats``).
    """

    def __init__(self, expr, fmt: Format, schedule: Schedule,
                 dims: Dict[str, int], *, use_kernels: bool = True,
                 shard_lanes: Optional[bool] = None,
                 mem_budget: Optional[int] = None,
                 densities: Optional[Dict[str, float]] = None):
        from . import tiling

        self.assign: Assignment = (parse(expr) if isinstance(expr, str)
                                   else expr)
        self.fmt = fmt
        self.schedule = schedule
        self.dims = dict(dims)
        tile = tiling.normalize_tile(schedule)
        tiling.check_tile(self.assign, tile, schedule=schedule)
        for v, n in tile.items():
            if n > dims[v]:
                raise ValueError(f"tile {v}:{n} exceeds its extent "
                                 f"{dims[v]}")
        self.tile_of = tile
        self.n_tiles = tiling.n_tiles(tile)
        self.inner_dims = tiling.tile_extents(self.dims, tile)
        inner = dataclasses.replace(schedule, tile={})
        self.mem_budget = (None if mem_budget is None
                           else tiling.parse_budget(mem_budget))
        self.tile_bytes = tiling.estimate_call_bytes(
            self.assign, fmt, inner, self.inner_dims, densities=densities)
        if self.mem_budget is not None and self.tile_bytes > self.mem_budget:
            raise tiling.MemoryBudgetExceeded(
                f"one tile of tile={tile} still needs "
                f"~{tiling.format_bytes(self.tile_bytes)} > budget "
                f"{tiling.format_bytes(self.mem_budget)}; tile finer",
                estimate=self.tile_bytes, budget=self.mem_budget)
        # ONE engine for every tile: identical expression/format/schedule/
        # extents => identical canonical key => the process-wide cached
        # CompiledExpr, whose plan cache all tiles share
        self.engine = compile_expr(self.assign, fmt, inner, self.inner_dims,
                                   use_kernels=use_kernels,
                                   shard_lanes=shard_lanes)
        # tile-merge stage impl: the Pallas dense-workspace kernel on TPU
        # (same dispatch entry as the engine's lane/term merge)
        self._union_reduce = (kops.sam_primitive("keyed_union_reduce")
                              if use_kernels else None)
        self.rvars = self.engine.orig_result_order   # orig vars, loop order
        self._scalar = not self.rvars
        self._out_strides = [(v, self.dims[v]) for v in self.rvars]
        bound = 1
        for _, d in self._out_strides:
            bound *= d
        # the merge's key space: small bounds take the dense workspace,
        # larger ones a 32-bit-key sort (see coord_ops.keyed_union_reduce)
        self._key_bound = bound
        # running max input-bucket per (tensor, level) across tiles, so
        # EVERY tile pads to one shared signature and hits one plan
        self._hints: Dict[str, List[int]] = {}
        self.stats = {"calls": 0, "tile_calls": 0, "tiles": self.n_tiles,
                      "batch_calls": 0}

    # engine facets the serving paths read ------------------------------
    @property
    def low(self):
        return self.engine.low

    @property
    def par_n(self) -> int:
        return self.engine.par_n

    @property
    def _shard_lanes(self) -> bool:
        return self.engine._shard_lanes

    @property
    def _lane_mesh(self) -> int:
        return self.engine._lane_mesh

    # -- execution -------------------------------------------------------
    def _global_keys(self, coords: np.ndarray,
                     tids: Dict[str, int]) -> np.ndarray:
        """Shift a tile's result coordinates by its offsets and flatten
        into int64 keys over the FULL result extents."""
        keys = np.zeros(len(coords), dtype=np.int64)
        for col, (v, dim) in enumerate(self._out_strides):
            c = coords[:, col]
            if v in self.tile_of:
                c = c + tids[v] * self.inner_dims[v]
            keys = keys * dim + c
        return keys

    def _measure_hints(self, arrays: Dict[str, np.ndarray]) -> None:
        """Grow the shared per-level input buckets to cover every tile of
        this operand set. Host-side only (fibertrees, no device arrays):
        the measuring pass costs one extra walk over the operands but
        keeps all tiles on ONE input signature — the first tile pays the
        trace, the rest hit the plan cache. Deliberately NOT the
        ``execute_many`` shape (build every raw flat once, derive shared
        hints, dispatch) — that would hold every tile's padded device
        arrays simultaneously, which is exactly the allocation the
        memory budget exists to forbid; here at most one tile is on the
        device at a time, and the hints persist across calls."""
        from . import tiling

        for tids in tiling.tile_grid(self.tile_of):
            sliced = tiling.slice_operands(self.assign, arrays, self.dims,
                                           self.tile_of, tids)
            for name, ft in self.engine.low.build_inputs(sliced).items():
                cur = self._hints.setdefault(name, [0] * len(ft.levels))
                for i, lv in enumerate(ft.levels):
                    if lv.format == COMPRESSED:
                        cur[i] = max(cur[i], _bucket(len(lv.crd)))

    def _finalize(self, acc_k: np.ndarray, acc_v: np.ndarray,
                  total: float) -> FiberTree:
        """Assemble the merged tile partials — the accumulated COO, or
        the running scalar ``total`` — into the result ``FiberTree`` in
        the ORIGINAL coordinate space, exactly as the untiled
        ``CompiledExpr`` would return it. Shared with the distributed
        tile driver (``dist_exec.DistTiledExpr``) so both paths produce
        bit-identical results by construction."""
        if self._scalar:
            return FiberTree.from_dense(np.asarray(float(total)), "")
        # coo_to_fibertree also drops zeros (cancelled partial sums)
        lhs = self.assign.lhs
        return coo_to_fibertree(
            acc_k, acc_v, np.ones(len(acc_k), bool), self._out_strides,
            tuple(self.dims[v] for v in self.rvars),
            self.fmt.of(lhs.tensor, len(self.rvars))
            or "c" * len(self.rvars),
            tuple(lhs.vars.index(v) for v in self.rvars))

    def __call__(self, arrays: Dict[str, np.ndarray]) -> FiberTree:
        """Execute one operand set tile by tile; returns the result
        ``FiberTree`` in the ORIGINAL coordinate space, exactly as the
        untiled ``CompiledExpr`` would."""
        from . import tiling

        self.stats["calls"] += 1
        self._measure_hints(arrays)
        total = 0.0
        acc_k = np.zeros(0, np.int64)
        acc_v = np.zeros(0, np.float32)
        for tids in tiling.tile_grid(self.tile_of):
            sliced = tiling.slice_operands(self.assign, arrays, self.dims,
                                           self.tile_of, tids)
            coords, vals = self.engine.execute_coo(sliced,
                                                   hints=self._hints)
            self.stats["tile_calls"] += 1
            if coords is None:                       # scalar partial
                total += vals
                continue
            acc_k, acc_v = co.accumulate_coo(
                acc_k, acc_v, self._global_keys(coords, tids), vals,
                key_bound=self._key_bound,
                union_reduce_impl=self._union_reduce)
        return self._finalize(acc_k, acc_v, total)

    def execute(self, arrays: Dict[str, np.ndarray]) -> FiberTree:
        """Alias of ``__call__`` (API parity with ``CompiledExpr``)."""
        return self(arrays)

    def execute_batch(self, arrays_list: Sequence[Dict[str, np.ndarray]]
                      ) -> List[FiberTree]:
        """Requests execute one after another — under a memory budget the
        tile stream IS the batching axis (each tile still reuses the
        shared per-tile plan, so warm requests never re-trace)."""
        self.stats["batch_calls"] += 1
        return [self(a) for a in arrays_list]

    execute_many = execute_batch


_TILED: Dict[Tuple, TiledExpr] = {}
_BSR: Dict[Tuple, Any] = {}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def compile_expr(expr, fmt: Format, schedule,
                 dims: Dict[str, int], *,
                 use_kernels: bool = True,
                 shard_lanes: Optional[bool] = None,
                 sparsity=None,
                 mem_budget=None,
                 auto_tile: bool = True):
    """Compile an expression once into a jit-cached executable engine.

    Args:
        expr: tensor index notation text or a parsed ``Assignment``.
        fmt: per-tensor level formats.
        schedule: a ``Schedule``, or ``"auto"`` to resolve one through the
            autoscheduler + the persistent on-disk schedule cache (keyed
            by expression + format + dims bucket + sparsity bucket, so a
            shape is searched at most once per cache; DESIGN.md §5).
        dims: extent of every index variable.
        use_kernels: route hot primitives through the ``kernels/``
            dispatch table (Pallas on TPU, the coord_ops fallbacks
            elsewhere).
        shard_lanes: §4.4 lane placement — None auto-shards over a device
            mesh when one fits, False forces a single-device vmap,
            True/int requires a mesh (of at most that many devices).
        sparsity: density hint for ``schedule="auto"`` and the memory
            estimator (float or per-tensor dict; defaults to
            ``autoschedule.DEFAULT_SPARSITY``).
        mem_budget: peak-device-allocation budget in bytes (int or a
            string like ``"64MB"``). A schedule whose untiled estimate
            exceeds it is routed through the out-of-core ``TiledExpr``
            driver (``auto_tile=True``, the default) or refused with
            ``tiling.MemoryBudgetExceeded`` (``auto_tile=False``);
            ``schedule="auto"`` additionally bounds the schedule search
            with the budget (DESIGN.md §7, docs/TILING.md).
        auto_tile: set False to refuse over-budget expressions instead
            of tiling them.

    Returns:
        The process-wide engine for this configuration — a
        ``CompiledExpr``, or a ``TiledExpr`` when the schedule carries
        ``tile`` (explicitly or via the budget). Repeated calls with the
        same (expression, formats, schedule, dims) return the SAME
        engine, so its plans and the underlying jit cache are shared.
        The schedule's split/parallelize/tile spec is part of the
        canonical key: each scheduled variant is its own engine.

    >>> import numpy as np
    >>> from repro.core.schedule import Format, Schedule
    >>> eng = compile_expr("x(i) = B(i,j) * c(j)",
    ...                    Format({"B": "cc", "c": "c"}),
    ...                    Schedule(loop_order=("i", "j")), {"i": 2, "j": 3})
    >>> eng({"B": np.eye(2, 3), "c": np.ones(3)}).to_dense()
    array([1., 1.])

    A tiled schedule streams out-of-core with identical results:

    >>> tiled = compile_expr("x(i) = B(i,j) * c(j)",
    ...                      Format({"B": "cc", "c": "c"}),
    ...                      Schedule(loop_order=("i", "j"),
    ...                               tile={"j": 3}), {"i": 2, "j": 3})
    >>> tiled.n_tiles, tiled({"B": np.eye(2, 3), "c": np.ones(3)}).to_dense()
    (3, array([1., 1.]))
    """
    from . import tiling

    if mem_budget is not None:
        mem_budget = tiling.parse_budget(mem_budget)
    if isinstance(schedule, str):
        if schedule != "auto":
            raise ValueError(
                f"schedule must be a Schedule or 'auto', got {schedule!r}")
        from .autoschedule import resolve_schedule
        # the search must rank under the parallelism this engine will
        # actually run: shard_lanes=False executes serially regardless of
        # the host's device count, an int bounds the mesh
        if shard_lanes is False:
            dev = 1
        elif shard_lanes is None or shard_lanes is True:
            dev = None                       # full host device count
        else:
            dev = int(shard_lanes)
        # auto_tile=False means "refuse rather than tile": keep the
        # budget OUT of the search (a budgeted search returns tiled
        # schedules) so the refusal gate below sees an untiled winner
        kw = ({} if mem_budget is None or not auto_tile
              else {"mem_budget": mem_budget})
        schedule = resolve_schedule(expr, fmt, dims, sparsity=sparsity,
                                    device_count=dev, **kw).schedule
    assign = parse(expr) if isinstance(expr, str) else expr

    # -- block-format (b) BSR routing (core/bsr_bridge.py) ----------------
    # recognized block-sparse contractions execute on the BSR Pallas
    # kernels end-to-end instead of the streaming engine
    from .bsr_bridge import BsrEngine, bsr_pattern
    pat = bsr_pattern(assign, fmt)
    if pat is not None:
        bkey = expr_cache_key(assign, fmt, schedule, dims)
        eng = _BSR.get(bkey)
        if eng is None:
            eng = BsrEngine(assign, fmt, dims, pat)
            _BSR[bkey] = eng
        return eng

    # resolve the lane-mesh size BEFORE keying, so shard_lanes=None and an
    # explicit equivalent request share one engine (and its plan/jit caches)
    par_n = max([n for n in schedule.parallelize.values() if n > 1],
                default=1)
    mesh = _resolve_shard_lanes(shard_lanes, par_n)

    # -- memory-budget gate + tiled routing (DESIGN.md §7) ----------------
    if mem_budget is not None or schedule.tile:
        densities = None
        if sparsity is not None:
            from .autoschedule import resolve_densities
            densities = resolve_densities(assign, sparsity)
        if mem_budget is not None and not schedule.tile:
            if not auto_tile:
                # refuse over-budget untiled requests loudly
                tiling.require_budget(assign, fmt, schedule, dims,
                                      mem_budget, densities=densities)
            else:
                plan = tiling.resolve_plan(assign, fmt, schedule, dims,
                                           mem_budget, densities=densities)
                if plan.tile:
                    schedule = dataclasses.replace(schedule,
                                                   tile=dict(plan.tile))
        if schedule.tile:
            # densities steer the per-tile budget check (and the logged
            # estimates), so they partition the tiled-engine cache
            tkey = (expr_cache_key(assign, fmt, schedule, dims),
                    use_kernels, mesh, mem_budget,
                    tuple(sorted(densities.items())) if densities
                    else None)
            teng = _TILED.get(tkey)
            if teng is None:
                teng = TiledExpr(assign, fmt, schedule, dims,
                                 use_kernels=use_kernels,
                                 shard_lanes=shard_lanes,
                                 mem_budget=mem_budget, densities=densities)
                _TILED[tkey] = teng
            return teng

    key = (expr_cache_key(assign, fmt, schedule, dims), use_kernels, mesh)
    eng = _COMPILED.get(key)
    if eng is None:
        eng = CompiledExpr(assign, fmt, schedule, dims,
                           use_kernels=use_kernels, shard_lanes=shard_lanes)
        _COMPILED[key] = eng
    return eng


def clear_compile_cache() -> None:
    _COMPILED.clear()
    _TILED.clear()
    _BSR.clear()


def execute_graph(graph_: g.Graph, tensors: Dict[str, FiberTree],
                  dims: Dict[str, int], result_vars: List[str]
                  ) -> Dict[str, FiberTree]:
    jt = {k: JTensor.from_fibertree(v) for k, v in tensors.items()}
    return JaxBackend(graph_, jt, dims, list(result_vars)).run()


def execute_expr(expr: str, fmt: Format, schedule: Schedule,
                 arrays: Dict[str, np.ndarray], dims: Dict[str, int],
                 compiled: bool = True) -> FiberTree:
    """Execute an expression via the compiled engine (jit-cached, fused
    multi-term). Falls back to the eager per-term reference path when the
    compiled engine does not support the configuration."""
    if compiled:
        try:
            return compile_expr(expr, fmt, schedule, dims)(arrays)
        except NotImplementedError:
            pass
    # the eager reference path has no static capacities to bound, so a
    # tile spec is moot here: strip it rather than hand Custard a tiled
    # schedule (which it rejects) — results are identical either way
    if schedule.tile:
        schedule = dataclasses.replace(schedule, tile={})
    low = lower(expr, fmt, schedule, dims)
    tensors = low.build_inputs(arrays)
    rvars = low.result_vars
    total: Optional[np.ndarray] = None
    for t in low.require_terms():
        res = execute_graph(t.graph, tensors, low.dims, rvars)
        dense = res[low.assign.lhs.tensor].to_dense()
        total = t.sign * dense if total is None else total + t.sign * dense
    total = low.unsplit(total)
    out_fmt = fmt.of(low.orig_assign.lhs.tensor,
                     len(low.orig_assign.lhs.vars))
    return FiberTree.from_dense(np.asarray(total), out_fmt or "")


# ---------------------------------------------------------------------------
# compiled programs: fused producer→consumer cascades (DESIGN.md §6)
# ---------------------------------------------------------------------------

class _FusedChain:
    """One fused pipeline compiled into ONE jitted callable.

    The stages (program order; the last one is the chain's sink) execute
    back to back inside a single trace: each fused intermediate's keyed
    COO result converts to on-device ``(seg, crd)`` level arrays
    (``coord_ops.coo_to_levels``) that the next stage's level scanners
    read directly — the intermediate never round-trips through a host
    ``FiberTree``. Capacities (scan streams, stage outputs, intermediate
    levels) are recorded eagerly on first call, bucketed, and grown on
    overflow exactly like ``CompiledExpr``.
    """

    def __init__(self, stages, *, segsum=None, intersect=None,
                 coo_levels=None):
        from .einsum import Term as _Term

        self.stages = stages
        self.names = [s.name for s in stages]
        fused = {t for s in stages for t in s.fused_inputs}
        self.graphs = [s.lowered.graph for s in stages]
        self.signs = [s.lowered.terms[0].sign for s in stages]
        self._segsum = segsum
        self._intersect = intersect
        # COO → (seg, crd) splice impl for the fused handoff; falls back to
        # coord_ops when the kernels layer is unavailable
        self._coo_levels = coo_levels or co.coo_to_levels
        # external accesses per stage (everything not spliced), and the
        # sub-assignment used to build their concordant fibertrees
        self._ext: List[Tuple] = []
        for s in stages:
            accs, seen = [], set()
            for t in s.lowered.assign.terms:
                for f in t.factors:
                    if f.tensor not in fused and f.tensor not in seen:
                        accs.append(f)
                        seen.add(f.tensor)
            self._ext.append((tuple(accs),
                              Assignment(lhs=s.lowered.assign.lhs,
                                         terms=(_Term(1, tuple(accs)),))))
        self.inputs = tuple(dict.fromkeys(
            f.tensor for accs, _ in self._ext for f in accs))
        # fused intermediates' level extents (producer storage order)
        self._inter_dims = {
            s.name: [s.lowered.dims[v] for v in s.lowered.result_vars]
            for s in stages if s.fused_output}
        final = stages[-1]
        self._final_rvars = final.lowered.result_vars
        self._scalar = not self._final_rvars
        writer = _val_writer_node(self.graphs[-1])
        self._out_shape = writer.params.get("shape", ())
        self._out_fmt = (writer.params.get("format")
                         or "c" * len(self._final_rvars))
        self._mode_order = writer.params.get("mode_order")
        self._strides = [(v, final.lowered.dims[v])
                         for v in self._final_rvars]
        self._level_meta: Dict[str, List[Tuple[str, int]]] = {}
        self._plans: Dict[Tuple, _Plan] = {}
        self._jit_cache: Dict[Tuple, Callable] = {}
        self.stats = {"traces": 0, "plan_hits": 0, "plan_misses": 0,
                      "overflow_retries": 0, "calls": 0,
                      "caps_passes": 0, "caps_s": 0.0}

    # -- operand flattening ------------------------------------------------
    def _raw_flat(self, env: Dict[str, np.ndarray]) -> Dict[str, Any]:
        from .schedule import build_inputs as _build_inputs

        raw = {}
        for i, stg in enumerate(self.stages):
            accs, sub = self._ext[i]
            fts = _build_inputs(sub, stg.lowered.fmt, stg.lowered.schedule,
                                {a.tensor: env[a.tensor] for a in accs})
            for name, ft in fts.items():
                key = f"s{i}.{name}"
                ft = _engine_tree(ft)
                self._level_meta.setdefault(
                    key, [(lv.format, lv.dim) for lv in ft.levels])
                raw[key] = _raw_flat_of(ft)
        return raw

    def _stage_tensors(self, flat, i: int, inter: Dict[str, JTensor]
                       ) -> Dict[str, JTensor]:
        accs, _ = self._ext[i]
        sub = {f"s{i}.{a.tensor}": flat[f"s{i}.{a.tensor}"] for a in accs}
        tensors = {k.split(".", 1)[1]: v for k, v in
                   _tensors_from_flat_arrays(sub, self._level_meta).items()}
        for t in self.stages[i].fused_inputs:
            tensors[t] = inter[t]
        return tensors

    # -- the COO -> levels splice ------------------------------------------
    def _jt_from_coo(self, coo: COOResult, sign: int, level_caps
                     ) -> Tuple[JTensor, List]:
        dims_list = [d for _, d in coo.strides]
        segs, crds, counts = self._coo_levels(coo.keys, coo.valid,
                                              dims_list, level_caps)
        cap_in = level_caps[-1]
        vals = coo.vals if sign == 1 else sign * coo.vals
        vals = (vals[:cap_in] if vals.shape[0] >= cap_in
                else jnp.pad(vals, (0, cap_in - vals.shape[0])))
        levels = [JLevel(seg, crd, d)
                  for seg, crd, d in zip(segs, crds, dims_list)]
        return JTensor(levels, vals), counts

    # -- capacity recording ------------------------------------------------
    @_capacity_pass
    def _record_caps(self, flat) -> Dict[str, int]:
        caps: Dict[str, int] = {}
        inter: Dict[str, JTensor] = {}
        for i, stg in enumerate(self.stages):
            tensors = self._stage_tensors(flat, i, inter)
            be = JaxBackend(self.graphs[i], tensors, stg.lowered.dims,
                            stg.lowered.result_vars)
            v = be.run_streams()
            for k, n in be.caps_record.items():
                caps[f"s{i}.{k}"] = _bucket_cap(n)
            if not stg.fused_output:
                continue
            keys = np.asarray(v.keys)[np.asarray(v.valid)]
            dims_list = [d for _, d in v.strides]
            cnts: List[int] = []
            p = keys
            for l in range(len(dims_list) - 1, -1, -1):
                cnts.insert(0, len(np.unique(p)))
                p = p // dims_list[l]
            level_caps = [_bucket_cap(c) for c in cnts]
            for l, c in enumerate(cnts):
                caps[f"s{i}.lv{l}"] = level_caps[l]
            inter[stg.name], _ = self._jt_from_coo(v, self.signs[i],
                                                   level_caps)
        return caps

    # -- the jitted cascade -------------------------------------------------
    def _build_core(self, caps: Dict[str, int]) -> Callable:
        scan_caps = [
            {n.id: caps[f"s{i}.s{n.id}"] for n in G.of_kind(g.LEVEL_SCAN)}
            for i, G in enumerate(self.graphs)]
        out_caps = [caps.get(f"s{i}.out") for i in range(len(self.graphs))]
        level_caps = {
            s.name: [caps[f"s{i}.lv{l}"]
                     for l in range(len(self._inter_dims[s.name]))]
            for i, s in enumerate(self.stages) if s.fused_output}

        def core(flat):
            self.stats["traces"] += 1      # runs only while jax traces
            required: Dict[str, jnp.ndarray] = {}
            inter: Dict[str, JTensor] = {}
            v = None
            for i, stg in enumerate(self.stages):
                tensors = self._stage_tensors(flat, i, inter)
                be = JaxBackend(self.graphs[i], tensors, stg.lowered.dims,
                                stg.lowered.result_vars,
                                scan_caps=scan_caps[i], out_cap=out_caps[i],
                                segsum=self._segsum,
                                intersect=self._intersect)
                v = be.run_streams()
                for k, r in be.required.items():
                    required[f"s{i}.{k}"] = r
                if stg.fused_output:
                    jt, counts = self._jt_from_coo(
                        v, self.signs[i], level_caps[stg.name])
                    for l, c in enumerate(counts):
                        required[f"s{i}.lv{l}"] = c
                    inter[stg.name] = jt
            sign = self.signs[-1]
            if self._scalar:
                return {"scalar": sign * v}, required
            vals = v.vals if sign == 1 else sign * v.vals
            return {"keys": v.keys, "vals": vals, "valid": v.valid}, required

        return core

    def _install_plan(self, sig, caps: Dict[str, int]) -> _Plan:
        jit_key = (sig, tuple(sorted(caps.items())),
                   self._segsum is not None)
        fn = self._jit_cache.get(jit_key)
        if fn is None:
            fn = jax.jit(self._build_core(caps))
            self._jit_cache[jit_key] = fn
        plan = _Plan(caps=caps, fn=fn)
        self._plans[sig] = plan
        return plan

    def _run_plan(self, plan: _Plan, sig, flat):
        return _run_with_growth(plan, flat, self.stats,
                                lambda caps: self._install_plan(sig, caps))

    # -- public --------------------------------------------------------------
    def execute(self, env: Dict[str, np.ndarray]) -> FiberTree:
        self.stats["calls"] += 1
        flat, sig = _pad_flat_arrays(self._raw_flat(env), self._level_meta)
        plan = self._plans.get(sig)
        if plan is None:
            self.stats["plan_misses"] += 1
            with TraceAnnotation("sam.plan.miss"):
                plan = self._install_plan(sig, self._record_caps(flat))
        else:
            self.stats["plan_hits"] += 1
        out = self._run_plan(plan, sig, flat)
        if "scalar" in out:
            return FiberTree.from_dense(np.asarray(float(out["scalar"])), "")
        return coo_to_fibertree(out["keys"], out["vals"], out["valid"],
                                self._strides, self._out_shape,
                                self._out_fmt, self._mode_order)


class CompiledProgram:
    """A multi-assignment program compiled into executable units.

    Fused pipelines (``LoweredProgram.components`` with >1 stage) become
    one ``_FusedChain`` — one jitted callable, intermediates living on
    device. Every other stage runs through its own process-wide
    ``CompiledExpr`` (which brings split/parallelize, multi-term and the
    full plan cache along), with dense materialization between units.

    Calling the program returns one ``FiberTree`` per MATERIALIZED stage
    output; fused-away intermediates are never built and do not appear.
    """

    def __init__(self, lp, *, use_kernels: bool = True, mem_budget=None,
                 sparsity=None):
        self.lp = lp
        self.cache_key = _program_key(lp)
        self.mem_budget = mem_budget
        segsum = intersect = coo_levels = None
        if use_kernels:
            segsum = kops.plan_primitive("keyed_segment_sum")
            intersect = kops.plan_primitive("sorted_intersect")
            coo_levels = kops.sam_primitive("coo_to_levels")
        self.units: List[Tuple[str, List[int], Any]] = []
        for comp in lp.components():
            if len(comp) == 1:
                # a memory budget routes over-sized stages through the
                # tiled driver; fused chains keep their own working sets
                # (tiling a stage forbids fusing it — see docs/TILING.md)
                stg = lp.stages[comp[0]]
                eng = compile_expr(stg.assign, lp.fmt, stg.schedule,
                                   stg.dims, use_kernels=use_kernels,
                                   mem_budget=mem_budget,
                                   sparsity=sparsity)
                self.units.append(("expr", comp, eng))
            else:
                chain = _FusedChain([lp.stages[i] for i in comp],
                                    segsum=segsum, intersect=intersect,
                                    coo_levels=coo_levels)
                self.units.append(("chain", comp, chain))
        self.stats = {
            "calls": 0,
            "fused_stages": sum(len(c) for k, c, _ in self.units
                                if k == "chain"),
            "fused_intermediates": len(lp.fused_tensors),
            "materialized_handoffs": len(
                [d for d in lp.decisions if not d.fused]),
        }

    @property
    def decisions(self):
        return self.lp.decisions

    @property
    def inputs(self) -> Tuple[str, ...]:
        return self.lp.program.inputs

    def execute(self, arrays: Dict[str, np.ndarray]) -> Dict[str, FiberTree]:
        """Run the program; returns ``{lhs tensor: FiberTree}`` for every
        stage whose result materializes (fused intermediates excluded)."""
        return self(arrays)

    def __call__(self, arrays: Dict[str, np.ndarray]
                 ) -> Dict[str, FiberTree]:
        self.stats["calls"] += 1
        env = {k: np.asarray(v, dtype=float) for k, v in arrays.items()}
        results: Dict[str, FiberTree] = {}
        for kind, comp, unit in self.units:
            if kind == "expr":
                stg = self.lp.stages[comp[0]]
                ft = unit({t: env[t]
                           for t in stg.lowered.orig_assign.input_tensors})
                name = stg.name
            else:
                ft = unit.execute(env)
                name = unit.names[-1]
            results[name] = ft
            if self.lp.program.consumers(name):
                env[name] = ft.to_dense()   # materialized handoff
        return results


def _program_key(lp) -> str:
    from .program import program_cache_key
    return program_cache_key(lp)


_COMPILED_PROGRAMS: Dict[Tuple, CompiledProgram] = {}


def compile_program(program, fmt: Format, schedules, dims: Dict[str, int],
                    *, use_kernels: bool = True, sparsity=None,
                    fuse: bool = True, mem_budget=None) -> CompiledProgram:
    """Compile a multi-assignment program once; jit-cached per cascade.

    Args:
        program: program text (``;``/newline-separated assignments), a
            ``program.Program``, or a sequence of assignments.
        fmt: per-tensor formats, intermediates included.
        schedules: ``"auto"`` (each stage resolved through the
            autoscheduler + persistent schedule cache), a dict keyed by
            stage lhs tensor, or a sequence aligned with the stages.
        dims: extent of every index variable used by any stage.
        use_kernels: route hot primitives through ``kernels/`` when
            available.
        sparsity: density hint for ``schedules="auto"``.
        fuse: set False to force materialization between all stages (the
            unfused comparison baseline).
        mem_budget: peak-device-allocation budget in bytes (int or
            ``"64MB"``-style string); unfused stages whose untiled
            estimate exceeds it execute through the tiled driver
            (docs/TILING.md). Fused chains are not tiled — pass
            ``fuse=False`` with a budget for a fully tiled program.

    Returns:
        The process-wide ``CompiledProgram`` for this configuration —
        the cache key is the per-stage canonical expression keys PLUS the
        fusion plan (DESIGN.md §6), so a fused and an unfused build of
        the same program are distinct engines.

    >>> import numpy as np
    >>> from repro.core.schedule import Format, Schedule
    >>> cp = compile_program(
    ...     "T(i,k) = B(i,j) * C(j,k); x(i) = T(i,k) * d(k)",
    ...     Format(default="c"),
    ...     {"T": Schedule(loop_order=("i", "j", "k")),
    ...      "x": Schedule(loop_order=("i", "k"))},
    ...     {"i": 2, "j": 2, "k": 2})
    >>> out = cp({"B": np.eye(2), "C": np.eye(2), "d": np.ones(2)})
    >>> sorted(out), out["x"].to_dense().tolist()
    (['x'], [1.0, 1.0])
    """
    from .program import lower_program
    from . import tiling
    if mem_budget is not None:
        mem_budget = tiling.parse_budget(mem_budget)
    lp = lower_program(program, fmt, schedules, dims, sparsity=sparsity,
                       fuse=fuse)
    # with a budget, the sparsity hint steers the per-stage tiling
    # decision, so it joins the key (without one it only feeds "auto"
    # resolution, which is already reflected in the program key);
    # canonicalized so dict order / numpy scalars can't split the cache
    if mem_budget is None or sparsity is None:
        skey = None
    elif isinstance(sparsity, dict):
        skey = tuple(sorted((k, float(v)) for k, v in sparsity.items()))
    else:
        skey = float(sparsity)
    key = (_program_key(lp), use_kernels, mem_budget, skey)
    hit = _COMPILED_PROGRAMS.get(key)
    if hit is None:
        hit = CompiledProgram(lp, use_kernels=use_kernels,
                              mem_budget=mem_budget, sparsity=sparsity)
        _COMPILED_PROGRAMS[key] = hit
    return hit


def clear_program_cache() -> None:
    _COMPILED_PROGRAMS.clear()
