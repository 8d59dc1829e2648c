"""Reduces a profiler trace of the window to what the program's own
spans and named scopes say, beside ``trace.reduce``'s view from outside.

* Host spans: every ``sam.*`` ``TraceAnnotation`` on a host plane. The
  server's stage spans (``sam.encode``/``sam.execute``/``sam.decode``)
  carry a ``dispatch`` stat, numbered in the order the batcher popped
  the dispatch, and an ``n`` stat, its live requests. The benchmark
  numbers its ``bench.*`` spans in the same order, so dispatch ``d`` of
  the server is ``Dispatch.seq`` ``d``; a window whose ``n`` disagrees
  with the dispatch log for any of its dispatches is refused (None),
  never guessed at. The engine's spans carry no id: each belongs to the
  stage span that encloses it on the same thread.
* Device scopes: on the TPU each op's event metadata holds a ``tf_op``
  stat, the ``jax.named_scope`` path it was traced under
  (``sam.<kind>.n<id>``, ``sam.collapse``, ``sam.merge``,
  ``kops.<primitive>.<side>``). ``jax.profiler.ProfileData`` gives an
  event's own stats only, so ``op_paths`` reads the metadata from the
  serialized trace. A ``while`` op has no path; the fusions of its body
  do, and cover it. Time under a scope is the union of its ops'
  intervals, never their sum.
* Idle gaps: named by the innermost ``sam.*`` spans covering at least
  half of each, with the ``tensor`` stat where there is one.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace

SCOPE = re.compile(r"\b((?:sam|kops)\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*)")
STAGES = ("sam.encode", "sam.execute", "sam.decode")
SCAN = ("sam.level_scan.",)
REDUCE = ("sam.reduce.", "sam.collapse", "sam.merge")

Interval = Tuple[float, float]


class Span:
    """One ``sam.*`` host span: name, interval (ns), stats, and the
    thread (plane, line index) it ran on."""

    __slots__ = ("name", "start", "end", "stats", "thread")

    def __init__(self, name, start, end, stats, thread):
        self.name, self.start, self.end = name, start, end
        self.stats, self.thread = stats, thread

    @property
    def label(self) -> str:
        tensor = self.stats.get("tensor")
        return f"{self.name}[{tensor}]" if tensor is not None else self.name

    def within(self, other: "Span") -> bool:
        return (self.thread == other.thread and other.start <= self.start
                and self.end <= other.end)


def program_spans(profile) -> List[Span]:
    out = []
    for plane in profile.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        # line names repeat (every Python thread is a "python3"), so a
        # thread is its line's place in the plane
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("sam."):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats), (plane.name, i)))
    return out


def _xspace_class():
    """The parts of the XSpace proto (``tsl/profiler/protobuf/
    xplane.proto``) that hold event metadata, built from field numbers
    so that reading them needs only the protobuf runtime; other fields
    are skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(parent, name, fields, entry=False):
        m = parent.add(name=name)
        m.options.map_entry = entry
        for number, (field, kind, repeated) in fields.items():
            fd = m.field.add(name=field, number=number, label=(
                F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
            if isinstance(kind, str):
                fd.type, fd.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                fd.type = kind
        return m

    top = proto.message_type
    message(top, "XStat", {1: ("metadata_id", F.TYPE_INT64, False),
                           5: ("str_value", F.TYPE_STRING, False),
                           7: ("ref_value", F.TYPE_UINT64, False)})
    message(top, "XEventMetadata", {2: ("name", F.TYPE_STRING, False),
                                    5: ("stats", "XStat", True)})
    message(top, "XStatMetadata", {2: ("name", F.TYPE_STRING, False)})
    plane = message(top, "XPlane", {
        2: ("name", F.TYPE_STRING, False),
        4: ("event_metadata", "XPlane.EventEntry", True),
        5: ("stat_metadata", "XPlane.StatEntry", True)})
    for entry, value in (("EventEntry", "XEventMetadata"),
                         ("StatEntry", "XStatMetadata")):
        message(plane.nested_type, entry, {1: ("key", F.TYPE_INT64, False),
                                           2: ("value", value, False)},
                entry=True)
    message(top, "XSpace", {1: ("planes", "XPlane", True)})
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_paths(xspace: bytes) -> Dict[str, str]:
    """Each device op's name (as ``ProfileData`` gives it) to the
    ``tf_op`` stat of its event metadata."""
    space = _xspace_class()()
    space.ParseFromString(xspace)
    out = {}
    for plane in space.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for st in meta.stats:
                if names.get(st.metadata_id) == "tf_op":
                    out.setdefault(meta.name, st.str_value
                                   or names.get(st.ref_value, ""))
    return out


def scoped_ops(profile, paths: Dict[str, str]
               ) -> Dict[str, List[Tuple[float, float, Tuple[str, ...]]]]:
    """Per device plane, its ``(start_ns, end_ns, scopes)`` op events,
    the ``sam.*``/``kops.*`` scopes of each op's path outermost first."""
    out = {}
    for plane in profile.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        out[plane.name] = [
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             tuple(SCOPE.findall(paths.get(ev.name, ""))))
            for line in plane.lines if line.name == trace.OPS_LINE
            for ev in line.events]
    return out


def _busy(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in trace.union(intervals, lo, hi))


def _stage_of(spans: List[Span]) -> Dict[int, Span]:
    """Each engine span (by index) to the server stage span that
    encloses it on its thread."""
    stages = [s for s in spans if s.name in STAGES]
    by_thread: Dict[tuple, List[Span]] = defaultdict(list)
    for s in stages:
        by_thread[s.thread].append(s)
    out = {}
    for i, s in enumerate(spans):
        if s.name in STAGES:
            continue
        owner = next((st for st in by_thread[s.thread] if s.within(st)),
                     None)
        if owner is not None:
            out[i] = owner
    return out


def name_gap(gap: Interval, spans: List[Span]) -> str:
    """The innermost span labels whose spans together cover at least
    half of ``gap``; else the label that covers most of it; else
    ``none``."""
    cover: Dict[str, float] = defaultdict(float)
    members: Dict[str, List[Span]] = defaultdict(list)
    for s in spans:
        c = min(gap[1], s.end) - max(gap[0], s.start)
        if c > 0:
            cover[s.label] += c
            members[s.label].append(s)
    if not cover:
        return "none"
    half = [k for k, c in cover.items() if c >= (gap[1] - gap[0]) / 2]
    if not half:
        return max(cover, key=cover.get)

    def encloses(outer: str, inner: str) -> bool:
        return any(i.within(o) for i in members[inner] for o in members[outer])

    return "+".join(sorted(k for k in half if not any(
        o != k and encloses(k, o) for o in half)))


def reduce(xspace: bytes, open_seq: int, close_seq: int,
           dispatch_n: Dict[int, int]) -> Optional[Dict]:
    """The program's view of the window that the ends of dispatches
    ``open_seq`` and ``close_seq`` bound (``trace.dispatch_ends``), from the
    serialized trace ``xspace``; ``dispatch_n`` maps each of the
    window's dispatches to its requests. None when the trace holds no
    ``sam.*`` stage spans for the window's dispatches or their ``n``
    disagrees with ``dispatch_n``."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(xspace)
    ends = trace.dispatch_ends(trace.host_spans(profile))
    ops = scoped_ops(profile, op_paths(xspace))
    if open_seq not in ends or close_seq not in ends or not ops:
        return None
    lo, hi = ends[open_seq], ends[close_seq]
    spans = program_spans(profile)
    stage_n: Dict[int, set] = defaultdict(set)
    stage_s: Dict[int, float] = defaultdict(float)
    for s in spans:
        if s.name in STAGES and "dispatch" in s.stats:
            d = int(s.stats["dispatch"])
            stage_n[d].add(int(s.stats["n"]))
            stage_s[d] += (s.end - s.start) / 1e9
    for seq, n in dispatch_n.items():
        if stage_n.get(seq) != {n}:
            return None
    owner = _stage_of(spans)
    summed: Dict[str, float] = defaultdict(float)
    for i, s in enumerate(spans):
        st = owner.get(i)
        if st is not None and int(st.stats["dispatch"]) in dispatch_n:
            summed[s.label] += (s.end - s.start) / 1e9

    busy_ns, scoped_ns, scan_ns, reduce_ns = [], [], [], []
    per_scope: Dict[str, float] = defaultdict(float)
    idle: List[Interval] = []
    for events in ops.values():
        busy = trace.union([(s, e) for s, e, _ in events], lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        scoped_ns.append(_busy([(s, e) for s, e, sc in events if sc],
                               lo, hi))
        scan_ns.append(_busy([(s, e) for s, e, sc in events
                              if any(x.startswith(SCAN) for x in sc)],
                             lo, hi))
        reduce_ns.append(_busy([(s, e) for s, e, sc in events
                                if any(x.startswith(REDUCE) for x in sc)],
                               lo, hi))
        by_scope: Dict[str, List[Interval]] = defaultdict(list)
        for s, e, sc in events:
            for x in set(sc):
                by_scope[x].append((s, e))
        for x, ivs in by_scope.items():
            per_scope[x] += _busy(ivs, lo, hi) / len(ops)
        idle += trace.gaps(busy, lo, hi)
    chips = len(busy_ns)
    busy_s = sum(busy_ns) / chips / 1e9
    if busy_s <= 0:
        return None
    idle.sort(key=lambda g: g[0] - g[1])
    top = sorted(per_scope.items(), key=lambda kv: -kv[1])[:trace.TOP]
    return {
        "busy_s": busy_s,
        "unscoped_s": busy_s - sum(scoped_ns) / chips / 1e9,
        "scan_s": sum(scan_ns) / chips / 1e9,
        "reduce_s": sum(reduce_ns) / chips / 1e9,
        "spans_s": dict(summed),
        "stage_s": {d: stage_s[d] for d in dispatch_n},
        "device_scopes": [[x, t / 1e9] for x, t in top],
        "idle_gaps_program": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                              for g in idle[:trace.TOP]],
    }
