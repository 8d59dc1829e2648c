"""Reduces a profiler trace of the window to the device's busy time and
the breakdown: what ran on the device, and what the host was doing in
its longest idle gaps.

* Device busy: the union of the event intervals on the ``XLA Ops`` line
  of every ``/device:TPU:<n>`` plane, clipped to the window and averaged
  over the chips.
* The window: from the end of the opening dispatch's last ``bench.*``
  span to the end of the closing one's: its decode span where it has
  one, else its execute span. The spans are ``bench.<stage>.<seq>``
  annotations that ``load.Instrument`` puts around the engine calls the
  server makes (``encode_batch``, ``execute_encoded`` and
  ``decode_batch``, or the one call of another engine kind); host planes
  are read for these spans only.
* Each idle gap is named by the stages whose spans cover at least half
  of it, or else by the one that covers most of it, or ``none``.

A trace with no device plane or no window spans reduces to None.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN = re.compile(r"^bench\.([a-z]+)\.(\d+)$")
TOP = 10

Interval = Tuple[float, float]


def trace_file(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {directory}")
    return found[0]


def host_spans(profile) -> List[Tuple[str, int, float, float]]:
    """``(stage, seq, start_ns, end_ns)`` of every ``bench.*`` span."""
    out = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                m = SPAN.match(ev.name)
                if m:
                    out.append((m.group(1), int(m.group(2)),
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def dispatch_ends(spans) -> Dict[int, float]:
    """Each dispatch's end: the end of its last ``bench.*`` span."""
    ends: Dict[int, float] = {}
    for _, seq, _, e in spans:
        ends[seq] = max(e, ends.get(seq, e))
    return ends


def op_name(event_name: str) -> str:
    """An op event's HLO instruction name: ``%fusion.7 = f32[..] ...``
    reads ``fusion.7``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(profile) -> Dict[str, List[Tuple[float, float, str]]]:
    """Per device plane, its ``(start_ns, end_ns, op name)`` events."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        out[plane.name] = [
            (ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
            for line in plane.lines if line.name == OPS_LINE
            for ev in line.events]
    return out


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: Interval, spans: Dict[str, List[Interval]]) -> str:
    length = gap[1] - gap[0]
    cover = {stage: sum(_overlap(gap, iv) for iv in ivs)
             for stage, ivs in spans.items()}
    cover = {s: c for s, c in cover.items() if c > 0}
    if not cover:
        return "none"
    half = sorted((s for s, c in cover.items() if c >= length / 2),
                  key=lambda s: -cover[s])
    return "+".join(half) if half else max(cover, key=cover.get)


def reduce(profile, open_seq: int, close_seq: int) -> Optional[Dict]:
    """Busy and window seconds and the breakdown of the window that the
    ends of dispatches ``open_seq`` and ``close_seq`` bound."""
    spans = host_spans(profile)
    ends = dispatch_ends(spans)
    ops = device_ops(profile)
    if open_seq not in ends or close_seq not in ends or not ops:
        return None
    lo, hi = ends[open_seq], ends[close_seq]
    by_stage: Dict[str, List[Interval]] = defaultdict(list)
    for stage, _, s, e in spans:
        by_stage[stage].append((s, e))
    busy_ns, per_op, idle = [], defaultdict(float), []
    for plane, events in ops.items():
        busy = union([(s, e) for s, e, _ in events], lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, name in events:
            if min(e, hi) > max(s, lo):
                per_op[name] += (min(e, hi) - max(s, lo)) / len(ops)
        idle += [(g[1] - g[0], name_gap(g, by_stage))
                 for g in gaps(busy, lo, hi)]
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    if busy_s <= 0:
        return None
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle.sort(key=lambda x: -x[0])
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, s / 1e9] for n, s in top_ops],
        "idle_gaps": [[n, g / 1e9] for g, n in idle[:TOP]],
    }


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
