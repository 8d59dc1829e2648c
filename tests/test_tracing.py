"""The program's own tracing: ``sam.*`` host spans (``TraceAnnotation``),
named scopes inside the compiled plan (``sam.<kind>.n<id>``,
``sam.collapse``, ``sam.merge``, ``kops.<primitive>.<side>``), the
capacity-pass counters, and the serving layer's stage wait.
"""
import glob
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import jax_backend
from repro.core.jax_backend import compile_expr
from repro.core.schedule import Format, Schedule
from repro.core.serving import AdmissionError, FakeClock, Request, SamServer

MV = "x(i) = B(i,j) * c(j)"
MM = "X(i,j) = B(i,k) * C(k,j)"
N = 8


def _mv():
    return compile_expr(MV, Format({"B": "cc", "c": "c"}),
                        Schedule(loop_order=("i", "j")), {"i": N, "j": N})


def _mm():
    return compile_expr(MM, Format({"B": "cc", "C": "cc"}),
                        Schedule(loop_order=("i", "k", "j")),
                        {"i": N, "j": N, "k": N})


def _mv_ops(rng):
    B = (rng.random((N, N)) < 0.5) * rng.integers(1, 9, (N, N))
    return {"B": B.astype(np.float32),
            "c": rng.integers(1, 9, N).astype(np.float32)}


def _host_spans(path):
    """``(name, start, end, stats, thread)`` of every ``sam.*`` span."""
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sam."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, line.name)))
    return out


def test_server_stage_spans_number_dispatches_and_nest_engine_spans(
        tmp_path):
    eng = _mv()
    rng = np.random.default_rng(0)
    with SamServer(max_batch=2, sync=True) as srv:
        eng.execute_batch([_mv_ops(rng)] * 2)      # warm: no compile traced
        jax.profiler.start_trace(str(tmp_path))
        try:
            for n in (2, 1):
                hs = [srv.submit(Request(MV, _mv_ops(rng)), engine=eng)
                      for _ in range(n)]
                srv.flush()
                assert all(h.exception() is None for h in hs)
        finally:
            jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    stages = [s for s in spans if s[0] in ("sam.encode", "sam.execute",
                                           "sam.decode")]
    got = sorted((s[3]["dispatch"], s[0], s[3]["n"]) for s in stages)
    assert got == [(0, "sam.decode", 2), (0, "sam.encode", 2),
                   (0, "sam.execute", 2), (1, "sam.decode", 1),
                   (1, "sam.encode", 1), (1, "sam.execute", 1)]

    def owner(span):
        return [st for st in stages if st[4] == span[4]
                and st[1] <= span[1] and span[2] <= st[2]]

    inner = {"sam.encode.build": "sam.encode", "sam.encode.tree":
             "sam.encode", "sam.encode.pack": "sam.encode",
             "sam.execute.launch": "sam.execute",
             "sam.execute.sync": "sam.execute",
             "sam.decode.fetch": "sam.decode",
             "sam.decode.assemble": "sam.decode"}
    for name, stage in inner.items():
        found = [s for s in spans if s[0] == name]
        assert found, name
        for s in found:
            (st,) = owner(s)
            assert st[0] == stage, (name, st[0])
    trees = [s for s in spans if s[0] == "sam.encode.tree"]
    assert sorted({s[3]["tensor"] for s in trees}) == ["B", "c"]
    # one build per member, one tree per tensor per member
    assert len([s for s in spans if s[0] == "sam.encode.build"]) == 3
    assert len(trees) == 6


class _SleepyEngine:
    """A batch engine whose three stages sleep, so dispatches queue
    between stages in the threaded pipeline; ``ran[x]`` sums the seconds
    the stages of the dispatch carrying request ``x`` ran."""

    def __init__(self, seconds):
        self.s = seconds
        self.ran = {}

    def _sleep(self, arrays):
        t = time.monotonic()
        time.sleep(self.s)
        for a in arrays:
            self.ran[a["x"]] = self.ran.get(a["x"], 0.0) \
                + time.monotonic() - t

    def encode_batch(self, arrays):
        self._sleep(arrays)
        return list(arrays)

    def execute_encoded(self, enc):
        self._sleep(enc)
        return enc

    def decode_batch(self, enc, out):
        self._sleep(out)
        return [a["x"] for a in out]


def test_stage_wait_partitions_latency_threaded():
    eng = _SleepyEngine(0.03)
    srv = SamServer(max_batch=1, pipeline_depth=1)
    try:
        hs = srv.submit_many([Request(MV, {"x": i}) for i in range(6)],
                             engine=eng)
        assert [h.result(timeout=30) for h in hs] == list(range(6))
    finally:
        srv.shutdown()
    for x, h in enumerate(hs):
        assert h.latency_s == pytest.approx(h.queue_wait_s + h.service_s)
        # what is neither queue wait nor stage wait is the time the
        # dispatch's three stages ran
        busy = h.latency_s - h.queue_wait_s - h.stage_wait_s
        assert busy == pytest.approx(eng.ran[x], abs=0.01)
        assert h.stage_wait_s >= 0
    # later dispatches were popped while encode was busy, and waited
    assert max(h.stage_wait_s for h in hs) > 0.02
    st = srv.stats()
    assert st["stage_wait_p99_ms"] >= st["stage_wait_p50_ms"] > 0


def test_stage_wait_is_zero_in_sync_mode():
    clock = FakeClock()
    srv = SamServer(max_batch=2, sync=True, clock=clock)
    hs = [srv.submit(Request(MV, {"x": i}), engine=_SleepyEngine(0))
          for i in range(3)]
    srv.flush()
    assert [h.result() for h in hs] == [0, 1, 2]
    assert [h.stage_wait_s for h in hs] == [0.0, 0.0, 0.0]
    assert srv.stats()["stage_wait_p50_ms"] == 0.0


def test_submit_to_closing_server_is_refused_and_starts_nothing():
    srv = SamServer(max_batch=2)
    h = srv.submit(Request(MV, {"x": 0}), engine=_SleepyEngine(0))
    assert h.result(timeout=30) == 0
    srv.shutdown()
    before = {t.name for t in threading.enumerate()}
    late = srv.submit(Request(MV, {"x": 1}), engine=_SleepyEngine(0))
    err = late.exception(timeout=5)
    assert isinstance(err, AdmissionError) and err.reason == "closed"
    assert srv._threads == [] and srv._stage_qs == []
    started = {t.name for t in threading.enumerate()} - before
    assert not any(n.startswith("sam-serve") for n in started)


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("make,operands", [
    (_mv, lambda rng: _mv_ops(rng)),
    (_mm, lambda rng: {
        "B": ((rng.random((N, N)) < 0.4) * 3.0).astype(np.float32),
        "C": ((rng.random((N, N)) < 0.4) * 2.0).astype(np.float32)}),
], ids=["spmv", "spmm"])
def test_compiled_plan_ops_carry_node_and_kernel_scopes(make, operands):
    eng = make()
    enc = eng.encode_batch([operands(np.random.default_rng(1))])
    eng.execute_encoded(enc)
    names = _op_names(eng.batch_plan_text(enc))
    assert any(re.search(r"sam\.level_scan\.n\d+", n) for n in names)
    assert any(re.search(r"kops\.[a-z_]+\.fallback", n) for n in names)
    assert any("sam.collapse" in n for n in names)


def test_capacity_pass_runs_unscoped_and_is_counted(monkeypatch):
    eng = _mm()
    flat, _ = eng._pad_flat(eng._raw_flat(
        {"B": np.eye(N, dtype=np.float32), "C": np.eye(N, dtype=np.float32)}))
    scopes = []
    real = jax.named_scope

    def spy(name):
        scopes.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", spy)
    passes, secs = eng.stats["caps_passes"], eng.stats["caps_s"]
    caps = eng._record_caps([flat])
    assert caps and scopes == []
    assert eng.stats["caps_passes"] == passes + 1
    assert eng.stats["caps_s"] > secs
    # the same backend in static mode does name its nodes
    G = eng.graphs[0]
    be = jax_backend.JaxBackend(
        G, eng._tensors_from_flat(flat), eng.low.dims, eng.rvars,
        scan_caps={n.id: caps[f"t0.s{n.id}"]
                   for n in G.of_kind("level_scan")},
        out_cap=caps.get("t0.out"))
    be.run_streams()
    assert any(s.startswith("sam.level_scan.n") for s in scopes)
    assert "sam.collapse" in scopes
