"""Concurrency/soak suite for the serving subsystem (DESIGN.md §9).

The contracts under test:

- **bit-identical**: every result served through the continuous-batching
  pipeline equals the single-request ``CompiledExpr.execute`` output —
  batching is a dispatch optimization, never a numeric one;
- **coalescing**: a burst of same-key requests costs fewer dispatches
  than requests (engine stats prove the vmapped batch actually formed);
- **admission control**: over-budget requests are refused
  (``admission="reject"``) or routed out-of-core (``"tile"``) BEFORE
  entering a batch; engine-unsupported formats are refused;
- **graceful shutdown** drains the queue; non-draining shutdown fails
  pending requests loudly;
- **reset** (the ``clear_lowering_cache()`` analogue): back-to-back
  serve sessions leak no threads, queues, or stale compiled handles.

Determinism: every test drives the server in ``sync=True`` mode with a
``FakeClock`` or synchronizes on request futures — there are NO
wall-clock sleeps in this file (the tier-1 flake guard for the
threading this subsystem introduces).
"""
import threading

import numpy as np
import pytest

from repro.core.jax_backend import compile_expr
from repro.core.schedule import Format, Schedule
from repro.core.serving import (AdmissionError, FakeClock, Request,
                                ResultHandle, SamServer, active_servers,
                                reset_serving)

MV = "x(i) = B(i,j) * c(j)"
MM = "X(i,j) = B(i,k) * C(k,j)"
N = 8


def _ops_mv(rng, density=0.5):
    B = (rng.random((N, N)) < density) * rng.integers(1, 9, (N, N))
    return {"B": B.astype(np.float32),
            "c": rng.integers(1, 9, N).astype(np.float32)}


def _ops_mm(rng, density=0.5):
    def sp():
        return ((rng.random((N, N)) < density)
                * rng.integers(1, 9, (N, N))).astype(np.float32)
    return {"B": sp(), "C": sp()}


def _mv_engine():
    return compile_expr(MV, Format({"B": "cc", "c": "c"}),
                        Schedule(loop_order=("i", "j")),
                        {"i": N, "j": N})


def _mm_engine():
    return compile_expr(MM, Format({"B": "cc", "C": "cc"}),
                        Schedule(loop_order=("i", "k", "j")),
                        {"i": N, "j": N, "k": N})


# -- sync mode: deterministic batching + stats ------------------------------

def test_sync_coalescing_auto_dispatch_and_fake_clock_stats():
    rng = np.random.default_rng(0)
    clock = FakeClock()
    srv = SamServer(sync=True, max_batch=4, clock=clock)
    sets = [_ops_mv(rng) for _ in range(6)]
    handles = []
    for s in sets:
        clock.advance(0.01)        # requests arrive 10ms apart
        handles.append(srv.submit(Request(MV, s,
                                          formats={"B": "cc", "c": "c"})))
    # 4 of 6 auto-dispatched at max_batch; 2 pending until flush
    assert [h.done() for h in handles] == [True] * 4 + [False] * 2
    clock.advance(0.5)
    srv.flush()
    assert all(h.done() for h in handles)

    eng = _mv_engine()
    for h, s in zip(handles, sets):
        assert np.array_equal(h.result().to_dense(),
                              eng.execute(s).to_dense())

    st = srv.stats()
    assert st["dispatches"] == 2 < st["completed"] == 6   # coalesced
    assert st["batch_occupancy"] == 3.0
    assert st["max_batch_seen"] == 4
    # all timing through the fake clock => exact, repeatable figures:
    # latencies are [30, 20, 10, 0] ms (auto-dispatch at the 4th submit)
    # and [510, 500] ms (the two stragglers flushed after advance(0.5))
    assert st["p99_ms"] == pytest.approx(509.5)
    assert st["p50_ms"] == pytest.approx(25.0)
    srv.shutdown()


def test_latency_split_service_vs_queue_wait():
    # the queue-inclusive p50/p99 from a burst submit conflate waiting
    # with executing; the split fields separate them: queue_wait runs
    # submit -> dispatch-start, service runs dispatch-start -> done, and
    # on the FakeClock (no time passes inside dispatch) service is
    # exactly 0 while queue_wait carries the whole latency
    rng = np.random.default_rng(2)
    clock = FakeClock()
    srv = SamServer(sync=True, max_batch=8, clock=clock)
    h1 = srv.submit(Request(MV, _ops_mv(rng), formats={"B": "cc",
                                                       "c": "c"}))
    clock.advance(0.1)
    h2 = srv.submit(Request(MV, _ops_mv(rng), formats={"B": "cc",
                                                       "c": "c"}))
    clock.advance(0.15)
    srv.flush()                    # dispatch leaves the queue at t=0.25
    assert h1.queue_wait_s == pytest.approx(0.25)
    assert h2.queue_wait_s == pytest.approx(0.15)
    assert h1.service_s == h2.service_s == 0.0
    for h in (h1, h2):             # the split partitions the old figure
        assert h.latency_s == pytest.approx(h.queue_wait_s + h.service_s)
    st = srv.stats()
    assert st["queue_wait_p50_ms"] == pytest.approx(200.0)
    assert st["queue_wait_p99_ms"] == pytest.approx(249.0)
    assert st["service_p50_ms"] == st["service_p99_ms"] == 0.0
    # old keys stay queue-inclusive (trajectory continuity)
    assert st["p50_ms"] == pytest.approx(200.0)
    srv.shutdown()


def test_sync_results_match_execute_batch_and_staged_api():
    rng = np.random.default_rng(1)
    eng = _mm_engine()
    sets = [_ops_mm(rng) for _ in range(4)]
    singles = [eng.execute(s).to_dense() for s in sets]
    batched = [o.to_dense() for o in eng.execute_batch(sets)]
    enc = eng.encode_batch(sets)
    staged = [o.to_dense()
              for o in eng.decode_batch(enc, eng.execute_encoded(enc))]
    srv = SamServer(sync=True, max_batch=4)
    served = [h.result().to_dense()
              for h in srv.submit_many(
                  [Request(MM, s, formats={"B": "cc", "C": "cc"})
                   for s in sets])]
    srv.shutdown()
    for got in (batched, staged, served):
        assert all(np.array_equal(a, b) for a, b in zip(singles, got))


def test_sync_queue_full_rejects_with_reason():
    rng = np.random.default_rng(2)
    srv = SamServer(sync=True, max_batch=64, max_queue=2)
    hs = [srv.submit(Request(MV, _ops_mv(rng),
                             formats={"B": "cc", "c": "c"}))
          for _ in range(3)]
    with pytest.raises(AdmissionError) as ei:
        hs[2].result()
    assert ei.value.reason == "queue-full"
    srv.flush()
    assert hs[0].result() is not None and hs[1].result() is not None
    assert srv.stats()["rejected"] == 1
    srv.shutdown()


# -- threaded mode: soak, coalescing, graceful shutdown ---------------------

def test_threaded_soak_mixed_exprs_bit_identical():
    """N submitter threads × mixed expressions through the async
    pipeline: every result bit-identical to single-request execute, and
    coalescing provably batched (dispatches < requests)."""
    rng = np.random.default_rng(3)
    per_thread, n_threads = 6, 4
    jobs = []           # (kind, operand set) per request, per thread
    for _ in range(n_threads):
        jobs.append([("mv", _ops_mv(rng)) if rng.random() < 0.5
                     else ("mm", _ops_mm(rng))
                     for _ in range(per_thread)])
    srv = SamServer(max_batch=4)
    results: dict = {}
    errors: list = []

    def submit_loop(ti: int):
        try:
            hs = []
            for kind, ops in jobs[ti]:
                req = (Request(MV, ops, formats={"B": "cc", "c": "c"})
                       if kind == "mv"
                       else Request(MM, ops,
                                    formats={"B": "cc", "C": "cc"}))
                hs.append(srv.submit(req))
            results[ti] = [h.result(timeout=600) for h in hs]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submit_loop, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors
    st = srv.stats()
    srv.shutdown()

    mv_eng, mm_eng = _mv_engine(), _mm_engine()
    for ti, job in enumerate(jobs):
        for (kind, ops), got in zip(job, results[ti]):
            eng = mv_eng if kind == "mv" else mm_eng
            assert np.array_equal(got.to_dense(),
                                  eng.execute(ops).to_dense())
    total = per_thread * n_threads
    assert st["completed"] == total
    assert st["dispatches"] < total          # coalescing actually batched
    assert st["batch_occupancy"] > 1.0


def test_threaded_graceful_shutdown_drains_queue():
    rng = np.random.default_rng(4)
    srv = SamServer(max_batch=4)
    sets = [_ops_mv(rng) for _ in range(10)]
    hs = srv.submit_many([Request(MV, s, formats={"B": "cc", "c": "c"})
                          for s in sets])
    srv.shutdown(drain=True)                 # graceful: serves everything
    eng = _mv_engine()
    for h, s in zip(hs, sets):
        assert np.array_equal(h.result().to_dense(),
                              eng.execute(s).to_dense())
    # after shutdown new submissions are refused, not silently dropped
    h = srv.submit(Request(MV, sets[0], formats={"B": "cc", "c": "c"}))
    with pytest.raises(AdmissionError) as ei:
        h.result()
    assert ei.value.reason == "closed"


def test_shutdown_without_drain_fails_pending():
    rng = np.random.default_rng(5)
    srv = SamServer(sync=True, max_batch=64)     # nothing auto-dispatches
    hs = [srv.submit(Request(MV, _ops_mv(rng),
                             formats={"B": "cc", "c": "c"}))
          for _ in range(3)]
    srv.shutdown(drain=False)
    for h in hs:
        with pytest.raises(AdmissionError) as ei:
            h.result()
        assert ei.value.reason == "shutdown"


# -- admission control -------------------------------------------------------

def _budget_case():
    """An expression sized so the untiled estimate exceeds the budget
    (mirrors benchmarks/tiled_oob.py: dense C densification blows up)."""
    from repro.core import tiling
    n = 64
    dims = {"i": n, "j": n, "k": n}
    est = tiling.estimate_call_bytes(
        MM, Format({"B": "cc", "C": "dd"}),
        Schedule(loop_order=("i", "k", "j")), dims,
        densities={"B": 0.05, "C": 1.0})
    rng = np.random.default_rng(6)
    B = ((rng.random((n, n)) < 0.05)
         * rng.integers(1, 9, (n, n))).astype(np.float32)
    C = rng.integers(1, 9, (n, n)).astype(np.float32)
    return dims, est // 3, {"B": B, "C": C}


def test_admission_rejects_over_budget_before_batching():
    dims, budget, ops = _budget_case()
    srv = SamServer(sync=True, max_batch=2, mem_budget=budget,
                    admission="reject")
    h = srv.submit(Request(MM, ops, formats={"B": "cc", "C": "dd"},
                           dims=dims, order="ikj",
                           density=0.05))
    with pytest.raises(AdmissionError) as ei:
        h.result()
    assert ei.value.reason == "over-budget"
    st = srv.stats()
    assert st["rejected"] == 1 and st["dispatches"] == 0
    srv.shutdown()


def test_admission_tiles_over_budget_requests():
    dims, budget, ops = _budget_case()
    srv = SamServer(sync=True, max_batch=2, mem_budget=budget,
                    admission="tile")
    h = srv.submit(Request(MM, ops, formats={"B": "cc", "C": "dd"},
                           dims=dims, order="ikj", density=0.05))
    srv.flush()
    got = h.result().to_dense()
    assert np.array_equal(got, ops["B"] @ ops["C"])   # integer-exact
    st = srv.stats()
    assert st["tiled_requests"] == 1 and st["completed"] == 1
    srv.shutdown()


def test_admission_refuses_engine_unsupported_formats():
    rng = np.random.default_rng(7)
    srv = SamServer(sync=True, max_batch=2)
    h = srv.submit(Request(MV, _ops_mv(rng),
                           formats={"B": "bb", "c": "c"}))
    with pytest.raises(AdmissionError) as ei:
        h.result()
    assert ei.value.reason == "unsupported-format"
    srv.shutdown()


# -- reset: the clear_lowering_cache() analogue -----------------------------

def test_reset_releases_threads_queues_and_engines():
    rng = np.random.default_rng(8)
    baseline_threads = threading.active_count()
    srv = SamServer(max_batch=4)
    sets = [_ops_mv(rng) for _ in range(6)]
    hs = srv.submit_many([Request(MV, s, formats={"B": "cc", "c": "c"})
                          for s in sets])
    for h in hs:
        h.result(timeout=600)
    assert srv.stats()["engines"] >= 1

    srv.reset()
    assert threading.active_count() == baseline_threads   # workers joined
    st = srv.stats()
    assert st["submitted"] == st["completed"] == st["dispatches"] == 0
    assert st["engines"] == 0 and st["queue_depth"] == 0
    assert st["p50_ms"] == st["p99_ms"] == 0.0

    # session 2 on the SAME server: fully functional after reset
    hs2 = srv.submit_many([Request(MV, s, formats={"B": "cc", "c": "c"})
                           for s in sets[:4]])
    eng = _mv_engine()
    for h, s in zip(hs2, sets[:4]):
        assert np.array_equal(h.result(timeout=600).to_dense(),
                              eng.execute(s).to_dense())
    assert srv.stats()["completed"] == 4
    srv.shutdown()
    assert threading.active_count() == baseline_threads


def test_reset_serving_resets_every_live_server():
    rng = np.random.default_rng(9)
    a = SamServer(sync=True, max_batch=2)
    b = SamServer(sync=True, max_batch=2)
    assert a in active_servers() and b in active_servers()
    for srv in (a, b):
        hs = srv.submit_many(
            [Request(MV, _ops_mv(rng), formats={"B": "cc", "c": "c"})
             for _ in range(2)])
        assert all(h.done() for h in hs)
        assert srv.stats()["completed"] == 2
    reset_serving()
    assert a.stats()["completed"] == 0 and b.stats()["completed"] == 0
    a.shutdown(), b.shutdown()


# -- handle semantics --------------------------------------------------------

def test_result_handle_timeout_and_exception_surface():
    h = ResultHandle(FakeClock())
    with pytest.raises(TimeoutError):
        h.result(timeout=0.0)
    err = AdmissionError("nope", reason="test")
    h._fulfill(error=err)
    assert h.exception() is err
    with pytest.raises(AdmissionError):
        h.result()


def test_launcher_fails_the_run_when_a_served_request_failed():
    """The server keeps going past a failed group; the launcher
    (``launch/serve.py``) turns any failed request into an error."""
    from repro.launch.serve import _raise_failed

    rng = np.random.default_rng(7)
    eng = _mv_engine()
    srv = SamServer(sync=True, max_batch=1, clock=FakeClock())
    good = _ops_mv(rng)
    missing_c = {"B": good["B"]}
    handles = srv.submit_many([Request(MV, good), Request(MV, missing_c)],
                              engine=eng)
    srv.drain()
    assert srv.stats()["failed"] == 1 and srv.stats()["completed"] == 1
    _raise_failed(handles[:1])                       # all served: no error
    with pytest.raises(RuntimeError, match="1 of 2 served requests failed"):
        _raise_failed(handles)


def test_concurrent_first_submits_start_one_pipeline():
    """Submitters race to start the lazy pipeline; exactly one set of
    stage threads may start (two sets over one set of queues deadlock)."""
    import sys

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            srv = SamServer(max_batch=2)
            start = threading.Barrier(16)

            def race():
                start.wait(timeout=60)
                srv._ensure_threads()

            racers = [threading.Thread(target=race) for _ in range(16)]
            for t in racers:
                t.start()
            for t in racers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in racers)
            assert len(srv._threads) == 4
            srv.shutdown()
            assert not srv._threads
    finally:
        sys.setswitchinterval(old)
