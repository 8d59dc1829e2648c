"""Drives the system under test the way ``python -m repro.launch.serve
--sam`` does: ``compile_expr`` builds the engine, then a
``SamServer(max_batch=...)`` serves ``Request``s submitted with
``engine=`` from closed-loop client threads. Each client submits its next
request as soon as its last result has come back, with no think time.

Set-up compiles the engine and warms every batch width the cell's
clients can form, with warm-up operands that are the same for every
seed. The benchmark's spans wrap
the engine instance's ``encode_batch``, ``execute_encoded`` and
``decode_batch``; the decode span starts once the device result is
ready, so it holds no device wait. After the window the server is shut
down, and every answer is compared with the float64 reference.
"""
from __future__ import annotations

import dataclasses
import itertools
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from . import check, operands, spec, trace as tracing
from .compiles import CompileCounter
from .window import Dispatch, DispatchLog, Window

SRC = spec.ROOT / "src"


def program():
    """The program's entry points (``src/`` of the checkout)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core.jax_backend import compile_expr
    from repro.core.schedule import Format, Schedule
    from repro.core.serving import Request, SamServer
    return compile_expr, Format, Schedule, Request, SamServer


def batch_widths(clients: int, max_batch: int) -> List[int]:
    """The padded batch widths a closed loop of ``clients`` can form:
    the powers of two the engine pads batches of 1..cap to."""
    cap = min(clients, max_batch)
    return sorted({1 << (b - 1).bit_length() for b in range(1, cap + 1)})


@dataclasses.dataclass
class Served:
    client: int
    k: int
    t_submit: float
    t_result: float
    seq: Optional[int]                 # the dispatch that carried it
    queue_wait_s: Optional[float]
    error: Optional[str]
    result: Any                        # the served FiberTree
    fresh: Dict[str, operands.Operand]


class Instrument:
    """Wraps one engine instance's three stages in host spans and logs
    every completed dispatch; ``seq_of`` maps a request's fresh operand
    (by ``id``) to the dispatch that encoded it."""

    STAGES = ("encode_batch", "execute_encoded", "decode_batch")

    def __init__(self, eng, log: DispatchLog, key: str):
        self.eng, self.log, self.key = eng, log, key
        self.seq_of: Dict[int, int] = {}
        self._seq = itertools.count()
        self._spans: Dict[int, List[float]] = {}

    def _timed(self, stage: str, seq: int, fn, *args):
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.{stage}.{seq}"):
            out = fn(*args)
        self._spans.setdefault(seq, []).append(time.monotonic() - t)
        return out

    def __enter__(self) -> "Instrument":
        encode, execute, decode = (getattr(self.eng, s) for s in self.STAGES)

        def encode_batch(arrays_list):
            seq = next(self._seq)
            for a in arrays_list:
                self.seq_of[id(a[self.key])] = seq
            enc = self._timed("encode", seq, encode, arrays_list)
            enc.bench_seq = seq
            return enc

        def execute_encoded(enc):
            return self._timed("execute", enc.bench_seq, execute, enc)

        def decode_batch(enc, out):
            jax.block_until_ready(out)
            results = self._timed("decode", enc.bench_seq, decode, enc, out)
            enc_s, _, dec_s = self._spans.pop(enc.bench_seq)
            self.log.record(Dispatch(enc.bench_seq, time.monotonic(), enc.b,
                                     enc_s, dec_s))
            return results

        self.eng.encode_batch = encode_batch
        self.eng.execute_encoded = execute_encoded
        self.eng.decode_batch = decode_batch
        return self

    def __exit__(self, *exc) -> None:
        for s in self.STAGES:
            self.eng.__dict__.pop(s, None)


_COUNTER: Optional[CompileCounter] = None


def _say(text: str) -> None:
    print(f"bench: {text}", file=sys.stderr, flush=True)


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def run(cell: spec.Cell, seed: int, seconds: float, *, trace: bool,
        t_process: float, stall_s: float = 300.0) -> Dict[str, Any]:
    """One run of ``cell``: set-up, the window, the comparison. Returns
    the record that the metric readers read."""
    compile_expr, Format, Schedule, Request, SamServer = program()
    counter = compile_counter()
    config, traffic = cell.config, cell.traffic
    fmt = Format(dict(config["formats"]))
    schedule = Schedule(loop_order=tuple(config["order"]),
                        locate=frozenset(tuple(x)
                                         for x in config.get("locate", [])))
    dims = dict(config["dims"])
    expr = config["expr"]
    fresh_names = [n for n, o in config["operands"].items()
                   if o["share"] == "fresh"]
    if not fresh_names:
        raise ValueError("a configuration needs an operand fresh for "
                         "every request")

    t = time.monotonic()
    eng = compile_expr(expr, fmt, schedule, dims)
    shared = operands.draw_all(config, "shared", seed, operands.SHARED)
    shared_dense = {n: o.dense for n, o in shared.items()}
    _say(f"set-up: engine and shared operands {time.monotonic() - t:.1f} s")
    clients, max_batch = int(traffic["clients"]), int(traffic["max_batch"])
    warm = {n: o.dense for n, o in operands.draw_all(
        config, "shared", 0, operands.WARM).items()}
    for w in batch_widths(clients, max_batch):
        t, c, h = time.monotonic(), counter.total(), counter.cache_hits()
        eng.execute_batch([
            {**warm, **{n: o.dense for n, o in operands.draw_all(
                config, "fresh", 0, operands.WARM, w, m).items()}}
            for m in range(w)])
        _say(f"set-up: batch width {w} warmed in "
             f"{time.monotonic() - t:.1f} s, {counter.total() - c} programs "
             f"compiled, {counter.cache_hits() - h} of them from the cache")

    srv = SamServer(max_batch=max_batch)
    log = DispatchLog()
    served: List[Served] = []
    cancelled: List[int] = []
    lock, gate = threading.Lock(), threading.Lock()
    stop = threading.Event()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    with Instrument(eng, log, fresh_names[0]) as inst:
        def client(j: int) -> None:
            for k in itertools.count():
                fresh = operands.draw_all(config, "fresh", seed,
                                          operands.FRESH, j, k)
                arrays = {**shared_dense,
                          **{n: o.dense for n, o in fresh.items()}}
                with gate:      # no submit once the window has closed
                    if stop.is_set():
                        return
                    t0 = time.monotonic()
                    h = srv.submit(Request(expr, arrays, formats=fmt,
                                           dims=dims), engine=eng)
                try:
                    err = h.exception(timeout=stall_s)
                except TimeoutError as e:
                    err = e
                t1 = time.monotonic()
                seq = inst.seq_of.pop(id(arrays[fresh_names[0]]), None)
                for o in fresh.values():
                    o.drop_dense()
                if stop.is_set() and getattr(err, "reason", None) == \
                        "shutdown":
                    cancelled.append(1)
                    return
                with lock:
                    served.append(Served(
                        j, k, t0, t1, seq, h.queue_wait_s,
                        None if err is None else repr(err),
                        None if err is not None else h.result(), fresh))
                if err is not None:
                    return

        if trace:
            jax.profiler.start_trace(trace_dir)
        t_load = time.monotonic()
        threads = [threading.Thread(target=client, args=(j,),
                                    name=f"bench-client-{j}")
                   for j in range(clients)]
        for t in threads:
            t.start()
        try:
            window = log.wait_window(seconds, stall_s)
        finally:
            with gate:
                stop.set()
            if trace:
                jax.profiler.stop_trace()
            # the window has closed: requests still queued are cancelled,
            # those in the pipeline finish (and are compared)
            srv.shutdown(drain=False)
            for t in threads:
                t.join(timeout=stall_s)
        hung = sum(t.is_alive() for t in threads)
        compiles = counter.between(window.t_open, window.t_close)
        device = jax.devices()[0]
        mem = (device.memory_stats() or {}).get("peak_bytes_in_use")
    _say(f"{len(cancelled)} requests still queued at the close cancelled")

    reduced = None
    if trace:
        try:
            reduced = tracing.reduce(
                tracing.load(tracing.trace_file(trace_dir)),
                _open_seq(log, window), max(window.seqs))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    return record(cell, seed, served, window, hung, compiles, mem, reduced,
                  setup_s=t_load - t_process, shared=shared)


def _open_seq(log: DispatchLog, window: Window) -> int:
    """The dispatch whose completion opened the window."""
    return next(d.seq for d in log.done if d.t_done == window.t_open)


def record(cell: spec.Cell, seed: int, served: List[Served], window: Window,
           hung: int, compiles: int, mem: Optional[int],
           reduced: Optional[Dict], *, setup_s: float,
           shared: Dict[str, operands.Operand]) -> Dict[str, Any]:
    """Everything the metric readers and the result line read. Every
    answer is compared, or where the configuration sets
    ``compare_sample``, that many drawn from the seed."""
    config = cell.config
    ref = cell.module("reference", config["reference"])
    work = cell.module("work", config["work"])
    in_window = [s for s in served if s.seq in window.seqs]
    failed = hung + sum(s.error is not None for s in served)
    answered = sorted((s for s in served if s.error is None),
                      key=lambda s: (s.client, s.k))
    sample = config.get("compare_sample")
    if sample and len(answered) > sample:
        pick = operands.rng(seed, operands.SAMPLE).choice(
            len(answered), sample, replace=False)
        answered = [answered[i] for i in sorted(pick)]
    max_err = check.max_error(
        (s.result.to_dense(), ref.reference({**shared, **s.fresh}))
        for s in answered)
    totals = np.zeros(2)
    for s in in_window:
        totals += work.work({**shared, **s.fresh})
    found = check.checks(max_err, failed,
                         float(config["limits"]["max_rel_err"]))
    return {
        "setup_s": setup_s,
        "attempted": len(served) + hung,
        "failed": failed,
        "checks": found,
        "correct": check.passed(found),
        "window": {
            "seconds": window.seconds,
            "requests": window.requests,
            "dispatches": len(window.dispatches),
            "encode_s": sum(d.encode_s for d in window.dispatches),
            "decode_s": sum(d.decode_s for d in window.dispatches),
            "compiles": compiles,
        },
        "latencies_s": [s.t_result - s.t_submit for s in in_window],
        "queue_waits_s": [s.queue_wait_s for s in in_window
                          if s.queue_wait_s is not None],
        "work": {"flops": float(totals[0]), "bytes": float(totals[1])},
        "memory_peak_bytes": mem,
        "trace": reduced,
        "compared": answered,
        "shared": shared,
    }
