"""Drives the system under test the way ``python -m repro.launch.serve
--sam`` does: the configuration's engine module builds the engine, then
a ``SamServer(max_batch=...)`` serves ``Request``s submitted with
``engine=`` from closed-loop client threads. Each client submits its next
request as soon as its last result has come back, with no think time.

The engine module, ``engines/<config["engine"]>.py``, holds:

* ``build(config)``: the engine, any that ``SamServer`` serves;
* ``arrays(operands)``: ``{name: what a Request carries}`` for drawn
  operands, keyed by their names. It is called once for the shared
  operands, whose arrays then stay the same objects for the whole run,
  and once per request for its fresh ones;
* ``request(config, arrays)``: the ``Request`` to submit;
* ``answer(result)``: a served result as the array that the
  configuration's reference returns.

Set-up builds the engine and warms every batch width the cell's clients
can form, through a ``SamServer(sync=True)`` of each width, so by the
calls the timed path's server makes for the engine's kind, with warm-up
operands that are the same for every seed. ``Instrument`` wraps
those calls in host spans and logs each dispatch. After the window the
server is shut down, and every answer is compared with the float64
reference.
"""
from __future__ import annotations

import dataclasses
import itertools
import shutil
import sys
import tempfile
import threading
import time
import types
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from . import check, operands, spec, trace as tracing
from .compiles import CompileCounter
from .window import Dispatch, DispatchLog, Window

SRC = spec.ROOT / "src"


def program() -> types.SimpleNamespace:
    """The program's entry points (``src/`` of the checkout)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.core import serving
    from repro.core.jax_backend import compile_expr, compile_program
    from repro.core.schedule import Format, Schedule
    return types.SimpleNamespace(
        compile_expr=compile_expr, compile_program=compile_program,
        Format=Format, Schedule=Schedule, Request=serving.Request,
        SamServer=serving.SamServer, engine_kind=serving._engine_kind)


def batch_widths(clients: int, max_batch: int) -> List[int]:
    """The padded batch widths a closed loop of ``clients`` can form:
    the powers of two the engine pads batches of 1..cap to."""
    cap = min(clients, max_batch)
    return sorted({1 << (b - 1).bit_length() for b in range(1, cap + 1)})


def serve_sync(eng, engine, config: Dict, arrays_list: Sequence[Dict]
               ) -> List:
    """One group of requests through a ``SamServer(sync=True)`` as wide
    as the group: the calls the timed path's server makes for the
    engine's kind, run inline on this thread. The served results."""
    p = program()
    srv = p.SamServer(sync=True, max_batch=len(arrays_list))
    try:
        handles = srv.submit_many(
            [engine.request(config, a) for a in arrays_list], engine=eng)
        return [h.result() for h in handles]
    finally:
        srv.shutdown()


def memory_peaks(devices) -> List[Optional[int]]:
    """``peak_bytes_in_use`` of each device, None where the backend
    reports none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def fullest(per_chip: Sequence[Optional[int]]) -> Optional[int]:
    """The largest reading, None where there is none."""
    known = [b for b in per_chip if b is not None]
    return max(known) if known else None


@dataclasses.dataclass
class Served:
    client: int
    k: int
    t_submit: float
    t_result: float
    seq: Optional[int]                 # the dispatch that carried it
    queue_wait_s: Optional[float]
    error: Optional[str]
    result: Any                        # as the server returned it
    fresh: Dict[str, operands.Operand]


class Instrument:
    """Wraps the calls ``SamServer`` makes on one engine instance, for
    the engine's kind, in ``bench.<stage>.<seq>`` host spans, and logs a
    ``Dispatch`` for each:

    * ``batch``: ``encode_batch``, ``execute_encoded`` and
      ``decode_batch``; one ``Dispatch`` per server dispatch, once its
      decode returns, with its live requests and its encode and decode
      spans (decode opens once the device result is ready);
    * ``seq`` and ``many``: ``execute_batch`` or ``execute_many``; one
      ``Dispatch`` per server dispatch, with its width, and no encode or
      decode span (``None``);
    * ``program``: the per-member ``__call__``, on a subclass assigned to
      the instance, so ``isinstance`` still holds. The server calls it
      once per member of a dispatch, so a ``Dispatch`` of one request is
      logged per member call.

    ``seq_of`` maps a request's ``key`` array (by ``id``) to the dispatch
    that carried it."""

    def __init__(self, eng, kind: str, log: DispatchLog, key: str):
        self.eng, self.kind, self.log, self.key = eng, kind, log, key
        self.seq_of: Dict[int, int] = {}
        self._seq = itertools.count()
        self._patched: List[str] = []
        self._cls = type(eng)

    def _begin(self, arrays_list) -> int:
        seq = next(self._seq)
        for a in arrays_list:
            self.seq_of[id(a[self.key])] = seq
        return seq

    @staticmethod
    def _timed(stage: str, seq: int, fn, *args):
        """``fn(*args)`` under its span, and the span's seconds."""
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.{stage}.{seq}"):
            out = fn(*args)
        return out, time.monotonic() - t

    def _done(self, seq: int, n: int, encode_s=None, decode_s=None) -> None:
        self.log.record(Dispatch(seq, time.monotonic(), n, encode_s,
                                 decode_s))

    def _patch(self, name: str, fn) -> None:
        setattr(self.eng, name, fn)
        self._patched.append(name)

    def __enter__(self) -> "Instrument":
        if self.kind == "batch":
            self._wrap_stages()
        elif self.kind == "program":
            self._wrap_call()
        else:
            self._wrap_group("execute_many" if self.kind == "many"
                             else "execute_batch")
        return self

    def _wrap_stages(self) -> None:
        encode, execute, decode = (getattr(self.eng, s) for s in (
            "encode_batch", "execute_encoded", "decode_batch"))

        def encode_batch(arrays_list):
            seq = self._begin(arrays_list)
            enc, enc_s = self._timed("encode", seq, encode, arrays_list)
            enc.bench_seq, enc.bench_encode_s = seq, enc_s
            return enc

        def execute_encoded(enc):
            return self._timed("execute", enc.bench_seq, execute, enc)[0]

        def decode_batch(enc, out):
            jax.block_until_ready(out)
            results, dec_s = self._timed("decode", enc.bench_seq, decode,
                                         enc, out)
            self._done(enc.bench_seq, enc.b, enc.bench_encode_s, dec_s)
            return results

        self._patch("encode_batch", encode_batch)
        self._patch("execute_encoded", execute_encoded)
        self._patch("decode_batch", decode_batch)

    def _wrap_group(self, name: str) -> None:
        call = getattr(self.eng, name)

        def execute(arrays_list):
            seq = self._begin(arrays_list)
            results = self._timed("execute", seq, call, arrays_list)[0]
            self._done(seq, len(arrays_list))
            return results

        self._patch(name, execute)

    def _wrap_call(self) -> None:
        inst, call = self, self._cls.__call__

        def member(eng, arrays):
            seq = inst._begin([arrays])
            result = inst._timed("execute", seq, call, eng, arrays)[0]
            inst._done(seq, 1)
            return result

        self.eng.__class__ = type(self._cls.__name__, (self._cls,),
                                  {"__call__": member})

    def __exit__(self, *exc) -> None:
        for name in self._patched:
            self.eng.__dict__.pop(name, None)
        self.eng.__class__ = self._cls


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
    p = program()
    counter = compile_counter()
    config, traffic = cell.config, cell.traffic
    engine = cell.module("engines", config["engine"])
    fresh_names = [n for n, o in config["operands"].items()
                   if o["share"] == "fresh"]
    if not fresh_names:
        raise ValueError("a configuration needs an operand fresh for "
                         "every request")

    def draw(share: str, seed: int, *stream: int):
        return operands.draw_all(config, share, seed, *stream,
                                 bench=cell.bench)

    t = time.monotonic()
    eng = engine.build(config)
    kind = p.engine_kind(eng)
    shared = draw("shared", seed, operands.SHARED)
    shared_arrays = engine.arrays(shared)
    _say(f"set-up: {kind} engine and shared operands "
         f"{time.monotonic() - t:.1f} s")
    clients, max_batch = int(traffic["clients"]), int(traffic["max_batch"])
    warm = engine.arrays(draw("shared", 0, operands.WARM))
    for w in batch_widths(clients, max_batch):
        t, c, h = time.monotonic(), counter.total(), counter.cache_hits()
        serve_sync(eng, engine, config, [
            {**warm, **engine.arrays(draw("fresh", 0, operands.WARM, w, m))}
            for m in range(w)])
        _say(f"set-up: batch width {w} warmed in "
             f"{time.monotonic() - t:.1f} s, {counter.total() - c} programs "
             f"compiled, {counter.cache_hits() - h} of them from the cache")

    srv = p.SamServer(max_batch=max_batch)
    log = DispatchLog()
    served: List[Served] = []
    cancelled: List[int] = []
    lock, gate = threading.Lock(), threading.Lock()
    stop = threading.Event()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None

    with Instrument(eng, kind, log, fresh_names[0]) as inst:
        def client(j: int) -> None:
            for k in itertools.count():
                fresh = draw("fresh", seed, operands.FRESH, j, k)
                arrays = {**shared_arrays, **engine.arrays(fresh)}
                with gate:      # no submit once the window has closed
                    if stop.is_set():
                        return
                    t0 = time.monotonic()
                    h = srv.submit(engine.request(config, arrays),
                                   engine=eng)
                try:
                    err = h.exception(timeout=stall_s)
                except TimeoutError as e:
                    err = e
                t1 = time.monotonic()
                seq = inst.seq_of.pop(id(arrays[fresh_names[0]]), None)
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
        mem = memory_peaks(jax.devices()[:cell.chips])
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
           hung: int, compiles: int, mem: List[Optional[int]],
           reduced: Optional[Dict], *, setup_s: float,
           shared: Dict[str, operands.Operand]) -> Dict[str, Any]:
    """Everything the metric readers and the result line read. Every
    answer is compared, or where the configuration sets
    ``compare_sample``, that many drawn from the seed. ``mem`` is the
    peak of each of the cell's chips; the fullest chip's is the run's.
    The window's encode and decode seconds are None where its engine
    has no such stage."""
    config = cell.config
    engine = cell.module("engines", config["engine"])
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
        (engine.answer(s.result), ref.reference({**shared, **s.fresh}))
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
            "encode_s": _total(d.encode_s for d in window.dispatches),
            "decode_s": _total(d.decode_s for d in window.dispatches),
            "compiles": compiles,
        },
        "latencies_s": [s.t_result - s.t_submit for s in in_window],
        "queue_waits_s": [s.queue_wait_s for s in in_window
                          if s.queue_wait_s is not None],
        "work": {"flops": float(totals[0]), "bytes": float(totals[1])},
        "memory_peak_bytes": fullest(mem),
        "memory_peak_bytes_per_chip": mem,
        "trace": reduced,
        "compared": answered,
        "shared": shared,
    }


def _total(values) -> Optional[float]:
    values = list(values)
    return None if None in values else sum(values)
