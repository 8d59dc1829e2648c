#!/usr/bin/env python3
"""One traced run of a cell, read through the program's own spans,
counters and named scopes.

    python3 bench/trace_program.py --workload <cell> --seed <n> \
        --seconds <s>

Runs the cell exactly as ``run.py --trace 1`` does and reduces the same
profiler trace a second time with ``benchlib/program_trace.py``. The
record gains ``program``: that reduction (``trace``) and the
``ResultHandle.stage_wait_s`` of the window's requests
(``stage_waits_s``), which the readers of ``PROGRAM_METRICS`` read.
Prints one JSON line last on stdout: ``run.py``'s traced result line,
with those metrics added to ``metrics``, ``device_scopes`` and
``idle_gaps_program`` added to ``breakdown``, and ``program``: the
traced window's requests per second, the device-busy share under no
``sam.``/``kops.`` scope, and the window's mean latency beside its mean
queue wait + stage wait + stage busy time. Exits 1 with no result off
the chip, like ``run.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402
from benchlib import spec  # noqa: E402

PROGRAM_METRICS = [
    ("encode.build_ms_per_req", "ms"),
    ("execute.launch_ms_per_req", "ms"),
    ("serving.stage_wait_ms_per_req", "ms"),
    ("plan.scan_ms_per_req", "ms"),
    ("plan.reduce_ms_per_req", "ms"),
]


def instrument():
    """Hooks around the harness, which stays as it is: the server logs
    each dispatch's handles, the trace's bytes and the window's bounding
    dispatches are kept as ``trace`` reads them, and ``load.record``
    adds ``program`` to the record."""
    from benchlib import load, program_trace, trace

    load.program()
    from repro.core import serving

    handles = {}                  # server dispatch -> its handles
    kept = {}

    class LoggedServer(serving.SamServer):
        def _stage_decode(self, group):
            super()._stage_decode(group)
            handles[group.dispatch] = list(group.handles)

    serving.SamServer = LoggedServer
    trace_file, reduce_outside, record = (trace.trace_file, trace.reduce,
                                          load.record)

    def trace_file_kept(directory):
        path = trace_file(directory)
        with open(path, "rb") as f:
            kept["xspace"] = f.read()
        return path

    def reduce_kept(profile, open_seq, close_seq):
        kept["seqs"] = (open_seq, close_seq)
        return reduce_outside(profile, open_seq, close_seq)

    def record_with_program(cell, seed, served, window, *args, **kwargs):
        rec = record(cell, seed, served, window, *args, **kwargs)
        n = {d.seq: d.n for d in window.dispatches}
        hs = [h for seq in n for h in handles.get(seq, [])]
        reduced = (program_trace.reduce(kept["xspace"], *kept["seqs"], n)
                   if "seqs" in kept else None)
        rec["program"] = {
            "trace": reduced,
            "stage_waits_s": [h.stage_wait_s for h in hs],
            "partition": partition(hs, reduced, n, rec["latencies_s"]),
        }
        return rec

    trace.trace_file, trace.reduce, load.record = (
        trace_file_kept, reduce_kept, record_with_program)


def partition(handles, reduced, dispatch_n, latencies_s):
    """The window's mean latency, as the clients saw it and by the
    requests' handles, beside mean queue wait + stage wait + stage busy
    time, the busy time from the ``sam.encode/execute/decode`` spans of
    each request's dispatch."""
    if not handles or not reduced:
        return None
    busy = sum(reduced["stage_s"][d] * n for d, n in dispatch_n.items())
    k = len(handles)
    return {
        "client_latency_ms": sum(latencies_s) / len(latencies_s) * 1e3,
        "latency_ms": sum(h.latency_s for h in handles) / k * 1e3,
        "queue_wait_ms": sum(h.queue_wait_s for h in handles) / k * 1e3,
        "stage_wait_ms": sum(h.stage_wait_s for h in handles) / k * 1e3,
        "stage_busy_ms": busy / k * 1e3,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    device = run.chips(cell.chips)
    if device is None:
        return 1
    peaks = spec.peaks(device["kind"])
    run.setup_jax()
    instrument()
    from benchlib import load
    from benchlib.check import lines

    rec = load.run(cell, args.seed, args.seconds, trace=True,
                   t_process=T_PROCESS)
    rec["peaks"] = peaks
    line = run.result_line(cell, rec, device, True)
    for name, unit in PROGRAM_METRICS:
        value = cell.module("metrics", name).read(rec)
        if value is not None:
            line["metrics"][name] = {"value": value, "unit": unit}
    t = rec["program"]["trace"]
    if t:
        line.setdefault("breakdown", {}).update(
            device_scopes=t["device_scopes"],
            idle_gaps_program=t["idle_gaps_program"])
        unscoped = t["unscoped_s"] / t["busy_s"] * 100
        print(f"bench: {unscoped:.2f}% of device busy time under no "
              f"sam./kops. scope", file=sys.stderr)
    else:
        unscoped = None
    w = rec["window"]
    line["program"] = {"req_per_s": w["requests"] / w["seconds"],
                       "unscoped_pct": unscoped,
                       "partition": rec["program"]["partition"]}
    print("\n".join(lines(rec["checks"])), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
