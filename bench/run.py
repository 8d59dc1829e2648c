#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, run on this machine's
chips.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up the cell (engine, operands from the seed, every batch width
warmed), drives closed-loop load through ``SamServer`` for a window of
``--seconds`` (opened and closed on dispatch completions), compares
every served answer with the float64 reference, and prints one JSON
line last on stdout: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones, read from a profiler trace of the window), ``device``
(``memory_peak_bytes`` is the peak of the fullest of the cell's chips,
``memory_peak_bytes_per_chip`` each one's),
``breakdown`` (traced runs) and ``checks`` (each number compared, with
its limit; also the last lines on stderr). Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import spec  # noqa: E402


def result_line(cell: spec.Cell, rec, device, trace: bool) -> dict:
    """The last line of stdout, from the run's record. A reader that
    finds nothing to read returns None, and its metric is left out; one
    with a ``note`` says more on stderr."""
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = cell.module("metrics", m["name"])
        value = reader.read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if hasattr(reader, "note"):
                print(f"bench: {m['name']}: {reader.note(rec)}",
                      file=sys.stderr)
    dev = dict(device, memory_peak_bytes=rec["memory_peak_bytes"],
               memory_peak_bytes_per_chip=rec["memory_peak_bytes_per_chip"])
    line = {"correct": rec["correct"], "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": dev}
    if trace and rec["trace"]:
        dev.update(busy_s=rec["trace"]["busy_s"],
                   window_s=rec["trace"]["window_s"])
        line["breakdown"] = {k: rec["trace"][k]
                             for k in ("device_ops", "idle_gaps")}
    line["checks"] = rec["checks"]
    return line


def chips(need: int):
    """The device description, or None (with a message) when this
    machine has no TPU or fewer than ``need`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX platform is {devs[0].platform!r}, not 'tpu'",
              file=sys.stderr)
        return None
    if len(devs) < need:
        print(f"bench: {need} chips needed, {len(devs)} present",
              file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def setup_jax() -> None:
    """The compile cache inside the checkout, with every compile kept
    (the capacity pass's small ones too), so only a cell's first run in
    a checkout compiles."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from benchlib.load import program

    program()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    device = chips(cell.chips)
    if device is None:
        return 1
    peaks = spec.peaks(device["kind"])
    setup_jax()
    from benchlib import load
    from benchlib.check import lines

    rec = load.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                   t_process=T_PROCESS)
    rec["peaks"] = peaks
    line = result_line(cell, rec, device, bool(args.trace))
    print("\n".join(lines(rec["checks"])), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
