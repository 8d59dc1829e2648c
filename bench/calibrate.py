#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> [--workload <cell> ...] \
        --seeds 1,2,3 --seconds <s> [--out <file.jsonl>]

In one process (set-up is paid once per configuration), for each cell
and seed: a run at the cell's own load for ``--seconds``, whose largest
error over the answers it compares is the program's reading; then the
control, the configuration's reference computed in bfloat16 over the
same requests' operands, read against the float64 reference. One JSON
line per run, then per configuration the largest program reading (the
lower) and the smallest control reading (the upper). The benchmark's
own runs never run this.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from benchlib import check, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cells = [spec.cell(w) for w in args.workload]
    if run.chips(max(c.chips for c in cells)) is None:
        return 1
    run.setup_jax()
    from benchlib import load

    out = open(args.out, "a") if args.out else None
    readings = {}
    for cell in cells:
        control = cell.module("reference", cell.config["reference"])
        for seed in (int(s) for s in args.seeds.split(",")):
            rec = load.run(cell, seed, args.seconds, trace=False,
                           t_process=time.monotonic())
            ctl = check.max_error(
                (control.control({**rec["shared"], **s.fresh}),
                 control.reference({**rec["shared"], **s.fresh}))
                for s in rec["compared"])
            line = {"cell": cell.name, "seed": seed,
                    "correct": rec["correct"],
                    "program": rec["checks"]["max_rel_err"]["value"],
                    "control": ctl, "compared": len(rec["compared"]),
                    "attempted": rec["attempted"],
                    "failed": rec["failed"],
                    "req_per_s": rec["window"]["requests"]
                    / rec["window"]["seconds"]}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
            r = readings.setdefault(cell.config["name"],
                                    {"lower": 0.0, "upper": float("inf")})
            r["lower"] = max(r["lower"], line["program"])
            r["upper"] = min(r["upper"], ctl)
    print(json.dumps({"readings": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
