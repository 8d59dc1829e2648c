#!/usr/bin/env python3
"""Smoke run of the sparse-expression serving path on one TPU chip.

Drives the engine through its user entry points — ``compile_expr`` for
the plan and ``SamServer`` for the traffic, as ``python -m
repro.launch.serve --sam`` uses them — at the scale of the largest
matrices of the paper's Table 3 (``benchmarks/fig14.MATRICES``), and
checks every served result against a float64 numpy reference:

* device: the platform is ``tpu`` and the SAM primitives resolve to
  their Pallas entries;
* SpMV ``x(i) = B(i,j) * c(j)``, B in rail507's shape: 16 requests in
  batches of 8, sharing one B, each with its own c. The dense c is
  located, not co-iterated (iterate-locate, paper §4.1): co-iterating it
  expands c under every row of B, and the batch-8 plan then needs more
  HBM than one v5e holds. The served plan must hold a Pallas kernel
  (``tpu_custom_call``);
* SpMM ``X(i,j) = B(i,k) * C(k,j)``, B and C in G42's shape: 16
  requests in batches of 8, sharing one B, each with its own C. The 4M
  output key space is past the dense-workspace guard, so this phase
  runs the sort-merge path.

``--four-chips`` runs only the two multi-device paths, each beside the
one-device result it must reproduce: lane sharding (the SpMM with
``split={"k": 4}`` over a 4-device lane mesh) and distributed tiles
(the SpMM under a memory budget, as ``DistTiledExpr`` with 4 workers).

Operands are random, made from ``--seed``. The times printed are those
of a smoke run, not measurements. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; a failed request, a wrong
result or a missing kernel exits non-zero without printing it.

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter
from typing import Callable, Dict, List, Sequence

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.fig14 import MATRICES  # noqa: E402
from repro.core import tiling  # noqa: E402
from repro.core.dist_exec import DistTiledExpr  # noqa: E402
from repro.core.einsum import parse  # noqa: E402
from repro.core.jax_backend import TiledExpr, compile_expr  # noqa: E402
from repro.core.schedule import Format, Schedule  # noqa: E402
from repro.core.serving import Request, SamServer  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

TABLE3 = {name: (shape, nnz) for name, shape, nnz in MATRICES}
# max abs error against the float64 reference, relative to max |ref|:
# f32 accumulation sits far below it, a bf16 pass anywhere far above
REL_TOL = 1e-4
# primitives that must resolve to their Pallas ("tpu") entry on the chip
KERNEL_PRIMITIVES = ("keyed_segment_sum", "keyed_union_reduce",
                     "mul_reduce", "coo_to_levels")
SPMV = "x(i) = B(i,j) * c(j)"
# B's j coordinates probe the dense c directly (see the module docstring)
SPMV_SCHEDULE = Schedule(loop_order=("i", "j"),
                         locate=frozenset({("c", "j")}))
SPMM = "X(i,j) = B(i,k) * C(k,j)"
SPMM_FMT = Format({"B": "cc", "C": "cc"})
SPMM_ORDER = ("i", "k", "j")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = Counter()


def _count_compiles(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compiles["xla"] += 1


def _listen_for_compiles() -> None:
    if not _compiles["listening"]:
        jax.monitoring.register_event_duration_secs_listener(
            _count_compiles)
        _compiles["listening"] = 1


class SmokeError(RuntimeError):
    """A phase could not run to its end."""


# -- operands and references ------------------------------------------------
def random_sparse(rng: np.random.Generator, shape, nnz: int):
    """A dense float32 matrix with ``nnz`` nonzeros at distinct random
    positions, and its COO triplets ``(rows, cols, vals)``."""
    n_rows, n_cols = shape
    flat = rng.choice(n_rows * n_cols, size=nnz, replace=False)
    vals = rng.uniform(-1.0, 1.0, nnz).astype(np.float32)
    vals[vals == 0] = 1.0
    dense = np.zeros(shape, np.float32)
    dense.reshape(-1)[flat] = vals
    rows, cols = np.divmod(flat, n_cols)
    return dense, (rows, cols, vals)


def rel_error(got, ref: np.ndarray) -> float:
    """max |got - ref| / max |ref| (inf on a shape mismatch or NaN)."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    err = float(np.max(np.abs(got - ref), initial=0.0))
    scale = float(np.max(np.abs(ref), initial=0.0))
    err = err / scale if scale > 0 else err
    return float("inf") if np.isnan(err) else err


def grade(handles, reference: Callable[[int], np.ndarray],
          tol: float = REL_TOL) -> Dict:
    """Check every served request against ``reference(i)``: requests that
    raised count as failed, results off by more than ``tol`` as wrong."""
    completed = failed = wrong = 0
    worst = 0.0
    for i, h in enumerate(handles):
        if h.exception() is not None:
            failed += 1
            continue
        completed += 1
        err = rel_error(h.result().to_dense(), reference(i))
        worst = max(worst, err)
        if not err <= tol:
            wrong += 1
    return {"completed": completed, "failed": failed, "wrong": wrong,
            "max_rel_err": worst}


def _impls_since(before: Counter) -> List[str]:
    """``primitive=implementation`` for every Pallas-table entry traced
    since ``before`` (a copy of ``kops.TRACED``)."""
    return sorted(f"{name}={impl}"
                  for (name, impl), n in kops.TRACED.items()
                  if n > before.get((name, impl), 0))


# -- serving ------------------------------------------------------------------
def serve(eng, expr: str, fmt: Format, dims: Dict[str, int],
          arrays: Sequence[Dict[str, np.ndarray]],
          reference: Callable[[int], np.ndarray], max_batch: int) -> Dict:
    """Serve ``arrays`` through one ``SamServer`` on the precompiled
    engine, one burst of ``max_batch`` requests at a time, so the first
    dispatch (compile included) and the warm ones are timed apart."""
    _listen_for_compiles()
    srv = SamServer(max_batch=max_batch)
    handles, secs, compiles = [], [], []
    try:
        for start in range(0, len(arrays), max_batch):
            c0, t0 = _compiles["xla"], time.perf_counter()
            burst = srv.submit_many(
                [Request(expr, a, formats=fmt, dims=dims)
                 for a in arrays[start:start + max_batch]], engine=eng)
            srv.drain(timeout=900)
            secs.append(time.perf_counter() - t0)
            compiles.append(_compiles["xla"] - c0)
            handles += burst
        dispatches = srv.stats()["dispatches"]
    finally:
        srv.shutdown()
    report = grade(handles, reference)
    report.update(
        requests=len(arrays), dispatches=dispatches,
        first_s=secs[0], compiles_first=compiles[0],
        warm_s=(sum(secs[1:]) / len(secs[1:])) if secs[1:] else None,
        compiles_warm=sum(compiles[1:]))
    return report


def spmv_phase(rng, shape=TABLE3["rail507"][0], nnz=TABLE3["rail507"][1],
               n_requests: int = 16, max_batch: int = 8) -> Dict:
    """SpMV on a rail507-shaped B (one shared array) with a c per
    request; ``kernel`` says whether the served plan holds a Pallas
    kernel."""
    traced = Counter(kops.TRACED)
    B, (rows, cols, vals) = random_sparse(rng, shape, nnz)
    fmt = Format({"B": "cc", "c": "d"})
    dims = {"i": shape[0], "j": shape[1]}
    eng = compile_expr(SPMV, fmt, SPMV_SCHEDULE, dims)
    cs = [rng.uniform(-1.0, 1.0, shape[1]).astype(np.float32)
          for _ in range(n_requests)]
    arrays = [{"B": B, "c": c} for c in cs]
    vals64 = vals.astype(np.float64)

    def reference(i):
        return np.bincount(rows, weights=vals64 * cs[i][cols],
                           minlength=shape[0])

    report = serve(eng, SPMV, fmt, dims, arrays, reference, max_batch)
    enc = eng.encode_batch(arrays[:max_batch])
    report["kernel"] = "tpu_custom_call" in eng.batch_plan_text(enc)
    report["impls"] = _impls_since(traced)
    return report


def spmm_operands(rng, shape, nnz: int, n: int):
    """One shared B and ``n`` Cs, all G42-shaped by default."""
    B, _ = random_sparse(rng, shape, nnz)
    Cs = [random_sparse(rng, shape, nnz)[0] for _ in range(n)]
    return B, Cs


def spmm_dims(shape) -> Dict[str, int]:
    return {"i": shape[0], "k": shape[1], "j": shape[1]}


def spmm_phase(rng, shape=TABLE3["G42"][0], nnz=TABLE3["G42"][1],
               n_requests: int = 16, max_batch: int = 8) -> Dict:
    """SpMM on G42-shaped operands: one shared B, a C per request."""
    traced = Counter(kops.TRACED)
    B, Cs = spmm_operands(rng, shape, nnz, n_requests)
    dims = spmm_dims(shape)
    eng = compile_expr(SPMM, SPMM_FMT, Schedule(loop_order=SPMM_ORDER),
                       dims)
    B64 = B.astype(np.float64)
    report = serve(eng, SPMM, SPMM_FMT, dims,
                   [{"B": B, "C": C} for C in Cs],
                   lambda i: B64 @ Cs[i].astype(np.float64), max_batch)
    report["impls"] = _impls_since(traced)
    return report


# -- the multi-device paths ---------------------------------------------------
def lane_sharding_phase(rng, shape=TABLE3["G42"][0], nnz=TABLE3["G42"][1],
                        n_requests: int = 2, devices: int = 4) -> Dict:
    """The SpMM with ``split={"k": devices}`` sharded over ``devices``
    devices (``execute_many``, the sharded serving path), against the
    unsplit one-device result and numpy."""
    B, Cs = spmm_operands(rng, shape, nnz, n_requests)
    dims = spmm_dims(shape)
    arrays = [{"B": B, "C": C} for C in Cs]
    split = Schedule(loop_order=SPMM_ORDER, split={"k": devices},
                     parallelize={"k": devices})
    eng = compile_expr(SPMM, SPMM_FMT, split, dims, shard_lanes=devices)
    base = compile_expr(SPMM, SPMM_FMT, Schedule(loop_order=SPMM_ORDER),
                        dims, shard_lanes=False)
    t0 = time.perf_counter()
    sharded = [ft.to_dense() for ft in eng.execute_many(arrays)]
    secs = time.perf_counter() - t0
    single = [base(a).to_dense() for a in arrays]
    B64 = B.astype(np.float64)
    refs = [B64 @ C.astype(np.float64) for C in Cs]
    vs_single = max(rel_error(s, r) for s, r in zip(sharded, single))
    vs_numpy = max(rel_error(s, r) for s, r in zip(sharded, refs))
    lane_devices = {d.id for d in eng.lane_devices}
    return {"requests": n_requests,
            "sharded_dispatches": eng.stats["sharded_dispatches"],
            "lane_devices": len(lane_devices),
            "max_rel_err_vs_one_device": vs_single,
            "max_rel_err_vs_numpy": vs_numpy, "first_s": secs,
            "ok": (eng.stats["sharded_dispatches"] > 0
                   and len(lane_devices) == devices
                   and vs_single <= REL_TOL and vs_numpy <= REL_TOL)}


def dist_tiles_phase(rng, shape=TABLE3["G42"][0], nnz=TABLE3["G42"][1],
                     n_requests: int = 2, workers: int = 4) -> Dict:
    """The SpMM under a memory budget that forces at least ``workers``
    tiles, fanned out over ``workers`` workers (``DistTiledExpr``); it
    must be bit-identical to the one-device ``TiledExpr``."""
    B, Cs = spmm_operands(rng, shape, nnz, n_requests)
    dims = spmm_dims(shape)
    arrays = [{"B": B, "C": C} for C in Cs]
    sch = Schedule(loop_order=SPMM_ORDER)
    density = nnz / (shape[0] * shape[1])
    untiled = tiling.estimate_call_bytes(
        parse(SPMM), SPMM_FMT, sch, dims,
        densities={"B": density, "C": density})
    tiled = compile_expr(SPMM, SPMM_FMT, sch, dims, sparsity=density,
                         mem_budget=untiled // workers)
    if not isinstance(tiled, TiledExpr) or tiled.n_tiles < workers:
        raise SmokeError(f"the memory budget planned "
                         f"{getattr(tiled, 'n_tiles', 1)} tile(s), "
                         f"fewer than {workers}")
    dist = DistTiledExpr(tiled, workers=workers)
    t0 = time.perf_counter()
    spread = [ft.to_dense() for ft in (dist(a) for a in arrays)]
    secs = time.perf_counter() - t0
    single = [tiled(a).to_dense() for a in arrays]
    B64 = B.astype(np.float64)
    vs_numpy = max(rel_error(s, B64 @ C.astype(np.float64))
                   for s, C in zip(spread, Cs))
    identical = all(s.tobytes() == o.tobytes()
                    for s, o in zip(spread, single))
    worker_devices = {w.device.id for w in dist.workers}
    return {"requests": n_requests, "tiles": tiled.n_tiles,
            "worker_devices": len(worker_devices),
            "bit_identical_to_one_device": identical,
            "max_rel_err_vs_numpy": vs_numpy, "first_s": secs,
            "ok": (identical and len(worker_devices) == workers
                   and vs_numpy <= REL_TOL)}


# -- driver -------------------------------------------------------------------
def serving_ok(report: Dict, *, need_kernel: bool = False) -> bool:
    return (report["failed"] == 0 and report["wrong"] == 0
            and report["completed"] == report["requests"]
            and (report.get("kernel", False) or not need_kernel))


def _print_serving(name: str, r: Dict) -> None:
    warm = "n/a" if r["warm_s"] is None else f"{r['warm_s']:.3f}"
    print(f"[{name}] requests completed={r['completed']} "
          f"failed={r['failed']} wrong={r['wrong']} "
          f"of {r['requests']} in {r['dispatches']} dispatches")
    print(f"[{name}] max relative error {r['max_rel_err']:.3e} "
          f"(bound {REL_TOL:g})")
    print(f"[{name}] XLA compiles: first burst {r['compiles_first']}, "
          f"warm bursts {r['compiles_warm']}")
    print(f"[{name}] smoke-run seconds, not a measurement: first burst "
          f"{r['first_s']:.3f}, warm burst {warm}")
    print(f"[{name}] implementations: {', '.join(r['impls']) or 'none'}")
    if "kernel" in r:
        print(f"[{name}] Pallas kernel in the served plan: {r['kernel']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharding and distributed-tile "
                         "paths, on four devices")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX platform is {dev.platform!r}, not 'tpu': "
              f"no accelerator to run on", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: {need} TPU devices needed, {len(devs)} present",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} compile-cache={cache}")
    rng = np.random.default_rng(args.seed)
    ok = True

    if args.four_chips:
        for name, phase in (("lane-sharding", lane_sharding_phase),
                            ("dist-tiles", dist_tiles_phase)):
            r = phase(rng)
            print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in r.items()))
            ok &= r["ok"]
    else:
        wrong_impl = [n for n in KERNEL_PRIMITIVES
                      if kops.sam_primitive(n)
                      is not kops.SAM_PRIMITIVES[n]["tpu"]]
        print(f"[device] Pallas entries resolved: "
              f"{len(KERNEL_PRIMITIVES) - len(wrong_impl)}/"
              f"{len(KERNEL_PRIMITIVES)}"
              + (f" (not: {', '.join(wrong_impl)})" if wrong_impl else ""))
        ok &= not wrong_impl
        r = spmv_phase(rng)
        _print_serving("spmv", r)
        ok &= serving_ok(r, need_kernel=True)
        r = spmm_phase(rng)
        _print_serving("spmm", r)
        ok &= serving_ok(r)

    if not ok:
        print("chip_smoke: FAILED (see the phase lines above)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
