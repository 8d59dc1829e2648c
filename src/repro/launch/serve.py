"""Serving launcher: batched prefill + decode with per-family caches, plus
batched sparse-expression serving through the compiled SAM engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --batch 4 --prompt-len 32 --gen 16

    # sparse-expression serving: compile once, dispatch batches through the
    # vmapped jit-cached engine
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "X(i,j) = B(i,k) * C(k,j)" --sam-order ikj \
        --sam-formats B=cc,C=cc --sam-dims i=64,j=64,k=64 \
        --batch 8 --reps 16

    # §4.4 iteration splitting + parallel lanes, sharded over 4 devices
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "X(i,j) = B(i,k) * C(k,j)" --sam-order ikj \
        --sam-formats B=cc,C=cc --split k=4 --devices 4

    # autoscheduled serving: the first request shape searches the schedule
    # space and persists the winner; repeats hit the schedule cache
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "X(i,j) = B(i,k) * C(k,j)" --autotune \
        --sam-formats B=cc,C=cc --sam-dims i=250,j=250,k=100 \
        --sam-density 0.05

    # multi-expression PROGRAM serving: ';'-separated assignments compile
    # as one cascade; fusable producer→consumer stages execute as a single
    # jitted pipeline (the intermediate never materializes)
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "T(i,j) = B(i,j) * C(i,k) * D(j,k); A(i,j) = T(i,k) * E(k,j)" \
        --sam-dims i=32,j=32,k=32 --sam-density 0.2 --batch 4

    # out-of-core serving under a memory budget: a request whose untiled
    # allocation estimate exceeds the budget streams coordinate-space
    # tiles through one jit-cached per-tile engine (docs/TILING.md)
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "X(i,j) = B(i,k) * C(k,j)" --sam-order ikj \
        --sam-formats B=cc,C=dd --sam-dims i=512,j=512,k=512 \
        --mem-budget 24MB --batch 2 --reps 2

    # distributed out-of-core serving: over-budget requests tile AND the
    # tiles spread over N simulated workers with fault-tolerant retry
    # (docs/DISTRIBUTED.md); --workers forces the host device count
    PYTHONPATH=src python -m repro.launch.serve \
        --sam "X(i,j) = B(i,k) * C(k,j)" --sam-order ikj \
        --sam-formats B=cc,C=dd --sam-dims i=512,j=512,k=512 \
        --mem-budget 24MB --workers 4 --batch 2 --reps 2
"""
from __future__ import annotations

import argparse
import os
import sys
import time

if __name__ == "__main__":
    # must run before jax initializes: force the host platform device count
    # so --devices (lane sharding) and --workers (distributed tiles) can
    # place work on distinct devices even on a CPU-only machine
    _dv = 0
    for _flag in ("--devices", "--workers"):
        for _i, _a in enumerate(sys.argv[1:], 1):
            _v = None
            if _a == _flag and _i + 1 < len(sys.argv):
                _v = sys.argv[_i + 1]
            elif _a.startswith(_flag + "="):
                _v = _a.split("=", 1)[1]
            if _v and _v.isdigit():
                _dv = max(_dv, int(_v))
    if _dv > 1 and ("--xla_force_host_platform_device_count"
                    not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={_dv} "
            + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, list_archs
from ..core.einsum import parse
from ..core.jax_backend import compile_expr, compile_program, lane_mesh_size
from ..core.program import parse_program
from ..core.schedule import Format, Schedule
from ..models.model import decode_step, forward, init_caches, init_params
from ..train.train_step import make_prefill_step, make_serve_step


def generate(cfg, params, prompts, gen_len: int, max_seq: int,
             temperature: float = 0.0, seed: int = 0):
    """prompts: (B, P) int32. Greedy/temperature sampling, batched."""
    b, plen = prompts.shape
    caches = init_caches(cfg, b, max_seq)
    prefill = jax.jit(make_prefill_step(cfg))
    step = jax.jit(make_serve_step(cfg))

    logits, caches = prefill(params, caches, {"tokens": prompts})
    out = [prompts]
    key = jax.random.PRNGKey(seed)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(gen_len):
        out.append(tok)
        logits, caches = step(params, caches, {"tokens": tok})
        if temperature > 0:
            key, k2 = jax.random.split(key)
            tok = jax.random.categorical(
                k2, logits / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def _parse_kv(text: str, cast=str):
    out = {}
    for item in text.split(","):
        if not item:
            continue
        if "=" not in item:
            raise SystemExit(
                f"expected comma-separated key=value pairs, got {item!r} "
                f"(e.g. B=cc,C=cc or i=64,j=64)")
        k, v = item.split("=", 1)
        out[k.strip()] = cast(v.strip())
    return out


def serve_sam(expr: str, order: str, formats, dims, *, batch: int = 8,
              reps: int = 8, density: float = 0.1, seed: int = 0,
              split=None, devices: int = 0, workers: int = 0,
              autotune: bool = False, mem_budget=None,
              use_server: bool = True, log=print):
    """Sparse-expression serving: compile ONCE, then stream requests
    through the continuous-batching server (``core.serving.SamServer``).

    Every request in a dispatch shares the expression/format/schedule (the
    jit signature); only the operand data differs — the SAM analogue of
    batched decode. The server coalesces the submitted requests by
    compiled-cache key into batched vmapped dispatches of width ``batch``
    and overlaps host encode / device execute / host decode across
    consecutive dispatches (docs/SERVING.md); ``use_server=False`` keeps
    the legacy one-dispatch-at-a-time loop (the sequential baseline that
    ``benchmarks/serving.py`` measures against). ``split={var: n}``
    applies §4.4 iteration splitting AND parallel lane duplication over
    that variable; with multiple devices the lanes shard over the device
    mesh. ``autotune=True`` picks the whole schedule instead: the first
    request shape searches the schedule space (cost-model ranking,
    ``core.autoschedule``) and persists the winner in the on-disk
    schedule cache, so every later request with the same cache key —
    same expression/format, dims bucket, sparsity bucket — serves
    compiled with NO search. ``mem_budget`` (bytes or ``"64MB"``)
    bounds peak device allocation: requests whose untiled estimate
    exceeds it route through the out-of-core tiled driver automatically
    (docs/TILING.md). Returns (results of the last dispatch, engine
    stats).
    """
    from ..core import tiling

    if devices and jax.device_count() < devices:
        raise SystemExit(
            f"--devices {devices} requested but only {jax.device_count()} "
            f"jax device(s) present; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices} (done "
            f"automatically when running this module as a script)")
    split = dict(split or {})
    if autotune and split:
        raise SystemExit("--autotune searches the schedule (including "
                         "splits); drop --split")
    if mem_budget is not None:
        mem_budget = tiling.parse_budget(mem_budget)
    fmt = Format(dict(formats))
    if autotune:
        from ..core.autoschedule import resolve_schedule

        kw = {} if mem_budget is None else {"mem_budget": mem_budget}
        res = resolve_schedule(expr, fmt, dims, sparsity=density,
                               device_count=devices or None, **kw)
        sch = res.schedule
        if res.cache_hit:
            log(f"[serve-sam] autotune: schedule cache HIT -> "
                f"order={''.join(sch.loop_order)} split={sch.split} "
                f"par={sch.parallelize} (no search, compiled dispatch only)")
        else:
            rep = res.report
            top = ", ".join(f"{c.spec.key()}:{c.cycles}cyc"
                            for c in rep.candidates[:3])
            log(f"[serve-sam] autotune: searched {rep.enumerated} schedules"
                + (" (order space capped)" if rep.orders_truncated else "")
                + f" ({rep.simulated} simulated at {rep.sample_dims}) in "
                f"{rep.elapsed_s * 1e3:.0f}ms -> "
                f"order={''.join(sch.loop_order)} split={sch.split} "
                f"par={sch.parallelize}; top: {top}")
        split = dict(sch.split)
    else:
        # §4.4: every requested variable splits; the OUTERMOST split
        # variable also parallelizes (the lowering supports one parallel
        # var)
        par = {v: split[v] for v in order if v in split}
        sch = Schedule(loop_order=tuple(order), split=split,
                       parallelize=dict(list(par.items())[:1]))
    if devices and not split:
        raise SystemExit(
            "--devices shards parallel lanes; "
            + ("--autotune picked an unsplit schedule for this shape"
               if autotune else "give --split too (e.g. --split k=4)"))
    par_n = max(sch.parallelize.values(), default=1)
    if devices and lane_mesh_size(par_n, devices) < 2:
        # an explicit --devices must shard or fail loudly (auto-detection
        # would silently fall back to vmap)
        raise SystemExit(
            f"--devices {devices}: no >1-device mesh fits {par_n} lane(s) "
            f"on {jax.device_count()} present device(s); "
            + ("--autotune picked a schedule without matching parallel "
               "lanes for this shape; drop --devices"
               if autotune else
               "pick a split factor a device subset divides"))
    eng = compile_expr(expr, fmt, sch, dims,
                       shard_lanes=devices if devices else None,
                       sparsity=density, mem_budget=mem_budget)
    # lanes shard over the device mesh only on the single-call path (the
    # batch path nests lanes inside the outer vmap, which cannot carry a
    # shard_map); with a mesh present, dispatch requests one by one so
    # every request's lanes actually spread across the devices
    shard = eng._shard_lanes
    tiled = getattr(eng, "tile_of", None)
    if tiled:
        log(f"[serve-sam] mem-budget "
            f"{tiling.format_bytes(mem_budget) if mem_budget else 'n/a'}: "
            f"request routed OUT-OF-CORE -> tile={tiled} "
            f"({eng.n_tiles} tiles, ~{tiling.format_bytes(eng.tile_bytes)}"
            f"/tile; tiles stream through one jit-cached per-tile plan)")
    elif mem_budget is not None:
        log(f"[serve-sam] mem-budget {tiling.format_bytes(mem_budget)}: "
            f"untiled estimate fits, serving in-core")
    if workers and workers > 1:
        if tiled:
            from ..core.dist_exec import DistTiledExpr

            eng = DistTiledExpr(eng, workers=workers)
            log(f"[serve-sam] --workers {workers}: {eng.n_tiles} tiles "
                f"DISTRIBUTED over {len(eng.workers)} simulated worker(s) "
                f"with fault-tolerant retry (docs/DISTRIBUTED.md)")
        else:
            log(f"[serve-sam] --workers {workers}: request fits in-core "
                f"(untiled), nothing to distribute; serving single-device")
    if split:
        log(f"[serve-sam] split={split} parallelize={sch.parallelize}: "
            f"{eng.par_n}-lane {eng.low.merge_kind}-merge, "
            + (f"per-request shard_map over {eng._lane_mesh} devices"
               if shard else "lanes vmapped inside the batched dispatch"))
    assign = parse(expr)
    rng = np.random.default_rng(seed)

    def operand_set():
        from ..core.autoschedule import random_operand

        arrays = {}
        for term in assign.terms:
            for acc in term.factors:
                if acc.tensor in arrays:
                    continue
                shape = tuple(dims[v] for v in acc.vars)
                arrays[acc.tensor] = random_operand(shape, density, rng)
        return arrays

    if not use_server:
        # legacy sequential loop: one hand-assembled dispatch at a time
        # (the baseline benchmarks/serving.py compares the server against)
        def dispatch():
            ops = [operand_set() for _ in range(batch)]
            if shard:
                return eng.execute_many(ops)
            return eng.execute_batch(ops)

        t0 = time.perf_counter()
        results = dispatch()      # dispatch 1 pays record + trace cost
        t_first = time.perf_counter() - t0
        t1 = time.perf_counter()
        for _ in range(max(reps - 1, 0)):
            results = dispatch()
        if reps > 1:
            warm = (time.perf_counter() - t1) / (reps - 1)
            warm_txt = (f"warm={warm * 1e3:.1f}ms/dispatch "
                        f"({batch / warm:.1f} expr/s)")
        else:
            warm_txt = "warm=n/a (reps<2)"
        log(f"[serve-sam] {expr!r}: batch={batch} reps={reps} "
            f"first={t_first * 1e3:.1f}ms {warm_txt}")
        log(f"[serve-sam] engine stats: {eng.stats}")
        return results, eng.stats

    # continuous-batching server: submit the whole load as one burst;
    # the batcher coalesces same-key requests into vmapped dispatches of
    # width ``batch`` while the async pipeline overlaps encode/execute/
    # decode across consecutive dispatches (docs/SERVING.md)
    from ..core.serving import Request, SamServer

    srv = SamServer(max_batch=batch)
    reqs = [Request(expr if isinstance(expr, str) else str(expr),
                    operand_set(), formats=fmt, dims=dims, density=density)
            for _ in range(batch * max(reps, 1))]
    handles = srv.submit_many(reqs, engine=eng)
    srv.drain(timeout=600)
    sstats = srv.stats()
    srv.shutdown()
    _raise_failed(handles)
    results = [h.result() for h in handles[-batch:]]
    log(f"[serve-sam] {expr!r}: {sstats['completed']} requests in "
        f"{sstats['dispatches']} dispatches "
        f"(occupancy {sstats['batch_occupancy']:.1f}): "
        f"{sstats['requests_per_sec']:.1f} req/s "
        f"p50={sstats['p50_ms']:.1f}ms p99={sstats['p99_ms']:.1f}ms "
        f"stage-wait p50={sstats['stage_wait_p50_ms']:.1f}ms")
    log(f"[serve-sam] engine stats: {eng.stats}")
    return results, eng.stats


def _raise_failed(handles) -> None:
    """A served request that failed fails the run: the server keeps going
    past a failed dispatch group, the launcher must not."""
    errors = [e for e in (h.exception() for h in handles) if e is not None]
    if errors:
        raise RuntimeError(f"{len(errors)} of {len(handles)} served "
                           f"requests failed") from errors[0]


def serve_program(text: str, formats, dims, *, batch: int = 8,
                  reps: int = 8, density: float = 0.1, seed: int = 0,
                  autotune: bool = False, mem_budget=None, log=print):
    """Multi-expression program serving: compile the cascade ONCE
    (``jax_backend.compile_program``), then dispatch batches of operand
    sets through it.

    Fused producer→consumer stages execute as one jitted pipeline with
    the intermediates living on device; illegal fusions materialize
    between stages (the decisions are logged). ``autotune=True`` resolves
    every stage's schedule through the autoscheduler + persistent
    schedule cache. ``mem_budget`` routes over-sized unfused stages
    through the out-of-core tiled driver (docs/TILING.md). Returns
    (results of the last dispatch, program stats).
    """
    prog = parse_program(text)
    fmt = Format(dict(formats))
    schedules = "auto" if autotune else {
        a.lhs.tensor: Schedule(loop_order=tuple(a.all_vars))
        for a in prog.assigns}
    cp = compile_program(prog, fmt, schedules, dims, sparsity=density,
                         mem_budget=mem_budget)
    for d in cp.decisions:
        src, dst = prog.names[d.producer], prog.names[d.consumer]
        log(f"[serve-program] {d.tensor}: {src} -> {dst} "
            + ("FUSED (spliced streams, no materialization)" if d.fused
               else f"materialized ({d.reason})"))
    if not cp.decisions:
        log("[serve-program] single-stage program (nothing to fuse)")
    for kind, comp, unit in cp.units:
        if kind == "expr" and getattr(unit, "tile_of", None):
            from ..core import tiling
            log(f"[serve-program] stage {unit.assign.lhs.tensor}: "
                f"OUT-OF-CORE tile={unit.tile_of} ({unit.n_tiles} tiles, "
                f"~{tiling.format_bytes(unit.tile_bytes)}/tile)")
    rng = np.random.default_rng(seed)

    def operand_set():
        from ..core.autoschedule import random_operand

        free = set(prog.inputs)
        out = {}
        for a in prog.assigns:
            for trm in a.terms:
                for f in trm.factors:
                    if f.tensor in free and f.tensor not in out:
                        out[f.tensor] = random_operand(
                            tuple(dims[v] for v in f.vars), density, rng)
        return out

    # program requests stream through the same continuous-batching
    # server (coalesced by program cache key; stages execute per request
    # inside the pipeline's dispatch stage)
    from ..core.serving import Request, SamServer

    srv = SamServer(max_batch=batch)
    reqs = [Request(text, operand_set(), formats=fmt, dims=dims,
                    density=density)
            for _ in range(batch * max(reps, 1))]
    handles = srv.submit_many(reqs, engine=cp)
    srv.drain(timeout=600)
    sstats = srv.stats()
    srv.shutdown()
    _raise_failed(handles)
    results = [h.result() for h in handles[-batch:]]
    log(f"[serve-program] {len(prog.assigns)} stages, outputs="
        f"{','.join(prog.outputs)}: {sstats['completed']} requests in "
        f"{sstats['dispatches']} dispatches: "
        f"{sstats['requests_per_sec']:.1f} req/s "
        f"p50={sstats['p50_ms']:.1f}ms p99={sstats['p99_ms']:.1f}ms")
    log(f"[serve-program] program stats: {cp.stats}")
    return results, cp.stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--sam", default=None, metavar="EXPR",
                    help="serve a sparse expression instead of an LM; "
                         "';'-separated assignments serve as a PROGRAM "
                         "with producer→consumer fusion, e.g. "
                         "\"T(i,j) = B(i,k) * C(k,j); "
                         "A(i,j) = T(i,k) * E(k,j)\"")
    ap.add_argument("--sam-order", default=None,
                    help="loop order, e.g. ikj (default: lhs+reduction vars)")
    ap.add_argument("--sam-formats", default="",
                    help="per-tensor formats, e.g. B=cc,C=cc")
    ap.add_argument("--sam-dims", default="",
                    help="index extents, e.g. i=64,j=64,k=64")
    ap.add_argument("--sam-density", type=float, default=0.1)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--split", default="", metavar="VAR=N[,VAR=N]",
                    help="§4.4 iteration splitting + N parallel lanes, "
                         "e.g. k=4 (implies parallelize)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard parallel lanes over this many devices "
                         "(forces the host device count when run as a "
                         "script on CPU)")
    ap.add_argument("--workers", type=int, default=0,
                    help="distribute out-of-core tile grids over this "
                         "many simulated workers with fault-tolerant "
                         "retry (docs/DISTRIBUTED.md); needs --mem-budget "
                         "small enough to tile. Forces the host device "
                         "count when run as a script on CPU")
    ap.add_argument("--autotune", action="store_true",
                    help="search the schedule space (loop order, split, "
                         "lanes) with the simulator cost model on the "
                         "first request per shape; later requests hit the "
                         "persistent schedule cache and serve compiled")
    ap.add_argument("--mem-budget", default=None, metavar="BYTES",
                    help="peak device-allocation budget (e.g. 64MB or "
                         "67108864); requests whose untiled estimate "
                         "exceeds it stream through the out-of-core "
                         "tiled engine automatically (docs/TILING.md)")
    args = ap.parse_args(argv)

    if args.sam and ";" in args.sam:
        # multi-expression program serving (producer→consumer fusion)
        if args.sam_order or args.split:
            raise SystemExit("program serving schedules per stage; drop "
                             "--sam-order/--split (use --autotune)")
        if args.devices:
            raise SystemExit("program serving does not shard lanes yet; "
                             "drop --devices (stages run serial, fused "
                             "where legal)")
        if args.workers:
            raise SystemExit("program serving does not distribute tiles "
                             "yet; drop --workers (single-expression "
                             "--sam supports it)")
        prog = parse_program(args.sam)
        all_vars = [v for a in prog.assigns for v in a.all_vars]
        dims = {**{v: 64 for v in all_vars},
                **_parse_kv(args.sam_dims, int)}
        results, _ = serve_program(args.sam, _parse_kv(args.sam_formats),
                                   dims, batch=args.batch, reps=args.reps,
                                   density=args.sam_density,
                                   autotune=args.autotune,
                                   mem_budget=args.mem_budget)
        return results

    if args.sam:
        if args.autotune and args.sam_order:
            raise SystemExit("--autotune searches the loop order; drop "
                             "--sam-order (like --split)")
        assign = parse(args.sam)
        order = args.sam_order or "".join(assign.all_vars)
        dims = {**{v: 64 for v in order}, **_parse_kv(args.sam_dims, int)}
        formats = _parse_kv(args.sam_formats)
        results, _ = serve_sam(args.sam, order, formats, dims,
                               batch=args.batch, reps=args.reps,
                               density=args.sam_density,
                               split=_parse_kv(args.split, int),
                               devices=args.devices,
                               workers=args.workers,
                               autotune=args.autotune,
                               mem_budget=args.mem_budget)
        return results

    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab,
                                 jnp.int32)
    t0 = time.perf_counter()
    seqs = generate(cfg, params, prompts, args.gen,
                    args.prompt_len + args.gen + 8, args.temperature)
    dt = time.perf_counter() - t0
    tput = args.batch * args.gen / dt
    print(f"[serve] {args.arch}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: {dt:.2f}s  ({tput:.1f} tok/s incl. compile)")
    print("[serve] first sequence:", seqs[0, :24].tolist(), "...")
    return seqs


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
