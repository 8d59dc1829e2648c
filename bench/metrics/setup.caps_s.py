"""Seconds the process's compiled engines spent in the eager capacity
pass (``CompiledExpr.stats["caps_s"]``, summed over the engines
``compile_expr`` holds), read after the run: set-up's passes, and any a
plan miss in the load added. None where the program has no such
counter."""
LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(rec):
    from repro.core import jax_backend

    secs = [e.stats["caps_s"] for e in jax_backend._COMPILED.values()
            if "caps_s" in e.stats]
    return sum(secs) if secs else None


def note(rec):
    from repro.core import jax_backend

    passes = sum(e.stats.get("caps_passes", 0)
                 for e in jax_backend._COMPILED.values())
    return f"{passes} capacity passes"
