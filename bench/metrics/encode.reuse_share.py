"""Share of the encodes of operands with a compressed level that the
engine served from raw levels it had kept, an exact check proving the
array unchanged (``CompiledExpr.stats["encode_reuse_hits"]`` over hits
plus ``["encode_reuse_misses"]``, summed over the engines
``compile_expr`` holds), read after the run. None where the program has
no such counters."""
LAYER = "host encode"
UNIT = "share"
MOVES = "req_per_s"


def _counts():
    from repro.core import jax_backend

    engines = [e.stats for e in jax_backend._COMPILED.values()
               if "encode_reuse_hits" in e.stats]
    return (sum(s["encode_reuse_hits"] for s in engines),
            sum(s["encode_reuse_misses"] for s in engines))


def read(rec):
    hits, misses = _counts()
    return hits / (hits + misses) if hits + misses else None


def note(rec):
    hits, misses = _counts()
    return f"{hits} reused, {misses} built"
