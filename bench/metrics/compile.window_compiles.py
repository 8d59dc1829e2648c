"""XLA programs compiled inside the window, one served from the
persistent compilation cache included (JAX's monitoring events); 0 when
set-up warmed every plan the window runs."""
LAYER = "compile"
UNIT = "count"
MOVES = "req_per_s"


def read(rec):
    return rec["window"]["compiles"]
