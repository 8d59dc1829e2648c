"""Host decode: the summed ``bench.decode`` spans (around the engine's
``decode_batch``, opened once the device result is ready) of the
window's dispatches, per request."""
LAYER = "host decode"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    w = rec["window"]
    return w["decode_s"] / w["requests"] * 1e3
