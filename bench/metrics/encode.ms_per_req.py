"""Host encode: the summed ``bench.encode`` spans (around the engine's
``encode_batch``) of the window's dispatches, per request."""
LAYER = "host encode"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    w = rec["window"]
    return w["encode_s"] / w["requests"] * 1e3
