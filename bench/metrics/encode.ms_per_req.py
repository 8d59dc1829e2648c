"""Host encode: the summed ``bench.encode`` spans (around the engine's
``encode_batch``) of the window's dispatches, per request; None for
an engine kind with no encode stage of its own."""
LAYER = "host encode"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    w = rec["window"]
    if w["encode_s"] is None:
        return None
    return w["encode_s"] / w["requests"] * 1e3
