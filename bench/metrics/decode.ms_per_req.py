"""Host decode: the summed ``bench.decode`` spans (around the engine's
``decode_batch``, opened once the device result is ready) of the
window's dispatches, per request; None for an engine kind with no
decode stage of its own."""
LAYER = "host decode"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    w = rec["window"]
    if w["decode_s"] is None:
        return None
    return w["decode_s"] / w["requests"] * 1e3
