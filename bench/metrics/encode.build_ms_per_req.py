"""Host encode, the operand build alone: the summed ``sam.encode.build``
spans (each member's dense-to-fibertree build in ``encode_batch``) of
the window's dispatches, per request. Read from the program's spans
(``rec["program"]``, ``benchlib/program_trace.py``); None without them."""
LAYER = "host encode"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    t = (rec.get("program") or {}).get("trace")
    if not t:
        return None
    return t["spans_s"].get("sam.encode.build", 0.0) \
        / rec["window"]["requests"] * 1e3
