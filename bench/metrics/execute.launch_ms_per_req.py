"""Device execute, the launch alone: the summed ``sam.execute.launch``
spans (the plan call: argument upload and enqueue) of the window's
dispatches, per request. Read from the program's spans
(``rec["program"]``, ``benchlib/program_trace.py``); None without them."""
LAYER = "compiled plan and kernels"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    t = (rec.get("program") or {}).get("trace")
    if not t:
        return None
    return t["spans_s"].get("sam.execute.launch", 0.0) \
        / rec["window"]["requests"] * 1e3
