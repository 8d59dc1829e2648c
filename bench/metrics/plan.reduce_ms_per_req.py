"""Device time of the plan's reductions: the union of the device op
intervals under ``sam.reduce.*``, ``sam.collapse`` and ``sam.merge``
named scopes (the kernels inside them included) in the window, per
request (``rec["program"]``, ``benchlib/program_trace.py``); None
without scoped ops."""
LAYER = "compiled plan and kernels"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    t = (rec.get("program") or {}).get("trace")
    if not t:
        return None
    return t["reduce_s"] / rec["window"]["requests"] * 1e3
