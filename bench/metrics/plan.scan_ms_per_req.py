"""Device time of the plan's level scanners: the union of the device op
intervals under ``sam.level_scan.*`` named scopes in the window, per
request (``rec["program"]``, ``benchlib/program_trace.py``); None
without scoped ops."""
LAYER = "compiled plan and kernels"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    t = (rec.get("program") or {}).get("trace")
    if not t:
        return None
    return t["scan_s"] / rec["window"]["requests"] * 1e3
