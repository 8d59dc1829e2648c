"""Mean ``ResultHandle.stage_wait_s`` over the window's requests: the
time a request's dispatch waited between leaving the pending queue and
its decode ending, less the time its stages ran (``rec["program"]``);
None where the program has no such counter."""
LAYER = "serving"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    waits = (rec.get("program") or {}).get("stage_waits_s")
    if not waits:
        return None
    return sum(waits) / len(waits) * 1e3
