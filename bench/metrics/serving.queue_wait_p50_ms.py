"""Median of ``ResultHandle.queue_wait_s`` (submit to the dispatch
leaving the queue) over the window's requests."""
from benchlib.window import percentile

LAYER = "serving"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    waits = rec["queue_waits_s"]
    return percentile(waits, 50) * 1e3 if waits else None
