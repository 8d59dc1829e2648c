"""90th percentile (nearest rank) of the time from submit to result, as
the client sees it, over every request completed in the window."""
from benchlib.window import percentile

LAYER = None
UNIT = "ms"
MOVES = None


def read(rec):
    return percentile(rec["latencies_s"], 90) * 1e3
