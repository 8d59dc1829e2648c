"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the trace."""
LAYER = "device"
UNIT = "%"
MOVES = "req_per_s"


def read(rec):
    t = rec["trace"]
    if not t:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
