"""Device busy time in the traced window, per request completed in it."""
LAYER = "device"
UNIT = "ms"
MOVES = "req_per_s"


def read(rec):
    t = rec["trace"]
    if not t:
        return None
    return t["busy_s"] / rec["window"]["requests"] * 1e3
