"""Requests per dispatch in the window, from the dispatch log."""
LAYER = "serving"
UNIT = "requests"
MOVES = "req_per_s"


def read(rec):
    w = rec["window"]
    return w["requests"] / w["dispatches"]
