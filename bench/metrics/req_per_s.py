"""Requests completed per second: every request of the dispatches that
completed in the window, over the window's whole length (the window
opens and closes on dispatch completions, ``benchlib/window.py``)."""
LAYER = None
UNIT = "requests/s"
MOVES = None


def read(rec):
    w = rec["window"]
    return w["requests"] / w["seconds"]
