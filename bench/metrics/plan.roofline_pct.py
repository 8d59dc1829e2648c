"""The window's requests' least time on the chip, the larger of their
FLOPs over the peak FLOP/s and their minimum bytes over the HBM
bandwidth (``work/``, ``peaks.json``), over the device's busy time."""
LAYER = "compiled plan and kernels"
UNIT = "%"
MOVES = "req_per_s"


def bound(rec):
    """Seconds the FLOPs and the bytes need at peak, and which binds."""
    p, w = rec["peaks"], rec["work"]
    flops_s = w["flops"] / p["flops_per_s"]
    bytes_s = w["bytes"] / p["hbm_bytes_per_s"]
    return max(flops_s, bytes_s), "flops" if flops_s > bytes_s else "bytes"


def note(rec):
    return f"bound by {bound(rec)[1]}"


def read(rec):
    t = rec["trace"]
    if not t:
        return None
    return bound(rec)[0] / t["busy_s"] * 100
