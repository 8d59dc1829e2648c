"""The reduction from a profiler trace to busy time and the breakdown."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import trace  # noqa: E402

jax_profiler = pytest.importorskip("jax.profiler")


def xspace(device_ops, host_spans):
    """An XSpace text proto: one TPU plane with ``device_ops`` on its
    ``XLA Ops`` line and one host thread with ``host_spans``; each event
    is ``(name, start_ns, duration_ns)``."""
    names = sorted({n for n, _, _ in device_ops + host_spans})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def events(evs):
        return " ".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}" for n, s, d in evs)

    def metadata():
        return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in meta.items())

    return (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0 {events(device_ops)} }} '
            f'{metadata()} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 2 '
            f'name: "python3" timestamp_ns: 0 {events(host_spans)} }} '
            f'{metadata()} }}')


def profile(device_ops, host_spans):
    return jax_profiler.ProfileData.from_text_proto(
        xspace(device_ops, host_spans))


def test_union_clips_and_merges():
    got = trace.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12)
    assert got == [(1, 4), (5, 8), (9, 12)]
    assert trace.gaps(got, 0, 14) == [(0, 1), (4, 5), (8, 9), (12, 14)]


def test_gap_named_by_the_stages_that_cover_it():
    spans = {"encode": [(0, 10)], "decode": [(8, 30)]}
    assert trace.name_gap((0, 10), spans) == "encode"
    assert trace.name_gap((5, 25), spans) == "decode"
    assert trace.name_gap((8, 10), spans) == "encode+decode"
    assert trace.name_gap((40, 50), spans) == "none"


def test_reduce_a_window_between_decode_spans():
    # window: end of decode.1 (100) to end of decode.3 (1100)
    host = [("bench.decode.1", 90, 10), ("bench.encode.2", 100, 300),
            ("bench.execute.2", 400, 400), ("bench.decode.2", 800, 50),
            ("bench.encode.3", 850, 200), ("bench.decode.3", 1050, 50)]
    dev = [("fusion.1", 50, 100),            # half inside the window
           ("sort.2", 400, 300), ("fusion.1", 650, 100),   # overlap
           ("copy.3", 2000, 50)]             # after the window
    got = trace.reduce(profile(dev, host), 1, 3)
    assert got["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 150) and [400, 750)
    assert got["busy_s"] == pytest.approx(400e-9)
    assert got["device_ops"] == [["sort.2", pytest.approx(300e-9)],
                                 ["fusion.1", pytest.approx(150e-9)]]
    gaps = {g[0]: g[1] for g in got["idle_gaps"]}
    assert got["idle_gaps"][0][1] == pytest.approx(350e-9)
    assert gaps["encode"] == pytest.approx(250e-9)       # [150, 400)


def test_reduce_a_window_between_execute_spans():
    """An engine kind with no decode stage: a dispatch ends with its
    execute span, and the window runs between those ends."""
    host = [("bench.execute.1", 100, 200), ("bench.execute.2", 300, 300),
            ("bench.execute.3", 600, 300)]
    dev = [("fusion.1", 350, 200), ("fusion.1", 650, 100)]
    got = trace.reduce(profile(dev, host), 1, 3)
    assert got["window_s"] == pytest.approx(600e-9)      # [300, 900)
    assert got["busy_s"] == pytest.approx(300e-9)
    assert got["idle_gaps"][0] == ["execute", pytest.approx(150e-9)]
    assert trace.dispatch_ends(trace.host_spans(profile(dev, host))) == {
        1: 300, 2: 600, 3: 900}


def test_reduce_without_device_plane_or_window_is_none():
    host = [("bench.decode.1", 0, 10), ("bench.decode.2", 50, 10)]
    assert trace.reduce(profile([("fusion", 0, 5)], host), 1, 7) is None
    p = jax_profiler.ProfileData.from_text_proto(
        'planes { id: 2 name: "/host:CPU" }')
    assert trace.reduce(p, 1, 2) is None


def test_reduce_a_recorded_chip_trace():
    """``data/spmv-rail507.window.xspace.txt``: a traced run of
    ``spmv-rail507.clients16`` on one TPU v5 lite, trimmed to the device
    ops and ``bench.*`` spans between the decode spans of dispatches 1
    and 5 (times as recorded). The numbers are those the full trace
    reduced to on that run."""
    text = (BENCH / "data" / "spmv-rail507.window.xspace.txt").read_text()
    got = trace.reduce(jax_profiler.ProfileData.from_text_proto(text), 1, 5)
    assert got["window_s"] == pytest.approx(3.158767879, abs=1e-9)
    assert got["busy_s"] == pytest.approx(2.125142644, abs=1e-9)
    assert got["device_ops"][0] == ["while.19", pytest.approx(0.711954216)]
    assert [n for n, _ in got["device_ops"][:4]] == [
        "while.19", "fusion.177", "while.20", "fusion.181"]
    # the device waited a second for the host to encode dispatch 4
    assert got["idle_gaps"][0] == ["encode", pytest.approx(0.995038796)]
    assert len(got["device_ops"]) == len(got["idle_gaps"]) == trace.TOP
    assert trace.reduce(jax_profiler.ProfileData.from_text_proto(text),
                        1, 9) is None
