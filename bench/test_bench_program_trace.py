"""The reduction of a profiler trace through the program's own spans and
named scopes (``benchlib/program_trace.py``), and the readers of the
metrics it feeds."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import program_trace, spec  # noqa: E402

jax_profiler = pytest.importorskip("jax.profiler")


def _stat(value):
    if isinstance(value, str):
        return f'str_value: "{value}"'
    return f"int64_value: {value}"


def xspace(device_ops, host_threads):
    """A serialized XSpace: one TPU plane with ``device_ops`` on its
    ``XLA Ops`` line, and one host plane with a line per thread of
    ``host_threads``. Each event is ``(name, start_ns, duration_ns,
    stats)``; a device op's ``stats`` is the ``tf_op`` path its event
    metadata holds, as on the chip."""
    host_keys = sorted({k for evs in host_threads.values() for e in evs
                        for k in e[3]})
    smeta = {k: 1000 + i for i, k in enumerate(host_keys + ["tf_op"])}

    def plane(pid, name, lines, paths):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        meta = {n: i + 1 for i, n in enumerate(names)}

        def events(evs):
            out = []
            for n, s, d, stats in evs:
                st = " ".join(
                    f"stats {{ metadata_id: {smeta[k]} {_stat(v)} }}"
                    for k, v in (stats.items() if isinstance(stats, dict)
                                 else ()))
                out.append(f"events {{ metadata_id: {meta[n]} "
                           f"offset_ps: {s * 1000} duration_ps: {d * 1000} "
                           f"{st} }}")
            return " ".join(out)

        def event_meta(n, i):
            st = (f' stats {{ metadata_id: {smeta["tf_op"]} '
                  f'{_stat(paths[n])} }}' if paths.get(n) else "")
            return (f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}"{st} }} }}')

        body = " ".join(
            f'lines {{ id: {j + 1} name: "{t}" timestamp_ns: 0 '
            f'{events(evs)} }}' for j, (t, evs) in enumerate(lines.items()))
        return (f'planes {{ id: {pid} name: "{name}" {body} '
                + " ".join(event_meta(n, i) for n, i in meta.items())
                + " ".join(f' stat_metadata {{ key: {i} value {{ id: {i} '
                           f'name: "{k}" }} }}' for k, i in smeta.items())
                + " }")

    paths = {n: path for n, _, _, path in device_ops}
    text = (plane(1, "/device:TPU:0", {"XLA Ops": device_ops}, paths) + " "
            + plane(2, "/host:CPU", dict(sorted(host_threads.items())), {}))
    return jax_profiler.ProfileData.text_proto_to_serialized_xspace(text)


SCAN = "jit(core)/vmap(sam.level_scan.n3)/jit(searchsorted)/while"
# window: end of bench.decode.1 (100) to end of bench.decode.3 (1100)
DEVICE = [
    ("while.19", 400, 300, ""),      # no path; encloses fusion.177
    ("fusion.177", 410, 280, SCAN + "/body/gather"),
    ("fusion.9", 700, 50, "jit(core)/vmap(sam.reduce.n7)/vmap(sam.collapse)"
                          "/kops.mul_reduce.pallas/scatter"),
    ("copy.3", 760, 40, ""),                         # under no scope
    ("fusion.2", 50, 100, "jit(core)/vmap(sam.merge)/add"),  # half in
]
HOST = {
    "bench": [("bench.decode.1", 90, 10, {}),
              ("bench.encode.2", 100, 300, {}),
              ("bench.decode.2", 800, 50, {}),
              ("bench.encode.3", 850, 200, {}),
              ("bench.decode.3", 1050, 50, {})],
    "encode": [("sam.encode", 100, 300, {"dispatch": 2, "n": 3}),
               ("sam.encode.build", 110, 200, {}),
               ("sam.encode.tree", 120, 170, {"tensor": "B"}),
               ("sam.encode.pack", 320, 60, {}),
               ("sam.encode", 850, 200, {"dispatch": 3, "n": 1}),
               ("sam.encode.build", 860, 100, {})],
    "execute": [("sam.execute", 400, 400, {"dispatch": 2, "n": 3}),
                ("sam.execute.launch", 400, 20, {}),
                ("sam.execute.sync", 420, 380, {}),
                ("sam.execute", 1050, 20, {"dispatch": 3, "n": 1}),
                ("sam.execute.launch", 1050, 5, {})],
    "decode": [("sam.decode", 800, 50, {"dispatch": 2, "n": 3}),
               ("sam.decode", 1070, 30, {"dispatch": 3, "n": 1})],
}


def test_reduce_attributes_spans_and_scoped_device_time():
    got = program_trace.reduce(xspace(DEVICE, HOST), 1, 3, {2: 3, 3: 1})
    # busy: [100, 150), [400, 750) and [760, 800); the while has no
    # path, and the fusion of its body covers all but 20 ns of it
    assert got["busy_s"] == pytest.approx(440e-9)
    assert got["scan_s"] == pytest.approx(280e-9)
    assert got["reduce_s"] == pytest.approx(100e-9)      # reduce + merge
    assert got["unscoped_s"] == pytest.approx(60e-9)     # copy.3 + 20
    assert got["spans_s"]["sam.encode.build"] == pytest.approx(300e-9)
    assert got["spans_s"]["sam.execute.launch"] == pytest.approx(25e-9)
    assert got["spans_s"]["sam.encode.tree[B]"] == pytest.approx(170e-9)
    assert got["stage_s"] == {2: pytest.approx(750e-9),
                              3: pytest.approx(250e-9)}
    scopes = dict(got["device_scopes"])
    assert list(scopes)[0] == "sam.level_scan.n3"
    assert scopes["sam.level_scan.n3"] == pytest.approx(280e-9)
    assert scopes["kops.mul_reduce.pallas"] == pytest.approx(50e-9)
    assert scopes["sam.collapse"] == pytest.approx(50e-9)
    # each idle gap is named by the innermost spans covering at least half
    # of it: [800, 1100) by encode 3 (its build covers a third), and
    # [150, 400) by encode 2's tree build of B
    assert got["idle_gaps_program"] == [
        ["sam.encode", pytest.approx(300e-9)],
        ["sam.encode.tree[B]", pytest.approx(250e-9)],
        ["sam.execute.sync", pytest.approx(10e-9)]]


def test_reduce_refuses_a_dispatch_count_that_disagrees():
    p = xspace(DEVICE, HOST)
    assert program_trace.reduce(p, 1, 3, {2: 2, 3: 1}) is None
    assert program_trace.reduce(p, 1, 3, {2: 3, 3: 1, 4: 1}) is None
    # a program without the spans (an older one) reduces to None
    bare = {"bench": HOST["bench"]}
    assert program_trace.reduce(xspace(DEVICE, bare), 1, 3,
                                {2: 3, 3: 1}) is None


def test_op_paths_come_from_the_event_metadata():
    paths = program_trace.op_paths(xspace(DEVICE, HOST))
    assert paths["fusion.177"] == SCAN + "/body/gather"
    assert not {"while.19", "copy.3", "bench.decode.1"} & set(paths)


def test_reduce_a_recorded_chip_trace():
    """``data/spmv-rail507.program.xspace.txt``: a traced run of
    ``spmv-rail507.clients16`` on one TPU v5 lite, trimmed to the device
    ops and the ``bench.*``/``sam.*`` spans around the window between
    the decode spans of dispatches 10 and 13 (times as recorded; op
    names are HLO names, with ``/1`` where two plans share one; each
    op's ``tf_op`` path kept in its event metadata). The numbers are
    those the full trace reduced to over that window."""
    text = (BENCH / "data" / "spmv-rail507.program.xspace.txt").read_text()
    xs = jax_profiler.ProfileData.text_proto_to_serialized_xspace(text)
    got = program_trace.reduce(xs, 10, 13, {11: 1, 12: 4, 13: 2})
    assert got["busy_s"] == pytest.approx(1.477643643, abs=1e-9)
    assert got["scan_s"] == pytest.approx(0.593441333, abs=1e-9)
    assert got["unscoped_s"] / got["busy_s"] < 0.03
    assert got["spans_s"]["sam.encode.build"] == pytest.approx(
        3.107690031, abs=1e-9)
    assert got["spans_s"]["sam.encode.tree[B]"] > 0.99 * got["spans_s"][
        "sam.encode.build"]
    # the plan's top loops: the locator's and the scanner's searchsorted
    assert [n for n, _ in got["device_scopes"][:2]] == [
        "sam.locate.n4", "sam.level_scan.n3"]
    assert got["device_scopes"][0][1] == pytest.approx(0.765766031,
                                                       abs=1e-9)
    # the device waited a second for the host to build dispatch 13's B
    assert got["idle_gaps_program"][0] == [
        "sam.encode.tree[B]", pytest.approx(0.966293276, abs=1e-9)]
    assert program_trace.reduce(xs, 10, 13, {11: 1, 12: 3, 13: 2}) is None


READ = {
    "encode.build_ms_per_req": 300e-9 / 4 * 1e3,
    "execute.launch_ms_per_req": 25e-9 / 4 * 1e3,
    "plan.scan_ms_per_req": 280e-9 / 4 * 1e3,
    "plan.reduce_ms_per_req": 100e-9 / 4 * 1e3,
    "serving.stage_wait_ms_per_req": 0.25 * 1e3,
}


@pytest.mark.parametrize("name", sorted(READ))
def test_program_readers(name):
    reduced = program_trace.reduce(xspace(DEVICE, HOST), 1, 3,
                                   {2: 3, 3: 1})
    rec = {"window": {"requests": 4},
           "program": {"trace": reduced,
                       "stage_waits_s": [0.1, 0.2, 0.3, 0.4]}}
    reader = spec.module("metrics", name)
    assert reader.read(rec) == pytest.approx(READ[name])
    # a record without the program's spans and counters reads nothing
    assert reader.read({"window": {"requests": 4}}) is None
    assert reader.read({"window": {"requests": 4},
                        "program": {"trace": None,
                                    "stage_waits_s": []}}) is None


def test_caps_reader_sums_the_compiled_engines(monkeypatch):
    from repro.core import jax_backend

    class Engine:
        def __init__(self, stats):
            self.stats = stats

    reader = spec.module("metrics", "setup.caps_s")
    monkeypatch.setattr(jax_backend, "_COMPILED", {
        "a": Engine({"caps_passes": 2, "caps_s": 1.5}),
        "b": Engine({"caps_passes": 1, "caps_s": 0.25})})
    assert reader.read({}) == pytest.approx(1.75)
    assert reader.note({}) == "3 capacity passes"
    # engines of a program without the counter read nothing
    monkeypatch.setattr(jax_backend, "_COMPILED",
                        {"a": Engine({"plan_misses": 1})})
    assert reader.read({}) is None
