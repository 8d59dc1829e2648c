"""The benchmark's files are found by name, and BENCHMARK.json keeps to
the shape its readers and the harness expect."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = spec.benchmark()


@pytest.fixture
def copy(tmp_path):
    """A copy of the benchmark's files in a temporary directory."""
    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    return dst


def _with_cell(name, config, traffic):
    extra = dict(SPEC)
    extra["workloads"] = SPEC["workloads"] + [
        {"name": name, "config": config, "traffic": traffic, "chips": 1,
         "why": "a cell added as files"}]
    return extra


def test_config_and_traffic_added_as_files_are_found_by_name(copy):
    config = json.loads((copy / "configs" / "spmv-rail507.json").read_text())
    config["dims"] = {"i": 3, "j": 5}
    (copy / "configs" / "spmv-tiny.json").write_text(json.dumps(config))
    (copy / "traffic" / "clients3.json").write_text(
        json.dumps({"clients": 3, "max_batch": 2}))
    cell = spec.cell("spmv-tiny.clients3",
                     _with_cell("spmv-tiny.clients3", "spmv-tiny",
                                "clients3"), copy)
    assert cell.config["dims"] == {"i": 3, "j": 5}
    assert cell.traffic == {"clients": 3, "max_batch": 2}
    assert cell.bench == copy
    # the new cell reports every metric that names no workloads
    assert [m["name"] for m in cell.end_to_end] == [
        m["name"] for m in SPEC["end_to_end"] if "workloads" not in m]
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in SPEC["per_layer"] if "workloads" not in m]


def test_metric_work_and_reference_added_as_files_are_found_by_name(copy):
    (copy / "metrics" / "serving.tiny_count.py").write_text(
        "LAYER = 'serving'\nUNIT = 'count'\nMOVES = 'req_per_s'\n"
        "def read(rec):\n    return rec['window']['dispatches']\n")
    (copy / "work" / "tiny.py").write_text(
        "def work(ops):\n    return 1, 2\n")
    (copy / "reference" / "tiny.py").write_text(
        "def reference(ops):\n    return 3\n")
    cell = spec.cell("spmv-rail507.clients1", SPEC, copy)
    metric = cell.module("metrics", "serving.tiny_count")
    assert metric.read({"window": {"dispatches": 7}}) == 7
    assert cell.module("work", "tiny").work({}) == (1, 2)
    assert cell.module("reference", "tiny").reference({}) == 3


def test_a_missing_name_is_an_error(copy):
    with pytest.raises(KeyError):
        spec.cell("no-such.cell", SPEC, copy)
    with pytest.raises(KeyError):
        spec.module("metrics", "no.such_metric", copy)
    with pytest.raises(FileNotFoundError):
        spec.cell("x.y", _with_cell("x.y", "no-config", "clients1"), copy)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_every_name_of_benchmark_json_is_a_file_with_a_reader():
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    for c in SPEC["configs"]:
        body = json.loads((spec.ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        spec.module("work", body["work"])
        spec.module("reference", body["reference"])
        engine = spec.module("engines", body["engine"])
        assert all(callable(getattr(engine, f)) for f in
                   ("build", "arrays", "request", "answer"))
        for name, op in body["operands"].items():
            assert callable(spec.module("generators", op["kind"]).draw)
            assert set(body["tiny"]["operands"][name]) <= {"shape", "nnz"}
        assert set(body["tiny"]["dims"]) == set(body["dims"])
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]] + list(configs)
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in SPEC["workloads"]:
        cell = spec.cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                         "req_per_s"}
        assert cell.per_layer
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        reader = spec.module("metrics", m["name"])
        assert reader.UNIT == m["unit"]
        assert reader.LAYER == m.get("layer")
        assert reader.MOVES == m.get("moves")


def test_bounds_and_run_length_keep_to_their_ranges():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    assert len(json.dumps(SPEC)) < 64 * 1024
    texts = [e[k] for e in SPEC["configs"] + SPEC["workloads"]
             + SPEC["per_layer"] for k in ("why", "source", "layer")
             if k in e]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
