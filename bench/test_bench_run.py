"""The harness end to end at a tiny size on the CPU: off a TPU it exits
non-zero with no result; a run's last line has the contract's keys; a
tiny run of each configuration is correct."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from benchlib import load, spec, tiny  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.tiny_bench(tmp_path_factory.mktemp("tiny") / "bench")


def test_main_off_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "spmv-rail507.clients16", "--seed",
                   str(2**31 + 7), "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "not 'tpu'" in err


@pytest.mark.parametrize("config", tiny.configs())
def test_tiny_run_is_correct_with_the_contract_keys(bench, config):
    cell = tiny.tiny_cell(bench, config)
    rec = load.run(cell, 2**31 + 11, 1.0, trace=False, t_process=0.0)
    rec["peaks"] = spec.peaks("TPU v5 lite")
    line = run.result_line(cell, rec, CPU, trace=False)
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"req_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes",
                                   "memory_peak_bytes_per_chip"}
    assert line["device"]["memory_peak_bytes_per_chip"] == \
        rec["memory_peak_bytes_per_chip"]
    assert len(rec["memory_peak_bytes_per_chip"]) == cell.chips
    assert line["checks"]["max_rel_err"]["value"] < 1e-5
    json.loads(json.dumps(line))


def test_traced_line_carries_busy_window_and_breakdown(bench):
    cell = tiny.tiny_cell(bench, "spmv-rail507")
    rec = {
        "correct": False, "attempted": 5, "failed": 1,
        "checks": {"max_rel_err": {"value": 1.0, "limit": 1e-4}},
        "memory_peak_bytes": 123, "memory_peak_bytes_per_chip": [123],
        "setup_s": 9.0, "peaks":
            spec.peaks("TPU v5 lite"),
        "window": {"seconds": 2.0, "requests": 4, "dispatches": 2,
                   "encode_s": 0.4, "decode_s": 0.2, "compiles": 0},
        "latencies_s": [0.5] * 4, "queue_waits_s": [0.1] * 4,
        "work": {"flops": 8e3, "bytes": 4e6},
        "trace": {"busy_s": 0.5, "window_s": 2.0,
                  "device_ops": [["fusion.1", 0.3]],
                  "idle_gaps": [["encode", 0.7]]},
    }
    line = run.result_line(cell, rec, CPU, trace=True)
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert line["device"]["busy_s"] == 0.5
    assert line["device"]["window_s"] == 2.0
    assert line["breakdown"] == {"device_ops": [["fusion.1", 0.3]],
                                 "idle_gaps": [["encode", 0.7]]}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["device.idle_pct"] == pytest.approx(75.0)
    assert got["device.busy_ms_per_req"] == pytest.approx(125.0)
    assert got["serving.batch_occupancy"] == 2.0
    assert got["encode.ms_per_req"] == pytest.approx(100.0)
    assert got["decode.ms_per_req"] == pytest.approx(50.0)
    assert got["compile.window_compiles"] == 0
    # a run whose trace holds no device plane leaves its metrics out
    rec["trace"] = None
    line = run.result_line(cell, rec, CPU, trace=True)
    assert "device.idle_pct" not in line["metrics"]
    assert "breakdown" not in line
    assert list(line) == KEYS + ["checks"]
