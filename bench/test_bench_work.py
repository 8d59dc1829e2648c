"""The work functions, checked by hand on tiny operands."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import spec  # noqa: E402
from benchlib.operands import Operand  # noqa: E402


def sparse(dense):
    dense = np.asarray(dense, np.float32)
    rows, cols = np.nonzero(dense)
    return Operand(dense.shape, dense, (rows, cols, dense[rows, cols]))


def test_spmv_work_by_hand():
    # B: 2 x 3 with 3 nonzeros; c: 3; x: 2
    B = sparse([[1, 0, 2], [0, 3, 0]])
    c = Operand((3,), np.ones(3, np.float32))
    flops, nbytes = spec.module("work", "spmv").work({"B": B, "c": c})
    assert flops == 2 * 3
    # 3 x (value + coordinate) + 3 row pointers + c + x, 4 bytes each
    assert nbytes == 3 * 8 + 3 * 4 + 3 * 4 + 2 * 4


def test_spmm_work_by_hand():
    # B(:,0) has 2 nonzeros, B(:,1) has 1; C(0,:) has 1, C(1,:) has 2
    B = sparse([[1, 0], [1, 1]])
    C = sparse([[0, 5], [6, 7]])
    flops, nbytes = spec.module("work", "spmm").work({"B": B, "C": C})
    assert flops == 2 * (2 * 1 + 1 * 2)
    # X = [[0, 5], [6, 12]]: 3 nonzeros; B 3, C 3; 3 row pointers each
    assert nbytes == 3 * (3 * 8 + 3 * 4)


def test_spmm_output_count_does_not_depend_on_cancellation():
    # B(0,0) C(0,0) + B(0,1) C(1,0) = 1 - 1 = 0 still needs its entry
    B = sparse([[1, 1]])
    C = sparse([[1], [-1]])
    flops, nbytes = spec.module("work", "spmm").work({"B": B, "C": C})
    assert flops == 4
    assert nbytes == (2 * 8 + 2 * 4) + (2 * 8 + 3 * 4) + (1 * 8 + 2 * 4)


def test_roofline_bound_names_what_binds():
    roof = spec.module("metrics", "plan.roofline_pct")
    peaks = spec.peaks("TPU v5 lite")
    rec = {"peaks": peaks, "work": {"flops": 197e12, "bytes": 819e9 / 2},
           "trace": {"busy_s": 4.0}}
    assert roof.bound(rec) == (1.0, "flops")
    assert roof.read(rec) == pytest.approx(25.0)
    rec["work"]["bytes"] = 819e9 * 2
    assert roof.bound(rec) == (2.0, "bytes")
    assert roof.read({**rec, "trace": None}) is None


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        spec.peaks("TPU v5p")
