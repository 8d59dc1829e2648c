"""A run with the timed path broken underneath comes out not correct:
the harness's look for a chip is skipped, the rest of a run is driven at
a tiny size, and the engine's decode stage is broken in each way a
served answer can go wrong. (One chip: no exchange between chips.)"""
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import load, tiny  # noqa: E402

load.program()
from repro.core.fibertree import FiberTree  # noqa: E402
from repro.core.jax_backend import CompiledExpr  # noqa: E402

SEED = 3_000_000_017


def _served():
    """Whether this is the server's decode stage (not set-up's warm-up)."""
    return threading.current_thread().name == "sam-serve-decode"


def _zeros_like(ft):
    dense = ft.to_dense()
    return FiberTree.from_dense(np.zeros_like(dense), "c" * dense.ndim)


def altered(results, state):
    """One value of the first answer moved by a thousandth of its
    largest value, where the answer is produced."""
    dense = results[0].to_dense()
    flat = dense.reshape(-1)
    flat[np.argmax(np.abs(flat))] *= 1.001
    return [FiberTree.from_dense(dense, "c" * dense.ndim)] + results[1:]


def half_left_out(results, state):
    """The second half of the batch (a lone request too) left out."""
    keep = len(results) // 2
    return results[:keep] + [_zeros_like(r) for r in results[keep:]]


def unchanged(results, state):
    """The state returned unchanged: every dispatch hands back the
    answers of the first one."""
    first = state.setdefault("first", results)
    return [first[i % len(first)] for i in range(len(results))]


def misrouted(results, state):
    """Each answer routed to the next request of its batch; a lone
    request gets the previous dispatch's answer."""
    prev = state.get("prev")
    state["prev"] = results
    if len(results) > 1:
        return results[1:] + results[:1]
    return prev if prev is not None else [_zeros_like(results[0])]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.tiny_bench(tmp_path_factory.mktemp("faults") / "bench",
                           clients=6, max_batch=4)


@pytest.mark.parametrize("fault", [altered, half_left_out, unchanged,
                                   misrouted], ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(bench, fault, monkeypatch):
    cell = tiny.tiny_cell(bench, "spmv-rail507")
    encode, decode = CompiledExpr.encode_batch, CompiledExpr.decode_batch
    state = {}

    def slow_encode(self, arrays_list):
        time.sleep(0.02)            # let requests queue, so batches form
        return encode(self, arrays_list)

    def broken_decode(self, enc, out):
        results = decode(self, enc, out)
        return fault(results, state) if _served() else results

    monkeypatch.setattr(CompiledExpr, "encode_batch", slow_encode)
    monkeypatch.setattr(CompiledExpr, "decode_batch", broken_decode)
    rec = load.run(cell, SEED + 1, 1.0, trace=False, t_process=0.0)
    assert rec["failed"] == 0
    assert rec["correct"] is False
    assert rec["checks"]["max_rel_err"]["value"] > \
        rec["checks"]["max_rel_err"]["limit"]


def test_a_request_that_fails_is_not_correct(bench, monkeypatch):
    cell = tiny.tiny_cell(bench, "spmv-rail507")
    decode = CompiledExpr.decode_batch
    calls = []

    def failing_decode(self, enc, out):
        if _served():
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("decode failed")
        return decode(self, enc, out)

    monkeypatch.setattr(CompiledExpr, "decode_batch", failing_decode)
    rec = load.run(cell, SEED + 2, 1.0, trace=False, t_process=0.0)
    assert rec["failed"] > 0
    assert rec["correct"] is False
