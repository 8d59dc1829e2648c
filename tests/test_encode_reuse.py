"""Encode reuse: ``CompiledExpr`` serves an operand array object it has
encoded before from the raw levels it kept, only when an exact check
proves the array unchanged, and forgets the array when it dies.

Every case compares with a fresh engine, which encodes everything anew.
"""
import gc
import sys
import threading

import numpy as np
import pytest

from repro.core.jax_backend import CompiledExpr, compile_expr
from repro.core.schedule import Format, Schedule
from repro.core.serving import Request, SamServer

MV = "x(i) = B(i,j) * c(j)"
FMT = Format({"B": "cc", "c": "d"})
SCH = Schedule(loop_order=("i", "j"))
DIMS = {"i": 12, "j": 40}


def _engine():
    return compile_expr(MV, FMT, SCH, DIMS)


def _fresh():
    """An engine of the same expression that has encoded nothing."""
    return CompiledExpr(MV, FMT, SCH, DIMS)


def _B(seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    keep = rng.random((DIMS["i"], DIMS["j"])) < 0.2
    return np.where(keep, rng.uniform(-1, 1, keep.shape), 0).astype(dtype)


def _c(seed=1):
    return np.random.default_rng(seed).uniform(-1, 1, DIMS["j"]) \
        .astype(np.float32)


def _counts(eng):
    return eng.stats["encode_reuse_hits"], eng.stats["encode_reuse_misses"]


def _delta(eng, before):
    h, m = _counts(eng)
    return h - before[0], m - before[1]


def _same_raw(a, b):
    for x, y in zip((*a["segs"], *a["crds"], a["vals"]),
                    (*b["segs"], *b["crds"], b["vals"])):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def _assert_fresh_answer(eng, B, c):
    """``eng`` and a fresh engine give the same raw levels and the same
    answer, bit for bit."""
    got = eng({"B": B, "c": c}).to_dense()
    want = _fresh()({"B": B, "c": c}).to_dense()
    np.testing.assert_array_equal(got, want)
    _same_raw(eng._raw_flat({"B": B, "c": c})["B"],
              _fresh()._raw_flat({"B": B, "c": c})["B"])
    return got


def _seen_twice(eng, B):
    """Encode ``B`` twice, so its entry holds what the check reads."""
    for _ in range(2):
        eng({"B": B, "c": _c()})


def test_an_unchanged_object_hits_with_bit_identical_levels_and_answers():
    eng, B, c = _engine(), _B(), _c()
    _seen_twice(eng, B)
    before = _counts(eng)
    raw = eng._raw_flat({"B": B, "c": c})["B"]
    assert _delta(eng, before) == (1, 0)
    assert raw is eng._raw_flat({"B": B, "c": c})["B"]
    assert not raw["vals"].flags.writeable
    _same_raw(raw, _fresh()._raw_flat({"B": B, "c": c})["B"])
    before = _counts(eng)
    np.testing.assert_array_equal(eng({"B": B, "c": c}).to_dense(),
                                  _fresh()({"B": B, "c": c}).to_dense())
    assert _delta(eng, before) == (1, 0)


def _first_nonzero(B):
    return tuple(np.argwhere(B != 0)[0])


def _first_zero(B):
    return tuple(np.argwhere(B == 0)[0])


EDITS = {
    # a value changed, the count of nonzeros kept
    "value": lambda B: B.__setitem__(_first_nonzero(B),
                                     B[_first_nonzero(B)] + 0.5),
    "zero_made_nonzero": lambda B: B.__setitem__(_first_zero(B), 0.25),
    "nonzero_made_zero": lambda B: B.__setitem__(_first_nonzero(B), 0.0),
    "negative_zero": lambda B: B.__setitem__(_first_nonzero(B), -0.0),
    "nan": lambda B: B.__setitem__(_first_zero(B), np.nan),
}


@pytest.mark.parametrize("edit", sorted(EDITS))
def test_an_edit_in_place_misses_and_gives_the_new_answer(edit):
    eng, B, c = _engine(), _B(), _c()
    _seen_twice(eng, B)
    old = eng({"B": B, "c": c}).to_dense()
    EDITS[edit](B)
    before = _counts(eng)
    got = eng({"B": B, "c": c}).to_dense()
    assert _delta(eng, before) == (0, 1)
    want = _fresh()({"B": B, "c": c}).to_dense()
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(got, old, equal_nan=True)
    # the rebuilt entry serves the edited array from here on
    before = _counts(eng)
    np.testing.assert_array_equal(eng({"B": B, "c": c}).to_dense(), want)
    assert _delta(eng, before) == (1, 0)


def test_a_negative_zero_written_over_a_zero_misses():
    eng, B, c = _engine(), _B(), _c()
    _seen_twice(eng, B)
    B[_first_zero(B)] = -0.0
    before = _counts(eng)
    _assert_fresh_answer(eng, B, c)
    assert _delta(eng, before)[1] == 1


def test_an_operand_holding_negative_zeros_still_hits():
    eng, c = _engine(), _c()
    B = _B()
    B[B == 0] = -0.0
    _seen_twice(eng, B)
    before = _counts(eng)
    _assert_fresh_answer(eng, B, c)
    assert _delta(eng, before) == (2, 0)


def test_an_equal_copy_is_another_object_and_misses():
    eng, B, c = _engine(), _B(), _c()
    _seen_twice(eng, B)
    copy = B.copy()
    before = _counts(eng)
    got = eng({"B": copy, "c": c}).to_dense()
    assert _delta(eng, before) == (0, 1)
    np.testing.assert_array_equal(got, _fresh()({"B": B, "c": c})
                                  .to_dense())


def test_a_view_that_is_not_contiguous_misses_and_keeps_no_entry():
    eng, c = _engine(), _c()
    wide = np.zeros((DIMS["i"], 2 * DIMS["j"]), np.float32)
    wide[:, ::2] = _B()
    view = wide[:, ::2]
    assert not view.flags.c_contiguous
    entries = len(eng._encoded)
    before = _counts(eng)
    for _ in range(3):
        _assert_fresh_answer(eng, view, c)
    assert _delta(eng, before) == (0, 6)
    assert len(eng._encoded) == entries


def test_a_float64_operand_is_checked_in_its_own_dtype():
    eng, c = _engine(), _c()
    B = _B(dtype=np.float64)
    _seen_twice(eng, B)
    assert eng._encoded[("B", id(B))].at.dtype == np.int64
    at = _first_nonzero(B)
    v = B[at]
    B[at] = np.nextafter(v, 2.0)        # the same value in float32
    assert np.float32(B[at]) == np.float32(v)
    before = _counts(eng)
    _assert_fresh_answer(eng, B, c)
    assert _delta(eng, before)[1] == 1


def test_the_entry_leaves_with_its_array():
    eng, c = _engine(), _c()
    B = _B()
    _seen_twice(eng, B)
    key = ("B", id(B))
    assert key in eng._encoded
    del B
    gc.collect()
    assert key not in eng._encoded


def test_fresh_operands_leave_no_entry_once_dropped():
    eng = _engine()
    base = set(eng._encoded)
    for k in range(6):
        eng({"B": _B(seed=10 + k), "c": _c()})
        eng.execute_batch([{"B": _B(seed=20 + k + m), "c": _c()}
                           for m in range(3)])
    gc.collect()
    assert set(eng._encoded) <= base


def test_a_served_batch_of_eight_sharing_one_matrix_builds_it_once(
        monkeypatch):
    eng, B = _engine(), _B(seed=3)
    built = []
    build = eng._build_raw

    def counted(name, arrays):
        built.append(name)
        return build(name, arrays)

    monkeypatch.setattr(eng, "_build_raw", counted)
    reqs = [Request(MV, {"B": B, "c": _c(seed=k)}, formats=FMT, dims=DIMS)
            for k in range(8)]
    before = _counts(eng)
    with SamServer(max_batch=8, sync=True) as srv:
        hs = srv.submit_many(reqs, engine=eng)
        srv.flush()
        got = [h.result().to_dense() for h in hs]
    assert srv.stats()["dispatches"] == 1
    assert built.count("B") == 1 and built.count("c") == 8
    assert _delta(eng, before) == (7, 1)
    fresh = _fresh()
    for r, g in zip(reqs, got):
        np.testing.assert_array_equal(g, fresh(r.arrays).to_dense())


def test_concurrent_callers_share_the_table_and_answer_exactly():
    eng, shared = _engine(), _B(seed=5)
    fresh = _fresh()
    want = {}
    errors = []

    def caller(j):
        own = _B(seed=100 + j)
        try:
            for k in range(4):
                if k == 2:
                    own[_first_zero(own)] = 1.0
                arrays = [{"B": shared, "c": _c(seed=j)},
                          {"B": own, "c": _c(seed=j)}]
                for a, out in zip(arrays, eng.execute_batch(arrays)):
                    key = (j, k, a["B"] is shared)
                    want[key] = (out.to_dense(),
                                 {n: v.copy() for n, v in a.items()})
        except Exception as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(j,))
                   for j in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert not errors
    assert len(want) == 12 * 4 * 2
    for got, arrays in want.values():
        np.testing.assert_array_equal(
            got, fresh(arrays).to_dense())


def _reader():
    import importlib.util
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "bench" / "metrics"
            / "encode.reuse_share.py")
    spec = importlib.util.spec_from_file_location("encode_reuse_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Stats:
    def __init__(self, **stats):
        self.stats = stats


def test_the_reuse_share_reader_sums_the_engines_counters(monkeypatch):
    from repro.core import jax_backend

    reader = _reader()
    monkeypatch.setattr(jax_backend, "_COMPILED", {
        "a": _Stats(encode_reuse_hits=9, encode_reuse_misses=1),
        "b": _Stats(encode_reuse_hits=0, encode_reuse_misses=10),
        "older": _Stats(caps_s=1.0)})
    assert reader.read({}) == pytest.approx(0.45)
    assert reader.note({}) == "9 reused, 11 built"
    # a program without the counters, or with nothing encoded, reads None
    monkeypatch.setattr(jax_backend, "_COMPILED", {"older": _Stats()})
    assert reader.read({}) is None
    monkeypatch.setattr(jax_backend, "_COMPILED", {
        "a": _Stats(encode_reuse_hits=0, encode_reuse_misses=0)})
    assert reader.read({}) is None
