"""The control: the plain reference computed in bfloat16, the nearest
precision below the float32 the configurations state, put in the
program's place, comes out not correct; the program itself is correct.
On the chip the same readings are taken at the cells' own sizes by
``bench/calibrate.py``."""
import sys
import threading
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import check, load, operands, spec, tiny  # noqa: E402
from benchlib.operands import Operand  # noqa: E402

load.program()
from repro.core.fibertree import FiberTree  # noqa: E402
from repro.core.jax_backend import CompiledExpr  # noqa: E402


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.tiny_bench(tmp_path_factory.mktemp("control") / "bench")


@pytest.mark.parametrize("config", tiny.configs())
def test_control_in_the_programs_place_is_not_correct(bench, config,
                                                      monkeypatch):
    cell = tiny.tiny_cell(bench, config)
    control = cell.module("reference", cell.config["reference"]).control
    encode, decode = CompiledExpr.encode_batch, CompiledExpr.decode_batch
    served = threading.current_thread

    def keep_operands(self, arrays_list):
        enc = encode(self, arrays_list)
        enc.operands = [{n: Operand(a.shape, a) for n, a in arrays.items()}
                        for arrays in arrays_list]
        return enc

    def control_decode(self, enc, out):
        results = decode(self, enc, out)
        if served().name != "sam-serve-decode":
            return results
        return [FiberTree.from_dense(control(ops), "c" * r.order)
                for ops, r in zip(enc.operands, results)]

    monkeypatch.setattr(CompiledExpr, "encode_batch", keep_operands)
    monkeypatch.setattr(CompiledExpr, "decode_batch", control_decode)
    rec = load.run(cell, 4_000_000_001, 1.0, trace=False, t_process=0.0)
    err = rec["checks"]["max_rel_err"]
    assert rec["failed"] == 0
    assert rec["correct"] is False
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("config", tiny.configs())
def test_control_reads_far_above_the_program(config):
    """Directly on one tiny operand set: the program's float32 answer,
    served through a synchronous server, reads well under the limit, the
    control's well over it."""
    bench_config = tiny.cut(
        spec.load_json(spec.BENCH / "configs" / f"{config}.json"))
    ref = spec.module("reference", bench_config["reference"])
    engine = spec.module("engines", bench_config["engine"])
    ops = {name: operands.draw(o, operands.rng(5, 9, i))
           for i, (name, o) in enumerate(bench_config["operands"].items())}
    truth = ref.reference(ops)
    eng = engine.build(bench_config)
    served = load.serve_sync(eng, engine, bench_config,
                             [engine.arrays(ops)])
    limit = bench_config["limits"]["max_rel_err"]
    assert check.rel_error(engine.answer(served[0]), truth) < limit / 10
    assert check.rel_error(ref.control(ops), truth) > limit * 3
