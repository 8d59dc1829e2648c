"""A configuration brings its engine and its operand generators as files:
a configuration added in a copy of the benchmark by new files alone runs
correct through the harness, whatever kind of engine ``SamServer``
serves; the dispatch log sees every dispatch of each kind; the operands
of the existing configurations are those their streams gave before
generators were files; and the memory reading is the fullest chip's."""
import hashlib
import json
import shutil
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from benchlib import load, operands, spec, tiny  # noqa: E402
from benchlib.window import DispatchLog  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 23

# -- the files a later configuration would add --------------------------

TILED_ENGINE = '''
"""Out-of-core SpMM: ``compile_expr`` under the configuration's
``mem_budget``, which routes it to a ``TiledExpr`` (the ``seq`` kind)."""
from benchlib.load import program


def build(config):
    p = program()
    return p.compile_expr(config["expr"], p.Format(dict(config["formats"])),
                          p.Schedule(loop_order=tuple(config["order"])),
                          dict(config["dims"]),
                          mem_budget=config["mem_budget"])


def arrays(ops):
    return {name: op.to_dense() for name, op in ops.items()}


def request(config, arrays):
    return program().Request(config["expr"], arrays,
                             dims=dict(config["dims"]))


def answer(result):
    return result.to_dense()
'''

PROGRAM_ENGINE = '''
"""A fused two-stage chain built by ``compile_program`` (the ``program``
kind); the answer is the last stage's output."""
from benchlib.load import program


def build(config):
    p = program()
    schedules = {lhs: p.Schedule(loop_order=tuple(order))
                 for lhs, order in config["orders"].items()}
    return p.compile_program(config["expr"],
                             p.Format(dict(config["formats"])), schedules,
                             dict(config["dims"]))


def arrays(ops):
    return {name: op.to_dense() for name, op in ops.items()}


def request(config, arrays):
    return program().Request(config["expr"], arrays,
                             dims=dict(config["dims"]))


def answer(result):
    return result["x"].to_dense()
'''

POWERLAW_GENERATOR = '''
"""A matrix whose row r holds nonzeros in proportion to (r + 1) ** -alpha
(at least one, at most a full row), at distinct uniform columns, values
uniform in [-1, 1] (never 0)."""
import numpy as np

from benchlib.operands import Operand


def draw(spec, gen):
    n_rows, n_cols = (int(d) for d in spec["shape"])
    weights = np.arange(1, n_rows + 1, dtype=float) ** -float(spec["alpha"])
    per_row = np.clip(np.round(weights / weights.sum() * int(spec["nnz"])),
                      1, n_cols).astype(int)
    rows = np.repeat(np.arange(n_rows), per_row)
    cols = np.concatenate([np.sort(gen.choice(n_cols, k, replace=False))
                           for k in per_row])
    vals = gen.uniform(-1.0, 1.0, rows.size).astype(
        np.dtype(spec.get("dtype", "float32")))
    vals[vals == 0] = 1
    return Operand((n_rows, n_cols), None, (rows, cols, vals))
'''

CHAIN_REFERENCE = '''
"""Plain reference of ``T(i,k) = B(i,j) * C(j,k); x(i) = T(i,k) * d(k)``:
B (C d) in float64 from the COO triplets of B and C."""
import numpy as np
import scipy.sparse as sp


def _csr(op):
    rows, cols, vals = op.coo
    return sp.csr_matrix((vals.astype(np.float64), (rows, cols)),
                         shape=op.shape)


def reference(ops):
    return _csr(ops["B"]) @ (_csr(ops["C"]) @ ops["d"].dense.astype(
        np.float64))
'''

CHAIN_WORK = '''
"""Work of one chain request: a multiply and an add per nonzero of B and
of C in the two products, and each input and the output once, 4 bytes
a value or coordinate."""


def work(ops):
    B, C, d = ops["B"], ops["C"], ops["d"]
    nnz = len(B.coo[0]) + len(C.coo[0])
    return 2 * nnz, 8 * nnz + 4 * (d.shape[0] + B.shape[0])
'''


def _sparse(nnz, shape, share, kind="sparse", **extra):
    return dict(kind=kind, shape=shape, nnz=nnz, dtype="float32",
                share=share, **extra)


def _config(name, body, ops_tiny):
    body = dict(name=name, source="a test", limits={"max_rel_err": 1e-4},
                reduced=[], **body)
    body["tiny"] = {"dims": body["dims"], "operands": ops_tiny}
    return body


TILED = _config("spmm-tiled", {
    "expr": "X(i,j) = B(i,k) * C(k,j)", "formats": {"B": "cc", "C": "cc"},
    "order": "ikj", "dims": {"i": 24, "k": 24, "j": 24}, "mem_budget": 3520,
    "operands": {"B": _sparse(60, [24, 24], "shared", "powerlaw_rows",
                              alpha=1.0),
                 "C": _sparse(40, [24, 24], "fresh")},
    "engine": "tiled", "work": "spmm", "reference": "spmm"},
    {"B": {"shape": [24, 24], "nnz": 60}, "C": {"shape": [24, 24],
                                             "nnz": 40}})

CHAIN = _config("chain-program", {
    "expr": "T(i,k) = B(i,j) * C(j,k); x(i) = T(i,k) * d(k)",
    "formats": {"B": "cc", "C": "cc", "T": "cc", "d": "d", "x": "c"},
    "orders": {"T": "ijk", "x": "ik"}, "dims": {"i": 12, "j": 12, "k": 12},
    "operands": {"B": _sparse(30, [12, 12], "shared"),
                 "C": _sparse(30, [12, 12], "shared"),
                 "d": {"kind": "dense", "shape": [12], "dtype": "float32",
                       "share": "fresh"}},
    "engine": "program_chain", "work": "chain", "reference": "chain"},
    {"B": {"shape": [12, 12], "nnz": 30}, "C": {"shape": [12, 12],
                                             "nnz": 30}, "d": {"shape": [12]}})

ADDED = {
    "engines/tiled.py": TILED_ENGINE,
    "engines/program_chain.py": PROGRAM_ENGINE,
    "generators/powerlaw_rows.py": POWERLAW_GENERATOR,
    "reference/chain.py": CHAIN_REFERENCE,
    "work/chain.py": CHAIN_WORK,
    "configs/spmm-tiled.json": json.dumps(TILED),
    "configs/chain-program.json": json.dumps(CHAIN),
    "traffic/clients3.json": json.dumps({"clients": 3, "max_batch": 2}),
}


def _hashes(bench):
    return {str(p.relative_to(bench)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(bench.rglob("*")) if p.is_file()}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A copy of the benchmark with the two configurations added by new
    files and new ``BENCHMARK.json`` entries alone; the hashes of the
    copy's files from before."""
    root = tmp_path_factory.mktemp("added")
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    before = _hashes(bench)
    for rel, text in ADDED.items():
        path = bench / rel
        assert not path.exists(), rel
        path.write_text(textwrap.dedent(text).lstrip())
    base = spec.benchmark()
    entries = [
        {"name": c["name"], "source": c["source"],
         "file": f"bench/configs/{c['name']}.json", "reduced": [],
         "why": "a test"} for c in (TILED, CHAIN)]
    cells = [{"name": f"{c['name']}.clients3", "config": c["name"],
              "traffic": "clients3", "chips": 1, "why": "a test"}
             for c in (TILED, CHAIN)]
    (root / "BENCHMARK.json").write_text(json.dumps(dict(
        base, configs=base["configs"] + entries,
        workloads=base["workloads"] + cells)))
    return root, bench, before


def _unchanged(bench, before):
    now = _hashes(bench)
    return {rel: now.get(rel) for rel in before if now.get(rel) !=
            before[rel]}


@pytest.mark.parametrize("config, kind", [("spmm-tiled", "seq"),
                                          ("chain-program", "program")])
def test_a_configuration_added_by_files_alone_runs_correct(added, config,
                                                           kind):
    root, bench, before = added
    cell = spec.cell(f"{config}.clients3", spec.benchmark(root), bench)
    engine = cell.module("engines", cell.config["engine"])
    eng = engine.build(cell.config)
    assert load.program().engine_kind(eng) == kind
    if kind == "seq":
        assert eng.n_tiles >= 2
    rec = load.run(cell, SEED, 1.0, trace=False, t_process=0.0)
    assert rec["correct"] is True, rec["checks"]
    assert rec["failed"] == 0
    w = rec["window"]
    assert w["dispatches"] >= 1 and w["requests"] >= w["dispatches"]
    assert w["encode_s"] is None and w["decode_s"] is None
    assert len(rec["memory_peak_bytes_per_chip"]) == cell.chips
    rec["peaks"] = spec.peaks("TPU v5 lite")
    line = run.result_line(cell, rec, CPU, trace=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"req_per_s", "setup_s"}
    traced = run.result_line(cell, rec, CPU, trace=True)["metrics"]
    # no encode or decode stage of its own: those readers return nothing
    assert "encode.ms_per_req" not in traced
    assert "decode.ms_per_req" not in traced
    assert traced["serving.batch_occupancy"]["value"] >= 1
    assert _unchanged(bench, before) == {}


def test_the_added_configurations_cut_to_their_tiny_size(added):
    _, bench, _ = added
    assert {"spmm-tiled", "chain-program"} <= set(tiny.configs(bench))
    for name in ("spmm-tiled", "chain-program"):
        config = spec.load_json(bench / "configs" / f"{name}.json")
        assert tiny.cut(config)["operands"] == config["operands"]


# -- the dispatch log of each engine kind -------------------------------

@pytest.mark.parametrize("config, kind, key", [
    ("spmv-rail507", "batch", "c"), ("spmm-tiled", "seq", "C"),
    ("chain-program", "program", "d")])
def test_instrument_logs_every_dispatch_with_its_width(added, config, kind,
                                                       key):
    """Six requests, ``max_batch`` 4, the server in sync mode: two
    dispatches, of 4 and 2. The ``program`` kind calls the engine once
    per member, so its log holds one dispatch of one per member."""
    _, bench, _ = added
    body = tiny.cut(spec.load_json(bench / "configs" / f"{config}.json"))
    engine = spec.module("engines", body["engine"], bench)
    p = load.program()
    eng = engine.build(body)
    cls = type(eng)
    assert p.engine_kind(eng) == kind
    shared = engine.arrays(operands.draw_all(body, "shared", 1, 0,
                                             bench=bench))
    batch = [{**shared, **engine.arrays(operands.draw_all(
        body, "fresh", 1, 2, 0, m, bench=bench))} for m in range(6)]
    srv = p.SamServer(sync=True, max_batch=4)
    log = DispatchLog()
    with load.Instrument(eng, kind, log, key) as inst:
        assert isinstance(eng, cls)
        handles = srv.submit_many([engine.request(body, a) for a in batch],
                                  engine=eng)
        srv.flush()
        seqs = [inst.seq_of.pop(id(a[key])) for a in batch]
    assert all(h.exception(timeout=60) is None for h in handles)
    assert srv.stats()["dispatches"] == 2
    widths = [d.n for d in log.done]
    if kind == "program":
        assert widths == [1] * 6 and seqs == list(range(6))
    else:
        assert widths == [4, 2] and seqs == [0] * 4 + [1] * 2
    staged = kind == "batch"
    assert all((d.encode_s is not None) == staged and
               (d.decode_s is not None) == staged for d in log.done)
    # the engine is as it was
    assert type(eng) is cls
    assert not {"encode_batch", "execute_encoded", "decode_batch",
                "execute_batch", "execute_many"} & set(vars(eng))


class ManyEngine:
    """Stands in for a lane-sharded ``CompiledExpr`` (the ``many`` kind,
    which needs a device mesh): answers each request with its ``c``."""

    def execute_many(self, arrays_list):
        return [a["c"] for a in arrays_list]


def test_instrument_logs_a_many_dispatch_with_its_width():
    eng, log = ManyEngine(), DispatchLog()
    batch = [{"c": object()} for _ in range(3)]
    with load.Instrument(eng, "many", log, "c") as inst:
        assert eng.execute_many(batch) == [a["c"] for a in batch]
        assert eng.execute_many(batch[:1]) == [batch[0]["c"]]
    assert [(d.seq, d.n, d.encode_s, d.decode_s) for d in log.done] == [
        (0, 3, None, None), (1, 1, None, None)]
    assert inst.seq_of[id(batch[0]["c"])] == 1
    assert "execute_many" not in vars(eng)


# -- the operands did not move -----------------------------------------

# sha256 over each operand's dense array and then its COO arrays (dtype,
# shape and bytes of each), as drawn before generators were files
PARENT_DIGESTS = {
    "full/spmv-rail507/2147483653/B": "ea95c9f4d2355fe49153160048ab6c9c7209001b0d55cb5f03f69adf5ea0b1d1",  # noqa: E501
    "full/spmv-rail507/2147483653/c": "7fb03bd2b2080171c54998ca808124a0478ad256152bb4861889fcab29a6c5eb",  # noqa: E501
    "full/spmv-rail507/4000000007/B": "d5e1b1d48a080765d8d024a519dea7e5f4d5cd341b06525ef9839c676b5e24d4",  # noqa: E501
    "full/spmv-rail507/4000000007/c": "4c7f8c3824041fcd8c076ba84a60aa0d6130e0ec114bff529c4d9c8e304c44be",  # noqa: E501
    "full/spmm-g42/2147483653/B": "431403cf1aaac44dbf15978372d0ed709bda13d4a4da5ba8934dc5a76c8c439f",  # noqa: E501
    "full/spmm-g42/2147483653/C": "46cab24c41b6b8e0f5a993bfbd9d8c9a0e7d3aae18eedc9a4a52e32ace8e4861",  # noqa: E501
    "full/spmm-g42/4000000007/B": "c95d0cd90e4a0ffd74bd3d7463ac7bf3e453aec1b5bed59f81f5413af3be92ca",  # noqa: E501
    "full/spmm-g42/4000000007/C": "82098279a8b7cbd2b490f415fce46f838ddc39733a5f296e81f57fb242eab887",  # noqa: E501
    "tiny/spmv-rail507/2147483653/B": "cbf4654ca266ff12e1e2540b2a795bcb8ff3757770b3db47b20e87be055ed587",  # noqa: E501
    "tiny/spmv-rail507/2147483653/c": "e5048095e83485d3dc79e0ccf73d6e27caefb4e67c0915d6506f2202f97c4c7d",  # noqa: E501
    "tiny/spmv-rail507/4000000007/B": "22b3b3764c915f9b98c3fedf41e45a86a200e89e5e250c2a558d963386bba30a",  # noqa: E501
    "tiny/spmv-rail507/4000000007/c": "994adb1f627a8e9b5a790fe6867eeda45c49dd1b3234e8ab0604fda7355d9289",  # noqa: E501
    "tiny/spmm-g42/2147483653/B": "b1347ae1f96908ad727926c83b66515e282684490c68d625403dd3cc7b1bd720",  # noqa: E501
    "tiny/spmm-g42/2147483653/C": "ce241ecca43399871146c72ec234c3611df728a7e162a4ea1655656d0905dcae",  # noqa: E501
    "tiny/spmm-g42/4000000007/B": "d8c5d0ecbed1862db9b2d2ccda51634df6e2873320f8942c161c54994fe076d8",  # noqa: E501
    "tiny/spmm-g42/4000000007/C": "2c106df44224750a087c4d457267fc6437fa85ae16141417f918fceba43fb532",  # noqa: E501
}


def _digest(op):
    h = hashlib.sha256()
    for a in [op.to_dense(), *(op.coo or ())]:
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("size", ["full", "tiny"])
@pytest.mark.parametrize("config", ["spmv-rail507", "spmm-g42"])
def test_operands_are_those_drawn_before_generators_were_files(size,
                                                                config):
    body = spec.load_json(BENCH / "configs" / f"{config}.json")
    if size == "tiny":
        body = tiny.cut(body)
    for seed in (2**31 + 5, 4_000_000_007):
        ops = {**operands.draw_all(body, "shared", seed, operands.SHARED),
               **operands.draw_all(body, "fresh", seed, operands.FRESH,
                                   3, 1)}
        for name, op in ops.items():
            assert _digest(op) == \
                PARENT_DIGESTS[f"{size}/{config}/{seed}/{name}"], name
            if op.coo is not None:
                assert op.dense is None      # a generator builds no dense


# -- memory over the cell's chips --------------------------------------

class FakeDevice:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        if self.peak is None:
            return None
        return {"bytes_in_use": 1, "peak_bytes_in_use": self.peak}


def test_memory_peak_is_the_fullest_chips():
    per_chip = load.memory_peaks([FakeDevice(p) for p in
                                  (5 << 20, 9 << 20, 7 << 20, None)])
    assert per_chip == [5 << 20, 9 << 20, 7 << 20, None]
    assert load.fullest(per_chip) == 9 << 20
    assert load.fullest(load.memory_peaks([FakeDevice(3)])) == 3
    assert load.fullest([None]) is None
