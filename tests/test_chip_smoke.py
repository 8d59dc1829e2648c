"""``chip_smoke.py`` on the CPU, at tiny sizes.

The smoke's phases drive the real serving path; here they run with the
coord_ops fallbacks (the CPU resolves no Pallas entry) on operands small
enough for a unit test. The grading that decides the smoke's exit code
must reject a wrong result and count a failed request, and ``main`` must
refuse to run, and print no result, off the chip. Everything runs in
this process: nothing here starts a child or describes a TPU.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


class _Handle:
    """The two methods of a ``ResultHandle`` that grading reads."""

    def __init__(self, result=None, error=None):
        self._result, self._error = result, error

    def exception(self):
        return self._error

    def result(self):
        if self._error is not None:
            raise self._error
        return self._result


def test_main_refuses_a_non_tpu_platform(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "'cpu'" in err


def test_spmv_phase_serves_every_request_correctly():
    r = smoke.spmv_phase(np.random.default_rng(1), shape=(12, 40), nnz=90,
                         n_requests=16, max_batch=8)
    assert (r["completed"], r["failed"], r["wrong"]) == (16, 0, 0)
    assert r["dispatches"] == 2 and r["compiles_warm"] == 0
    assert r["max_rel_err"] <= smoke.REL_TOL
    assert smoke.serving_ok(r)
    # interpret-mode Pallas is no tpu_custom_call: the chip's check fails
    assert not r["kernel"] and not smoke.serving_ok(r, need_kernel=True)


def test_spmm_phase_serves_every_request_correctly():
    r = smoke.spmm_phase(np.random.default_rng(2), shape=(16, 16), nnz=40,
                         n_requests=16, max_batch=8)
    assert (r["completed"], r["failed"], r["wrong"]) == (16, 0, 0)
    assert r["dispatches"] == 2
    assert r["max_rel_err"] <= smoke.REL_TOL
    assert smoke.serving_ok(r)


def test_grading_rejects_a_corrupted_result():
    x = np.arange(1.0, 6.0)
    good = _Handle(result=type("FT", (), {"to_dense": lambda s: x})())
    bad_x = x.copy()
    bad_x[2] *= 1 + 10 * smoke.REL_TOL
    bad = _Handle(result=type("FT", (), {"to_dense": lambda s: bad_x})())
    r = smoke.grade([good, bad], lambda i: x)
    assert (r["completed"], r["failed"], r["wrong"]) == (2, 0, 1)
    assert r["max_rel_err"] > smoke.REL_TOL
    nan_x = np.full_like(x, np.nan)
    r = smoke.grade([_Handle(result=type("FT", (), {
        "to_dense": lambda s: nan_x})())], lambda i: x)
    assert r["wrong"] == 1
    assert smoke.rel_error(x[:4], x) == float("inf")


def test_a_failed_request_fails_the_phase():
    rng = np.random.default_rng(3)
    B, _ = smoke.random_sparse(rng, (6, 9), 20)
    fmt = smoke.Format({"B": "cc", "c": "d"})
    dims = {"i": 6, "j": 9}
    eng = smoke.compile_expr(smoke.SPMV, fmt, smoke.SPMV_SCHEDULE, dims)
    cs = [rng.standard_normal(9) for _ in range(3)]
    arrays = [{"B": B, "c": c} for c in cs]
    arrays[1] = {"B": B}                            # c is missing
    r = smoke.serve(eng, smoke.SPMV, fmt, dims, arrays,
                    lambda i: B.astype(np.float64) @ cs[i], max_batch=1)
    assert r["failed"] == 1 and r["completed"] == 2
    assert not smoke.serving_ok(r)
    assert smoke.grade([_Handle(error=RuntimeError("boom"))],
                       lambda i: None)["failed"] == 1


def test_random_sparse_has_exactly_nnz_distinct_nonzeros():
    dense, (rows, cols, vals) = smoke.random_sparse(
        np.random.default_rng(4), (30, 50), 200)
    assert dense.dtype == np.float32 and np.count_nonzero(dense) == 200
    np.testing.assert_array_equal(dense[rows, cols], vals)


def test_table3_operands_come_from_the_figure_table():
    from benchmarks.fig14 import MATRICES
    assert ("rail507", (507, 63516), 409856) in MATRICES
    assert smoke.TABLE3["rail507"] == ((507, 63516), 409856)
    assert smoke.TABLE3["G42"] == ((2000, 2000), 23558)


@pytest.fixture
def restore_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield cc
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
    cc.reset_cache()


def test_compile_cache_lands_in_the_env_directory(
        monkeypatch, tmp_path, restore_compile_cache):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    restore_compile_cache.reset_cache()
    jax.jit(lambda v: v * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_the_ignored_checkout_directory(
        monkeypatch, restore_compile_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
