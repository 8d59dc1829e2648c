"""Distributed runtime: checkpoint/restart determinism, straggler
detection, gradient compression, elastic resharding, sharding rules."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import Checkpointer
from repro.distributed.fault_tolerance import StragglerPolicy, TrainingRunner
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state
from repro.train.train_step import quantize_int8


def _toy_state():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "b": jnp.ones((4,), jnp.bfloat16),
            "step": jnp.zeros((), jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    state = _toy_state()
    ck.save(10, state, blocking=True)
    restored, step = ck.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 10
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 state, restored)


def test_checkpoint_gc_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _toy_state(), blocking=True)
    assert ck.all_steps() == [3, 4]
    assert ck.latest_step() == 4


def test_checkpoint_tmp_dir_ignored(tmp_path):
    """A crashed mid-write .tmp dir must not be seen as a checkpoint."""
    ck = Checkpointer(str(tmp_path))
    ck.save(5, _toy_state(), blocking=True)
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert ck.latest_step() == 5


def _runner(tmp_path, fail_at=None):
    def step_fn(state, batch):
        w = state["w"] - 0.1 * batch["g"]
        loss = jnp.sum(w ** 2)
        return {"w": w}, {"loss": loss}

    def data_fn(step):
        k = jax.random.PRNGKey(step)   # pure function of step
        return {"g": jax.random.normal(k, (3,))}

    return TrainingRunner(step_fn, data_fn, Checkpointer(str(tmp_path)),
                          ckpt_every=4)


def test_fault_tolerant_restart_is_bitexact(tmp_path):
    init = {"w": jnp.ones((3,))}
    # uninterrupted run
    golden, _ = _runner(tmp_path / "a").run(init, 10)
    # crashed at step 7, then resumed from step 8's predecessor checkpoint
    r = _runner(tmp_path / "b")
    with pytest.raises(RuntimeError, match="injected failure"):
        r.run(init, 10, fail_at=7)
    resumed, _ = _runner(tmp_path / "b").run(init, 10)
    np.testing.assert_array_equal(np.asarray(golden["w"]),
                                  np.asarray(resumed["w"]))


def test_straggler_watchdog_flags_slow_steps():
    pol = StragglerPolicy(threshold=2.0, grace_steps=1)
    for s in range(8):
        pol.observe(s, 0.1)
    assert not pol.flagged
    pol.observe(8, 0.5)      # 5x the EMA
    assert pol.flagged and pol.flagged[0][0] == 8
    # EMA not polluted by the straggler
    assert abs(pol._ema - 0.1) < 1e-6


def test_int8_gradient_compression_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)) * 0.01, jnp.float32)
    err = jnp.zeros_like(g)
    # single-shot quantization loses precision; error feedback recovers the
    # mean over repeated steps (compression contract for DP all-reduce)
    acc_plain = jnp.zeros_like(g)
    acc_fb = jnp.zeros_like(g)
    for _ in range(50):
        q1, _ = quantize_int8(g, jnp.zeros_like(g))
        acc_plain += q1
        q2, err = quantize_int8(g, err)
        acc_fb += q2
    err_plain = float(jnp.max(jnp.abs(acc_plain / 50 - g)))
    err_fb = float(jnp.max(jnp.abs(acc_fb / 50 - g)))
    assert err_fb < err_plain * 0.5 or err_fb < 1e-5


def test_adamw_bf16_states_converge():
    opt = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                      total_steps=200, state_dtype="bfloat16")
    params = {"w": jnp.asarray([3.0, -2.0, 1.5])}
    state = init_opt_state(opt, params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}      # d/dw of w^2
        params, state = adamw_update(opt, params, grads, state)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.15
    assert state["m"]["w"].dtype == jnp.bfloat16


_SUBPROC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import AxisType, Mesh
from repro.distributed.sharding import params_shardings, set_activation_policy
from repro.distributed.elastic import reshard, validate_mesh_for, shrink_mesh
from repro.configs import get_config
from repro.models.model import init_params, loss_fn
from repro.data.pipeline import batch_for_step
from repro.configs.base import ShapeConfig

cfg = get_config("qwen3-0.6b", reduced=True)
params = init_params(cfg, jax.random.PRNGKey(0))

# sharding constraints consume these meshes: their axes must be Auto
auto = (AxisType.Auto,) * 2
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
assert not validate_mesh_for(params, mesh)
sh = params_shardings(params, mesh)
params = jax.device_put(params, sh)
set_activation_policy(mesh)

batch = batch_for_step(cfg, ShapeConfig("t", 32, 8, "train"), 0)
loss, grads = jax.jit(jax.value_and_grad(
    lambda p: loss_fn(cfg, p, batch)))(params)
assert np.isfinite(float(loss))

# elastic: move the whole state onto a different mesh layout
mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=auto)
params2 = reshard(params, mesh2)
l2 = jax.jit(lambda p: loss_fn(cfg, p, batch))(params2)
np.testing.assert_allclose(float(l2), float(loss), rtol=1e-3)

# shrink after losing a host (2 devices/host)
m3, data3 = shrink_mesh(mesh, failed_hosts=1, devices_per_host=2)
assert dict(m3.shape)["model"] == 2 and data3 == 3
print("SUBPROC_OK")
"""


def test_sharded_train_and_elastic_reshard_8dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROC_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert "SUBPROC_OK" in out.stdout, out.stderr[-2000:]
