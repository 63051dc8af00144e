"""Data-parallel training over a mesh (``train_loop.data_parallel_step``).

The four-device assertions need ``XLA_FLAGS=
--xla_force_host_platform_device_count=4`` set before jax is imported; in a
single-device session they skip and a subprocess shim re-runs this module
with the flag set.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jaxpr_tools
from repro.config import load_config
from repro.launch import mesh as mesh_lib
from repro.train import train_loop

N_DEV = jax.device_count()
multi = pytest.mark.skipif(
    N_DEV < 4, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


def _cfg(*extra):
    return load_config("tiny", overrides=[
        "quant.use_pallas=true", "quant.container_dtype=int8_packed",
        "train.seq_len=32", "train.global_batch=4", "train.accum_steps=1",
        "train.adapt_interval=2", "train.log_every=1", *extra])


def _losses(history):
    return [h["loss"] for h in history]


def _assert_same_trajectory(a, b):
    # tests/test_dense_path.py's trajectory tolerances
    np.testing.assert_allclose(_losses(a), _losses(b), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose([h["grad_norm"] for h in a],
                               [h["grad_norm"] for h in b],
                               rtol=2e-2, atol=2e-2)


def test_one_device_mesh_matches_plain_loop():
    cfg = _cfg()
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    _, dp = train_loop.train(cfg, steps=3, mesh=mesh, log=lambda s: None)
    _, plain = train_loop.train(cfg, steps=3, log=lambda s: None)
    assert _losses(dp) == _losses(plain)


def test_size_one_axes_leave_parameters_replicated():
    """A size-1 model axis splits nothing; naming it would move leaves onto
    the shard_map quantize with a folded seed (another SR stream)."""
    cfg = _cfg()
    mesh = mesh_lib.make_cpu_mesh()
    state = jax.eval_shape(lambda: train_loop.init_state(cfg))
    sh = mesh_lib.state_shardings(state, cfg, mesh)
    assert all(a is None for s in jax.tree.leaves(sh["params"])
               for a in s.spec)


def test_step_runs_kernels_inside_shard_map():
    cfg = _cfg()
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    state = jax.eval_shape(lambda: train_loop.init_state(cfg))
    batch = jax.eval_shape(lambda: train_loop.make_batch(cfg, 0))
    step, switch, _, _ = train_loop.data_parallel_step(cfg, mesh, state,
                                                       batch)
    jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    assert jaxpr_tools.count_primitives(jaxpr, "shard_map") == 1
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_fxp_matmul_kernel") > 0
    assert jaxpr_tools.count_primitives(jaxpr, "psum") > 0
    sj = jax.make_jaxpr(switch)(state).jaxpr
    assert jaxpr_tools.count_pallas_calls(sj, "_edf_ladder_kernel") > 0


@multi
def test_sharded_state_refused():
    cfg = _cfg()
    mesh = mesh_lib.make_mesh((1, 4), ("data", "model"), jax.devices()[:4])
    state = jax.eval_shape(lambda: train_loop.init_state(cfg))
    batch = jax.eval_shape(lambda: train_loop.make_batch(cfg, 0))
    with pytest.raises(ValueError, match="needs them replicated"):
        train_loop.data_parallel_step(cfg, mesh, state, batch)


@multi
@pytest.mark.parametrize("extra", [(), ("quant.stochastic_rounding=false",),
                                   ("quant.use_pallas=false",)])
def test_four_way_matches_one_device(extra):
    """Same global batch on (4, 1) and on one device: the quantize draws the
    same words from replicated weights, gradients are averaged over equal
    shards, so the trajectories agree (precision switches included)."""
    cfg = _cfg(*extra)
    mesh = mesh_lib.make_mesh((4, 1), ("data", "model"), jax.devices()[:4])
    state, dp = train_loop.train(cfg, steps=3, mesh=mesh, log=lambda s: None)
    _, one = train_loop.train(cfg, steps=3, log=lambda s: None)
    _assert_same_trajectory(dp, one)
    leaf = state["params"]["head"]
    assert leaf.sharding.is_fully_replicated
    assert len(leaf.sharding.device_set) == 4
    assert all(bool(jnp.isfinite(h["loss"])) for h in dp)


@multi
def test_gradient_exchange_carries_grad_sync_scope():
    """Every all-reduce of the compiled step is the gradient exchange, and
    carries the ``adapt.grad_sync`` scope a trace reads it by."""
    cfg = _cfg()
    mesh = mesh_lib.make_mesh((4, 1), ("data", "model"), jax.devices()[:4])
    state = jax.eval_shape(lambda: train_loop.init_state(cfg))
    batch = jax.eval_shape(lambda: train_loop.make_batch(cfg, 0))
    step, _, _, _ = train_loop.data_parallel_step(cfg, mesh, state, batch)
    text = step.lower(state, batch).compile().as_text()
    reduces = [l for l in text.splitlines()
               if " all-reduce(" in l or " all-reduce-start(" in l]
    assert reduces
    assert all("adapt.grad_sync" in l for l in reduces), reduces[0][-300:]


@pytest.mark.skipif(
    N_DEV >= 4 or os.environ.get("GITHUB_ACTIONS") == "true",
    reason="already running multi-device, or CI (the multidevice-4 matrix "
           "entry runs this module with four devices)")
def test_multidevice_suite_in_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
