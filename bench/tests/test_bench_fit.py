"""Each cell's train step, compiled for a described TPU v5e at the cell's
own sizes, fits one chip's memory.

No chip is needed: the installed TPU compiler compiles for the devices of
a described ``v5e:2x2`` topology and reports the program's memory. The
topology is described inside a module fixture, so only the worker that
runs this file loads the TPU compiler library. The state's shapes come from
the program's ``init_state``: the benchmark makes the same leaves with its
own weights.
"""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import ROOT

HBM_GIB = 15.75          # what a v5e chip gives a program


def _cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: c["file"] for c in bench["configs"]}
    return [(w["name"], cfgs[w["config"]], w["traffic"], w["chips"])
            for w in bench["workloads"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def compile_for_the_chip():
    """Steer the kernels to Mosaic, and keep the persistent cache off: a
    described-topology compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import ops
    prev_tpu, prev_cache = ops._on_tpu, jax.config.jax_enable_compilation_cache
    ops._on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    ops._on_tpu = prev_tpu
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


@pytest.mark.parametrize("name,cfg_file,traffic_name,chips", _cells(),
                         ids=[c[0] for c in _cells()])
def test_cell_step_fits_one_chip(topo, name, cfg_file, traffic_name, chips):
    from bench import lm_train
    from repro.train import train_loop

    cfg = json.loads((ROOT / cfg_file).read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{traffic_name}.json").read_text())
    pcfg = lm_train.program_config(cfg, traffic)
    state = jax.eval_shape(lambda: train_loop.init_state(pcfg))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"]), jnp.int32)}

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    if chips == 1:
        one = SingleDeviceSharding(topo.devices[0])
        step = jax.jit(train_loop.make_train_step(pcfg), donate_argnums=0)
        args = (placed(state, jax.tree.map(lambda _: one, state)),
                placed(batch, jax.tree.map(lambda _: one, batch)))
    else:
        from repro.launch import mesh as mesh_lib
        mesh = mesh_lib.make_mesh((chips, 1), ("data", "model"),
                                  devices=topo.devices[:chips])
        step, _, state_sh, batch_sh = train_loop.data_parallel_step(
            pcfg, mesh, state, batch)
        args = (placed(state, state_sh), placed(batch, batch_sh))
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 2 ** 30
    assert used <= HBM_GIB, f"{name}: {used:.2f} GiB on one chip"
