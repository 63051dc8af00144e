"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of the tiny cell on the CPU (the look for a
chip skipped) with the train step broken underneath, and sees ``correct``
come out false; the sound step comes out true. The control — the program's
own 4-bit path (``controller.clamp_adapt_state``) — comes out false too,
and so does a precision switch that sets a wrong FL.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT, make_root, run_cell


def stuck(step, cell):
    """A step that returns its state unchanged."""
    def run(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return run


def half_batch(step, cell):
    """Half of the batch left out, the mean taken over the rest."""
    from repro.train import train_loop
    pcfg = cell.pcfg
    half = dataclasses.replace(pcfg, train=dataclasses.replace(
        pcfg.train, global_batch=cell.batch // 2))
    fn = jax.jit(train_loop.make_train_step(half), donate_argnums=0)
    return lambda state, batch: fn(
        state, {"tokens": batch["tokens"][:cell.batch // 2]})


def control(step, cell):
    """Every word length clamped to 4 bits by the program's own path."""
    from repro.core import controller
    clamp = jax.jit(lambda s: dict(s, adapt=controller.clamp_adapt_state(
        s["adapt"], 4)))
    return lambda state, batch: step(clamp(state), batch)


def wrong_fl(switch, cell):
    """A precision switch that sets the last layer's FL of one tensor one
    bit off."""
    def run(state):
        out = switch(state)
        tensors = dict(out["adapt"]["tensors"])
        p = cell.quantized[0]
        tensors[p] = dict(tensors[p], fl=tensors[p]["fl"].at[..., -1].add(1)
                          if tensors[p]["fl"].ndim else tensors[p]["fl"] + 1)
        return dict(out, adapt=dict(out["adapt"], tensors=tensors))
    return run


@pytest.mark.parametrize("fault", [None, stuck, half_batch, control],
                         ids=["sound", "stuck", "half_batch", "control"])
def test_broken_step_is_not_correct(tmp_path, capsys, no_compile_cache,
                                    fault):
    root = make_root(tmp_path)
    result, err = run_cell(root, capsys, step_wrapper=fault)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check change ")
    assert result["checks"]["switch"]["value"] == 0


def test_wrong_switch_is_not_correct(tmp_path, capsys, no_compile_cache):
    root = make_root(tmp_path)
    result, _ = run_cell(root, capsys, switch_wrapper=wrong_fl)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["switch"] == {"value": 1, "limit": 0}
    for n in ("loss", "grad", "grad_median", "change"):
        assert result["checks"][n]["value"] <= result["checks"][n]["limit"]


def test_setup_switch_leaves_the_state_as_it_was(no_compile_cache):
    """The switch run in set-up moves every tensor's precision and hands
    the window the controller state of the first steps unchanged."""
    import json
    from bench import lm_train
    cfg = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=2, vocab_size=256, program=[
                   o.replace("use_pallas=true", "use_pallas=false")
                   for o in cfg["program"]])
    traffic = {"kind": "lm_train", "seq_len": 64, "global_batch": 8,
               "data_parallel": 1, "adapt_interval": 4, "log_every": 2,
               "noise": 0.05}
    cell = lm_train.Cell(cfg, traffic)
    state, _ = cell.first_steps(cell.fresh_state(5), 5)
    before = jax.device_get(state)
    state, words = cell.check_switch(state)
    after = jax.device_get(state)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), before, after))
    assert words["program"] == words["xla"]
    assert sorted(words["xla"]) == cell.quantized


DP_SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    import jax
    from jax.sharding import PartitionSpec as P
    from bench import run
    run.use_cache = lambda root: None
    from repro import sharding
    from repro.train import train_loop

    def no_exchange(step, cell):
        fn = sharding.shard_map(
            train_loop.make_train_step(cell.pcfg, dp_axes=()), cell.mesh,
            axis_names=set(cell.mesh.axis_names),
            in_specs=(P(), P(("data",))), out_specs=(P(), P()))
        return jax.jit(fn, in_shardings=(cell.state_sh, cell.batch_sh),
                       out_shardings=(cell.state_sh, None),
                       donate_argnums=0)

    for wrapper in (None, no_exchange):
        rc = run.main(["--workload", "tiny.mix", "--seed", "11",
                       "--seconds", "1"], root={root!r},
                      require_accelerator=False, step_wrapper=wrapper)
        assert rc == 0
""")


def test_exchange_left_out_is_not_correct(tmp_path):
    """On four CPU devices: the data-parallel step is correct, and the same
    step without its gradient all-reduce is not."""
    root = make_root(tmp_path, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = DP_SCRIPT.format(root=str(root), src=str(ROOT / "src"),
                              tests=str(ROOT / "bench" / "tests"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = [json.loads(l) for l in proc.stdout.splitlines()
               if l.startswith("{")]
    assert [r["correct"] for r in results] == [True, False], \
        [r["checks"] for r in results]
    assert all(r["device"]["count"] == 4 for r in results)
