"""The plain float32 reference (models/reference.py) against the system's
forward and train step, on the CPU at tiny sizes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import load_config
from repro.core import controller
from repro.core import fixed_point as fxp
from repro.models import reference, transformer
from repro.train import train_loop

KEY = jax.random.PRNGKey(5)


def _cfg(use_pallas=True):
    return load_config("smollm-360m", overrides=[
        f"quant.use_pallas={str(use_pallas).lower()}",
        "quant.container_dtype=int8_packed", "model.num_layers=2",
        "model.d_model=120", "model.num_heads=3", "model.num_kv_heads=1",
        "model.d_ff=200", "model.vocab_size=300", "train.seq_len=32",
        "train.global_batch=2", "train.accum_steps=1"])


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _words(cfg, state):
    key = jax.random.fold_in(state["rng"], state["step"])
    return controller.quantize_params_packed(state["params"], state["adapt"],
                                             cfg.quant, key)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("act_quant", [False, True])
def test_forward_matches_reference(use_pallas, act_quant):
    """Same words, same tokens: the system's bf16 forward (kernels or XLA
    dispatch) stays within bf16 distance of the float32 reference; a
    wrong layer would be off by order one."""
    cfg = _cfg(use_pallas)
    state = train_loop.init_state(cfg)
    tokens = train_loop.make_batch(cfg, 0)["tokens"]
    qp = _words(cfg, state)
    awl = transformer.act_wl_from_state(state["adapt"]) if act_quant \
        else None
    got = transformer.forward(qp, cfg.model, tokens=tokens, act_wl=awl,
                              use_pallas=use_pallas)
    with jax.default_matmul_precision("highest"):
        want = reference.forward(reference.dequantize(qp), cfg.model, tokens,
                                 awl["s0_attn"] if act_quant else None)
    assert got.shape == want.shape
    assert _rel(got, want) < 0.05


def test_step0_loss_matches_reference():
    cfg = _cfg()
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    qp = _words(cfg, state)
    awl = transformer.act_wl_from_state(state["adapt"])["s0_attn"]
    with jax.default_matmul_precision("highest"):
        want = reference.lm_loss(
            reference.forward(reference.dequantize(qp), cfg.model,
                              batch["tokens"], awl), batch["tokens"])
    _, metrics = jax.jit(train_loop.make_train_step(cfg))(state, batch)
    assert abs(float(metrics["loss"]) - float(want)) < 1e-2


def test_reference_detects_a_wrong_layer():
    cfg = _cfg()
    state = train_loop.init_state(cfg)
    tokens = train_loop.make_batch(cfg, 0)["tokens"]
    w = reference.dequantize(_words(cfg, state))
    good = reference.forward(w, cfg.model, tokens)
    wo = w["blocks"]["s0_attn"]["wo"]
    w["blocks"]["s0_attn"]["wo"] = wo[:, ::-1]
    assert _rel(reference.forward(w, cfg.model, tokens), good) > 0.3


def test_dequantize_is_exact():
    cfg = _cfg()
    state = train_loop.init_state(cfg)
    qp = _words(cfg, state)
    w = reference.dequantize(qp)
    leaf = qp["blocks"]["s0_mlp"]["wo"]
    fl = state["adapt"]["tensors"]["blocks/s0_mlp/wo"]["fl"]
    want = leaf["q8"].astype(jnp.float32) * fxp.pow2i(-fl)[:, None, None]
    np.testing.assert_array_equal(np.asarray(w["blocks"]["s0_mlp"]["wo"]),
                                  np.asarray(want))
    assert w["final_norm"].dtype == jnp.float32


def test_quantize_act_matches_system():
    x = jax.random.normal(KEY, (2, 8, 16)) * 3.0
    for wl in (4, 8, 12):
        np.testing.assert_array_equal(
            np.asarray(reference._quantize_act(x, jnp.int32(wl))),
            np.asarray(fxp.quantize_activation(x, jnp.int32(wl))))


@pytest.mark.parametrize("arch", ["gemma2-2b", "mixtral-8x22b"])
def test_unsupported_families_refused(arch):
    cfg = load_config(arch)
    with pytest.raises(ValueError, match="not a plain dense decoder"):
        reference.check_supported(cfg.model)


def test_smollm_and_tiny_supported():
    for arch in ("smollm-360m", "tiny"):
        reference.check_supported(load_config(arch).model)
    m = dataclasses.replace(load_config("tiny").model, use_qk_norm=True)
    with pytest.raises(ValueError):
        reference.check_supported(m)
