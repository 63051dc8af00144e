"""§Perf lever correctness: every sharding/dtype lever must be a pure
performance choice — model outputs (up to container rounding) unchanged."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import sharding
from repro.config import load_config
from repro.launch import mesh as mesh_lib
from repro.models import transformer
from repro.train import train_loop


def _mesh3():
    return mesh_lib.make_mesh((1, 1, 1), ("pod", "data", "model"))


def _rules(cfg, mesh, kind="train"):
    return mesh_lib.make_rules(cfg, mesh, kind)


def _logits(cfg, rules_extra=None):
    m = cfg.model
    mesh = _mesh3()
    rules = _rules(cfg, mesh)
    rules.update(rules_extra or {})
    params = transformer.init_params(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              m.vocab_size)
    with sharding.use_rules(mesh, rules):
        return jax.jit(lambda p, t: transformer.forward(p, m, tokens=t))(
            params, toks)


def test_pad_heads_identical_logits():
    """Padding heads to a multiple of the TP degree must not change math."""
    from repro.configs import get_smoke_config
    base = get_smoke_config("smollm-360m")   # 3 heads in the smoke config
    ref = _logits(base, {"#pad_heads_to": None})
    padded = _logits(base, {"#pad_heads_to": 8, "heads": ()})
    assert float(jnp.max(jnp.abs(ref - padded))) < 1e-3


def test_tp_reduce_bf16_close():
    from repro.configs import get_smoke_config
    base = get_smoke_config("granite-8b")
    ref = _logits(base, {"#tp_reduce_bf16": None})
    bf16 = _logits(base, {"#tp_reduce_bf16": True})
    # bf16 dot outputs round at ~2^-8 relative
    denom = jnp.maximum(jnp.abs(ref), 1.0)
    assert float(jnp.max(jnp.abs(ref - bf16) / denom)) < 0.1


def test_split_kv_decode_consistent():
    """decode_kv_shard=seq must reproduce the default decode logits."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("granite-8b")
    m = cfg.model
    params = transformer.init_params(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, m.vocab_size)
    full = transformer.forward(params, m, tokens=toks)

    mesh = _mesh3()
    cfg_seq = dataclasses.replace(
        cfg, mesh=dataclasses.replace(cfg.mesh, decode_kv_shard="seq"))
    rules = mesh_lib.make_rules(cfg_seq, mesh, "decode")
    caches = transformer.init_caches(m, 2, 9, dtype=jnp.float32)
    with sharding.use_rules(mesh, rules):
        dec = jax.jit(lambda p, t, c, i: transformer.decode_step(
            p, m, t, c, i))
        for t in range(9):
            logits, caches = dec(params, toks[:, t], caches, jnp.int32(t))
    assert float(jnp.max(jnp.abs(logits - full[:, -1]))) < 0.05


def test_containers_agree_at_wl8():
    """f32 / bf16 / int8 / int8_packed containers produce identical grids
    when WL<=8 (int8 exactness boundary)."""
    losses = {}
    for container in ("float32", "bfloat16", "int8", "int8_packed"):
        cfg = load_config("tiny", overrides=[
            f"quant.container_dtype={container}", "quant.max_wl=8",
            "quant.init_wl=8", "quant.init_fl=4"])
        state = train_loop.init_state(cfg)
        batch = train_loop.make_batch(cfg, 0)
        _, metrics = jax.jit(train_loop.make_train_step(cfg))(state, batch)
        losses[container] = float(metrics["loss"])
    ref = losses["float32"]
    for k, v in losses.items():
        assert abs(v - ref) < 5e-2, (k, losses)


def test_qsgd_shard_map_single_device():
    cfg = load_config("tiny", overrides=["train.qsgd_pod_compression=true"])
    mesh = _mesh3()
    rules = mesh_lib.make_rules(cfg, mesh, "train")
    with sharding.use_rules(mesh, rules):
        step = jax.jit(train_loop.make_train_step(cfg))
        state = train_loop.init_state(cfg)
        s2, m = step(state, train_loop.make_batch(cfg, 0))
    assert bool(jnp.isfinite(m["loss"]))


def test_make_rules_modes():
    granite = load_config("granite-8b")

    class M:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    pad = dataclasses.replace(
        granite, mesh=dataclasses.replace(granite.mesh,
                                          seq_shard_attn="pad"))
    r = mesh_lib.make_rules(pad, M(), "train")
    # granite has 32 heads → divisible → no padding requested
    assert r["#pad_heads_to"] is None
    arctic = load_config("arctic-480b")
    pad2 = dataclasses.replace(
        arctic, mesh=dataclasses.replace(arctic.mesh, seq_shard_attn="pad"))
    r2 = mesh_lib.make_rules(pad2, M(), "train")
    assert r2["#pad_heads_to"] == 64        # 56 → 64
    assert r2["heads"] == ("model",)
    # split-KV decode rules
    seq = dataclasses.replace(
        granite, mesh=dataclasses.replace(granite.mesh,
                                          decode_kv_shard="seq"))
    r3 = mesh_lib.make_rules(seq, M(), "decode")
    assert r3["kv_seq"] == ("model",) and r3["heads"] == ()
