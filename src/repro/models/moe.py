"""Mixture-of-Experts FFN: top-k routing with capacity-bounded einsum
dispatch (mesh-TF style) — fully shardable: expert dim over the `model`
axis (EP) when divisible, else ff-dim TP inside each expert.

mixtral-8x22b: 8 experts top-2; arctic-480b: 128 experts top-2 *plus* a
parallel dense residual FFN (its "dense-MoE hybrid").

The router stays float32 and is excluded from AdaPT quantization
(DESIGN.md §4): top-k indices are discontinuous in the logits, so routing
flips under quantization noise destabilize training for no byte savings
(router is ~d_model×E ≈ 10⁻⁵ of parameters).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro import sharding
from repro.config import ModelConfig
from repro.models import common

Array = jax.Array

def init_layer(key: Array, cfg: ModelConfig, num_layers: int) -> Dict[str, Array]:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = cfg.num_experts
    ks = jax.random.split(key, 5)
    L = (num_layers,) if num_layers > 0 else ()
    p = {
        "router": common.init_dense(ks[0], L + (d, e)),
        "we_gate": common.init_dense(ks[1], L + (e, d, f)),
        "we_up": common.init_dense(ks[2], L + (e, d, f)),
        "we_down": common.init_dense(ks[3], L + (e, f, d)),
        "pre_norm": jnp.zeros(L + (d,), jnp.float32),
    }
    if cfg.dense_residual_d_ff:
        from repro.models import mlp
        p["dense"] = mlp.init_layer(ks[4], cfg, num_layers,
                                    d_ff=cfg.dense_residual_d_ff)
    return p


def apply(p: Dict[str, Array], x: Array, cfg: ModelConfig,
          dropless: bool = False, use_pallas: bool = False) -> Array:
    """x: (B, S, D) -> (B, S, D) with residual.

    GShard-style **group-limited** capacity dispatch: tokens are split into
    g groups aligned with the data-parallel shards (g = mesh dp size, read
    from the sharding rules at trace time; 1 on a single device). Each group
    ranks its own tokens and owns cap_g = cf·k·T_g/E expert slots, so the
    dispatch scatter, the (g, E, cap_g, D) expert buffer and the expert
    einsums all keep the group dim sharded over data — a *global* cumsum/
    buffer forces GSPMD to replicate the entire MoE across the data axis
    (measured 16× FLOPs on the 16-way mesh; EXPERIMENTS.md §Perf).

    Tokens past an expert's per-group capacity are dropped (standard) —
    except with ``dropless=True`` (decode: T tiny, g=1, cap=T).
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    h = common.rms_norm(x, p["pre_norm"], cfg.norm_eps)
    T = B * S
    g = 1 if dropless else sharding.axis_size("batch")
    if T % g or T < g:
        g = 1
    Tg = T // g
    cap = Tg if dropless else max(int(cfg.capacity_factor * k * Tg / E), 1)
    cap = min(cap, Tg * k)

    tokens = h.reshape(g, Tg, D)
    tokens = sharding.shard(tokens, "batch", None, None)
    logits = jnp.einsum("gtd,de->gte", tokens.astype(jnp.float32),
                        p["router"].astype(jnp.float32))         # (g, Tg, E)
    weights, chosen = jax.lax.top_k(logits, k)                   # (g, Tg, k)
    weights = jax.nn.softmax(weights, axis=-1)

    flat_e = chosen.reshape(g, Tg * k)                           # (g, Tg·k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (g, Tg·k, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot                    # rank in group
    pos_sel = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = pos_sel < cap
    dest = jnp.where(keep, flat_e * cap + pos_sel, E * cap)      # drop slot

    tok_rep = jnp.repeat(tokens, k, axis=1)                      # (g, Tg·k, D)
    xin = jnp.zeros((g, E * cap + 1, D), x.dtype)
    xin = jax.vmap(lambda xz, d, t: xz.at[d].add(t))(xin, dest, tok_rep)
    xin = xin[:, :E * cap].reshape(g, E, cap, D)
    xin = sharding.shard(xin, "batch", "experts", None, None)

    gate = common.einsum_f32("gecd,edf->gecf", xin,
                             p["we_gate"].astype(x.dtype))
    up = common.einsum_f32("gecd,edf->gecf", xin,
                           p["we_up"].astype(x.dtype))
    act = (common.act_fn(gate, cfg.act_fn) * up).astype(x.dtype)
    act = sharding.shard(act, "batch", "experts", None, "ff")
    eout = common.einsum_f32("gecf,efd->gecd", act,
                             p["we_down"].astype(x.dtype)).astype(x.dtype)
    eout = sharding.shard(eout, "batch", "experts", None, None)

    eflat = jnp.concatenate(
        [eout.reshape(g, E * cap, D), jnp.zeros((g, 1, D), x.dtype)], axis=1)
    gathered = jax.vmap(lambda ef, d: ef[d])(eflat, dest)        # (g, Tg·k, D)
    gathered = gathered.reshape(g, Tg, k, D).astype(jnp.float32)
    out = jnp.sum(gathered * weights[..., None], axis=2)
    out = out.reshape(B, S, D).astype(x.dtype)
    out = sharding.shard(out, "batch", "seq", None)

    if "dense" in p:  # arctic: parallel dense residual FFN
        from repro.models import mlp
        out = out + mlp.apply(p["dense"], h, cfg, residual=False,
                              use_pallas=use_pallas)
    return x + out


def aux_load_balance_loss(p: Dict[str, Array], x: Array, cfg: ModelConfig) -> Array:
    """Switch-style load-balancing auxiliary (mean over layers handled by
    caller). Kept separate so the dry-run path can skip it."""
    B, S, D = x.shape
    tokens = x.reshape(B * S, D).astype(jnp.float32)
    logits = jnp.dot(tokens, p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(logits, cfg.experts_per_token)
    frac = jnp.mean(jax.nn.one_hot(chosen[:, 0], cfg.num_experts), axis=0)
    return cfg.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
