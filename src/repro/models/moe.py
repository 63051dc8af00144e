"""Mixture-of-Experts FFN: top-k routing over all of the router's experts,
with the experts this device holds computed dropless over rows sorted by
expert.

A layer holds ``cfg.experts_held`` of the router's ``cfg.num_experts``
experts, those from ``cfg.expert_offset`` on (all of them when
``experts_held`` is 0): the chip's share under expert parallelism. The
router keeps its full width and its top-k; the layer computes what its own
experts add for the tokens routed to them, and what the absent experts
would add is left out (on one device the layer runs without the exchange
that would bring their part).

mixtral-8x22b: 8 experts top-2; arctic-480b: 128 experts top-2 *plus* a
parallel dense residual FFN (its "dense-MoE hybrid"); mellum2-12b: 64
experts top-8.

The router stays float32 and is excluded from AdaPT quantization: top-k
indices are discontinuous in the logits, so routing flips under
quantization noise destabilize training for no byte savings (the router is
~d_model×E ≈ 10⁻⁵ of the parameters).

Two dispatches, picked by what the layer sees:

* held (one device, or each device of a ``shard_map``): every assignment to
  a held expert is kept. The T·k assignments are sorted by expert (those to
  experts not held last, and dropped), the tokens are gathered into a
  buffer in that order, the SwiGLU experts run as grouped products over
  each expert's rows, and the weighted outputs are summed back per token.
  Under ``use_pallas`` with packed int8 words, the products are the grouped
  fixed-point kernels (``kernels/ops.fxp_gmm``: int8 words, each expert's
  own FL); otherwise ``jax.lax.ragged_dot`` on the dequantized weights,
  over the same layout.
  Routing, sort, gather and combine run under ``adapt.moe_route``.
* capacity (sharding rules that split the batch or the experts over a
  mesh, as GSPMD lowers them): the GShard group-limited dispatch, whose
  (g, E, cap, D) buffer keeps the data and expert dims sharded; tokens past
  an expert's capacity are dropped, except with ``dropless`` (decode).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.config import ModelConfig
from repro.core import fixed_point as fxp
from repro.models import common

Array = jax.Array


def held(cfg: ModelConfig) -> int:
    """Experts this device holds of each layer."""
    return cfg.experts_held or cfg.num_experts


def init_layer(key: Array, cfg: ModelConfig, num_layers: int) -> Dict[str, Array]:
    d = cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    e = held(cfg)
    ks = jax.random.split(key, 5)
    L = (num_layers,) if num_layers > 0 else ()
    p = {
        "router": common.init_dense(ks[0], L + (d, cfg.num_experts)),
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


def route(tokens: Array, router: Array, k: int) -> Tuple[Array, Array]:
    """(weights, experts), each (T, k): f32 router logits over every
    expert, the top k, and a softmax over the k chosen logits."""
    logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32))
    top, chosen = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top, axis=-1), chosen


def apply(p: Dict[str, Array], x: Array, cfg: ModelConfig,
          dropless: bool = False, use_pallas: bool = False
          ) -> Tuple[Array, Tuple[Array, Array]]:
    """x: (B, S, D) -> ((B, S, D) with residual, (rows, largest)): the rows
    routed to held experts and the most rows one held expert took (int32;
    after capacity on the capacity dispatch)."""
    h = common.rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if sharding.axis_size("batch") > 1 or sharding.axis_size("experts") > 1:
        out, counts = _apply_capacity(p, h, cfg, dropless)
    else:
        out, counts = _apply_held(p, h, cfg, use_pallas)
    if "dense" in p:  # arctic: parallel dense residual FFN
        from repro.models import mlp
        out = out + mlp.apply(p["dense"], h, cfg, residual=False,
                              use_pallas=use_pallas)
    return x + out, counts


def _experts(w, buf: Array, layout: Dict, tile: int, use_pallas: bool
             ) -> Array:
    """Grouped product of ``buf``'s rows with their experts' matrices."""
    from repro.kernels import ops
    if fxp.is_packed(w):
        return ops.fxp_gmm(buf, w["q8"], w["sc"], w["wref"], layout,
                           tile=tile, use_pallas=use_pallas,
                           out_dtype=buf.dtype)
    return ops.ragged_dot(buf, w.astype(buf.dtype), layout["sizes"]
                          ).astype(buf.dtype)


def _apply_held(p, h, cfg: ModelConfig, use_pallas: bool):
    from repro.kernels import fxp_gmm
    B, S, D = h.shape
    E, k, off = held(cfg), cfg.experts_per_token, cfg.expert_offset
    T = B * S
    tokens = h.reshape(T, D)
    tile = fxp_gmm.row_tile(T * k / cfg.num_experts)
    with jax.named_scope("adapt.moe_route"):
        weights, chosen = route(tokens, p["router"], k)
        local = chosen.reshape(T * k) - off
        group = jnp.where((local >= 0) & (local < E), local, E)
        layout, M = fxp_gmm.row_layout(group, E, tile)
        # buffer row -> token; rows no assignment fills read a zero row
        src = jnp.full((M + 1,), T, jnp.int32).at[layout["dest"]].set(
            jnp.arange(T * k, dtype=jnp.int32) // k)[:M]
        buf = jnp.concatenate([tokens, jnp.zeros((1, D), h.dtype)])[src]
    gate = _experts(p["we_gate"], buf, layout, tile, use_pallas)
    up = _experts(p["we_up"], buf, layout, tile, use_pallas)
    act = (common.act_fn(gate, cfg.act_fn) * up).astype(h.dtype)
    eout = _experts(p["we_down"], act, layout, tile, use_pallas)
    with jax.named_scope("adapt.moe_route"):
        rows = jnp.concatenate([eout, jnp.zeros((1, D), eout.dtype)]
                               )[jnp.minimum(layout["dest"], M)]
        out = jnp.sum(rows.reshape(T, k, D).astype(jnp.float32)
                      * weights[..., None], axis=1)
        counts = layout["rows"]
    return (out.reshape(B, S, D).astype(h.dtype),
            (jnp.sum(counts), jnp.max(counts)))


def _apply_capacity(p, h, cfg: ModelConfig, dropless: bool):
    """GShard-style **group-limited** capacity dispatch: tokens are split
    into g groups aligned with the data-parallel shards (g = mesh dp size,
    read from the sharding rules at trace time). Each group ranks its own
    tokens and owns cap_g = cf·k·T_g/E expert slots, so the dispatch
    scatter, the (g, E, cap_g, D) expert buffer and the expert einsums all
    keep the group dim sharded over data — a *global* cumsum/buffer forces
    GSPMD to replicate the entire MoE across the data axis."""
    B, S, D = h.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    if held(cfg) != E:
        raise ValueError("the capacity dispatch runs every expert; a layer "
                         "holding a share of them runs the held dispatch")
    T = B * S
    g = 1 if dropless else sharding.axis_size("batch")
    if T % g or T < g:
        g = 1
    Tg = T // g
    cap = Tg if dropless else max(int(cfg.capacity_factor * k * Tg / E), 1)
    cap = min(cap, Tg * k)

    tokens = h.reshape(g, Tg, D)
    tokens = sharding.shard(tokens, "batch", None, None)
    weights, chosen = route(tokens, p["router"], k)              # (g, Tg, k)

    flat_e = chosen.reshape(g, Tg * k)                           # (g, Tg·k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (g, Tg·k, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot                    # rank in group
    pos_sel = jnp.take_along_axis(pos, flat_e[..., None], axis=2)[..., 0]
    keep = pos_sel < cap
    dest = jnp.where(keep, flat_e * cap + pos_sel, E * cap)      # drop slot

    tok_rep = jnp.repeat(tokens, k, axis=1)                      # (g, Tg·k, D)
    xin = jnp.zeros((g, E * cap + 1, D), h.dtype)
    xin = jax.vmap(lambda xz, d, t: xz.at[d].add(t))(xin, dest, tok_rep)
    xin = xin[:, :E * cap].reshape(g, E, cap, D)
    xin = sharding.shard(xin, "batch", "experts", None, None)

    def weight(name):
        w = p[name]
        return fxp.unpack_tree(w) if isinstance(w, dict) else w

    gate = common.einsum_f32("gecd,edf->gecf", xin,
                             weight("we_gate").astype(h.dtype))
    up = common.einsum_f32("gecd,edf->gecf", xin,
                           weight("we_up").astype(h.dtype))
    act = (common.act_fn(gate, cfg.act_fn) * up).astype(h.dtype)
    act = sharding.shard(act, "batch", "experts", None, "ff")
    eout = common.einsum_f32("gecf,efd->gecd", act,
                             weight("we_down").astype(h.dtype)).astype(h.dtype)
    eout = sharding.shard(eout, "batch", "experts", None, None)

    eflat = jnp.concatenate(
        [eout.reshape(g, E * cap, D), jnp.zeros((g, 1, D), h.dtype)], axis=1)
    gathered = jax.vmap(lambda ef, d: ef[d])(eflat, dest)        # (g, Tg·k, D)
    gathered = gathered.reshape(g, Tg, k, D).astype(jnp.float32)
    out = jnp.sum(gathered * weights[..., None], axis=2)
    out = out.reshape(B, S, D).astype(h.dtype)
    out = sharding.shard(out, "batch", "seq", None)
    per = jnp.sum(jax.nn.one_hot(jnp.where(keep, flat_e, E), E + 1,
                                 dtype=jnp.int32), axis=(0, 1))[:E]
    return out, (jnp.sum(per), jnp.max(per))


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
