"""Unified LM stack over all assigned architectures.

Layer heterogeneity (gemma2 local/global alternation, zamba2 mamba+shared-attn
interleave, llama-vision cross-attn every k layers) is handled with a
**periodic plan**: the per-layer descriptor list is always periodic for these
architectures, so we stack parameters as (num_periods, ...) per *slot* within
the period and `lax.scan` over periods. Each scan step statically unrolls the
period's few slots — windows, layer kinds and FFN kinds are static per slot
(so e.g. gemma2's local slots get a *static* window, Pallas-kernel friendly),
while AdaPT's per-layer ⟨WL,FL⟩ remain runtime arrays indexed by period.

Params layout (all stacked leaves carry the leading num_periods dim):

    {"embed": (V, D)?,                 # absent for audio (frontend stub)
     "in_proj": (F, D)?,               # audio: frame-embedding projection
     "blocks": {"s{i}_attn"|"s{i}_mamba"|"s{i}_cross": {...},
                "s{i}_mlp"|"s{i}_moe": {...}},
     "shared": {...}?,                 # zamba2: one unstacked attn+mlp block
     "final_norm": (D,),
     "head": (D, V)?}                  # absent when tie_embeddings

The AdaPT controller sees "blocks/..." paths as per-layer stacked (leading
dim = num_periods) and everything else as per-tensor — matching the paper's
per-layer precision at period granularity.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.config import ModelConfig
from repro.core import fixed_point as fxp
from repro.models import attention, common, mlp, moe, ssm

Array = jax.Array

# Quantized leaves (fxp.PACKED_KEYS dicts) are dequantized at the use site:
# INSIDE the scan body for per-layer weights (so the FSDP gather moves int8,
# not bf16/f32) and at entry for embed/head. Under use_pallas,
# DENSE-consumed leaves (fixed_point.DENSE_PARAM_NAMES) are NOT dequantized
# at all — they ride through intact and common.dense feeds them straight to
# the fxp Pallas kernels (int8 tiles into the MXU, dequant in-register).
_unpack = fxp.unpack_tree


# ---------------------------------------------------------------------------
# Plan


@dataclass(frozen=True)
class Slot:
    kind: str          # attn | mamba | cross
    window: int        # 0 = full; >0 = sliding window (static!)
    ffn: str           # mlp | moe | none
    shared: bool = False  # weights shared across periods (zamba2 attn blocks)

    @property
    def name(self) -> str:
        return self.kind


def _layer_descriptors(cfg: ModelConfig) -> list:
    """Fully expanded per-layer slot list (length num_layers)."""
    ffn_default = ("moe" if cfg.num_experts else
                   ("mlp" if cfg.d_ff else "none"))
    out = []
    attn_idx = 0
    for i in range(cfg.num_layers):
        if cfg.cross_attn_every and (i + 1) % cfg.cross_attn_every == 0:
            kind = "cross"
        else:
            kind = cfg.layer_pattern[i % len(cfg.layer_pattern)]
        window = 0
        ffn = ffn_default
        shared = False
        if kind == "attn":
            pat = cfg.attn_pattern[attn_idx % len(cfg.attn_pattern)]
            window = cfg.window_size if pat == "local" else 0
            attn_idx += 1
            shared = cfg.shared_attn_weights
        elif kind == "mamba":
            ffn = "none"
        out.append(Slot(kind, window, ffn, shared))
    return out


def build_plan(cfg: ModelConfig) -> Tuple[Tuple[Slot, ...], int]:
    """Smallest periodic plan: (slots_per_period, num_periods)."""
    layers = _layer_descriptors(cfg)
    L = len(layers)
    for p in range(1, L + 1):
        if L % p:
            continue
        if all(layers[i] == layers[i % p] for i in range(L)):
            return tuple(layers[:p]), L // p
    return tuple(layers), 1


def slot_key(i: int, slot: Slot) -> str:
    return f"s{i}_{slot.kind}"


def ffn_key(i: int, slot: Slot) -> str:
    return f"s{i}_{slot.ffn}"


# ---------------------------------------------------------------------------
# Init


def init_params(key: Array, cfg: ModelConfig) -> Dict[str, Any]:
    plan, np_ = build_plan(cfg)
    keys = jax.random.split(key, 4 + 2 * len(plan))
    params: Dict[str, Any] = {"blocks": {}}
    ki = 0

    def nk():
        nonlocal ki
        ki += 1
        return keys[ki - 1]

    if not cfg.is_encoder:
        params["embed"] = common.init_embed(nk(), cfg.vocab_size, cfg.d_model)
    else:
        # audio stub frontend: frames arrive at d_model already (input_specs);
        # a learned projection keeps the path trainable end-to-end.
        params["in_proj"] = common.init_dense(nk(), (cfg.d_model, cfg.d_model))

    shared_attn = None
    for i, slot in enumerate(plan):
        if slot.kind in ("attn", "cross"):
            if slot.shared:
                if shared_attn is None:
                    shared_attn = attention.init_layer(nk(), cfg, 0)
                    params.setdefault("shared", {})["attn"] = shared_attn
                    if slot.ffn == "mlp":
                        params["shared"]["mlp"] = mlp.init_layer(nk(), cfg, 0)
            else:
                params["blocks"][slot_key(i, slot)] = attention.init_layer(
                    nk(), cfg, np_, cross=(slot.kind == "cross"))
        elif slot.kind == "mamba":
            params["blocks"][slot_key(i, slot)] = ssm.init_layer(nk(), cfg, np_)
        if slot.ffn == "mlp" and not slot.shared:
            params["blocks"][ffn_key(i, slot)] = mlp.init_layer(nk(), cfg, np_)
        elif slot.ffn == "moe":
            params["blocks"][ffn_key(i, slot)] = moe.init_layer(nk(), cfg, np_)

    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if not cfg.tie_embeddings:
        params["head"] = common.init_dense(
            nk(), (cfg.d_model, cfg.vocab_size or 1))
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)


def _slot_params(blocks, plan, i, slot, shared):
    if slot.shared:
        return shared["attn"]
    return blocks[slot_key(i, slot)]


def _apply_ffn(pffn, x, cfg, slot: Slot, shared, dropless: bool = False,
               use_pallas: bool = False):
    """(x, (rows, largest)) for an MoE slot (``moe.apply``'s counts),
    (x, None) for the others."""
    if slot.ffn == "none":
        return x, None
    if slot.shared:
        return (mlp.apply(shared["mlp"], x, cfg, use_pallas=use_pallas)
                if "mlp" in (shared or {}) else x), None
    if slot.ffn == "moe":
        return moe.apply(pffn, x, cfg, dropless=dropless,
                         use_pallas=use_pallas)
    return mlp.apply(pffn, x, cfg, use_pallas=use_pallas), None


def _checkpoint_slots(remat: str, plan) -> bool:
    """Whether ``remat="full"`` checkpoints each slot of the period on its
    own rather than the period whole. Either way the backward recomputes
    the period's forward; slot by slot it holds one slot's activations at a
    time, not all of them, at the price of saving each slot's input. That
    pays for a period with MoE slots, whose grouped buffers hold k rows per
    token (Mellum2's 4-layer step, compiled for a v5e: 15.5 -> 11.6 GiB;
    ``tests/test_chip_compile.py::test_remat_granularity_moe_period``).
    For periods of dense or SSM slots it went either way with the shapes,
    so they keep the whole-period checkpoint."""
    return (remat == "full" and len(plan) > 1
            and any(slot.ffn == "moe" for slot in plan))


def _maybe_qact(x, act_wl, name, enabled):
    if not enabled or act_wl is None or name not in act_wl:
        return x
    return common.quantize_act(x, act_wl[name], True)


def forward(params: Dict[str, Any], cfg: ModelConfig, *,
            tokens: Optional[Array] = None,
            embeds: Optional[Array] = None,
            memory: Optional[Array] = None,
            act_wl: Optional[Dict[str, Array]] = None,
            use_pallas: bool = False, remat: str = "none",
            with_moe_rows: bool = False):
    """Full-sequence forward → logits (B, S, V); with ``with_moe_rows``,
    (logits, {"moe_rows_held": rows routed to held experts summed over the
    MoE layers, "moe_rows_max": the most rows one held expert took in one
    layer}), both int32 and 0 in a model without experts.

    tokens: (B, S) int32 for LM archs; embeds: (B, S, D) for the audio stub;
    memory: (B, M, D) precomputed image-patch embeddings for cross slots.
    remat: "none" | "full" | "selective" — activation checkpointing of the
    per-period scan body (training at 4k×256 needs it to fit HBM).
    """
    plan, np_ = build_plan(cfg)
    params = {**params, **_unpack({k: v for k, v in params.items()
                                   if k != "blocks"}, keep_dense=use_pallas)}
    shared = params.get("shared")

    if tokens is not None:
        x = common.embed_lookup(params["embed"], tokens,
                                scale_by_dim=cfg.scale_embed)
        x = x.astype(jnp.bfloat16)
    else:
        x = common.dense(embeds.astype(jnp.bfloat16), params["in_proj"],
                         use_pallas=use_pallas)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    causal = not cfg.is_encoder

    # period-stacked xs for the scan (block params + per-period act WLs)
    xs = (params["blocks"], act_wl if act_wl is not None else {})

    has_moe = any(slot.ffn == "moe" for slot in plan)

    def layer(i, slot, x, pslice, awl):
        """Slot i of the period: (x, the MoE slot's counts or None)."""
        if slot.kind == "mamba":
            x = ssm.apply(pslice[slot_key(i, slot)], x, cfg,
                          use_pallas=use_pallas)
        elif slot.kind == "cross":
            p = _slot_params(pslice, plan, i, slot, shared)
            mem_k, mem_v = attention.project_memory(
                p, memory, cfg, use_pallas=use_pallas)
            x = attention.cross_attend(p, x, cfg, mem_k, mem_v,
                                       use_pallas=use_pallas)
        else:
            p = _slot_params(pslice, plan, i, slot, shared)
            x, _ = attention.attend_full(
                p, x, cfg, positions, window=slot.window, causal=causal,
                use_pallas=use_pallas)
        counts = None
        if slot.ffn != "none":
            pffn = None if slot.shared else pslice[ffn_key(i, slot)]
            x, counts = _apply_ffn(pffn, x, cfg, slot, shared,
                                   use_pallas=use_pallas)
        return _maybe_qact(x, awl, slot_key(i, slot), act_wl is not None), \
            counts

    per_slot = _checkpoint_slots(remat, plan)
    layers = [functools.partial(layer, i, slot) for i, slot in
              enumerate(plan)]
    if per_slot:
        layers = [jax.checkpoint(f) for f in layers]

    def body(x, xs_slice):
        pslice, awl = xs_slice
        pslice = _unpack(pslice, keep_dense=use_pallas)
        rows = (jnp.int32(0), jnp.int32(0))
        for f in layers:
            x, counts = f(x, pslice, awl)
            if counts is not None:
                rows = (rows[0] + counts[0], jnp.maximum(rows[1], counts[1]))
        return x, (rows if has_moe else None)

    if remat == "full" and not per_slot:
        body = jax.checkpoint(body)
    elif remat == "selective":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    with jax.named_scope("adapt.layers"):
        x, rows = jax.lax.scan(body, x, xs)

    with jax.named_scope("adapt.head"):
        x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("head")
        if head is None:
            logits = common.dense(x, params["embed"].T)
        else:
            logits = common.dense(x, head, out_logical="vocab",
                                  use_pallas=use_pallas)
        logits = common.softcap(logits.astype(jnp.float32),
                                cfg.final_logit_softcap)
        logits = sharding.shard(logits, "batch", "seq", "vocab")
    if with_moe_rows:
        held, largest = rows or (jnp.zeros((1,), jnp.int32),) * 2
        return logits, {"moe_rows_held": jnp.sum(held),
                        "moe_rows_max": jnp.max(largest)}
    return logits


# ---------------------------------------------------------------------------
# Loss


def lm_loss(logits: Array, tokens: Array, *, shift: bool = True) -> Array:
    """Causal LM loss (shifted) or framewise CE (shift=False, encoder)."""
    if shift:
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
    else:
        targets = tokens
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Decode (single new token against per-slot caches)


def cache_len(slot: Slot, context: int) -> int:
    return min(slot.window, context) if slot.window else context


def init_caches(cfg: ModelConfig, batch: int, context: int,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    plan, np_ = build_plan(cfg)
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    caches: Dict[str, Any] = {}
    for i, slot in enumerate(plan):
        key = slot_key(i, slot)
        if slot.kind == "attn":
            C = cache_len(slot, context)
            caches[key] = {
                "k": jnp.zeros((np_, batch, C, hkv, dh), dtype),
                "v": jnp.zeros((np_, batch, C, hkv, dh), dtype),
            }
        elif slot.kind == "mamba":
            caches[key] = ssm.init_cache(cfg, batch, np_, dtype=dtype)
        elif slot.kind == "cross":
            M = cfg.num_image_tokens
            caches[key] = {
                "k": jnp.zeros((np_, batch, M, hkv, dh), dtype),
                "v": jnp.zeros((np_, batch, M, hkv, dh), dtype),
            }
    return caches


def _slot_positions(C: int, t: Array) -> Array:
    """Absolute position held by each rolling-cache slot at time t (-1 empty)."""
    idx = jnp.arange(C, dtype=jnp.int32)
    p = t.astype(jnp.int32) - ((t.astype(jnp.int32) - idx) % C)
    return jnp.where(p >= 0, p, -1)


def decode_step(params: Dict[str, Any], cfg: ModelConfig, token: Array,
                caches: Dict[str, Any], t: Array, *,
                act_wl: Optional[Dict[str, Array]] = None,
                use_pallas: bool = False
                ) -> Tuple[Array, Dict[str, Any]]:
    """token: (B,) int32; t: () int32 current absolute position.
    Returns (logits (B, V), new caches)."""
    plan, np_ = build_plan(cfg)
    params = {**params, **_unpack({k: v for k, v in params.items()
                                   if k != "blocks"}, keep_dense=use_pallas)}
    shared = params.get("shared")
    x = common.embed_lookup(params["embed"], token[:, None],
                            scale_by_dim=cfg.scale_embed).astype(jnp.bfloat16)

    def body(x, xs_slice):
        pslice, cslice, awl = xs_slice
        pslice = _unpack(pslice, keep_dense=use_pallas)
        new_c = {}
        for i, slot in enumerate(plan):
            key = slot_key(i, slot)
            if slot.kind == "mamba":
                x, nc = ssm.apply_decode(pslice[key], x, cfg, cslice[key],
                                         use_pallas=use_pallas)
                new_c[key] = nc
            elif slot.kind == "cross":
                p = _slot_params(pslice, plan, i, slot, shared)
                x = attention.cross_attend(p, x, cfg, cslice[key]["k"],
                                           cslice[key]["v"],
                                           use_pallas=use_pallas)
                new_c[key] = cslice[key]
            else:
                p = _slot_params(pslice, plan, i, slot, shared)
                C = cslice[key]["k"].shape[1]
                spos = _slot_positions(C, t)
                x, (ck, cv) = attention.attend_decode(
                    p, x, cfg, cslice[key]["k"], cslice[key]["v"], spos, t,
                    window=slot.window, use_pallas=use_pallas)
                new_c[key] = {"k": ck, "v": cv}
            if slot.ffn != "none":
                pffn = None if slot.shared else pslice[ffn_key(i, slot)]
                x, _ = _apply_ffn(pffn, x, cfg, slot, shared, dropless=True,
                                  use_pallas=use_pallas)
            x = _maybe_qact(x, awl, key, act_wl is not None)
        return x, new_c

    x, new_caches = jax.lax.scan(
        body, x, (params["blocks"], caches,
                  act_wl if act_wl is not None else {}))

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    logits = common.dense(x, params["embed"].T if head is None else head,
                          use_pallas=use_pallas)
    logits = common.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits[:, 0], new_caches


# ---------------------------------------------------------------------------
# Prefill (forward + cache collection → decode handoff)


def _roll_into_cache(k: Array, C: int) -> Array:
    """Scatter the last C positions of k (B,S,H,D) into rolling-cache layout
    (slot = position % C), matching attend_decode's write pattern."""
    S = k.shape[1]
    take = k[:, S - C:]
    idx = (jnp.arange(S - C, S, dtype=jnp.int32)) % C
    out = jnp.zeros_like(take)
    return out.at[:, idx].set(take)


def prefill(params: Dict[str, Any], cfg: ModelConfig, tokens: Array, *,
            memory: Optional[Array] = None,
            act_wl: Optional[Dict[str, Array]] = None,
            use_pallas: bool = False,
            cache_dtype=jnp.bfloat16) -> Tuple[Array, Dict[str, Any]]:
    """Process the prompt, returning (last-position logits (B,V), caches)."""
    plan, np_ = build_plan(cfg)
    params = {**params, **_unpack({k: v for k, v in params.items()
                                   if k != "blocks"}, keep_dense=use_pallas)}
    shared = params.get("shared")
    x = common.embed_lookup(params["embed"], tokens,
                            scale_by_dim=cfg.scale_embed).astype(jnp.bfloat16)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, xs_slice):
        pslice, awl = xs_slice
        pslice = _unpack(pslice, keep_dense=use_pallas)
        caches = {}
        for i, slot in enumerate(plan):
            key = slot_key(i, slot)
            if slot.kind == "mamba":
                x, st = ssm.apply(pslice[key], x, cfg, return_state=True,
                                  use_pallas=use_pallas)
                caches[key] = jax.tree.map(
                    lambda a: a.astype(cache_dtype)
                    if a.dtype != jnp.float32 else a, st)
            elif slot.kind == "cross":
                p = _slot_params(pslice, plan, i, slot, shared)
                mk, mv = attention.project_memory(p, memory, cfg,
                                                  use_pallas=use_pallas)
                x = attention.cross_attend(p, x, cfg, mk, mv,
                                           use_pallas=use_pallas)
                caches[key] = {"k": mk.astype(cache_dtype),
                               "v": mv.astype(cache_dtype)}
            else:
                p = _slot_params(pslice, plan, i, slot, shared)
                x, (k, v) = attention.attend_full(
                    p, x, cfg, positions, window=slot.window,
                    use_pallas=use_pallas)
                C = cache_len(slot, S)
                caches[key] = {"k": _roll_into_cache(k, C).astype(cache_dtype),
                               "v": _roll_into_cache(v, C).astype(cache_dtype)}
            if slot.ffn != "none":
                pffn = None if slot.shared else pslice[ffn_key(i, slot)]
                x, _ = _apply_ffn(pffn, x, cfg, slot, shared,
                                  use_pallas=use_pallas)
            x = _maybe_qact(x, awl, key, act_wl is not None)
        return x, caches

    x, caches = jax.lax.scan(
        body, x, (params["blocks"], act_wl if act_wl is not None else {}))
    x = common.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    logits = common.dense(x, params["embed"].T if head is None else head,
                          use_pallas=use_pallas)
    logits = common.softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits[:, 0], caches


# ---------------------------------------------------------------------------
# AdaPT integration helpers


def act_wl_from_state(adapt_state: Dict[str, Any]) -> Dict[str, Array]:
    """Per-slot activation word length = the slot out-projection's WL
    (paper: activations are quantized at the layer's precision)."""
    out = {}
    for path, ts in adapt_state["tensors"].items():
        parts = path.split("/")
        if len(parts) == 3 and parts[0] == "blocks" and parts[2] in (
                "wo", "out_proj"):
            out[parts[1]] = ts["wl"]
    return out


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
