"""Grouped-query attention block with SWA/softcap/cross-attn and KV caching.

One set of pure functions, used three ways:
  * ``attend_full``  — training / encoding / prefill (no or fresh cache)
  * ``attend_decode``— single-token decode against a (possibly rolling) cache
  * ``cross_attend`` — queries over a static encoder memory (VLM layers)

Per-layer parameters arrive already sliced by the scan driver; the runtime
``window`` scalar makes local/global alternation (gemma2) a data choice, not
a structural one — a "global" layer simply carries window >= seq_len.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.config import ModelConfig
from repro.kernels import ops
from repro.models import common

Array = jax.Array

def init_layer(key: Array, cfg: ModelConfig, num_layers: int,
               cross: bool = False) -> Dict[str, Array]:
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    L = (num_layers,) if num_layers > 0 else ()
    mk = lambda k, shape: common.init_dense(k, L + shape)
    p = {
        "wq": mk(ks[0], (d, h * dh)),
        "wk": mk(ks[1], (d, hkv * dh)),
        "wv": mk(ks[2], (d, hkv * dh)),
        "wo": mk(ks[3], (h * dh, d)),
        "pre_norm": jnp.zeros(L + (d,), jnp.float32),
    }
    if cfg.use_post_norm:
        p["post_norm"] = jnp.zeros(L + (d,), jnp.float32)
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.zeros(L + (dh,), jnp.float32)
        p["k_norm"] = jnp.zeros(L + (dh,), jnp.float32)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions: Optional[Array],
                 rope_on: bool = True, use_pallas: bool = False,
                 full: bool = True):
    B, S, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = common.dense(x, p["wq"], use_pallas=use_pallas).reshape(B, S, h, dh)
    k = common.dense(x, p["wk"], use_pallas=use_pallas).reshape(B, S, hkv, dh)
    v = common.dense(x, p["wv"], use_pallas=use_pallas).reshape(B, S, hkv, dh)
    if cfg.use_qk_norm:
        q = common.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope_on and positions is not None:
        inv, scale = common.rope_for(cfg, full)
        q = common.rope(q, positions, cfg.rope_theta, inv, scale)
        k = common.rope(k, positions, cfg.rope_theta, inv, scale)
    q = sharding.shard(q, "batch", "q_seq", "heads", None)
    k = sharding.shard(k, "batch", "seq", "kv_heads", None)
    v = sharding.shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def attend_full(p: Dict[str, Array], x: Array, cfg: ModelConfig,
                positions: Array, *, window: Array | int = 0,
                causal: bool = True, use_pallas: bool = False
                ) -> Tuple[Array, Tuple[Array, Array]]:
    """Self-attention over the whole sequence. Returns (out, (k, v)).

    ``window`` may be a traced scalar (per-layer from the scan); the pallas
    kernel needs a static window so the dynamic form uses the masked path.
    """
    h = common.rms_norm(x, p["pre_norm"], cfg.norm_eps)
    static_window = isinstance(window, int)
    q, k, v = _project_qkv(p, h, cfg, positions, use_pallas=use_pallas,
                           full=static_window and window == 0)
    if use_pallas and static_window:
        out = ops.attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.attn_logit_softcap, use_pallas=True)
    else:
        out = _masked_attention(q, k, v, positions, positions, window,
                                cfg.attn_logit_softcap, causal)
    B, S = x.shape[:2]
    out = common.dense(out.reshape(B, S, -1), p["wo"], use_pallas=use_pallas)
    out = sharding.shard(out, "batch", "seq", None)
    if cfg.use_post_norm:
        out = common.rms_norm(out, p["post_norm"], cfg.norm_eps)
    return x + out, (k, v)


def _masked_attention(q, k, v, qpos, kpos, window, cap, causal):
    """einsum attention with explicit position masks.

    GQA uses the repeat-kv formulation: K/V are broadcast to the full H
    query heads BEFORE the score einsums so the contraction keeps a single
    (B, H, Sq, Skv) structure whose head axis shards over `model`. The naive
    (Hkv, rep) reshape breaks GSPMD head-sharding propagation and silently
    replicates the quadratic einsums on every chip (measured 16× the FLOPs
    on the 16-way mesh — see EXPERIMENTS.md §Perf).

    qpos: (B, Sq), kpos: (B, Skv) absolute positions; kpos = -1 marks empty
    cache slots. ``window`` may be a traced scalar (0 disables it).
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)             # (B, Skv, H, D)
        v = jnp.repeat(v, rep, axis=2)
    pad_to = sharding.flag("#pad_heads_to")
    if pad_to and pad_to > H:                      # shardable-head padding
        pz = ((0, 0), (0, 0), (0, pad_to - H), (0, 0))
        q = jnp.pad(q, pz)
        k = jnp.pad(k, pz)
        v = jnp.pad(v, pz)
        q = sharding.shard(q, "batch", "q_seq", "heads", None)
    if rep > 1 or (pad_to and pad_to > H):
        # "kv_seq" is () except in split-KV decode / long-context rules,
        # where the cache sequence (not heads) carries the model axis
        k = sharding.shard(k, "batch", "kv_seq", "heads", None)
        v = sharding.shard(v, "batch", "kv_seq", "heads", None)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (1.0 / D ** 0.5)
    logits = common.softcap(logits, cap)
    logits = sharding.shard(logits, "batch", "heads", "q_seq", None)
    qp = qpos[:, :, None]                          # (B, Sq, 1)
    kp = kpos[:, None, :]                          # (B, 1, Skv)
    mask = kp >= 0                                 # (B, Sq, Skv) by broadcast
    if causal:
        mask = mask & (kp <= qp)
    w = jnp.asarray(window)
    mask = jnp.where(w > 0, mask & (kp > qp - w), mask)
    logits = jnp.where(mask[:, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    # probabilities in the input dtype for the AV contraction (what flash
    # kernels do): halves P/V traffic and the f32 dk/dv backward payloads
    out = common.einsum_f32("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    if pad_to and pad_to > H:
        out = out[:, :, :H, :]
    return out.astype(q.dtype)


def attend_decode(p: Dict[str, Array], x: Array, cfg: ModelConfig,
                  cache_k: Array, cache_v: Array, slot_pos: Array, t: Array,
                  *, window: Array | int = 0, use_pallas: bool = False
                  ) -> Tuple[Array, Tuple[Array, Array]]:
    """One-token decode. x: (B, 1, D); cache: (B, C, Hkv, Dh); slot_pos: (C,)
    absolute positions per cache slot (-1 = empty); t: current position."""
    h = common.rms_norm(x, p["pre_norm"], cfg.norm_eps)
    B = x.shape[0]
    pos = jnp.broadcast_to(t[None, None], (B, 1))
    q, k, v = _project_qkv(p, h, cfg, pos, use_pallas=use_pallas,
                           full=isinstance(window, int) and window == 0)
    C = cache_k.shape[1]
    slot = (t % C).astype(jnp.int32)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), slot, axis=1)
    kpos = jnp.broadcast_to(slot_pos[None, :], (B, C))
    out = _masked_attention(q, cache_k, cache_v, pos, kpos, window,
                            cfg.attn_logit_softcap, causal=True)
    out = common.dense(out.reshape(B, 1, -1), p["wo"], use_pallas=use_pallas)
    if cfg.use_post_norm:
        out = common.rms_norm(out, p["post_norm"], cfg.norm_eps)
    return x + out, (cache_k, cache_v)


def cross_attend(p: Dict[str, Array], x: Array, cfg: ModelConfig,
                 memory_k: Array, memory_v: Array,
                 use_pallas: bool = False) -> Array:
    """Cross-attention over a precomputed encoder memory (VLM layers).
    memory_k/v: (B, M, Hkv, Dh) — projected once at prefill."""
    h = common.rms_norm(x, p["pre_norm"], cfg.norm_eps)
    B, S, _ = x.shape
    hq, dh = cfg.num_heads, cfg.resolved_head_dim
    q = common.dense(h, p["wq"], use_pallas=use_pallas).reshape(B, S, hq, dh)
    q = sharding.shard(q, "batch", "seq", "heads", None)
    M = memory_k.shape[1]
    kpos = jnp.broadcast_to(jnp.arange(M)[None], (B, M))
    qpos = jnp.broadcast_to(jnp.full((1,), M, jnp.int32), (B, S))
    out = _masked_attention(q, memory_k, memory_v, qpos, kpos, 0,
                            cfg.attn_logit_softcap, causal=False)
    out = common.dense(out.reshape(B, S, -1), p["wo"], use_pallas=use_pallas)
    if cfg.use_post_norm:
        out = common.rms_norm(out, p["post_norm"], cfg.norm_eps)
    return x + out


def project_memory(p: Dict[str, Array], memory: Array, cfg: ModelConfig,
                   use_pallas: bool = False) -> Tuple[Array, Array]:
    """Project encoder memory to (k, v) once (used by cross layers)."""
    B, M, _ = memory.shape
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    k = common.dense(memory, p["wk"], use_pallas=use_pallas
                     ).reshape(B, M, hkv, dh)
    v = common.dense(memory, p["wv"], use_pallas=use_pallas
                     ).reshape(B, M, hkv, dh)
    return k, v
