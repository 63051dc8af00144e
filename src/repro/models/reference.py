"""Plain float32 reference of the dense decoder forward and its LM loss.

Written straight from the architecture (pre-norm RMSNorm with a ``1 +
scale`` gain, rotary embedding over split halves, grouped-query causal
attention, SwiGLU MLP, untied head) with nothing from ``models/`` or
``kernels/``, so it can judge the system's forward: the same weights and
tokens must give the same logits. Every matmul runs in float32; call it
under ``jax.default_matmul_precision("highest")`` so the TPU does too.

Covers the plain dense family (every layer global attention + MLP, no
softcaps, no qk-norm, untied head) — SmolLM-360M and ``tiny``. The AdaPT
per-layer activation quantize (``act_wl``) is reproduced: after each layer
the residual stream goes onto the ⟨WL, FL⟩ grid whose FL frames its
abs-max, rounded to nearest.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Array = jax.Array


def check_supported(cfg: ModelConfig) -> None:
    plain = (cfg.family == "dense" and cfg.layer_pattern == ("attn",)
             and set(cfg.attn_pattern) == {"global"}
             and not (cfg.attn_logit_softcap or cfg.final_logit_softcap
                      or cfg.use_qk_norm or cfg.use_post_norm
                      or cfg.tie_embeddings or cfg.scale_embed
                      or cfg.cross_attn_every or cfg.is_encoder))
    if not plain or cfg.act_fn != "silu":
        raise ValueError(f"reference: {cfg.name} is not a plain dense "
                         "decoder")


def dequantize(qparams) -> Dict:
    """Float32 values of a train-step weight tree: packed ⟨q8, sc, wref⟩
    leaves become q8 · sc, plain leaves are cast."""
    def is_packed(x):
        return isinstance(x, dict) and set(x) == {"q8", "sc", "wref"}

    def visit(x):
        if is_packed(x):
            return x["q8"].astype(jnp.float32) * x["sc"].astype(jnp.float32)
        return x.astype(jnp.float32)

    return jax.tree_util.tree_map(visit, qparams, is_leaf=is_packed)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, theta):
    """x: (B, S, heads, D); rotate the two halves of D by position."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., 2 * half:]], axis=-1)


def _quantize_act(x, wl):
    """Nearest rounding onto ⟨wl, fl⟩ with fl = wl − 1 − ⌈log2 max|x|⌉⁺."""
    il = jnp.maximum(jnp.ceil(jnp.log2(jnp.maximum(jnp.max(jnp.abs(x)),
                                                   1e-12))), 0.0)
    one = jnp.float32(1.0)
    scale = jnp.ldexp(one, wl - 1 - il.astype(jnp.int32))
    top = jnp.ldexp(one, wl - 1)
    return jnp.clip(jnp.round(x * scale), -top, top - 1.0) / scale


def _layer(cfg: ModelConfig, x, attn, mlp):
    B, S, _ = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    D = cfg.resolved_head_dim
    h = _rms_norm(x, attn["pre_norm"], cfg.norm_eps)
    q = _rope((h @ attn["wq"]).reshape(B, S, H, D), cfg.rope_theta)
    k = _rope((h @ attn["wk"]).reshape(B, S, Hkv, D), cfg.rope_theta)
    v = (h @ attn["wv"]).reshape(B, S, Hkv, D)
    # query head j reads key/value head j // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * D)
    x = x + o @ attn["wo"]
    h = _rms_norm(x, mlp["pre_norm"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ mlp["wi_gate"]) * (h @ mlp["wi_up"])) \
        @ mlp["wo"]


def forward(params, cfg: ModelConfig, tokens: Array,
            act_wl: Optional[Array] = None) -> Array:
    """Logits (B, S, V) in float32. ``params``: float weights in the model's
    tree layout (``blocks/s0_attn``, ``blocks/s0_mlp`` stacked over layers);
    ``act_wl``: optional (num_layers,) activation word lengths."""
    check_supported(cfg)
    blocks = params["blocks"]
    x = params["embed"][tokens]
    wls = (act_wl if act_wl is not None
           else jnp.zeros((cfg.num_layers,), jnp.int32))

    def body(x, layer):
        attn, mlp, wl = layer
        x = _layer(cfg, x, attn, mlp)
        if act_wl is not None:
            x = _quantize_act(x, wl)
        return x, None

    x, _ = jax.lax.scan(body, x, (blocks["s0_attn"], blocks["s0_mlp"], wls))
    return _rms_norm(x, params["final_norm"], cfg.norm_eps) @ params["head"]


def lm_loss(logits: Array, tokens: Array) -> Array:
    """Mean next-token cross entropy."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                         axis=-1))
