"""Shared model building blocks (pure functions over param dicts)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import sharding
from repro.core import fixed_point as fxp
from repro.core import init as weight_init

Array = jax.Array


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def softcap(logits: Array, cap: float) -> Array:
    if cap <= 0.0:
        return logits
    return cap * jnp.tanh(logits / cap)


def act_fn(x: Array, kind: str) -> Array:
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def einsum_f32(spec: str, a: Array, b: Array) -> Array:
    """``jnp.einsum`` with f32 accumulation. XLA's CPU runtime cannot run
    every BF16×BF16→F32 dot ("DotThunk: unsupported element type"), so a
    program lowered for the CPU upcasts the operands first — the products
    of bf16 values are exact in f32 — while the TPU keeps its bf16 MXU
    path. Decided per lowering, so a program compiled for a TPU from a
    CPU host takes the TPU branch."""
    def dot(a, b):
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)

    def upcast(a, b):
        return dot(a.astype(jnp.float32), b.astype(jnp.float32))

    return jax.lax.platform_dependent(a, b, cpu=upcast, default=dot)


def dense(x: Array, w, *, out_logical: str | None = None,
          use_pallas: bool = False) -> Array:
    """x @ w with f32 accumulation; annotates the contraction output.

    ``w`` is either a plain weight array or a packed ⟨q8, sc, wref⟩ dict
    of materialized int8 words the controller emitted
    (container_dtype="int8_packed"). Under ``use_pallas`` the words
    stream straight into the fxp Pallas kernels (``kernels/ops.fxp_dense``:
    fwd + dx on int8 tiles, dequant in-register, straight-through dw onto
    wref) — the weights are never dequantized into HBM. Without it, the
    XLA dequant-then-dot path.

    With the '#tp_reduce_bf16' rules flag, the plain dot's output dtype is
    bf16: the MXU still accumulates in f32 internally, but row-parallel
    partial sums cross the ICI in bf16 — half the TP all-reduce bytes for
    a ~2^-8 relative rounding on a 16-way sum (§Perf lever). The flag
    applies to the PLAIN-array path only: the kernel paths accumulate in
    f32 VMEM scratch and emit x.dtype. NOTE the kernel paths are
    per-device constructs — GSPMD refuses to partition a Mosaic kernel.
    Data-parallel training runs the whole step per device under
    ``shard_map`` (``train_loop.data_parallel_step``); the controller
    refuses explicitly-sharded dense leaves under use_pallas
    (controller.quantize_params_packed), and shard_map-wrapping the dense
    kernels for sharded weights is the open ROADMAP item."""
    if isinstance(w, dict):
        y = _dense_quantized(x, w, use_pallas)
    else:
        pref = (jnp.bfloat16 if sharding.flag("#tp_reduce_bf16")
                and x.dtype == jnp.bfloat16 else jnp.float32)
        y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=pref)
        y = y.astype(x.dtype)
    if out_logical and x.ndim == 3:
        y = sharding.shard(y, "batch", "seq", out_logical)
    return y


def _dense_quantized(x: Array, w: dict, use_pallas: bool) -> Array:
    """Dense over a packed dict; x may be (..., K) — the kernels take 2-D,
    so leading dims are flattened into M."""
    from repro.kernels import ops  # local: models stay importable sans ops

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if fxp.is_packed(w):
        if use_pallas:
            y2 = ops.fxp_dense(x2, w["q8"], jnp.reshape(w["sc"], ()),
                               w["wref"], use_pallas=True, out_dtype=x.dtype)
        else:
            # Defensive only: the model's own call sites unpack packed
            # dicts upstream when use_pallas is off, so this branch serves
            # direct callers handing dense() a packed leaf — it is the
            # EXACT legacy path (unpack_tree's dequant + the plain dot),
            # not a reimplementation of ops.fxp_dense's f32 fallback.
            wd = fxp.dequant_packed(w["q8"], w["sc"], w["wref"])
            y2 = jnp.dot(x2, wd.astype(x.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        raise TypeError(f"dense: unrecognized weight dict keys {set(w)}")
    return y2.reshape(lead + (y2.shape[-1],))


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> Array:
    """YaRN's rotary frequencies (HF ``_compute_yarn_parameters``): each
    pair i < dim/2 blends the extrapolated θ^(-2i/dim) and the interpolated
    θ^(-2i/dim) / factor by a linear ramp between the correction dims
    low = ⌊dim·ln(orig / (β_fast·2π)) / (2 ln θ)⌋ and high = ⌈the same at
    β_slow⌉ (clamped to [0, dim - 1]): pairs below ``low`` keep the
    extrapolated frequency, pairs past ``high`` take the interpolated one."""
    half = dim // 2

    def corr(beta):
        return dim * math.log(original_max / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ext = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return ext / factor * ramp + ext * (1.0 - ramp)


def rope_for(cfg, full: bool):
    """(inverse frequencies or None, cos/sin scale) of a slot's rotary
    embedding: YaRN on full-attention slots where the model gives it,
    plain rope at ``rope_theta`` otherwise."""
    if not (full and cfg.yarn_factor > 0):
        return None, 1.0
    inv = yarn_inv_freq(cfg.resolved_head_dim, cfg.rope_theta,
                        cfg.yarn_factor, cfg.yarn_original_max,
                        cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    return inv, cfg.yarn_attention_factor


def rope(x: Array, positions: Array, theta: float, inv_freq=None,
         scale: float = 1.0) -> Array:
    """Rotary embedding. x: (..., S, H, D); positions: (..., S) int32.
    ``inv_freq`` (D/2,) replaces θ^(-2i/D) where given, and ``scale``
    multiplies cos and sin (YaRN's attention factor)."""
    d = x.shape[-1]
    half = d // 2
    freq = (jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half) if inv_freq is None else inv_freq)
    ang = positions[..., None].astype(jnp.float32) * freq       # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                             # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * half < d:  # odd head dim: pass the tail through
        rot = jnp.concatenate([rot, x[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


def quantize_act(x: Array, wl: Array | None, enabled: bool) -> Array:
    """Activation fixed-point quantization at the layer's word length
    (dynamic-range FL, nearest rounding — see DESIGN.md §8)."""
    if not enabled or wl is None:
        return x
    return fxp.quantize_activation(x, wl)


def embed_lookup(table: Array, ids: Array, scale_by_dim: bool = False) -> Array:
    out = jnp.take(table, ids, axis=0)
    if scale_by_dim:
        out = out * jnp.asarray(table.shape[-1] ** 0.5, out.dtype)
    return sharding.shard(out, "batch", "seq", None)


def init_dense(key: Array, shape, scale: float = 1.0) -> Array:
    return weight_init.tnvs(key, shape, scale=scale, kind="linear")


def init_embed(key: Array, vocab: int, d: int, scale: float = 1.0) -> Array:
    return weight_init.tnvs(key, (vocab, d), scale=scale, kind="embed")
