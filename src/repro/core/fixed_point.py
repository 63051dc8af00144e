"""Fixed-point ⟨WL, FL⟩ quantization with stochastic rounding (paper §2.1, §3.2).

A signed fixed-point number with word length ``WL`` and fractional length
``FL`` represents values q / 2**FL with integer q in [-2**(WL-1), 2**(WL-1)-1].

Everything here is jit-friendly: WL/FL are *runtime* int32 scalars/arrays so
AdaPT precision switches never trigger recompilation. Quantized values live in
a float32 container ("simulate" mode — exactly what the paper did via QPyTorch)
or as int8 + scale ("native_int8" mode, TPU MXU path).

Stochastic rounding follows Hopkins et al. [50]: round x down with probability
1 - frac(x), up with probability frac(x). Uniform bits are supplied externally
(jax.random) so the op stays deterministic under a fixed key and matches the
Pallas kernel, which consumes identical bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

MAX_WL = 32


def pow2i(e: Array) -> Array:
    """Exact 2^e (f32) for integer e, built from the exponent bits (clamped
    to the normal range [-126, 127]). XLA CPU lowers ``exp2`` to
    ``exp(e·ln2)``, which is off by an ulp for |e| ≳ 10 — enough to knock
    the ⟨WL,FL⟩ grid off its exact powers of two (e.g. exp2(15) =
    32767.984); every grid scale must go through this instead. The Pallas
    kernels carry their own in-kernel mirror (``sr_quantize._pow2i``)."""
    e = jnp.clip(jnp.asarray(e, jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def fxp_bounds(wl: Array) -> tuple[Array, Array]:
    """(qmin, qmax) integer bounds of a signed WL-bit word (f32 container,
    exact up to WL=32: 2^31 is representable)."""
    qmax = pow2i(jnp.asarray(wl, jnp.int32) - 1) - 1.0
    return -qmax - 1.0, qmax


def stochastic_round(x: Array, u: Array) -> Array:
    """SR(x): floor(x) + (u < frac(x)). ``u`` ~ U[0,1) with x's shape."""
    f = jnp.floor(x)
    return f + (u < (x - f)).astype(x.dtype)


def quantize(w: Array, wl: Array, fl: Array, *, u: Array | None = None) -> Array:
    """Quantize to the ⟨WL,FL⟩ grid, returning values on the grid (f32 container).

    ``u`` supplies uniform [0,1) noise for stochastic rounding; ``None`` means
    round-to-nearest (used by PushDown's KL probe, which must be deterministic).
    WL/FL may be scalars or broadcastable arrays (e.g. per-scanned-layer (L,1,1)).
    """
    w = w.astype(jnp.float32)
    scale = pow2i(fl)
    qmin, qmax = fxp_bounds(wl)
    x = w * scale
    if u is None:
        q = jnp.round(x)
    else:
        q = stochastic_round(x, u.astype(jnp.float32))
    q = jnp.clip(q, qmin, qmax)
    return q / scale


def quantize_int8(w: Array, fl: Array, *, u: Array | None = None) -> tuple[Array, Array]:
    """Native path: quantize to int8 storage (WL<=8 enforced by clip) + scale 2^-FL.

    Returns (q_int8, scale) with dequant = q * scale.
    """
    w = w.astype(jnp.float32)
    scale = pow2i(fl)
    x = w * scale
    q = jnp.round(x) if u is None else stochastic_round(x, u.astype(jnp.float32))
    q = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)
    return q, (1.0 / scale).astype(jnp.float32)


def required_integer_bits(w: Array, axes=None) -> Array:
    """IL bits needed to represent max|w| without overflow (excl. sign bit)."""
    amax = jnp.max(jnp.abs(w), axis=axes) if axes is not None else jnp.max(jnp.abs(w))
    amax = jnp.maximum(amax, 1e-12)
    return jnp.maximum(jnp.ceil(jnp.log2(amax + 1e-12)), 0.0).astype(jnp.int32)


def fl_for_wl(w_absmax: Array, wl: Array) -> Array:
    """Largest FL for word length WL s.t. max|w| is representable: FL = WL-1-IL."""
    il = jnp.maximum(jnp.ceil(jnp.log2(jnp.maximum(w_absmax, 1e-12))), 0.0)
    return jnp.asarray(wl, jnp.int32) - 1 - il.astype(jnp.int32)


def quantize_activation(a: Array, wl: Array, *, u: Array | None = None,
                        buff: int = 0) -> Array:
    """Dynamic-range activation quantization (paper quantizes activations too).

    FL is derived per call from the batch's abs-max so the value range always
    fits; ``buff`` extra integer headroom bits guard accumulation overflow.
    Differentiable via the straight-through estimator (round has zero
    gradient; STE passes the incoming cotangent through unchanged — the
    standard treatment [34] the paper's training relies on).
    """
    amax = jnp.max(jnp.abs(jax.lax.stop_gradient(a)))
    fl = fl_for_wl(amax, wl) - buff
    q = quantize(jax.lax.stop_gradient(a), wl, fl, u=u).astype(a.dtype)
    return a + jax.lax.stop_gradient(q - a)  # STE


def uniform_noise_like(key: Array, x: Array) -> Array:
    return jax.random.uniform(key, x.shape, jnp.float32)


# ---------------------------------------------------------------------------
# Packed int8 wire format (native_int8 §Perf): a quantized tensor travels as
# {"q8": int8, "sc": bf16 scale, "wref": bf16 zeros}. Dequant happens at the
# USE site (inside the scanned layer body, after the per-layer FSDP gather),
# so cross-chip weight movement costs 1 byte/param. Gradients route through
# the custom_vjp to "wref" — the straight-through read of paper alg. 1.

PACKED_KEYS = frozenset(("q8", "sc", "wref"))

# Param-tree leaf names consumed by models/common.dense (2-D x@W matmuls).
# Only these are eligible for the kernel dense path. MoE expert matrices
# (EXPERT_PARAM_NAMES) keep the materialized packed container and, under
# use_pallas, reach the grouped kernels (kernels/ops.fxp_gmm) as int8
# words; everything else that quantizes (embed tables, depthwise conv
# kernels, d_skip) is dequantized at its use site.
DENSE_PARAM_NAMES = frozenset((
    "wq", "wk", "wv", "wo",            # attention projections
    "wi_gate", "wi_up",                # gated-MLP in-projections
    "in_proj", "out_proj",             # SSM / audio-frontend projections
    "head",                            # LM head
))


# MoE expert matrices, (L, E, K, N) stacks: each expert of each layer is
# its own quantized tensor with its own <WL, FL> (controller), fed to the
# grouped kernels under use_pallas (models/moe.py).
EXPERT_PARAM_NAMES = frozenset(("we_gate", "we_up", "we_down"))


def is_expert_param(path: str) -> bool:
    """True when the (slash-joined) param path names an expert matrix."""
    return path.rsplit("/", 1)[-1] in EXPERT_PARAM_NAMES


def is_packed(leaf) -> bool:
    return isinstance(leaf, dict) and frozenset(leaf) == PACKED_KEYS


def is_dense_param(path: str) -> bool:
    """True when the (slash-joined) param path names a dense-layer weight
    — the leaves ``models/common.dense`` knows how to feed to the Pallas
    fxp kernels without an HBM dequant copy."""
    return path.rsplit("/", 1)[-1] in DENSE_PARAM_NAMES


@jax.custom_vjp
def dequant_packed(q8: Array, sc: Array, wref: Array) -> Array:
    del wref
    return q8.astype(jnp.bfloat16) * sc


def _dequant_fwd(q8, sc, wref):
    return dequant_packed(q8, sc, wref), sc


def _dequant_bwd(sc, g):
    import numpy as np
    return (np.zeros(g.shape, jax.dtypes.float0),
            jnp.zeros_like(sc),
            g.astype(jnp.bfloat16))


dequant_packed.defvjp(_dequant_fwd, _dequant_bwd)


def unpack_tree(tree, keep_dense: bool = False):
    """Dequantize every packed leaf in a (sub)tree; plain leaves pass.
    ``keep_dense=True`` leaves dicts whose path names a dense-layer weight
    (``is_dense_param``) or an expert matrix (``is_expert_param``) INTACT
    — the kernels consume them directly (``models/common.dense``,
    ``models/moe.py``), so they must survive the use-site unpack that
    every other quantized leaf still gets.

    If the sharding rules carry '#packed_slice_specs' (path-suffix →
    NamedSharding), the int8 payload is constrained to that (TP-only) spec
    FIRST — this pins the FSDP all-gather onto the 1-byte tensor; without
    it GSPMD reshards after the dequant-multiply and the wire carries bf16
    (measured on arctic-480b; EXPERIMENTS.md §Perf)."""
    from repro import sharding as _sh
    specs = _sh.flag("#packed_slice_specs") or {}

    def visit(path, leaf):
        if not is_packed(leaf):
            return leaf
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        if keep_dense and (is_dense_param(key) or is_expert_param(key)):
            return leaf
        q8 = leaf["q8"]
        if specs:
            for suffix, spec in specs.items():
                if key.endswith(suffix) and \
                        len(spec.spec) == q8.ndim:
                    q8 = jax.lax.with_sharding_constraint(q8, spec)
                    break
        return dequant_packed(q8, leaf["sc"], leaf["wref"])

    return jax.tree_util.tree_map_with_path(visit, tree,
                                            is_leaf=is_packed)


def sparsity(w: Array, axes=None, eps: float = 0.0) -> Array:
    """Fraction of non-zero elements (paper's sp^l). eps treats |w|<=eps as zero."""
    nz = (jnp.abs(w) > eps).astype(jnp.float32)
    return jnp.mean(nz, axis=axes)
