"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the semantic ground truth the kernels are tested
against (tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _pow2(e) -> Array:
    """Exact 2^e (f32) for integer(-valued) e, via ldexp. Grid scales must
    never go through a transcendental lowering — XLA CPU's exp2 (and
    potentially pow) is off an ulp for |e| ≳ 10, which would knock the
    oracles off the exact ⟨WL,FL⟩ grid the kernels (sr_quantize._pow2i)
    guarantee."""
    return jnp.ldexp(jnp.float32(1.0), jnp.asarray(e, jnp.int32))


def ref_sr_quantize(x: Array, u: Array, wl: int, fl: int) -> Array:
    """Fixed-point ⟨WL,FL⟩ stochastic-round quantize (f32-container grid)."""
    xf = x.astype(jnp.float32)
    scale = _pow2(fl)
    qmax = _pow2(wl - 1) - 1.0
    s = xf * scale
    f = jnp.floor(s)
    q = f + (u.astype(jnp.float32) < (s - f)).astype(jnp.float32)
    q = jnp.clip(q, -qmax - 1.0, qmax)
    return (q / scale).astype(x.dtype)


def ref_sr_quantize_fused(x: Array, seed: Array, wl: int, fl: int) -> Array:
    """Oracle for the in-kernel-PRNG variant: same grid semantics, noise
    drawn from jax.random keyed on ``seed``. Deterministic per seed but a
    *different* stream than the kernel's (hardware or counter-hash) PRNG —
    parity with the kernel is distributional, not bitwise."""
    u = jax.random.uniform(jax.random.PRNGKey(seed), x.shape, jnp.float32)
    return ref_sr_quantize(x, u, wl, fl)


def ref_sr_quantize_fused_int8(x: Array, seed: Array, fl: int) -> Array:
    """Oracle for the int8-word flavor (int8 storage clip, WL≤8 by mode)."""
    u = jax.random.uniform(jax.random.PRNGKey(seed), x.shape, jnp.float32)
    xf = x.astype(jnp.float32) * jnp.float32(2.0) ** fl
    f = jnp.floor(xf)
    q = f + (u < (xf - f)).astype(jnp.float32)
    return jnp.clip(q, -128.0, 127.0).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Bit-exact oracles of the fused kernels' PORTABLE noise stream.
#
# The fused kernels draw noise in-register. On compiled TPU that is the
# hardware PRNG (not reproducible off-device); everywhere else — interpret
# mode, i.e. CPU CI and any non-TPU backend — it is a murmur3-finalizer
# counter hash over the global padded element index. That stream is a
# CONTRACT: the functions below regenerate it in pure jnp so the
# differential harness (tests/test_quantize_differential.py) can demand
# word-for-word equality with the kernels, and the golden-stream test can
# pin it against drift. Padding in the kernels' (rows, 512) layout only
# appends elements at the end of each flat plane, so the live stream of an
# unstacked tensor is simply hash(0..n-1) and layer l of a stacked tensor
# starts at flat offset l·rows·512.

FUSED_LANES = 512          # the fused kernels' padded row width (LANE * 4)


def ref_fused_noise(seed, n: int, offset: int = 0) -> Array:
    """U[0,1) words the fused kernels draw for flat padded elements
    [offset, offset + n) under the portable counter-hash stream."""
    h = (jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(offset)
         + jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
         * jnp.uint32(0x9E3779B9))
    h ^= h >> 16
    h = h * jnp.uint32(0x7FEB352D)
    h ^= h >> 15
    h = h * jnp.uint32(0x846CA68B)
    h ^= h >> 16
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def ref_fold_shard_seed(seed, idx) -> Array:
    """Mirror of ``sr_quantize.fold_shard_seed`` (independent jnp
    implementation): the per-shard seed the shard_map wrapper derives from
    the linear shard index."""
    s = (jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
         + jnp.asarray(idx, jnp.uint32) * jnp.uint32(0x9E3779B9))
    s = s ^ (s >> 16)
    s = s * jnp.uint32(0x7FEB352D)
    s = s ^ (s >> 15)
    return jax.lax.bitcast_convert_type(s, jnp.int32)


def ref_sr_quantize_fused_words(x: Array, seed, wl, fl) -> Array:
    """Bit-exact oracle of ``sr_quantize_fused`` under the portable stream
    (vs :func:`ref_sr_quantize_fused`, which is only distributional)."""
    u = ref_fused_noise(seed, x.size).reshape(x.shape)
    return ref_sr_quantize(x, u, wl, fl)


def ref_sr_quantize_fused_int8_words(x: Array, seed, fl) -> Array:
    """Bit-exact oracle of ``sr_quantize_fused_int8``'s portable stream."""
    u = ref_fused_noise(seed, x.size).reshape(x.shape)
    xf = x.astype(jnp.float32) * _pow2(fl)
    f = jnp.floor(xf)
    q = f + (u < (xf - f)).astype(jnp.float32)
    return jnp.clip(q, -128.0, 127.0).astype(jnp.int8)


def _stacked_offsets(x: Array):
    n = x[0].size
    rows = -(-n // FUSED_LANES)
    return n, rows * FUSED_LANES


def ref_sr_quantize_fused_stacked_words(x: Array, seed, wl, fl) -> Array:
    """Bit-exact oracle of ``sr_quantize_fused_stacked``: slice l on the
    ⟨wl[l], fl[l]⟩ grid, noise from flat offset l·rows·512 of the shared
    stream."""
    n, stride = _stacked_offsets(x)
    outs = []
    for l in range(x.shape[0]):
        u = ref_fused_noise(seed, n, offset=l * stride)
        outs.append(ref_sr_quantize(x[l].reshape(-1), u, wl[l],
                                    fl[l]).reshape(x.shape[1:]))
    return jnp.stack(outs)


def ref_sr_quantize_fused_stacked_int8_words(x: Array, seed, fl) -> Array:
    """Bit-exact oracle of ``sr_quantize_fused_stacked_int8``."""
    n, stride = _stacked_offsets(x)
    outs = []
    for l in range(x.shape[0]):
        u = ref_fused_noise(seed, n, offset=l * stride)
        xf = x[l].reshape(-1).astype(jnp.float32) * _pow2(fl[l])
        f = jnp.floor(xf)
        q = f + (u < (xf - f)).astype(jnp.float32)
        outs.append(jnp.clip(q, -128.0, 127.0).astype(jnp.int8)
                    .reshape(x.shape[1:]))
    return jnp.stack(outs)


def ref_sr_quantize_fused_sharded_words(x: Array, seed, wl, fl,
                                        grid: tuple, *,
                                        int8: bool = False) -> Array:
    """Bit-exact oracle of the shard_map-wrapped fused quantize, assembled
    on one device: ``grid[d]`` equal blocks per dim; block b (row-major
    over ``grid``, matching the wrapper's flattened-axis fold order)
    quantizes with seed ``ref_fold_shard_seed(seed, b)`` and its own local
    padded-layout stream. wl/fl may be scalars or (L,) vectors (stacked
    leaf — dim-0 blocks then carry the matching precision slice)."""
    import itertools
    blocks = [s // g for s, g in zip(x.shape, grid)]
    stacked = bool(jnp.ndim(fl))
    out = jnp.zeros(x.shape, jnp.int8 if int8 else x.dtype)
    for lin, coords in enumerate(itertools.product(
            *[range(g) for g in grid])):
        sl = tuple(slice(c * b, (c + 1) * b)
                   for c, b in zip(coords, blocks))
        s = ref_fold_shard_seed(seed, lin)
        blk = x[sl]
        if int8:
            q = (ref_sr_quantize_fused_stacked_int8_words(blk, s, fl[sl[0]])
                 if stacked else ref_sr_quantize_fused_int8_words(blk, s, fl))
        else:
            q = (ref_sr_quantize_fused_stacked_words(blk, s, wl[sl[0]],
                                                     fl[sl[0]])
                 if stacked else ref_sr_quantize_fused_words(blk, s, wl, fl))
        out = out.at[sl].set(q)
    return out


def ref_edf_ladder_hists(w: Array, fls: Array, r: Array, *, wl_ladder: tuple,
                         r_upr: int) -> Array:
    """Oracle for the fused EDF ladder: scatter-add histograms of the master
    weights and each round-to-nearest ⟨WL,FL⟩-requantized candidate, r live
    bins inside a static r_upr buffer over w's [min, max] range."""
    wf = w.reshape(-1).astype(jnp.float32)
    lo, hi = jnp.min(wf), jnp.max(wf)
    span = jnp.maximum(hi - lo, 1e-12)
    rf = r.astype(jnp.float32)

    def hist(x):
        idx = jnp.clip(jnp.floor((x - lo) / span * rf),
                       0, rf - 1).astype(jnp.int32)
        return jnp.zeros((r_upr,), jnp.float32).at[idx].add(1.0)

    rows = [hist(wf)]
    for t, wl in enumerate(wl_ladder):
        scale = _pow2(fls[t])
        qmax = jnp.float32(2.0 ** (wl - 1) - 1.0)
        q = jnp.clip(jnp.round(wf * scale), -qmax - 1.0, qmax) / scale
        rows.append(hist(q))
    return jnp.stack(rows)


def ref_fxp_matmul(x: Array, wq: Array, scale: Array,
                   bias: Array | None = None) -> Array:
    """x @ (wq * scale) with f32 accumulation.

    x: (M, K) float; wq: (K, N) int8 fixed-point words; scale: () or (N,) f32.
    """
    acc = jnp.dot(x.astype(jnp.float32), wq.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    out = acc * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def ref_int8_matmul(xq: Array, wq: Array, sx: Array, sw: Array) -> Array:
    """Full int8×int8→int32 path: (xq @ wq) * sx * sw, f32 out."""
    acc = jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx.astype(jnp.float32) * sw.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Backward-pass oracles (ground truth for the custom-VJP Pallas kernels —
# tests/test_vjp_differential.py additionally checks against raw XLA
# autodiff of the forward oracles, so these stay closed-form and readable).


def ref_matmul_dx(dy: Array, wq: Array, scale: Array) -> Array:
    """dx = dy @ (wq·scale)ᵀ, f32 accumulation, dy.dtype out."""
    acc = jnp.dot(dy.astype(jnp.float32), wq.astype(jnp.float32).T,
                  preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)).astype(dy.dtype)


def ref_matmul_dw(x: Array, dy: Array) -> Array:
    """dw = xᵀ @ dy in f32."""
    return jnp.dot(x.astype(jnp.float32).T, dy.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def ref_fxp_matmul_grads(x: Array, wq: Array, scale: Array, dy: Array):
    """(dx, dscale) cotangents of ``ref_fxp_matmul`` (dwq is float0 —
    the int8 words are non-differentiable storage)."""
    dw = ref_matmul_dw(x, dy)
    dscale = (jnp.sum(dw * wq.astype(jnp.float32))
              .reshape(jnp.shape(scale)).astype(scale.dtype))
    return ref_matmul_dx(dy, wq, scale).astype(x.dtype), dscale


def ref_int8_matmul_grads(xq: Array, wq: Array, sx: Array, sw: Array,
                          dy: Array):
    """(dsx, dsw) cotangents of ``ref_int8_matmul``."""
    acc = jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    g0 = jnp.sum(dy.astype(jnp.float32) * acc)
    return ((g0 * sw.astype(jnp.float32)).reshape(jnp.shape(sx)),
            (g0 * sx.astype(jnp.float32)).reshape(jnp.shape(sw)))


def ref_attention_lse(q: Array, k: Array, v: Array, *, causal: bool = True,
                      window: int = 0, softcap: float = 0.0,
                      scale: float | None = None) -> Array:
    """Per-row logsumexp (B, H, Sq) of the masked (softcapped) logits —
    the residual flash_attention(return_lse=True) stashes for its VJP."""
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
    sc = scale if scale is not None else (1.0 / D ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sc
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    return jax.scipy.special.logsumexp(logits, axis=-1)


def ref_attention_grads(q: Array, k: Array, v: Array, dy: Array, **kwargs):
    """(dq, dk, dv) via XLA autodiff of :func:`ref_attention` — the oracle
    the Pallas backward kernels are pinned against."""
    _, vjp = jax.vjp(lambda a, b, c: ref_attention(a, b, c, **kwargs),
                     q, k, v)
    return vjp(dy)


def ref_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                  window: int = 0, softcap: float = 0.0,
                  scale: float | None = None) -> Array:
    """Multi-head attention oracle.

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D). GQA via head-group broadcast.
    window > 0: sliding-window causal mask. softcap > 0: tanh logit cap.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    sc = scale if scale is not None else (1.0 / D ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sc
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)   # align ends (decode-friendly)
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
