"""Pallas TPU kernels: fixed-point stochastic-rounding quantize (VPU, tiled).

The quantize→dequantize of every weight tensor runs once per optimizer step
(alg. 1 ln. 9–11) over *all* parameters — on an 8B model that is 8 G elements
of pure elementwise traffic, i.e. strictly HBM-bandwidth-bound. The kernels
tile HBM→VMEM in (block_rows, 512)-float chunks and fuse scale/round/clip/
descale into one pass (vs 5+ XLA ops → one read+write of the tensor instead
of several).

Two families:

* ``sr_quantize`` — takes a precomputed U[0,1) noise tensor. Three
  param-sized HBM transfers per tensor (x in, u in, q out), *plus* the
  earlier write of u when jax.random generated it: ~4 total.
* ``sr_quantize_fused`` / ``sr_quantize_fused_int8`` — draws the noise
  *inside* the kernel, so the U[0,1) tensor never exists in HBM: exactly
  two param-sized transfers per tensor (x in, q out). On TPU the noise
  comes from the hardware PRNG (``pltpu.prng_seed`` seeded per ⟨seed,
  block⟩ + ``pltpu.prng_random_bits``); under ``interpret=True`` (CPU/CI,
  where those primitives have no lowering) an in-kernel counter-based
  hash (splitmix/murmur3-finalizer over the global element index) supplies
  the bits instead. Both streams are deterministic per seed; they are
  *different* streams, so cross-backend runs agree in distribution (and on
  every grid/clip property) but not bit-for-bit.
* ``sr_quantize_fused_stacked`` / ``sr_quantize_fused_stacked_int8`` —
  the same 2-transfer contract for per-layer-stacked leaves: ⟨WL,FL⟩ is an
  (L,)-vector staged through SMEM, the grid grows a leading per-layer dim,
  and layer l quantizes with its own scale/clip in the SAME launch (vs the
  old L-pass XLA fallback). The portable noise stream indexes the padded
  (L·rows, 512) stack flat, so L=1 is bit-identical to the unstacked
  kernel and the stream is independent of ``block_rows``.

⟨WL,FL⟩ (and the seed) arrive as an SMEM int32 operand so one compiled
kernel serves every precision the controller chooses at runtime. The
portable counter-hash stream is a *contract* — ``kernels/ref.py``
regenerates it bit-for-bit (``ref_fused_noise``) so the differential
harness (tests/test_quantize_differential.py) demands word equality, and
``fold_shard_seed`` defines the per-shard seed derivation the shard_map
wrapper in ``kernels/ops.py`` uses for sharded leaves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANE = 128


def _pow2i(e: Array) -> Array:
    """Exact 2^e (f32) for int32 e, built from the exponent bits (clamped
    to the normal range [-126, 127]). XLA CPU lowers ``exp2`` to
    ``exp(e·ln2)``, which is off by an ulp for |e| ≳ 10 — enough to knock
    the ⟨WL,FL⟩ grid off its exact powers of two; the quantize kernels must
    never be. In-kernel mirror of ``core.fixed_point.pow2i`` (the kernels
    stay import-free of core). ``e`` is a scalar read from SMEM; the result
    is a (1, 1) vector because Mosaic bitcasts vectors only — it
    broadcasts against any tile."""
    e = jnp.clip(e.astype(jnp.int32), -126, 127)
    bits = jnp.broadcast_to((e + 127) << 23, (1, 1))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _sr_quantize_kernel(wlfl_ref, x_ref, u_ref, o_ref):
    scale = _pow2i(wlfl_ref[0, 1])
    qmax = _pow2i(wlfl_ref[0, 0] - 1) - 1.0
    x = x_ref[...].astype(jnp.float32)
    s = x * scale
    f = jnp.floor(s)
    q = f + (u_ref[...] < (s - f)).astype(jnp.float32)
    q = jnp.clip(q, -qmax - 1.0, qmax)
    o_ref[...] = (q / scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def sr_quantize(x: Array, u: Array, wl: Array, fl: Array, *,
                block_rows: int = 256, interpret: bool = False) -> Array:
    """Quantize ``x`` onto the ⟨wl,fl⟩ grid with stochastic rounding.

    x: any shape/float dtype; u: U[0,1) f32 of same shape; wl/fl: int32 scalars.
    """
    shape, dtype = x.shape, x.dtype
    n = x.size
    cols = LANE * 4                       # 512-float lanes per row
    rows = pl.cdiv(n, cols)
    pad = rows * cols - n
    x2 = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad)).reshape(rows, cols)
    u2 = jnp.pad(u.reshape(-1).astype(jnp.float32), (0, pad)).reshape(rows, cols)
    wlfl = jnp.stack([wl, fl]).astype(jnp.int32).reshape(1, 2)

    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        _sr_quantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # wl/fl scalars
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        interpret=interpret,
    )(wlfl, x2, u2)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Fused-PRNG variants: noise is drawn inside the kernel, never touching HBM.


def uniform_from_index(seed: Array, idx: Array) -> Array:
    """Portable U[0,1) from a uint32 element index: murmur3-finalizer of
    the index mixed with the seed (golden-ratio stride). THE bit-pinned
    portable stream (``ref.ref_fused_noise`` regenerates it; the golden
    file trips on drift) — every kernel that draws noise for element
    ``idx`` of a tensor must come through here so streams agree across
    kernels that tile the same tensor differently (e.g. the flat and the
    stacked fused quantize, at any block rows)."""
    h = idx.astype(jnp.uint32) + seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    h ^= h >> 16
    h = h * jnp.uint32(0x7FEB352D)
    h ^= h >> 15
    h = h * jnp.uint32(0x846CA68B)
    h ^= h >> 16
    return _top24_uniform(h)


def _top24_uniform(h: Array) -> Array:
    """U[0,1) from the top 24 bits of uint32 ``h``. Mosaic has no
    uint32→f32 cast; the 24-bit value is below 2^24, so going through
    int32 is exact and leaves the stream bit-identical."""
    return ((h >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _hash_uniform(seed: Array, shape, row0: Array, cols: int) -> Array:
    """Portable in-kernel U[0,1) over a (rows, cols) padded layout: the
    global element index (row0 + r)·cols + c fed to
    :func:`uniform_from_index`. Runs anywhere — it is the noise source
    whenever the hardware PRNG primitives are unavailable (interpret mode /
    CPU CI). Index arithmetic wraps mod 2^32, so streams repeat only
    beyond 4G-element tensors."""
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    idx = (row0.astype(jnp.uint32) + r) * jnp.uint32(cols) + c
    return uniform_from_index(seed, idx)


def _hw_uniform(seed: Array, shape, block_id) -> Array:
    # Distinct hardware stream per ⟨seed, linear block id⟩ (Mosaic takes at
    # most two seed words); reseeding per block keeps the stream
    # independent of the grid schedule.
    pltpu.prng_seed(seed, block_id)
    bits = pltpu.prng_random_bits(shape)
    return _top24_uniform(pltpu.bitcast(bits, jnp.uint32))


def _inkernel_uniform(seed: Array, shape, block_rows: int, cols: int,
                      hw_prng: bool) -> Array:
    if hw_prng:
        return _hw_uniform(seed, shape, pl.program_id(0))
    row0 = pl.program_id(0) * block_rows
    return _hash_uniform(seed, shape, row0, cols)


def fold_shard_seed(seed: Array, idx: Array) -> Array:
    """Per-shard seed for the shard_map-wrapped fused quantize: splitmix-
    style fold of the linear shard index into the base seed (int32 in/out,
    bit pattern of the mixed uint32). The sharded stream is thus a pure
    function of ⟨seed, mesh layout⟩ — ``ref.ref_fold_shard_seed`` mirrors
    this exactly, and the golden-stream test pins it against drift."""
    s = (jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
         + jnp.asarray(idx, jnp.uint32) * jnp.uint32(0x9E3779B9))
    s = s ^ (s >> 16)
    s = s * jnp.uint32(0x7FEB352D)
    s = s ^ (s >> 15)
    return jax.lax.bitcast_convert_type(s, jnp.int32)


def _sr_fused_kernel(ctl_ref, x_ref, o_ref, *, block_rows: int, cols: int,
                     hw_prng: bool):
    seed = ctl_ref[0, 2]
    scale = _pow2i(ctl_ref[0, 1])
    qmax = _pow2i(ctl_ref[0, 0] - 1) - 1.0
    x = x_ref[...].astype(jnp.float32)
    u = _inkernel_uniform(seed, x.shape, block_rows, cols, hw_prng)
    s = x * scale
    f = jnp.floor(s)
    q = f + (u < (s - f)).astype(jnp.float32)
    q = jnp.clip(q, -qmax - 1.0, qmax)
    o_ref[...] = (q / scale).astype(o_ref.dtype)


def _sr_fused_int8_kernel(ctl_ref, x_ref, o_ref, *, block_rows: int,
                          cols: int, hw_prng: bool):
    # Native-int8 storage path: the word is clipped to int8 range (WL≤8 by
    # construction of the mode), matching controller.quantize_params' int8
    # branch; dequant (· 2^-FL) happens at the consumer.
    seed = ctl_ref[0, 1]
    scale = _pow2i(ctl_ref[0, 0])
    x = x_ref[...].astype(jnp.float32)
    u = _inkernel_uniform(seed, x.shape, block_rows, cols, hw_prng)
    s = x * scale
    f = jnp.floor(s)
    q = f + (u < (s - f)).astype(jnp.float32)
    o_ref[...] = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)


def _fused_call(kernel, ctl: Array, x: Array, out_dtype, *, block_rows: int,
                interpret: bool, hw_prng: bool):
    n = x.size
    cols = LANE * 4
    rows = pl.cdiv(n, cols)
    pad = rows * cols - n
    x2 = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad)).reshape(rows, cols)
    grid = (pl.cdiv(rows, block_rows),)
    body = functools.partial(kernel, block_rows=block_rows, cols=cols,
                             hw_prng=hw_prng)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # ⟨wl,fl,seed⟩ / ⟨fl,seed⟩
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        interpret=interpret,
    )(ctl, x2)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "hw_prng"))
def sr_quantize_fused(x: Array, seed: Array, wl: Array, fl: Array, *,
                      block_rows: int = 256, interpret: bool = False,
                      hw_prng: bool = False) -> Array:
    """SR quantize with in-kernel noise: 2 param-sized HBM transfers total.

    x: any shape/float dtype; seed: int32 scalar; wl/fl: int32 scalars.
    ``hw_prng=True`` uses the TPU hardware PRNG (compiled TPU runs only);
    otherwise the portable counter-hash stream is used. Deterministic per
    ⟨seed, block_rows⟩ either way.
    """
    shape, dtype = x.shape, x.dtype
    ctl = jnp.stack([jnp.asarray(wl), jnp.asarray(fl),
                     jnp.asarray(seed)]).astype(jnp.int32).reshape(1, 3)
    out = _fused_call(_sr_fused_kernel, ctl, x, jnp.float32,
                      block_rows=block_rows, interpret=interpret,
                      hw_prng=hw_prng)
    return out.reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "hw_prng"))
def sr_quantize_fused_int8(x: Array, seed: Array, fl: Array, *,
                           block_rows: int = 256, interpret: bool = False,
                           hw_prng: bool = False) -> Array:
    """Int8-word flavor for the native_int8/packed path: returns
    round-stochastic(x·2^FL) clipped to int8, as an int8 tensor. Dequant is
    ``q8 * 2^-FL`` at the consumer (after the FSDP gather)."""
    shape = x.shape
    ctl = jnp.stack([jnp.asarray(fl),
                     jnp.asarray(seed)]).astype(jnp.int32).reshape(1, 2)
    out = _fused_call(_sr_fused_int8_kernel, ctl, x, jnp.int8,
                      block_rows=block_rows, interpret=interpret,
                      hw_prng=hw_prng)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Per-layer-stacked variants: an (L,)-vector ⟨WL,FL⟩ operand in SMEM plus a
# leading per-layer grid dimension — one launch quantizes a whole
# transformer stack, each layer on its own grid.


def _stacked_uniform(seed: Array, shape, l, blk, block_rows: int, cols: int,
                     rows: int, hw_prng: bool) -> Array:
    if hw_prng:
        return _hw_uniform(seed, shape, l * pl.num_programs(1) + blk)
    # Flat index over the padded (L·rows, cols) stack: layer l's stream
    # starts at row l·rows, so L=1 degenerates to the unstacked stream and
    # the bits never depend on block_rows.
    row0 = l * rows + blk * block_rows
    return _hash_uniform(seed, shape, row0, cols)


def _sr_fused_stacked_kernel(seed_ref, wlfl_ref, x_ref, o_ref, *,
                             block_rows: int, cols: int, rows: int,
                             hw_prng: bool):
    l = pl.program_id(0)
    seed = seed_ref[0, 0]
    scale = _pow2i(wlfl_ref[l, 1])
    qmax = _pow2i(wlfl_ref[l, 0] - 1) - 1.0
    x = x_ref[0].astype(jnp.float32)
    u = _stacked_uniform(seed, x.shape, l, pl.program_id(1), block_rows,
                         cols, rows, hw_prng)
    s = x * scale
    f = jnp.floor(s)
    q = f + (u < (s - f)).astype(jnp.float32)
    q = jnp.clip(q, -qmax - 1.0, qmax)
    o_ref[0] = (q / scale).astype(o_ref.dtype)


def _sr_fused_stacked_int8_kernel(seed_ref, fl_ref, x_ref, o_ref, *,
                                  block_rows: int, cols: int, rows: int,
                                  hw_prng: bool):
    l = pl.program_id(0)
    seed = seed_ref[0, 0]
    scale = _pow2i(fl_ref[l, 0])
    x = x_ref[0].astype(jnp.float32)
    u = _stacked_uniform(seed, x.shape, l, pl.program_id(1), block_rows,
                         cols, rows, hw_prng)
    s = x * scale
    f = jnp.floor(s)
    q = f + (u < (s - f)).astype(jnp.float32)
    o_ref[0] = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)


def _stacked_call(kernel, ctl: Array, x: Array, out_dtype, *,
                  block_rows: int, interpret: bool, hw_prng: bool):
    L = x.shape[0]
    n = x.size // L
    cols = LANE * 4
    rows = pl.cdiv(n, cols)
    pad = rows * cols - n
    x2 = jnp.pad(x.reshape(L, -1).astype(jnp.float32),
                 ((0, 0), (0, pad))).reshape(L, rows, cols)
    seed2 = ctl[0]
    grid = (L, pl.cdiv(rows, block_rows))
    body = functools.partial(kernel, block_rows=block_rows, cols=cols,
                             rows=rows, hw_prng=hw_prng)
    out = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # seed (1, 1)
            pl.BlockSpec(memory_space=pltpu.SMEM),     # per-layer ⟨WL,FL⟩/FL
            pl.BlockSpec((1, block_rows, cols), lambda l, i: (l, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows, cols), lambda l, i: (l, i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, rows, cols), out_dtype),
        interpret=interpret,
    )(seed2, ctl[1], x2)
    return out.reshape(L, rows * cols)[:, :n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "hw_prng"))
def sr_quantize_fused_stacked(x: Array, seed: Array, wl: Array, fl: Array, *,
                              block_rows: int = 256, interpret: bool = False,
                              hw_prng: bool = False) -> Array:
    """Per-layer-stacked SR quantize with in-kernel noise: x (L, ...) is
    quantized so slice l sits on the ⟨wl[l], fl[l]⟩ grid, in ONE kernel
    launch (grid (L, row-blocks), precision vector in SMEM). Same 2-HBM-
    transfer contract as :func:`sr_quantize_fused`; bit-identical to it for
    L=1 under the portable stream."""
    shape, dtype = x.shape, x.dtype
    seed2 = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    wlfl = jnp.stack([jnp.asarray(wl), jnp.asarray(fl)],
                     axis=1).astype(jnp.int32)
    out = _stacked_call(_sr_fused_stacked_kernel, (seed2, wlfl), x,
                        jnp.float32, block_rows=block_rows,
                        interpret=interpret, hw_prng=hw_prng)
    return out.reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret",
                                             "hw_prng"))
def sr_quantize_fused_stacked_int8(x: Array, seed: Array, fl: Array, *,
                                   block_rows: int = 256,
                                   interpret: bool = False,
                                   hw_prng: bool = False) -> Array:
    """Int8-word flavor of :func:`sr_quantize_fused_stacked`: layer l's
    words are round-stochastic(x[l]·2^fl[l]) clipped to int8. Dequant is
    ``q8[l] * 2^-fl[l]`` at the consumer."""
    shape = x.shape
    seed2 = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    fl2 = jnp.asarray(fl, jnp.int32).reshape(-1, 1)
    out = _stacked_call(_sr_fused_stacked_int8_kernel, (seed2, fl2), x,
                        jnp.int8, block_rows=block_rows, interpret=interpret,
                        hw_prng=hw_prng)
    return out.reshape(shape)
