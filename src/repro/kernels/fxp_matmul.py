"""Pallas TPU kernel: fixed-point (int8-stored) matmul with fused dequant.

The AdaPT steady state keeps most layers at WL ≤ 8 (training starts at ⟨8,4⟩
and PushDown pushes down), so the hot matmul is
    y = x @ (wq · 2^-FL) (+ bias)
with wq int8. Doing dequant-then-matmul in XLA materializes a full f32/bf16
copy of the weights in HBM every step; this kernel streams int8 weight tiles
into VMEM (4× less HBM traffic than f32, 2× less than bf16) and dequantizes
in-register on the way into the MXU.

Block scheme: grid (⌈M/bm⌉, ⌈N/bn⌉, ⌈K/bk⌉), K innermost so the f32
accumulator tile lives in a VMEM scratch across the K loop. A block not
given is the shape rule's (``_dense_blocks``: from the kernel kind, the
three extents and the operand dtypes); MXU-aligned 128-multiples preferred
but NOT required — partial boundary blocks are tail-masked in-kernel
(``_mask_tail``: Pallas pads them with garbage/NaN), so any ⟨M,K,N⟩ runs
with the block clamped to its dim and bounded VMEM. The MXU takes the
operands in their stored dtype (the int8 words cast to the activation's),
accumulating in f32.

A full-integer variant (``int8_matmul``) takes int8 activations too and
accumulates in int32 — the v5e MXU's 2× int8 throughput path; used for
serving (W8A8) and benchmarked in §Perf.

Both ops also come in differentiable form (``fxp_matmul_vjp`` /
``int8_matmul_vjp``): ``jax.custom_vjp`` rules whose backward passes are
themselves Pallas kernels, so the differentiated training forward never
falls back to a dequantized HBM weight copy either.

  * dx = dy @ (wq·scale)ᵀ  — ``_matmul_dx_kernel`` streams the SAME int8
    weight tiles the forward reads, just with a transposed index map
    ((j, n) instead of (k, j)); dequant stays in-register.
  * dw = xᵀ @ dy           — ``_matmul_dw_kernel``, f32 VMEM accumulation;
    its contraction against wq yields the scale cotangent
    dscale = Σ dw∘wq (= Σ dy∘(x@wq), XLA's reassociation of the same sum).
  * dwq is float0: the int8 words are non-differentiable storage — the
    straight-through path to the f32 master runs through the quantize,
    not through the matmul words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _clamp_block(b: int, d: int) -> int:
    """Block size for a dim of true extent d: the requested b, clamped.
    Non-divisible boundaries are fine — every gridded kernel here
    tail-masks its padded lanes in-register (Pallas pads partial boundary
    blocks with garbage/NaN, and out-of-range boundary writes are
    dropped), so grids stay ``pl.cdiv`` with VMEM bounded by the
    *requested* block for ANY dim, primes included. O(1): the old
    divisor-scan fallback (largest divisor ≤ b, else the whole dim — a
    VMEM hazard for large prime-ish dims) is gone."""
    return min(b, d)


# ---------------------------------------------------------------------------
# Block rule: tiles from the product's shape.
#
# Each dense kernel computes one product C[P, Q] = A[P, R] · B[R, Q] on a
# grid whose innermost axis walks the contracted R, with the (p, q) output
# tile accumulated in f32 VMEM. A grid step reads a (p, r) A tile and an
# (r, q) B tile from HBM for 2·p·q·r FLOPs, so its arithmetic intensity is
# 2·p·q / (p·|A| + q·|B|) FLOP/byte (|·| = bytes per element): the output
# tile alone sets it, while r sets how much work a step carries against
# its fixed cost. The rule starts from 1024 on every side (the best of the
# blocks swept on a v5e at both benchmark cells' shapes), grows the side
# that carries the larger share of the bytes until the step clears v5e's
# ridge (197 TFLOP/s bf16 or 393 TOP/s int8 over 819 GB/s of HBM: 240 /
# 480 FLOP/byte) where the product's extents allow it at all, then shrinks
# r, and after it the wider output side, until the reckoned VMEM fits the
# budget. A dim no larger than its block is one whole, unmasked block; a
# larger one is cut into equal lane multiples where it can be
# (``_split_dim``). Small M (serving decode) is therefore one whole row
# block, and the wide word tiles stream the weights.

# kind → which of (M, K, N) is P, Q and R.
_DENSE_KINDS = {
    "fwd": ("M", "N", "K"),     # fxp_matmul: x @ words
    "int8": ("M", "N", "K"),    # int8_matmul: xq @ wq
    "dx": ("M", "K", "N"),      # matmul_dx: dy @ wordsᵀ
    "dw": ("K", "N", "M"),      # matmul_dw: xᵀ @ dy
}
_DENSE_CAP = 1024
_RIDGE_BF16, _RIDGE_INT8 = 240, 480     # FLOP/byte on v5e
_LANE = 128
_VMEM_BUDGET = 48 * 2**20               # reckoned bytes a rule block may take
_VMEM_SCOPED = 16 * 2**20               # Mosaic's default scoped VMEM (v5e)


def _split_dim(d: int, cap: int) -> int:
    """Block for a dim of extent ``d`` under ``cap`` (a lane multiple): the
    whole dim when ``d <= cap``; else the fewest equal blocks that are lane
    multiples, where up to twice the fewest blocks give such a cut; else
    the fewest blocks, rounded up to a lane multiple (one tail block)."""
    if d <= cap:
        return d
    n = -(-d // cap)
    for m in range(n, 2 * n + 1):
        if d % (m * _LANE) == 0:
            return d // m
    return -(-(-(-d // n)) // _LANE) * _LANE


def _dense_vmem(kind: str, p: int, q: int, r: int, a_bytes: int,
                b_bytes: int, out_bytes: int) -> int:
    """Reckoned VMEM of one grid step: the double-buffered A, B and output
    tiles, the 4-byte accumulator and the dot's 4-byte result, and the
    kind's in-register temporaries (the words cast to A's dtype; dw's A
    tile transposed for the MXU)."""
    need = 2 * (p * r * a_bytes + r * q * b_bytes) + 2 * p * q * out_bytes
    need += 2 * p * q * 4
    if kind in ("fwd", "dx"):
        need += r * q * a_bytes
    elif kind == "dw":
        need += p * r * a_bytes
    return need


def _dense_blocks(kind: str, M: int, K: int, N: int, a_dtype, b_dtype,
                  out_dtype) -> dict:
    """The rule's blocks for ``kind``'s product over (M, K, N), keyed by
    dim name ("M", "K", "N"). A is the operand that shares the output's
    rows (x, dy, or x for dw), B the other; the result depends on the
    kind, the three extents and the three dtypes only."""
    P, Q, R = (dict(M=M, K=K, N=N)[d] for d in _DENSE_KINDS[kind])
    a, b, o = (jnp.dtype(t).itemsize for t in (a_dtype, b_dtype, out_dtype))
    p, q, r = (_split_dim(d, _DENSE_CAP) for d in (P, Q, R))
    ridge = _RIDGE_INT8 if kind == "int8" else _RIDGE_BF16
    reach = 2 * P * Q >= ridge * (P * a + Q * b)    # else no tile clears it
    while reach and 2 * p * q < ridge * (p * a + q * b):
        # p amortizes B's re-reads (q·b of a step's bytes), q A's (p·a).
        if p < P and (b * q >= a * p or q == Q):
            p = _split_dim(P, 2 * p)
        elif q < Q:
            q = _split_dim(Q, 2 * q)
        else:
            break
    half = lambda blk: max(_LANE, blk // 2 // _LANE * _LANE)
    while _dense_vmem(kind, p, q, r, a, b, o) > _VMEM_BUDGET:
        if r > 4 * _LANE:
            r = _split_dim(R, half(r))
        elif q >= p and q > _LANE:
            q = _split_dim(Q, half(q))
        elif p > _LANE:
            p = _split_dim(P, half(p))
        else:
            break
    return dict(zip(_DENSE_KINDS[kind], (p, q, r)))


def _pick_blocks(kind: str, M: int, K: int, N: int, bm, bn, bk, a_dtype,
                 b_dtype, out_dtype):
    """(bm, bn, bk): each requested block, else the rule's, clamped to its
    dim; and the ``CompilerParams`` for them. Past Mosaic's default scoped
    VMEM they ask for their reckoning and a quarter more (Mosaic used 80–
    100% of the reckoning at both cells' shapes), and no more: what a
    kernel reserves, XLA's own ops around it cannot use."""
    rule = _dense_blocks(kind, M, K, N, a_dtype, b_dtype, out_dtype)
    bm, bn, bk = (_clamp_block(rule[n] if b is None else b, d)
                  for b, n, d in ((bm, "M", M), (bn, "N", N), (bk, "K", K)))
    blk = dict(M=bm, N=bn, K=bk)
    p, q, r = (blk[d] for d in _DENSE_KINDS[kind])
    need = _dense_vmem(kind, p, q, r, jnp.dtype(a_dtype).itemsize,
                       jnp.dtype(b_dtype).itemsize,
                       jnp.dtype(out_dtype).itemsize)
    limit = None if need <= _VMEM_SCOPED else min(need + need // 4,
                                                   100 * 2**20)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)
    return bm, bn, bk, params


def _mask_tail(x: Array, axis: int, pid, dim: int) -> Array:
    """Zero the garbage-padding tail of a boundary block along ``axis``.

    ``dim`` is the true (unpadded) extent of the axis; the block extent is
    read off ``x`` itself and ``pid`` is the grid index along that axis.
    Statically a no-op when the grid tiles ``dim`` evenly, so aligned
    shapes trace to exactly the unmasked kernel (zero overhead)."""
    b = x.shape[axis]
    if dim % b == 0:
        return x
    if x.dtype.itemsize < 4 and x.shape[0] < 8:
        # Mosaic cannot broadcast the mask over the sublanes of a packed
        # tile this short (decode rows): select in 32 bits.
        wide = jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.int32
        return _mask_tail(x.astype(wide), axis, pid, dim).astype(x.dtype)
    idx = b * pid + jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx < dim, x, jnp.zeros_like(x))


def float0_like(x: Array) -> np.ndarray:
    """The cotangent for a non-differentiable integer operand (custom_vjp
    requires an explicit float0 array for int primals)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _fxp_matmul_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, nk: int,
                       dims: tuple):
    M, K, N = dims
    i, j, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # K is contracted: garbage in EITHER operand's K tail would poison
    # every output element (0·NaN = NaN), so both tails go to exact zero.
    # The words (|q| ≤ 128) are exact in the activation's dtype, so the MXU
    # takes both operands as stored.
    x = _mask_tail(x_ref[...], 1, ik, K)
    w = _mask_tail(w_ref[...].astype(x.dtype), 0, ik, K)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        # M/N tails only pollute out-of-range output lanes (dropped on the
        # boundary write) — zero-fill them anyway so the block never holds
        # garbage.
        out = acc_ref[...] * scale_ref[0, 0]
        out = _mask_tail(_mask_tail(out, 0, i, M), 1, j, N)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def fxp_matmul(x: Array, wq: Array, scale: Array, *, bm: int | None = None,
               bn: int | None = None, bk: int | None = None, out_dtype=None,
               interpret: bool = False) -> Array:
    """y = x @ (wq * scale).  x: (M,K) float; wq: (K,N) int8; scale: () f32.

    Any ⟨M,K,N⟩ is accepted (primes included): partial boundary blocks are
    tail-masked in-kernel, so blocks stay the requested clamp and VMEM
    stays bounded."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    out_dtype = out_dtype or x.dtype
    bm, bn, bk, params = _pick_blocks("fwd", M, K, N, bm, bn, bk, x.dtype,
                                      wq.dtype, out_dtype)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    kernel = functools.partial(_fxp_matmul_kernel, nk=grid[2],
                               dims=(M, K, N))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(x, wq, scale.reshape(1, 1).astype(jnp.float32))


def _int8_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int,
                        dims: tuple):
    M, K, N = dims
    i, j, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 padding is arbitrary garbage words — zero both K tails so the
    # int32 accumulation over the tail is exactly 0.
    x = _mask_tail(x_ref[...], 1, ik, K)
    w = _mask_tail(w_ref[...], 0, ik, K)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ik == nk - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32) * s_ref[0, 0]
        out = _mask_tail(_mask_tail(out, 0, i, M), 1, j, N)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(xq: Array, wq: Array, sx: Array, sw: Array, *,
                bm: int | None = None, bn: int | None = None,
                bk: int | None = None, interpret: bool = False) -> Array:
    """W8A8 path: (xq @ wq) * (sx*sw); int32 MXU accumulation, f32 out.
    Accepts any ⟨M,K,N⟩ — partial boundary blocks are tail-masked."""
    M, K = xq.shape
    K2, N = wq.shape
    assert K == K2, (xq.shape, wq.shape)
    bm, bn, bk, params = _pick_blocks("int8", M, K, N, bm, bn, bk, xq.dtype,
                                      wq.dtype, jnp.float32)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    kernel = functools.partial(_int8_matmul_kernel, nk=grid[2],
                               dims=(M, K, N))
    s = (sx.astype(jnp.float32) * sw.astype(jnp.float32)).reshape(1, 1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=params,
    )(xq, wq, s)


# ---------------------------------------------------------------------------
# Backward kernels


def _matmul_dx_kernel(dy_ref, w_ref, scale_ref, dx_ref, acc_ref, *, nn: int,
                      dims: tuple):
    """dx tile = Σ_n dy(i,n) @ w(j,n)ᵀ — the weight tile is the forward's
    int8 (K,N) array read through a transposed index map, dequantized
    in-register; no transposed/dequantized weight copy ever exists in HBM."""
    M, K, N = dims
    i, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # N is the contracted dim here — zero both N tails before the MXU.
    dy = _mask_tail(dy_ref[...], 1, n, N)
    w = _mask_tail(w_ref[...].astype(dy.dtype), 1, n, N)
    acc_ref[...] += jax.lax.dot_general(
        dy, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(n == nn - 1)
    def _done():
        out = acc_ref[...] * scale_ref[0, 0]
        out = _mask_tail(_mask_tail(out, 0, i, M), 1, j, K)
        dx_ref[...] = out.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def matmul_dx(dy: Array, wq: Array, scale: Array, *, bm: int | None = None,
              bn: int | None = None, bk: int | None = None, out_dtype=None,
              interpret: bool = False) -> Array:
    """dx = dy @ (wq * scale)ᵀ.  dy: (M,N); wq: (K,N) int8; out (M,K)."""
    M, N = dy.shape
    K, N2 = wq.shape
    assert N == N2, (dy.shape, wq.shape)
    out_dtype = out_dtype or dy.dtype
    bm, bn, bk, params = _pick_blocks("dx", M, K, N, bm, bn, bk, dy.dtype,
                                      wq.dtype, out_dtype)
    grid = (pl.cdiv(M, bm), pl.cdiv(K, bk), pl.cdiv(N, bn))
    kernel = functools.partial(_matmul_dx_kernel, nn=grid[2],
                               dims=(M, K, N))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, n: (i, n)),
            pl.BlockSpec((bk, bn), lambda i, j, n: (j, n)),   # transposed map
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, n: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(dy, wq, scale.reshape(1, 1).astype(jnp.float32))


def _matmul_dw_kernel(x_ref, dy_ref, dw_ref, acc_ref, *, nm: int,
                      dims: tuple):
    M, K, N = dims
    i, j, m = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # M is the contracted dim here — zero both M tails before the MXU.
    # Operands go in as stored (one common dtype where they differ).
    ct = jnp.promote_types(x_ref.dtype, dy_ref.dtype)
    x = _mask_tail(x_ref[...].astype(ct), 0, m, M)
    dy = _mask_tail(dy_ref[...].astype(ct), 0, m, M)
    acc_ref[...] += jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(m == nm - 1)
    def _done():
        dw_ref[...] = _mask_tail(_mask_tail(acc_ref[...], 0, i, K), 1, j, N)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_dw(x: Array, dy: Array, *, bm: int | None = None,
              bn: int | None = None, bk: int | None = None,
              interpret: bool = False) -> Array:
    """dw = xᵀ @ dy in f32 (VMEM scratch accumulation over the M loop).
    x: (M,K); dy: (M,N); out (K,N) f32."""
    M, K = x.shape
    M2, N = dy.shape
    assert M == M2, (x.shape, dy.shape)
    bm, bn, bk, params = _pick_blocks("dw", M, K, N, bm, bn, bk, x.dtype,
                                      dy.dtype, jnp.float32)
    grid = (pl.cdiv(K, bk), pl.cdiv(N, bn), pl.cdiv(M, bm))
    kernel = functools.partial(_matmul_dw_kernel, nm=grid[2],
                               dims=(M, K, N))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, m: (m, i)),
            pl.BlockSpec((bm, bn), lambda i, j, m: (m, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j, m: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(x, dy)


# ---------------------------------------------------------------------------
# custom_vjp rules


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fxp_matmul_diff(cfg, x, wq, scale):
    bm, bn, bk, out_dtype, interpret = cfg
    return fxp_matmul(x, wq, scale, bm=bm, bn=bn, bk=bk,
                      out_dtype=out_dtype, interpret=interpret)


def _fxp_matmul_diff_fwd(cfg, x, wq, scale):
    return _fxp_matmul_diff(cfg, x, wq, scale), (x, wq, scale)


def _fxp_matmul_diff_bwd(cfg, res, dy):
    bm, bn, bk, _, interpret = cfg
    x, wq, scale = res
    dx = matmul_dx(dy, wq, scale, bm=bm, bn=bn, bk=bk,
                   out_dtype=x.dtype, interpret=interpret)
    dw = matmul_dw(x, dy, bm=bm, bn=bn, bk=bk, interpret=interpret)
    dscale = (jnp.sum(dw * wq.astype(jnp.float32))
              .reshape(scale.shape).astype(scale.dtype))
    return dx, float0_like(wq), dscale


_fxp_matmul_diff.defvjp(_fxp_matmul_diff_fwd, _fxp_matmul_diff_bwd)


def fxp_matmul_vjp(x: Array, wq: Array, scale: Array, *,
                   bm: int | None = None, bn: int | None = None,
                   bk: int | None = None, out_dtype=None,
                   interpret: bool = False) -> Array:
    """Differentiable :func:`fxp_matmul`: same forward kernel, Pallas
    backward (``matmul_dx`` / ``matmul_dw``)."""
    return _fxp_matmul_diff((bm, bn, bk, out_dtype, interpret),
                            x, wq, jnp.asarray(scale, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_matmul_diff(cfg, xq, wq, sx, sw):
    bm, bn, bk, interpret = cfg
    return int8_matmul(xq, wq, sx, sw, bm=bm, bn=bn, bk=bk,
                       interpret=interpret)


def _int8_matmul_diff_fwd(cfg, xq, wq, sx, sw):
    return _int8_matmul_diff(cfg, xq, wq, sx, sw), (xq, wq, sx, sw)


def _int8_matmul_diff_bwd(cfg, res, dy):
    bm, bn, bk, interpret = cfg
    xq, wq, sx, sw = res
    # Recompute-based backward: both operands are int8 words (float0
    # cotangents), so the only gradients are the two scales. The raw int32
    # accumulator is regenerated by the forward kernel at unit scale.
    acc = int8_matmul(xq, wq, jnp.float32(1.0), jnp.float32(1.0),
                      bm=bm, bn=bn, bk=bk, interpret=interpret)
    g0 = jnp.sum(dy.astype(jnp.float32) * acc)
    dsx = (g0 * sw.astype(jnp.float32)).reshape(sx.shape).astype(sx.dtype)
    dsw = (g0 * sx.astype(jnp.float32)).reshape(sw.shape).astype(sw.dtype)
    return float0_like(xq), float0_like(wq), dsx, dsw


_int8_matmul_diff.defvjp(_int8_matmul_diff_fwd, _int8_matmul_diff_bwd)


def int8_matmul_vjp(xq: Array, wq: Array, sx: Array, sw: Array, *,
                    bm: int | None = None, bn: int | None = None,
                    bk: int | None = None, interpret: bool = False) -> Array:
    """Differentiable :func:`int8_matmul` (scale cotangents only; the int8
    words are non-differentiable storage)."""
    return _int8_matmul_diff((bm, bn, bk, interpret), xq, wq,
                             jnp.asarray(sx, jnp.float32),
                             jnp.asarray(sw, jnp.float32))


# ---------------------------------------------------------------------------
# Dense-layer rule: the model's TRAINING matmul. Unlike ``fxp_matmul_vjp``
# (whose weight cotangent is only contracted into dscale), it carries the
# straight-through gradient of paper alg. 1: the full dw = xᵀ@dy lands on
# the MASTER copy through the ``wref`` receiver, so the optimizer step is
# exactly the one the XLA dequant-then-dot path produces — while the
# forward/dx stream int8 tiles through the MXU.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fxp_dense_diff(cfg, x, wq, scale, wref):
    del wref    # gradient receiver only: never read, so its zeros are DCE'd
    bm, bn, bk, out_dtype, interpret, _ = cfg
    return fxp_matmul(x, wq, scale, bm=bm, bn=bn, bk=bk,
                      out_dtype=out_dtype, interpret=interpret)


def _fxp_dense_diff_fwd(cfg, x, wq, scale, wref):
    return _fxp_dense_diff(cfg, x, wq, scale, wref), (x, wq, scale)


def _fxp_dense_diff_bwd(cfg, res, dy):
    bm, bn, bk, _, interpret, wref_dtype = cfg
    x, wq, scale = res
    dx = matmul_dx(dy, wq, scale, bm=bm, bn=bn, bk=bk,
                   out_dtype=x.dtype, interpret=interpret)
    dw = matmul_dw(x, dy, bm=bm, bn=bn, bk=bk, interpret=interpret)
    # straight-through: the whole weight cotangent routes to the master
    # receiver; the scale is controller state (2^-FL), not a trainable —
    # its cotangent is zero, matching fixed_point.dequant_packed's rule.
    return dx, float0_like(wq), jnp.zeros_like(scale), dw.astype(wref_dtype)


_fxp_dense_diff.defvjp(_fxp_dense_diff_fwd, _fxp_dense_diff_bwd)


def fxp_dense_vjp(x: Array, wq: Array, scale: Array, wref: Array, *,
                  bm: int | None = None, bn: int | None = None,
                  bk: int | None = None, out_dtype=None,
                  interpret: bool = False) -> Array:
    """Differentiable dense layer over MATERIALIZED int8 words: forward is
    :func:`fxp_matmul`, dx streams the same int8 tiles (``matmul_dx``), and
    dw = xᵀ@dy (``matmul_dw``) lands on ``wref`` — the straight-through
    path to the master copy. ``scale`` may be () or (1, 1) (a scan-sliced
    per-layer 2^-FL); ``wref`` is never read (its cotangent is the output)."""
    return _fxp_dense_diff((bm, bn, bk, out_dtype, interpret,
                            jnp.dtype(wref.dtype)), x, wq, scale, wref)
