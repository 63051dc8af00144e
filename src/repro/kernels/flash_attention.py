"""Pallas TPU kernel: blocked (flash) attention forward.

The 32k-prefill shapes are attention-dominated: naive attention materializes
a (Sq × Skv) = 32k×32k f32 logits tensor per head (4 GB) — far beyond VMEM
and a pure HBM-bandwidth disaster. This kernel runs the standard online-
softmax block scheme: for each (batch, head, q-block) the (m, l, acc) state
stays in VMEM while kv-blocks stream through, so HBM traffic is O(S·D)
instead of O(S²).

Features needed by the assigned archs, all fused:
  * causal masking with end-alignment (decode/prefill-with-cache friendly)
  * sliding-window masking (mixtral SWA, gemma2 local layers)
  * logit softcapping   (gemma2: softcap · tanh(logits / softcap))
  * GQA via kv-head index mapping (no jnp.repeat materialization)

Grid: (B, H, ⌈Sq/bq⌉, ⌈Skv/bk⌉), kv innermost ("arbitrary"). MXU-aligned
q/kv blocks preferred but NOT required: non-divisible Sq/Skv produce
partial boundary blocks whose garbage padding is tail-masked in-kernel —
q/k tail lanes are NEG_INF in the score path (excluded from max/logsumexp
and every backward contraction, via the shared ``_block_mask``) and the
padded k/v/do lanes are zeroed before any MXU contraction.

The forward optionally emits the per-row logsumexp (``return_lse``) — the
residual the recompute-based backward (``flash_attention_vjp``) needs. The
backward precomputes the tiny per-row D = Σ dy∘o (one XLA elementwise
pass; o is not an operand of either launch) and then runs two more Pallas
kernels over the same block scheme: ``_dq`` re-derives the probabilities
from the stashed lse, ``_dkv`` accumulates dK/dV tiles with the q-loop
innermost — the rep query heads of each GQA group fold into the same
accumulators, so HBM only ever sees (B, Hkv, Skv, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fxp_matmul import _clamp_block, _mask_tail

Array = jax.Array

NEG_INF = -1e30
LANE = 128


# The per-row lse and D live in HBM as (B, H, 1, Sq) rows, so their blocks
# (1, bq) meet the TPU tiling rule for any head count. Inside the kernels
# they are (bq, 1) columns; these two move between the layouts through one
# aligned (bq, 128) transpose.
def _col_to_row(c: Array) -> Array:
    return jnp.transpose(jnp.broadcast_to(c, (c.shape[0], LANE)))[:1]


def _row_to_col(r: Array) -> Array:
    return jnp.transpose(jnp.broadcast_to(r, (LANE, r.shape[1])))[:, :1]


def _positions(iq: int, ik: int, bq: int, bk: int, q_offset: int):
    """Absolute key-space positions of a (bq, bk) block: queries are
    end-aligned (q_offset = Skv − Sq)."""
    qpos = (iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            + q_offset)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos, kpos


def _block_mask(iq, ik, *, bq: int, bk: int, causal: bool, window: int,
                q_offset: int, sq: int, skv: int):
    """The ONE causal/sliding-window/tail mask both the forward and the
    backward recompute share — any inclusivity change here stays
    bit-identical across o, lse and dQ/dK/dV.

    ``sq``/``skv`` are the TRUE sequence extents: on boundary blocks of a
    non-divisible grid the q/k tail lanes hold Pallas garbage padding, so
    they are masked out of the score matrix (NEG_INF downstream — excluded
    from the softmax max, the logsumexp, and every backward contraction).
    Statically free when the grid tiles both dims evenly."""
    qpos, kpos = _positions(iq, ik, bq, bk, q_offset)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if sq % bq:
        mask &= qpos - q_offset < sq          # q-tail rows of the block
    if skv % bk:
        mask &= kpos < skv                    # k-tail cols of the block
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, *refs,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, nk: int, q_offset: int, sq: int,
                  skv: int, with_lse: bool):
    if with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = refs, None
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    # kv tails: k garbage only reaches masked logit columns, but v rides
    # p @ v where the masked p entries are exact zeros — 0·NaN = NaN, so
    # both tails are zeroed before any contraction (no-ops when aligned).
    k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)   # (bk, D)
    v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)   # (bk, D)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    logits *= scale
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)

    mask = _block_mask(iq, ik, bq=bq, bk=bk, causal=causal, window=window,
                       q_offset=q_offset, sq=sq, skv=skv)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                   # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _done():
        # Rows with NO surviving key (Sq > Skv under causal end-alignment)
        # keep m = NEG_INF: exp(NEG_INF − NEG_INF) would average v
        # uniformly, a meaningless row the backward cannot reconstruct
        # from the lse — emit exactly 0 (and lse = NEG_INF) instead, so
        # forward and VJP agree that the row is constant.
        dead = m_ref[...] <= NEG_INF * 0.5
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = jnp.where(dead, 0.0,
                                acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = _col_to_row(
                jnp.where(dead, NEG_INF, m_ref[...] + jnp.log(l)))


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "bq", "bk", "interpret",
                                             "return_lse"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, softcap: float = 0.0,
                    scale: float | None = None, bq: int = 512, bk: int = 512,
                    interpret: bool = False, return_lse: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Skv, Hkv, D); returns (B, Sq, H, D).

    Query positions are aligned to the *end* of the key space
    (q_offset = Skv − Sq), matching prefill-with-cache and decode semantics.
    ``return_lse`` additionally returns the per-row logsumexp (B, H, Sq)
    f32 — the backward pass's residual. Rows whose mask admits no key at
    all (Sq > Skv under causal alignment) are exactly 0 with lse = NEG_INF
    — flash convention, and what the VJP assumes (ref_attention instead
    softmaxes the all-masked row into a uniform average).

    Any Sq/Skv is accepted: bq/bk are clamped (never widened to a
    whole-dim block) and partial boundary blocks are tail-masked
    in-kernel, so grids stay multi-block with VMEM bounded by the
    requested blocks even for prime sequence lengths.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    sc = scale if scale is not None else (1.0 / D ** 0.5)
    bq = _clamp_block(bq, Sq)
    bk = _clamp_block(bk, Skv)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Skv, bk)

    qt = q.transpose(0, 2, 1, 3)                  # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                  # (B, Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=sc, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk, q_offset=Skv - Sq,
        sq=Sq, skv=Skv, with_lse=return_lse)

    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, bq),
                                      lambda b, h, i, j: (b, h, 0, i)))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt)
    o = out[0].transpose(0, 2, 1, 3)
    return (o, out[1].reshape(B, H, Sq)) if return_lse else o


# ---------------------------------------------------------------------------
# Backward kernels (recompute-based, standard flash scheme)


def _block_probs(q, k, lse, iq, ik, *, scale, causal, window, softcap,
                 bq, bk, q_offset, sq, skv):
    """Recompute the (bq, bk) probability block p = exp(t − lse) from the
    stashed logsumexp (a (bq, 1) column), plus the pre-mask softcapped
    logits t (needed for the tanh chain). Masked entries — including q/k
    tail lanes of partial boundary blocks — are exactly 0 (no NEG_INF
    arithmetic, so fully-masked rows can't poison the accumulators with
    inf·0). Callers must
    hand in tail-sanitized q/k so t itself stays finite (the softcap tanh
    chain multiplies by (1 − (t/cap)²) AFTER the p zeros are in place)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    t = softcap * jnp.tanh(s / softcap) if softcap > 0.0 else s
    mask = _block_mask(iq, ik, bq=bq, bk=bk, causal=causal, window=window,
                       q_offset=q_offset, sq=sq, skv=skv)
    p = jnp.where(mask, jnp.exp(t - lse), 0.0)
    return p, t


def _grad_wrt_logits(p, dp, delta, t, *, softcap):
    """dt = p∘(dp − D); chain through the softcap tanh back to the raw
    (pre-cap, post-scale) logits."""
    dt = p * (dp - delta)
    if softcap > 0.0:
        dt = dt * (1.0 - jnp.square(t / softcap))
    return dt


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                     acc_ref, *, scale: float, causal: bool,
                     window: int, softcap: float, bq: int, bk: int, nk: int,
                     q_offset: int, sq: int, skv: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Tail-sanitize every streamed operand (static no-ops when aligned):
    # the masked p/g entries are exact zeros, but g @ k and do @ vᵀ still
    # touch the garbage k/v tail lanes (0·NaN = NaN), and q/do/delta tails
    # keep t and dp finite so the softcap chain can't reintroduce NaNs.
    q = _mask_tail(q_ref[0, 0].astype(jnp.float32), 0, iq, sq)
    k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)
    v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)
    do = _mask_tail(do_ref[0, 0].astype(jnp.float32), 0, iq, sq)
    delta = _mask_tail(_row_to_col(d_ref[0, 0]), 0, iq, sq)
    p, t = _block_probs(q, k, _row_to_col(lse_ref[0, 0]), iq, ik,
                        scale=scale, causal=causal, window=window,
                        softcap=softcap, bq=bq, bk=bk, q_offset=q_offset,
                        sq=sq, skv=skv)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    g = _grad_wrt_logits(p, dp, delta, t, softcap=softcap)
    acc_ref[...] += jax.lax.dot_general(
        g, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = _mask_tail(acc_ref[...] * scale, 0, iq,
                                  sq).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                      causal: bool, window: int, softcap: float, bq: int,
                      bk: int, nq: int, nj: int, q_offset: int, sq: int,
                      skv: int):
    # Grid dim 3 runs (rep · nq) steps head-major: j = r·nq + iq. The rep
    # query heads of the GQA group fold into the SAME (bk, D) accumulators,
    # so the kernel writes the group-summed dK/dV tiles directly — never a
    # rep×-sized per-query-head cotangent in HBM.
    ik, j = pl.program_id(2), pl.program_id(3)
    iq = jax.lax.rem(j, nq)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # Here BOTH contractions run over the q rows (pᵀ @ do, gᵀ @ q), so the
    # q/do/delta tails must be exact zeros — and the k/v tails likewise,
    # or the masked-p zeros meet garbage through dp (0·NaN = NaN). All
    # static no-ops on aligned grids.
    q = _mask_tail(q_ref[0, 0].astype(jnp.float32), 0, iq, sq)
    k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)
    v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)
    do = _mask_tail(do_ref[0, 0].astype(jnp.float32), 0, iq, sq)
    delta = _mask_tail(_row_to_col(d_ref[0, 0]), 0, iq, sq)
    p, t = _block_probs(q, k, _row_to_col(lse_ref[0, 0]), iq, ik,
                        scale=scale, causal=causal, window=window,
                        softcap=softcap, bq=bq, bk=bk, q_offset=q_offset,
                        sq=sq, skv=skv)
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    g = _grad_wrt_logits(p, dp, delta, t, softcap=softcap)
    dk_acc[...] += jax.lax.dot_general(
        g, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _done():
        # kv-tail rows of the accumulators are exact zeros by construction
        # (every contribution above is tail-masked), so the boundary write
        # is already zero-filled.
        dk_ref[0, 0] = dk_acc[...] * scale
        dv_ref[0, 0] = dv_acc[...]


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "bq", "bk", "interpret"))
def flash_attention_bwd(q: Array, k: Array, v: Array, o: Array, lse: Array,
                        do: Array, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, scale: float | None = None,
                        bq: int = 512, bk: int = 512,
                        interpret: bool = False):
    """dQ/dK/dV for :func:`flash_attention` given the stashed (o, lse).

    Per-row D = Σ dy∘o is a tiny (B, H, Sq) f32 precompute (one fused XLA
    elementwise pass — o is not an operand of either kernel launch), then
    two launches: dQ with the kv loop innermost (one (bq, D) f32
    accumulator), and dK/dV gridded over KV heads with the (rep · nq)
    q-blocks of the whole GQA group innermost, group-summing in VMEM.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    sc = scale if scale is not None else (1.0 / D ** 0.5)
    bq = _clamp_block(bq, Sq)
    bk = _clamp_block(bk, Skv)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Skv, bk)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32)
                    * o.transpose(0, 2, 1, 3).astype(jnp.float32),
                    axis=-1)[:, :, None, :]                 # (B, H, 1, Sq)
    lse = lse.reshape(B, H, 1, Sq)

    qspec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    lspec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=sc, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nk=nk, q_offset=Skv - Sq, sq=Sq, skv=Skv),
        grid=(B, H, nq, nk),
        in_specs=[
            qspec,
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            qspec, lspec, lspec,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt, dot, lse, delta)

    # dK/dV: grid over KV heads and kv blocks; the innermost dim runs
    # (rep · nq) steps — the q blocks of every query head in the GQA group
    # — folding the group-sum into the kernel's own accumulation, so only
    # the real (B, Hkv, Skv, D) cotangents ever reach HBM.
    def _qh(h, j, r=rep, n=nq):
        return h * r + j // n
    qjspec = pl.BlockSpec((1, 1, bq, D),
                          lambda b, h, i, j: (b, _qh(h, j), j % nq, 0))
    ljspec = pl.BlockSpec((1, 1, 1, bq),
                          lambda b, h, i, j: (b, _qh(h, j), 0, j % nq))
    kvjspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0))
    dkv_out = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=sc, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nq=nq, nj=nq * rep, q_offset=Skv - Sq,
                          sq=Sq, skv=Skv),
        grid=(B, Hkv, nk, nq * rep),
        in_specs=[qjspec, kvjspec, kvjspec, qjspec, ljspec, ljspec],
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Skv, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, Skv, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt, dot, lse, delta)

    return (dq.transpose(0, 2, 1, 3),
            dk.astype(k.dtype).transpose(0, 2, 1, 3),
            dv.astype(v.dtype).transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# custom_vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_diff(cfg, q, k, v):
    causal, window, softcap, scale, bq, bk, interpret = cfg
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, bq=bq, bk=bk,
                           interpret=interpret)


def _flash_diff_fwd(cfg, q, k, v):
    causal, window, softcap, scale, bq, bk, interpret = cfg
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, bq=bq, bk=bk,
                             interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_diff_bwd(cfg, res, do):
    causal, window, softcap, scale, bq, bk, interpret = cfg
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, softcap=softcap, scale=scale,
                               bq=bq, bk=bk, interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention_vjp(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int = 0, softcap: float = 0.0,
                        scale: float | None = None, bq: int = 512,
                        bk: int = 512, interpret: bool = False) -> Array:
    """Differentiable :func:`flash_attention`: same forward kernel (plus the
    lse stash under differentiation), Pallas recompute-based backward."""
    return _flash_diff((causal, window, softcap, scale, bq, bk, interpret),
                       q, k, v)
