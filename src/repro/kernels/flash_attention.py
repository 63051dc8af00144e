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

Grid: (B, H, ⌈Sq/bq⌉, band), kv innermost ("arbitrary"). MXU-aligned
q/kv blocks preferred but NOT required: non-divisible Sq/Skv produce
partial boundary blocks whose garbage padding is tail-masked in-kernel —
q/k tail lanes are NEG_INF in the score path (excluded from max/logsumexp
and every backward contraction, via the shared ``_block_mask``) and the
padded k/v/do lanes are zeroed before any MXU contraction.

Block skipping: a launch visits only the (q, kv) blocks the mask leaves
live. ``_kv_blocks`` / ``_q_blocks`` give the live range of blocks for a q
(kv) block under causal/window/end-alignment, ``_block_full`` whether the
mask admits every pair of a block. The innermost grid axis spans the band
(``_band``: the most live blocks any q block has, ⌈Skv/bk⌉ unless a causal
window bounds it) starting at the first live block; a dead step does no
work, and its index map names the block the previous step holds, so the
pipeline issues no copy for it. A fully live block skips the mask, a
partial one applies it. Skipped blocks would contribute exact zeros, so
every output is the one the whole grid gives.

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


# The block-skipping predicate. Each helper takes the static mask parameters
# of ``_block_mask`` and a block index that is a Python int (the wrappers,
# ``block_counts``) or a traced grid index (kernels, index maps); with Python
# ints it returns Python ints and bools.
def _imin(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _imax(a, b):
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _kv_blocks(iq, *, bq: int, bk: int, causal: bool, window: int,
               q_offset: int, sq: int, skv: int):
    """[lo, hi): the kv blocks in which ``_block_mask`` admits a (q, k)
    pair of q block ``iq`` (empty when all its rows are dead)."""
    lo, hi = 0, pl.cdiv(skv, bk)
    if window > 0:      # keys inside the first query's window
        lo = _imax(iq * bq + q_offset - window + 1, 0) // bk
    if causal:          # keys at or before the last query
        end = _imin((iq + 1) * bq, sq) + q_offset
        hi = pl.cdiv(_imax(end, 0), bk)
    return lo, hi


def _q_blocks(ik, *, bq: int, bk: int, causal: bool, window: int,
              q_offset: int, sq: int, skv: int):
    """[lo, hi): the q blocks in which ``_block_mask`` admits a (q, k) pair
    of kv block ``ik`` (empty when no query reaches it)."""
    lo, hi = 0, pl.cdiv(sq, bq)
    if causal:          # queries at or after the first key
        lo = _imax(ik * bk - q_offset, 0) // bq
    if window > 0:      # queries whose window holds the last key
        end = _imin((ik + 1) * bk, skv) - 1 + window - q_offset
        hi = pl.cdiv(_imin(_imax(end, 0), sq), bq)
    return lo, hi


def _block_full(iq, ik, *, bq: int, bk: int, causal: bool, window: int,
                q_offset: int, sq: int, skv: int):
    """Whether ``_block_mask`` admits every pair of block (iq, ik): no tail
    lanes, wholly at or below the causal diagonal, wholly inside the
    window. Statically True where neither causal, window nor tails mask."""
    full = True
    if sq % bq:
        full = full & ((iq + 1) * bq <= sq)
    if skv % bk:
        full = full & ((ik + 1) * bk <= skv)
    if causal:          # the last key at or before the first query
        full = full & ((ik + 1) * bk <= iq * bq + q_offset + 1)
    if window > 0:      # the first key inside the last query's window
        full = full & (ik * bk > (iq + 1) * bq + q_offset - 1 - window)
    return full


def _band(n: int, blocks) -> int:
    """Innermost grid extent: the most live blocks any of the ``n`` outer
    blocks has under ``blocks`` (``_kv_blocks`` or ``_q_blocks``)."""
    return max(1, max(hi - lo for lo, hi in map(blocks, range(n))))


def _step_block(lo, hi, j):
    """The block grid step ``j`` of a band starting at ``lo`` names: the
    live block ``lo + j``, or on a dead step past the live range the last
    live one (what the previous step holds, so no copy is issued)."""
    return _imin(lo + j, _imax(hi - 1, lo))


def _visit(body, live, full):
    """Run ``body(masked)`` on a live block: without the mask where the
    block is fully live, with it where the mask cuts it. A dead block does
    no work."""
    if full is True:
        body(False)
        return
    pl.when(jnp.logical_and(live, full))(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(lambda: body(True))


def block_counts(sq: int, skv: int, bq: int, bk: int, *, causal: bool,
                 window: int) -> tuple[int, int, int]:
    """(live, fully live, all) (q, kv) blocks of one head's attention grid
    at the kernels' clamped blocks: the blocks a launch computes, those it
    computes without the mask, and the whole ⌈Sq/bq⌉·⌈Skv/bk⌉ grid."""
    bq, bk = _clamp_block(bq, sq), _clamp_block(bk, skv)
    m = dict(bq=bq, bk=bk, causal=causal, window=window, q_offset=skv - sq,
             sq=sq, skv=skv)
    live = full = 0
    for iq in range(pl.cdiv(sq, bq)):
        lo, hi = _kv_blocks(iq, **m)
        live += hi - lo
        full += sum(bool(_block_full(iq, ik, **m)) for ik in range(lo, hi))
    return live, full, pl.cdiv(sq, bq) * pl.cdiv(skv, bk)


def _kv_spec(bk: int, d: int, rep: int, mask: dict) -> pl.BlockSpec:
    """k or v blocks of a launch whose innermost axis walks q block i's band
    of kv blocks (forward, dQ); the GQA group's query heads share a kv
    head."""
    def index(b, h, i, j):
        lo, hi = _kv_blocks(i, **mask)
        return b, h // rep, _step_block(lo, hi, j), 0
    return pl.BlockSpec((1, 1, bk, d), index)


def _flash_kernel(q_ref, k_ref, v_ref, *refs, scale: float, softcap: float,
                  band: int, mask: dict, with_lse: bool):
    if with_lse:
        o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        (o_ref, m_ref, l_ref, acc_ref), lse_ref = refs, None
    skv = mask["skv"]
    iq, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_blocks(iq, **mask)
    ik = lo + j

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        # kv tails: k garbage only reaches masked logit columns, but v
        # rides p @ v where the masked p entries are exact zeros — 0·NaN =
        # NaN, so both tails are zeroed before any contraction (no-ops
        # when aligned).
        k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)
        v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)

        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logits *= scale
        if softcap > 0.0:
            logits = softcap * jnp.tanh(logits / softcap)
        if masked:
            logits = jnp.where(_block_mask(iq, ik, **mask), logits, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)                   # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    _visit(body, ik < hi, _block_full(iq, ik, **mask))

    @pl.when(j == band - 1)
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
    nq = pl.cdiv(Sq, bq)
    mask = dict(bq=bq, bk=bk, causal=causal, window=window,
                q_offset=Skv - Sq, sq=Sq, skv=Skv)
    band = _band(nq, functools.partial(_kv_blocks, **mask))

    qt = q.transpose(0, 2, 1, 3)                  # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                  # (B, Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=sc, softcap=softcap,
                               band=band, mask=mask, with_lse=return_lse)
    kvspec = _kv_spec(bk, D, rep, mask)

    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, bq),
                                      lambda b, h, i, j: (b, h, 0, i)))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, band),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            kvspec, kvspec,
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


def _block_probs(q, k, lse, iq, ik, *, scale, softcap, mask, masked):
    """Recompute the (bq, bk) probability block p = exp(t − lse) from the
    stashed logsumexp (a (bq, 1) column), plus the pre-mask softcapped
    logits t (needed for the tanh chain). On a block the mask cuts
    (``masked``), masked entries — including q/k tail lanes of partial
    boundary blocks — are exactly 0 (no NEG_INF arithmetic, so
    fully-masked rows can't poison the accumulators with inf·0). Callers
    must hand in tail-sanitized q/k so t itself stays finite (the softcap
    tanh chain multiplies by (1 − (t/cap)²) AFTER the p zeros are in
    place)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    t = softcap * jnp.tanh(s / softcap) if softcap > 0.0 else s
    p = jnp.exp(t - lse)
    if masked:
        p = jnp.where(_block_mask(iq, ik, **mask), p, 0.0)
    return p, t


def _grad_wrt_logits(p, dp, delta, t, *, softcap):
    """dt = p∘(dp − D); chain through the softcap tanh back to the raw
    (pre-cap, post-scale) logits."""
    dt = p * (dp - delta)
    if softcap > 0.0:
        dt = dt * (1.0 - jnp.square(t / softcap))
    return dt


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                     acc_ref, *, scale: float, softcap: float, band: int,
                     mask: dict):
    sq, skv = mask["sq"], mask["skv"]
    iq, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _kv_blocks(iq, **mask)
    ik = lo + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        # Tail-sanitize every streamed operand (static no-ops when
        # aligned): the masked p/g entries are exact zeros, but g @ k and
        # do @ vᵀ still touch the garbage k/v tail lanes (0·NaN = NaN), and
        # q/do/delta tails keep t and dp finite so the softcap chain can't
        # reintroduce NaNs.
        q = _mask_tail(q_ref[0, 0].astype(jnp.float32), 0, iq, sq)
        k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)
        v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)
        do = _mask_tail(do_ref[0, 0].astype(jnp.float32), 0, iq, sq)
        delta = _mask_tail(_row_to_col(d_ref[0, 0]), 0, iq, sq)
        p, t = _block_probs(q, k, _row_to_col(lse_ref[0, 0]), iq, ik,
                            scale=scale, softcap=softcap, mask=mask,
                            masked=masked)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        g = _grad_wrt_logits(p, dp, delta, t, softcap=softcap)
        acc_ref[...] += jax.lax.dot_general(
            g, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit(body, ik < hi, _block_full(iq, ik, **mask))

    @pl.when(j == band - 1)
    def _done():
        dq_ref[0, 0] = _mask_tail(acc_ref[...] * scale, 0, iq,
                                  sq).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                      softcap: float, band: int, rep: int, mask: dict):
    # Grid dim 3 runs (rep · band) steps head-major: j = r·band + s, where
    # step s of kv block ik's band is q block lo + s. The rep query heads of
    # the GQA group fold into the SAME (bk, D) accumulators, so the kernel
    # writes the group-summed dK/dV tiles directly — never a rep×-sized
    # per-query-head cotangent in HBM.
    sq, skv = mask["sq"], mask["skv"]
    ik, j = pl.program_id(2), pl.program_id(3)
    lo, hi = _q_blocks(ik, **mask)
    iq = lo + jax.lax.rem(j, band)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        # Here BOTH contractions run over the q rows (pᵀ @ do, gᵀ @ q), so
        # the q/do/delta tails must be exact zeros — and the k/v tails
        # likewise, or the masked-p zeros meet garbage through dp
        # (0·NaN = NaN). All static no-ops on aligned grids.
        q = _mask_tail(q_ref[0, 0].astype(jnp.float32), 0, iq, sq)
        k = _mask_tail(k_ref[0, 0].astype(jnp.float32), 0, ik, skv)
        v = _mask_tail(v_ref[0, 0].astype(jnp.float32), 0, ik, skv)
        do = _mask_tail(do_ref[0, 0].astype(jnp.float32), 0, iq, sq)
        delta = _mask_tail(_row_to_col(d_ref[0, 0]), 0, iq, sq)
        p, t = _block_probs(q, k, _row_to_col(lse_ref[0, 0]), iq, ik,
                            scale=scale, softcap=softcap, mask=mask,
                            masked=masked)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        g = _grad_wrt_logits(p, dp, delta, t, softcap=softcap)
        dk_acc[...] += jax.lax.dot_general(
            g, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _visit(body, iq < hi, _block_full(iq, ik, **mask))

    @pl.when(j == band * rep - 1)
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
    two launches: dQ with the band of live kv blocks innermost (one
    (bq, D) f32 accumulator), and dK/dV gridded over KV heads with the
    band of live q blocks of every query head of the GQA group innermost
    (rep · band steps), group-summing in VMEM.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, _ = k.shape
    rep = H // Hkv
    sc = scale if scale is not None else (1.0 / D ** 0.5)
    bq = _clamp_block(bq, Sq)
    bk = _clamp_block(bk, Skv)
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Skv, bk)
    mask = dict(bq=bq, bk=bk, causal=causal, window=window,
                q_offset=Skv - Sq, sq=Sq, skv=Skv)
    kv_band = _band(nq, functools.partial(_kv_blocks, **mask))
    q_band = _band(nk, functools.partial(_q_blocks, **mask))

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
    kvspec = _kv_spec(bk, D, rep, mask)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=sc, softcap=softcap,
                          band=kv_band, mask=mask),
        grid=(B, H, nq, kv_band),
        in_specs=[qspec, kvspec, kvspec, qspec, lspec, lspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt, dot, lse, delta)

    # dK/dV: grid over KV heads and kv blocks; the innermost dim runs
    # (rep · q_band) steps — the band of live q blocks of every query head
    # in the GQA group — folding the group-sum into the kernel's own
    # accumulation, so only the real (B, Hkv, Skv, D) cotangents ever reach
    # HBM. A dead step names the q block the previous step holds.
    def _qh(h, j):
        return h * rep + j // q_band

    def _qb(i, j):
        lo, hi = _q_blocks(i, **mask)
        return _step_block(lo, hi, j % q_band)
    qjspec = pl.BlockSpec((1, 1, bq, D),
                          lambda b, h, i, j: (b, _qh(h, j), _qb(i, j), 0))
    ljspec = pl.BlockSpec((1, 1, 1, bq),
                          lambda b, h, i, j: (b, _qh(h, j), 0, _qb(i, j)))
    kvjspec = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0))
    dkv_out = pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=sc, softcap=softcap,
                          band=q_band, rep=rep, mask=mask),
        grid=(B, Hkv, nk, q_band * rep),
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
