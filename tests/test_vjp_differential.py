"""Gradient-parity differential harness: jax.grad through the Pallas
custom-VJP forward kernels (fxp_matmul / int8_matmul / flash_attention) vs
XLA autodiff of the pure-jnp oracles in ``kernels/ref.py``.

Style of tests/test_quantize_differential.py: parametrized sweeps with
per-dtype pinned tolerances. Coverage: the WL/FL grid of int8 word
ranges × power-of-two scales, odd / non-tile-aligned M/K/N (single-block
clamping) AND multi-block grids (small explicit block sizes, exercising
the K/M/N accumulation loops), bf16 and f32 outputs, batched and
unbatched attention with GQA / sliding-window / softcap / non-square
Sq≠Skv, composition of both ops under jax.vjp with non-trivial
cotangents, the logsumexp residual stash, and the no-silent-fallback
jaxpr structure (forward AND backward kernel calls present when
use_pallas=True, none when False). A final section pins the end-to-end
train step: loss/grad-norm trajectories with use_pallas=True vs False
agree within tolerance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jaxpr_tools
from repro.config import load_config
from repro.kernels import flash_attention as fa
from repro.kernels import fxp_matmul as fm
from repro.kernels import ops, ref
from repro.train import train_loop

KEY = jax.random.PRNGKey(11)

# dtype-pinned tolerances for grad comparisons (f32 accumulation on both
# sides; differences are reduction-order only — bf16 pays its 8-bit
# mantissa on the cast of the cotangent itself)
TOL = {
    jnp.dtype(jnp.float32): dict(rtol=2e-4, atol=2e-4),
    jnp.dtype(jnp.bfloat16): dict(rtol=3e-2, atol=3e-2),
}


def _close(got, want, dtype=jnp.float32, msg=""):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[jnp.dtype(dtype)], err_msg=msg)


def _words(key, shape, wl):
    """int8 fixed-point words on the ⟨WL,·⟩ grid: wl ≤ 8 by storage."""
    lim = 2 ** (wl - 1)
    return jax.random.randint(key, shape, -lim, lim, jnp.int8)


# ---------------------------------------------------------------------------
# fxp_matmul: dx and dscale across the WL/FL grid, odd dims, dtypes


@pytest.mark.parametrize("wl,fl", [(2, 0), (4, 2), (5, 3), (8, 4), (8, 7),
                                   (8, -2)])
@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 53, 29), (100, 70, 50),
                                   (127, 257, 131)])
def test_fxp_matmul_grad_parity(m, k, n, wl, fl):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, wl * 31 + fl), 3)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    wq = _words(k2, (k, n), wl)
    s = jnp.ldexp(jnp.float32(1.0), -fl)
    cot = jax.random.normal(k3, (m, n), jnp.float32)

    gx_p, gs_p = jax.grad(
        lambda x, s: jnp.sum(ops.fxp_matmul(x, wq, s, use_pallas=True) * cot),
        (0, 1))(x, s)
    gx_r, gs_r = jax.grad(
        lambda x, s: jnp.sum(ref.ref_fxp_matmul(x, wq, s) * cot),
        (0, 1))(x, s)
    _close(gx_p, gx_r, msg=f"dx wl={wl} fl={fl}")
    _close(gs_p, gs_r, msg=f"dscale wl={wl} fl={fl}")
    # the closed-form oracle agrees too
    dx_o, ds_o = ref.ref_fxp_matmul_grads(x, wq, s, cot)
    _close(gx_p, dx_o)
    _close(gs_p, ds_o)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fxp_matmul_grad_dtype(dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (24, 48), jnp.float32).astype(dtype)
    wq = _words(k2, (48, 40), 8)
    s = jnp.float32(1 / 16)
    cot = jax.random.normal(k3, (24, 40), jnp.float32).astype(dtype)
    gp = jax.grad(lambda x: jnp.sum(
        (ops.fxp_matmul(x, wq, s, use_pallas=True) * cot)
        .astype(jnp.float32)))(x)
    gr = jax.grad(lambda x: jnp.sum(
        (ref.ref_fxp_matmul(x, wq, s) * cot).astype(jnp.float32)))(x)
    assert gp.dtype == dtype
    _close(gp, gr, dtype=dtype)


def test_fxp_matmul_grad_multiblock():
    """Small explicit blocks on aligned dims: the full 3-D grid with the
    contraction loop innermost runs in BOTH backward kernels."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (128, 96), jnp.float32)
    wq = _words(k2, (96, 64), 8)
    s = jnp.float32(1 / 32)
    cot = jax.random.normal(k3, (128, 64), jnp.float32)
    gp = jax.grad(lambda x, s: jnp.sum(
        fm.fxp_matmul_vjp(x, wq, s, bm=32, bn=32, bk=32,
                          interpret=True) * cot), (0, 1))(x, s)
    gr = jax.grad(lambda x, s: jnp.sum(
        ref.ref_fxp_matmul(x, wq, s) * cot), (0, 1))(x, s)
    _close(gp[0], gr[0])
    _close(gp[1], gr[1])


def test_matmul_dw_kernel_matches_oracle():
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (64, 96), jnp.float32)
    dy = jax.random.normal(k2, (64, 48), jnp.float32)
    got = fm.matmul_dw(x, dy, bm=32, bn=16, bk=32, interpret=True)
    _close(got, ref.ref_matmul_dw(x, dy))


def test_matmul_dx_streams_int8_tiles():
    """The dx kernel reads the SAME int8 (K,N) weight array the forward
    does — no transposed/dequantized HBM copy appears in its jaxpr."""
    dy = jnp.ones((32, 64), jnp.float32)
    wq = jnp.ones((48, 64), jnp.int8)
    jaxpr = jax.make_jaxpr(lambda d, w: fm.matmul_dx(
        d, w, jnp.float32(0.5), interpret=True))(dy, wq).jaxpr
    (eqn,) = jaxpr_tools.pallas_eqns(jaxpr)
    in_dtypes = [v.aval.dtype for v in eqn.invars if v.aval.size >= wq.size]
    assert jnp.int8 in in_dtypes, "weights entered the dx kernel dequantized"


# ---------------------------------------------------------------------------
# int8_matmul: scale cotangents


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (48, 72, 36)])
def test_int8_matmul_scale_grad_parity(m, k, n):
    k1, k2, k3 = jax.random.split(KEY, 3)
    xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    cot = jax.random.normal(k3, (m, n), jnp.float32)
    sx, sw = jnp.float32(0.02), jnp.float32(0.3)
    gp = jax.grad(lambda a, b: jnp.sum(
        ops.int8_matmul(xq, wq, a, b, use_pallas=True) * cot), (0, 1))(sx, sw)
    gr = jax.grad(lambda a, b: jnp.sum(
        ref.ref_int8_matmul(xq, wq, a, b) * cot), (0, 1))(sx, sw)
    _close(gp[0], gr[0], msg="dsx")
    _close(gp[1], gr[1], msg="dsw")
    do = ref.ref_int8_matmul_grads(xq, wq, sx, sw, cot)
    _close(gp[0], do[0])
    _close(gp[1], do[1])


# ---------------------------------------------------------------------------
# flash attention: dq/dk/dv across masks, GQA, dtypes, batching


ATTN_CASES = [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=16),
    dict(causal=True, softcap=20.0),
    dict(causal=True, window=32, softcap=10.0),
    dict(causal=True, window=5),          # inside one block
    dict(causal=True, window=70),         # across three blocks
    dict(causal=False, window=40),
]


@pytest.mark.parametrize("kw", ATTN_CASES,
                         ids=[str(c) for c in ATTN_CASES])
@pytest.mark.parametrize("b,h,hkv", [(1, 4, 4), (2, 8, 2), (1, 6, 2),
                                     (1, 8, 1)])
def test_attention_grad_parity(b, h, hkv, kw):
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, b * h), 4)
    q = jax.random.normal(k1, (b, 96, h, 32), jnp.float32)
    k = jax.random.normal(k2, (b, 96, hkv, 32), jnp.float32)
    v = jax.random.normal(k3, (b, 96, hkv, 32), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=32, bk=32, **kw) * cot),
        (0, 1, 2))(q, k, v)
    gr = ref.ref_attention_grads(q, k, v, cot, **kw)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name} {kw}")


@pytest.mark.parametrize("sq,skv", [(64, 128), (32, 96), (96, 96),
                                    (61, 131), (131, 257), (32, 160)])
def test_attention_grad_parity_prefill_offset(sq, skv):
    """Sq ≠ Skv: query positions end-aligned to the key space. The prime
    rows run partial tail-masked boundary blocks in both grid dims."""
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, sq + skv), 4)
    q = jax.random.normal(k1, (2, sq, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (2, skv, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (2, skv, 2, 32), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=32, bk=32) * cot),
        (0, 1, 2))(q, k, v)
    gr = ref.ref_attention_grads(q, k, v, cot)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name} sq={sq} skv={skv}")


def test_attention_grad_parity_dead_rows():
    """Sq > Skv under causal end-alignment: rows with NO surviving key.
    The kernel emits exactly-0 rows (flash convention; ref_attention's
    uniform softmax over an all-masked row is meaningless) and the VJP
    must agree that those rows are constant — dv once silently dropped
    their uniform-row contribution instead."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (1, 64, 4, 16), jnp.float32)
    k = jax.random.normal(k2, (1, 32, 2, 16), jnp.float32)
    v = jax.random.normal(k3, (1, 32, 2, 16), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    dead = 64 - 32                                 # q_offset = -32

    out = ops.attention(q, k, v, use_pallas=True, bq=16, bk=16)
    np.testing.assert_array_equal(np.asarray(out[:, :dead]), 0.0)

    def oracle(q, k, v):
        o = ref.ref_attention(q, k, v)
        rows = (jnp.arange(q.shape[1]) >= dead)[None, :, None, None]
        return jnp.where(rows, o, 0.0)            # ref with dead rows zeroed

    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=16, bk=16) * cot),
        (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(oracle(q, k, v) * cot),
                  (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name} with dead query rows")


@pytest.mark.parametrize("sq,skv,window", [(32, 160, 40), (61, 131, 20),
                                           (32, 160, 8)])
def test_attention_grad_parity_prefill_offset_window(sq, skv, window):
    """A causal window against a longer key space: the first kv blocks
    hold no key any query reaches, so the dK/dV launch visits no q block
    there and must still write exact zeros."""
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, skv), 4)
    q = jax.random.normal(k1, (1, sq, 8, 32), jnp.float32)
    k = jax.random.normal(k2, (1, skv, 1, 32), jnp.float32)
    v = jax.random.normal(k3, (1, skv, 1, 32), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    f = lambda q, k, v: jnp.sum(ops.attention(
        q, k, v, window=window, use_pallas=True, bq=32, bk=32) * cot)
    gp = jax.grad(f, (0, 1, 2))(q, k, v)
    gr = ref.ref_attention_grads(q, k, v, cot, window=window)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name} sq={sq} skv={skv} window={window}")
    reached = skv - sq - window + 1               # first key any query sees
    np.testing.assert_array_equal(np.asarray(gp[1][:, :reached]), 0.0)
    np.testing.assert_array_equal(np.asarray(gp[2][:, :reached]), 0.0)


@pytest.mark.parametrize("window", [10, 24])
def test_attention_grad_parity_dead_rows_window(window):
    """Dead rows (Sq > Skv) under a causal window with GQA rep 8: q blocks
    with no live kv block still write o = 0 and dq = 0."""
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, window), 4)
    q = jax.random.normal(k1, (1, 64, 8, 16), jnp.float32)
    k = jax.random.normal(k2, (1, 32, 1, 16), jnp.float32)
    v = jax.random.normal(k3, (1, 32, 1, 16), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    dead = 64 - 32

    def oracle(q, k, v):
        o = ref.ref_attention(q, k, v, window=window)
        rows = (jnp.arange(q.shape[1]) >= dead)[None, :, None, None]
        return jnp.where(rows, o, 0.0)

    f = lambda q, k, v: ops.attention(q, k, v, window=window,
                                      use_pallas=True, bq=16, bk=16)
    out = f(q, k, v)
    np.testing.assert_array_equal(np.asarray(out[:, :dead]), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    gp = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * cot),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(oracle(q, k, v) * cot),
                  (0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(gp[0][:, :dead]), 0.0)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name} dead rows, window {window}")


def test_attention_grad_parity_odd_dims():
    """Odd / non-tile-aligned Sq, Skv and head dim (45 % 32 ≠ 0: the
    boundary blocks are partial and tail-masked)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (1, 45, 3, 24), jnp.float32)
    k = jax.random.normal(k2, (1, 45, 3, 24), jnp.float32)
    v = jax.random.normal(k3, (1, 45, 3, 24), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=32, bk=32) * cot),
        (0, 1, 2))(q, k, v)
    gr = ref.ref_attention_grads(q, k, v, cot)
    for a, b_, name in zip(gp, gr, "qkv"):
        _close(a, b_, msg=f"d{name}")


def test_attention_grad_parity_bf16():
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (1, 64, 2, 64), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 64, 2, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 64, 2, 64), jnp.bfloat16)
    cot = jax.random.normal(k4, q.shape, jnp.bfloat16)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        (ops.attention(q, k, v, use_pallas=True, bq=32, bk=32) * cot)
        .astype(jnp.float32)), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        (ref.ref_attention(q, k, v) * cot).astype(jnp.float32)),
        (0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gr, "qkv"):
        assert a.dtype == jnp.bfloat16
        _close(a, b_, dtype=jnp.bfloat16, msg=f"d{name}")


def test_flash_lse_residual_matches_oracle():
    """The stash the backward reuses: per-row logsumexp, f32."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 64, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (2, 64, 2, 32), jnp.float32)
    o, lse = fa.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                                interpret=True, return_lse=True)
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(fa.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                                      interpret=True)),
        rtol=1e-6, atol=1e-6, err_msg="lse output changed o")
    _close(lse, ref.ref_attention_lse(q, k, v, causal=True))


# ---------------------------------------------------------------------------
# Composition under jax.vjp with non-trivial cotangents


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_composed_pipeline_vjp(dtype):
    """fxp_matmul feeding flash attention, differentiated as one pipeline
    via jax.vjp with a random (non-ones) cotangent."""
    B, S, H, D = 2, 32, 4, 16
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = jax.random.normal(k1, (B * S, 48), jnp.float32).astype(dtype)
    wq = _words(k2, (48, 3 * H * D), 8)
    s = jnp.float32(1 / 64)
    cot = jax.random.normal(k4, (B, S, H, D), jnp.float32).astype(dtype)

    def net(x, use_pallas):
        qkv = ops.fxp_matmul(x, wq, s, use_pallas=use_pallas)
        q, k, v = jnp.split(qkv.reshape(B, S, 3 * H, D), 3, axis=2)
        return ops.attention(q, k, v, causal=True, softcap=15.0,
                             use_pallas=use_pallas, bq=16, bk=16)

    out_p, vjp_p = jax.vjp(lambda x: net(x, True), x)
    out_r, vjp_r = jax.vjp(lambda x: net(x, False), x)
    _close(out_p, out_r, dtype=dtype, msg="forward")
    (gx_p,), (gx_r,) = vjp_p(cot), vjp_r(cot)
    assert gx_p.dtype == dtype
    if dtype == jnp.bfloat16:
        # two chained bf16 roundings: small-magnitude elements can sit a
        # few ulps-of-the-tensor-scale apart — compare scale-normalized
        gp, gr = np.asarray(gx_p, np.float32), np.asarray(gx_r, np.float32)
        assert np.abs(gp - gr).max() <= 3e-2 * np.abs(gr).max()
    else:
        _close(gx_p, gx_r, dtype=dtype, msg="dx through the pipeline")


# ---------------------------------------------------------------------------
# No-silent-fallback: the differentiated jaxpr contains fwd AND bwd kernels


def test_attention_grad_jaxpr_has_fwd_and_bwd_kernels():
    q = jnp.zeros((1, 32, 2, 16), jnp.float32)

    def loss(q, use_pallas):
        return jnp.sum(ops.attention(q, q, q, use_pallas=use_pallas))

    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q: loss(q, True)))(q).jaxpr
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_flash_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_flash_dq_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_flash_dkv_kernel") == 1
    off = jax.make_jaxpr(jax.grad(lambda q: loss(q, False)))(q).jaxpr
    assert jaxpr_tools.count_pallas_calls(off) == 0


def test_fxp_matmul_grad_jaxpr_has_fwd_and_bwd_kernels():
    x = jnp.zeros((32, 64), jnp.float32)
    wq = jnp.zeros((64, 32), jnp.int8)

    def loss(x, use_pallas):
        return jnp.sum(ops.fxp_matmul(x, wq, jnp.float32(0.5),
                                      use_pallas=use_pallas))

    jaxpr = jax.make_jaxpr(jax.grad(lambda x: loss(x, True)))(x).jaxpr
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_fxp_matmul_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_matmul_dx_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_matmul_dw_kernel") == 1
    off = jax.make_jaxpr(jax.grad(lambda x: loss(x, False)))(x).jaxpr
    assert jaxpr_tools.count_pallas_calls(off) == 0


def _tiny_pallas_cfg(**quant_kw):
    cfg = load_config("tiny")
    quant_kw.setdefault("stochastic_rounding", False)  # same RTN quantize
    return dataclasses.replace(                        # on both dispatches
        cfg,
        quant=dataclasses.replace(cfg.quant, **quant_kw),
        train=dataclasses.replace(cfg.train, adapt_interval=1000,
                                  log_every=1))


def test_train_step_jaxpr_has_fwd_and_bwd_kernels():
    """The acceptance criterion: with quant.use_pallas=True the jitted,
    differentiated train_step contains the flash forward AND backward
    kernels (train_loop._task_loss no longer hard-codes use_pallas=False);
    with False, no pallas_call at all."""
    for on, expect in [(True, 1), (False, 0)]:
        cfg = _tiny_pallas_cfg(use_pallas=on)
        state = train_loop.init_state(cfg)
        batch = train_loop.make_batch(cfg, 0)
        jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
            state, batch).jaxpr
        for kern in ("_flash_kernel", "_flash_dq_kernel",
                     "_flash_dkv_kernel"):
            n = jaxpr_tools.count_pallas_calls(jaxpr, kern)
            assert n == expect, (on, kern, n)
        if not on:
            assert jaxpr_tools.count_pallas_calls(jaxpr) == 0


# ---------------------------------------------------------------------------
# End-to-end train-step parity: the dispatch flip must not change numerics


def test_train_trajectory_parity_pallas_vs_xla():
    """A few real optimizer steps on the tiny transformer: loss and
    grad-norm trajectories with use_pallas=True (interpret kernels, custom
    VJPs) vs False (pure XLA) agree within float tolerance. SR is disabled
    so both dispatches quantize identically (the noise streams differ by
    design); what's under test is the differentiated forward."""
    hist = {}
    for on in (False, True):
        cfg = _tiny_pallas_cfg(use_pallas=on)
        state = train_loop.init_state(cfg)
        step = jax.jit(train_loop.make_train_step(cfg))
        rows = []
        for i in range(4):
            state, metrics = step(state, train_loop.make_batch(cfg, i))
            rows.append((float(metrics["loss"]),
                         float(metrics["grad_norm"])))
        hist[on] = rows
    for (l_x, g_x), (l_p, g_p) in zip(hist[False], hist[True]):
        np.testing.assert_allclose(l_p, l_x, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(g_p, g_x, rtol=2e-2, atol=2e-2)
