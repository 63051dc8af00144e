"""Tail-masked Pallas grids: prime/odd dims run correct multi-block kernels.

Pallas pads partial boundary blocks with garbage/NaN (interpret mode pads
with NaN; compiled TPU leaves whatever was in VMEM), so before this suite's
machinery existed the wrappers refused non-divisible block boundaries and
fell back to divisor blocks or — for prime-ish dims — one whole-dim block
(a TPU VMEM hazard). Now every gridded kernel masks its own tails, and this
suite pins the contract on the nastiest shapes:

  * prime ⟨M,K,N⟩ / Sq/Skv: forward AND VJP outputs match the XLA
    reference to the existing suite tolerances, zero NaNs anywhere;
  * the chosen block is the requested clamp — min(requested, dim), NEVER
    the whole dim — read off the traced pallas_call block shapes, and the
    grid is the matching multi-block ``pl.cdiv`` (VMEM stays bounded);
  * causal + sliding-window + GQA + softcap compose with the tail mask at
    the boundary blocks (the one shared ``_block_mask``);
  * the old `_fit_block` divisor scan is gone (no O(b) trace-time scan,
    no whole-dim fallback path left to regress into);
  * a prime-seq-len jitted train step still lowers to the Pallas fwd+bwd
    kernels — no silent XLA fallback at awkward dims.
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

KEY = jax.random.PRNGKey(23)

PRIME_MKN = [(127, 509, 257), (257, 127, 509), (131, 131, 131)]
PRIME_SEQ = [(131, 257), (127, 127), (61, 131)]


def _assert_no_nan(x, msg=""):
    assert not np.isnan(np.asarray(x, np.float32)).any(), f"NaN leak: {msg}"


# ---------------------------------------------------------------------------
# The divisor scan is gone: clamp only, O(1), no whole-dim fallback


def test_fit_block_divisor_scan_is_gone():
    """`_fit_block` (the per-dim O(b) pure-Python divisor scan at trace
    time, with its whole-dim VMEM-hazard fallback) must not survive
    anywhere in the kernel modules."""
    assert not hasattr(fm, "_fit_block")
    assert not hasattr(fa, "_fit_block")


def test_clamp_block_is_plain_min():
    # primes that the old scan would have blown up to the whole dim
    for b, d in [(256, 509), (512, 100003), (64, 127), (128, 128), (7, 3)]:
        assert fm._clamp_block(b, d) == min(b, d)


# ---------------------------------------------------------------------------
# Matmul kernels: prime dims, multi-block grids, fwd parity


@pytest.mark.parametrize("m,k,n", PRIME_MKN)
def test_fxp_matmul_prime_dims_multiblock(m, k, n):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, m))
    x = jax.random.normal(k1, (m, k), jnp.float32)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    s = jnp.float32(1 / 64)
    got = fm.fxp_matmul(x, wq, s, bm=64, bn=64, bk=64, interpret=True)
    _assert_no_nan(got, f"fxp_matmul {m}x{k}x{n}")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ref_fxp_matmul(x, wq, s)),
                               rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("m,k,n", PRIME_MKN)
def test_int8_matmul_prime_dims_exact(m, k, n):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, n))
    xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    got = fm.int8_matmul(xq, wq, jnp.float32(0.02), jnp.float32(0.3),
                         bm=64, bn=64, bk=64, interpret=True)
    want = ref.ref_int8_matmul(xq, wq, jnp.float32(0.02), jnp.float32(0.3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_int8_matmul_rejects_mismatched_k():
    """K mismatch must fail AT THE WRAPPER, not deep inside pallas_call."""
    xq = jnp.zeros((16, 32), jnp.int8)
    wq = jnp.zeros((48, 16), jnp.int8)
    with pytest.raises(AssertionError):
        fm.int8_matmul(xq, wq, jnp.float32(1.0), jnp.float32(1.0),
                       interpret=True)


@pytest.mark.parametrize("m,k,n", PRIME_MKN)
def test_matmul_blocks_are_clamp_never_whole_dim(m, k, n):
    """Structure criterion: with the requested blocks smaller than every
    prime dim, the traced pallas_call must carry exactly the requested
    block shape (VMEM bound) and a multi-block cdiv grid — the whole-dim
    escape hatch is gone."""
    bm = bn = bk = 64
    x = jnp.zeros((m, k), jnp.float32)
    wq = jnp.zeros((k, n), jnp.int8)
    jaxpr = jax.make_jaxpr(lambda a, b: fm.fxp_matmul(
        a, b, jnp.float32(1.0), bm=bm, bn=bn, bk=bk,
        interpret=True))(x, wq).jaxpr
    (grid,) = jaxpr_tools.pallas_grids(jaxpr)
    (blocks,) = jaxpr_tools.pallas_block_shapes(jaxpr)
    assert grid == (-(-m // bm), -(-n // bn), -(-k // bk))
    assert all(g > 1 for g in grid), f"single-block grid {grid}"
    assert (bm, bk) in blocks and (bk, bn) in blocks and (bm, bn) in blocks
    for shape in blocks:
        assert m not in shape and k not in shape and n not in shape, \
            f"whole-dim block leaked into {blocks}"


def test_matmul_grad_blocks_are_clamp_never_whole_dim():
    """Same structure criterion for BOTH backward kernels via jax.grad."""
    m, k, n = 127, 509, 257
    bm = bn = bk = 64
    x = jnp.zeros((m, k), jnp.float32)
    wq = jnp.zeros((k, n), jnp.int8)
    jaxpr = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(fm.fxp_matmul_vjp(
        a, wq, jnp.float32(1.0), bm=bm, bn=bn, bk=bk,
        interpret=True))))(x).jaxpr
    names = jaxpr_tools.pallas_kernel_names(jaxpr)
    assert any("_matmul_dx_kernel" in s for s in names)
    assert any("_matmul_dw_kernel" in s for s in names)
    for grid, blocks in zip(jaxpr_tools.pallas_grids(jaxpr),
                            jaxpr_tools.pallas_block_shapes(jaxpr)):
        assert all(g > 1 for g in grid), f"single-block grid {grid}"
        for shape in blocks:
            assert all(s <= 64 for s in shape), \
                f"block exceeded the requested clamp: {blocks}"


# ---------------------------------------------------------------------------
# Matmul VJPs: prime dims grad parity


@pytest.mark.parametrize("m,k,n", PRIME_MKN)
def test_fxp_matmul_grad_parity_prime_dims(m, k, n):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, m * 3 + n), 3)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    s = jnp.float32(1 / 32)
    cot = jax.random.normal(k3, (m, n), jnp.float32)
    gp = jax.grad(lambda x, s: jnp.sum(
        fm.fxp_matmul_vjp(x, wq, s, bm=64, bn=64, bk=64,
                          interpret=True) * cot), (0, 1))(x, s)
    gr = jax.grad(lambda x, s: jnp.sum(
        ref.ref_fxp_matmul(x, wq, s) * cot), (0, 1))(x, s)
    for got, want, name in zip(gp, gr, ("dx", "dscale")):
        _assert_no_nan(got, f"{name} {m}x{k}x{n}")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_int8_matmul_grad_parity_prime_dims():
    m, k, n = 127, 257, 131
    k1, k2, k3 = jax.random.split(KEY, 3)
    xq = jax.random.randint(k1, (m, k), -128, 128, jnp.int8)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    cot = jax.random.normal(k3, (m, n), jnp.float32)
    sx, sw = jnp.float32(0.02), jnp.float32(0.3)
    gp = jax.grad(lambda a, b: jnp.sum(
        fm.int8_matmul_vjp(xq, wq, a, b, bm=64, bn=64, bk=64,
                           interpret=True) * cot), (0, 1))(sx, sw)
    gr = jax.grad(lambda a, b: jnp.sum(
        ref.ref_int8_matmul(xq, wq, a, b) * cot), (0, 1))(sx, sw)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=2e-4, atol=2e-4, err_msg="dsx")
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=2e-4, atol=2e-4, err_msg="dsw")


# ---------------------------------------------------------------------------
# Flash attention: prime Sq/Skv under causal + window + GQA + softcap


ATTN_TAIL_CASES = [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=37),
    dict(causal=True, window=50, softcap=15.0),
    dict(causal=True, window=7),          # inside one block
    dict(causal=True, window=90, softcap=15.0),
]


@pytest.mark.parametrize("kw", ATTN_TAIL_CASES,
                         ids=[str(c) for c in ATTN_TAIL_CASES])
@pytest.mark.parametrize("sq,skv", PRIME_SEQ)
def test_attention_prime_dims_fwd_parity(sq, skv, kw):
    """Prime Sq/Skv with 32-blocks: every grid has tail blocks in BOTH
    sequence dims; causal/window/GQA/softcap compose with the tail mask."""
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, sq * skv), 3)
    q = jax.random.normal(k1, (2, sq, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (2, skv, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (2, skv, 2, 32), jnp.float32)
    got = ops.attention(q, k, v, use_pallas=True, bq=32, bk=32, **kw)
    _assert_no_nan(got, f"attention fwd {sq}/{skv} {kw}")
    want = ref.ref_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kw", ATTN_TAIL_CASES,
                         ids=[str(c) for c in ATTN_TAIL_CASES])
@pytest.mark.parametrize("sq,skv", PRIME_SEQ)
def test_attention_prime_dims_grad_parity(sq, skv, kw):
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, sq + skv), 4)
    q = jax.random.normal(k1, (1, sq, 4, 32), jnp.float32)
    k = jax.random.normal(k2, (1, skv, 2, 32), jnp.float32)
    v = jax.random.normal(k3, (1, skv, 2, 32), jnp.float32)
    cot = jax.random.normal(k4, q.shape, jnp.float32)
    gp = jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=32, bk=32, **kw) * cot),
        (0, 1, 2))(q, k, v)
    gr = ref.ref_attention_grads(q, k, v, cot, **kw)
    for got, want, name in zip(gp, gr, "qkv"):
        _assert_no_nan(got, f"d{name} {sq}/{skv} {kw}")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} {sq}/{skv} {kw}")


def test_attention_prime_dims_dead_rows():
    """Sq > Skv (both prime) under causal end-alignment: the dead-row
    convention (exact-0 rows, lse = NEG_INF) must survive tail masking."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    sq, skv = 131, 61
    q = jax.random.normal(k1, (1, sq, 2, 16), jnp.float32)
    k = jax.random.normal(k2, (1, skv, 2, 16), jnp.float32)
    v = jax.random.normal(k3, (1, skv, 2, 16), jnp.float32)
    out = ops.attention(q, k, v, use_pallas=True, bq=32, bk=32)
    _assert_no_nan(out, "dead-row fwd")
    np.testing.assert_array_equal(np.asarray(out[:, :sq - skv]), 0.0)


def test_attention_blocks_are_clamp_never_whole_dim():
    """Block/grid structure for all three attention kernels at prime
    Sq/Skv: q/k blocks equal the requested 32-clamp, grids stay
    multi-block in both sequence dims."""
    sq, skv = 131, 257
    q = jnp.zeros((1, sq, 4, 32), jnp.float32)
    k = jnp.zeros((1, skv, 2, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        ops.attention(q, k, v, use_pallas=True, bq=32, bk=32)),
        (0, 1, 2)))(q, k, k).jaxpr
    names = jaxpr_tools.pallas_kernel_names(jaxpr)
    assert {"_flash_kernel", "_flash_dq_kernel",
            "_flash_dkv_kernel"} <= {n for n in names}
    for name, grid in zip(names, jaxpr_tools.pallas_grids(jaxpr)):
        nq, nk = -(-sq // 32), -(-skv // 32)
        # _flash_dkv folds the GQA group into its innermost dim: rep·nq
        assert nk in grid and (nq in grid or 2 * nq in grid), (name, grid)
        assert sq not in grid and skv not in grid, \
            f"{name}: whole-dim block leaked, grid={grid}"
    for name, blocks in zip(names, jaxpr_tools.pallas_block_shapes(jaxpr)):
        for shape in blocks:
            assert sq not in shape and skv not in shape, \
                f"{name}: whole-dim block {shape}"


# ---------------------------------------------------------------------------
# ops-level default blocks on prime dims (the controller's entry points)


def test_ops_fxp_matmul_prime_dims_default_blocks():
    """The op-level wrapper (the shape rule's blocks) on prime dims: blocks
    are the rule's, clamped to the dim — multi-block where the dim exceeds
    the rule's cap, exact parity either way."""
    k1, k2 = jax.random.split(KEY)
    m, k, n = 509, 1031, 127        # K exceeds the rule's contracted cap
    x = jax.random.normal(k1, (m, k), jnp.float32)
    wq = jax.random.randint(k2, (k, n), -128, 128, jnp.int8)
    s = jnp.float32(1 / 64)
    got = ops.fxp_matmul(x, wq, s, use_pallas=True)
    _assert_no_nan(got, "ops.fxp_matmul prime")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ref_fxp_matmul(x, wq, s)),
                               rtol=1e-5, atol=5e-2)
    jaxpr = jax.make_jaxpr(lambda a: ops.fxp_matmul(
        a, wq, s, use_pallas=True))(x).jaxpr
    (grid,) = jaxpr_tools.pallas_grids(jaxpr)
    b = fm._dense_blocks("fwd", m, k, n, x.dtype, wq.dtype, x.dtype)
    assert grid == (-(-m // b["M"]), -(-n // b["N"]), -(-k // b["K"]))
    assert grid[2] > 1, f"single-block contraction {grid}"


# ---------------------------------------------------------------------------
# CI acceptance: a prime-seq-len jitted train step still lowers to Pallas


def test_prime_seq_train_step_keeps_pallas_kernels():
    """No silent XLA fallback at awkward dims: with quant.use_pallas=True
    and a PRIME seq_len, the jitted differentiated train step still
    contains the flash forward AND both backward kernels."""
    cfg = load_config("tiny")
    cfg = dataclasses.replace(
        cfg,
        quant=dataclasses.replace(cfg.quant, use_pallas=True,
                                  stochastic_rounding=False),
        train=dataclasses.replace(cfg.train, seq_len=61, adapt_interval=1000,
                                  log_every=1))
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, batch).jaxpr
    for kern in ("_flash_kernel", "_flash_dq_kernel", "_flash_dkv_kernel"):
        assert jaxpr_tools.count_pallas_calls(jaxpr, kern) == 1, kern


def test_prime_seq_train_step_runs_nan_free():
    """One real optimizer step at prime seq_len: finite loss and grads."""
    cfg = load_config("tiny")
    cfg = dataclasses.replace(
        cfg,
        quant=dataclasses.replace(cfg.quant, use_pallas=True,
                                  stochastic_rounding=False),
        train=dataclasses.replace(cfg.train, seq_len=61, adapt_interval=1000,
                                  log_every=1))
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    state, metrics = step(state, train_loop.make_batch(cfg, 0))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
