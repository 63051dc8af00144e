"""The dense kernels' block rule (``fxp_matmul._dense_blocks``).

Every dense entry point that is given no block takes the rule's, which is a
function of the kernel kind, (M, K, N) and the operand dtypes only. At every
dense shape of the three benchmark cells (SmolLM-360M, the Granite-8B cut and
the Mellum2 cut, M = 16,384 rows), at one 2,048-token sequence (M = 2,048,
two row blocks) and at serving-decode rows (M = 1, 4), the rule's blocks:

  * are the whole dim or a multiple of 128 (the (8, 128) tiling, and the 32
    sublanes of an int8 operand);
  * fit the stated VMEM budget by the kernel's own reckoning;
  * clear v5e's bf16 ridge (240 FLOP/byte per grid step) wherever M ≥ 1024;
  * keep every decode row in one block (bm == M).

Then parity at the rule's blocks, in interpret mode, for bf16 activations:
the forward against ``ref.ref_fxp_matmul``, and the dense layer's dx and dw
against the plain XLA dense path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jaxpr_tools
from repro.kernels import fxp_matmul as fm
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(14)
BF16_RIDGE = 240            # 197 TFLOP/s ÷ 819 GB/s, v5e

# (K, N) of every dense product: q/o, k/v, MLP in, MLP out, head.
SMOLLM_KN = [(960, 960), (960, 320), (960, 2560), (2560, 960), (960, 49152)]
GRANITE_KN = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096),
              (4096, 12288)]
# Mellum2's dense products: q, k/v, o, head (its FFN runs on the experts).
MELLUM_KN = [(2304, 4096), (2304, 512), (4096, 2304), (2304, 12288)]
ROWS = [16384, 2048, 1, 4]

BF, I8, F32 = jnp.bfloat16, jnp.int8, jnp.float32
# kind → (A dtype, B dtype, out dtype) as the model's training step and
# serving feed them.
KINDS = {
    "fwd": (BF, I8, BF),
    "dx": (BF, I8, BF),
    "dw": (BF, BF, F32),
    "int8": (I8, I8, F32),
}


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("kn", SMOLLM_KN + GRANITE_KN + MELLUM_KN)
def test_rule_blocks(kind, m, kn):
    K, N = kn
    a, b, out = KINDS[kind]
    dims = dict(M=m, K=K, N=N)
    blocks = fm._dense_blocks(kind, m, K, N, a, b, out)
    assert set(blocks) == {"M", "K", "N"}
    for d, blk in blocks.items():
        assert 0 < blk <= dims[d], (d, blk, dims)
        assert blk == dims[d] or blk % 128 == 0, (d, blk, dims)
    p, q, r = (blocks[d] for d in fm._DENSE_KINDS[kind])
    sizes = [jnp.dtype(t).itemsize for t in (a, b, out)]
    assert fm._dense_vmem(kind, p, q, r, *sizes) <= fm._VMEM_BUDGET
    if m >= 1024:
        intensity = 2 * p * q / (p * sizes[0] + q * sizes[1])
        assert intensity >= BF16_RIDGE, (kind, blocks, intensity)
    if m <= 4:
        assert blocks["M"] == m


def test_rule_depends_on_dtypes():
    """At SmolLM's k/v width (N = 320, one whole block) int8 words clear
    the ridge at 1024 rows; f32 words need twice as many rows per block to
    amortize their re-reads."""
    words = fm._dense_blocks("fwd", 16384, 960, 320, BF, I8, BF)
    wide = fm._dense_blocks("fwd", 16384, 960, 320, BF, F32, BF)
    assert (words["M"], wide["M"]) == (1024, 2048)


def test_requested_blocks_override_rule():
    """Explicit blocks win over the rule, each clamped to its dim."""
    x = jnp.zeros((300, 700), F32)
    wq = jnp.zeros((700, 500), I8)
    jaxpr = jax.make_jaxpr(lambda a: fm.fxp_matmul(
        a, wq, jnp.float32(1.0), bm=64, bn=1024, bk=128,
        interpret=True))(x).jaxpr
    (grid,) = jaxpr_tools.pallas_grids(jaxpr)
    assert grid == (-(-300 // 64), 1, -(-700 // 128))


# (M, K, N): whole-dim K and N at SmolLM's k/v widths; a prime K that the
# rule splits and an N split with a tail; decode rows with a contracted tail.
PARITY_MKN = [(1024, 960, 320), (300, 1031, 2560), (4, 1031, 384)]


def _operands(m, k, n, seed):
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    x = jax.random.normal(k1, (m, k), F32).astype(BF)
    wq = jax.random.randint(k2, (k, n), -128, 128, I8)
    cot = jax.random.normal(k3, (m, n), F32).astype(BF)
    return x, wq, cot


@pytest.mark.parametrize("m,k,n", PARITY_MKN)
def test_rule_blocks_fwd_parity_bf16(m, k, n):
    x, wq, _ = _operands(m, k, n, m + k)
    s = jnp.float32(1 / 64)
    got = fm.fxp_matmul(x, wq, s, interpret=True)
    assert got.dtype == BF
    want = ref.ref_fxp_matmul(x, wq, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("m,k,n", PARITY_MKN)
def test_rule_blocks_dense_grad_parity_bf16(m, k, n):
    """The model's dense layer at the rule's blocks: dx (bf16) and dw (f32
    receiver) against the XLA dequant-then-dot path, same words."""
    x, wq, cot = _operands(m, k, n, m + n)
    sc = jnp.float32(1 / 32)
    wref = jnp.zeros((k, n), F32)

    def loss(use_pallas):
        return lambda x, r: jnp.sum(ops.fxp_dense(
            x, wq, sc, r, use_pallas=use_pallas).astype(F32)
            * cot.astype(F32))

    gx, gr = jax.grad(loss(True), (0, 1))(x, wref)
    wx, wr = jax.grad(loss(False), (0, 1))(x, wref)
    assert gx.dtype == BF and gr.dtype == F32
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(wx, np.float32),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr),
                               rtol=1e-4, atol=1e-3)
