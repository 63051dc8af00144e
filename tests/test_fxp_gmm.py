"""The grouped fixed-point kernels (``kernels/fxp_gmm.py``) in interpret
mode against plain ``jnp``: forward, dx and dw over rows sorted by expert,
on layouts with balanced groups, an empty group, a single-row group, every
row in one group, and row counts off the tile."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jaxpr_tools
from repro.kernels import fxp_gmm as fg
from repro.kernels import ops

G, K, N, TILE = 4, 48, 40, 8

# rows per group; the last entry counts rows that go to no group here
CASES = {
    "balanced": [8, 8, 8, 8, 5],
    "empty_group": [9, 0, 13, 4, 3],
    "single_row": [1, 7, 1, 16, 2],
    "one_group": [0, 0, 27, 0, 4],
    "off_tile": [3, 11, 5, 19, 0],
}


def _groups(counts, seed):
    ids = np.concatenate([np.full(c, g, np.int32)
                          for g, c in enumerate(counts)])
    return jnp.asarray(np.random.default_rng(seed).permutation(ids))


def _setup(counts, seed=0):
    group = _groups(counts, seed)
    lay, M = fg.row_layout(group, G, TILE)
    A = group.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    src = jax.random.normal(ks[0], (A, K), jnp.float32).astype(jnp.bfloat16)
    x = jnp.zeros((M + 1, K), jnp.bfloat16).at[lay["dest"]].set(src)[:M]
    wq = jax.random.randint(ks[1], (G, K, N), -128, 128).astype(jnp.int8)
    fl = jnp.asarray([4, 5, 6, 3], jnp.int32)
    dy = jax.random.normal(ks[2], (M, N), jnp.float32).astype(jnp.bfloat16)
    # each buffer row's group, -1 for padding and dead rows
    row_group = jnp.full((M + 1,), -1, jnp.int32).at[lay["dest"]].set(
        jnp.where(group < G, group, -1))[:M]
    return group, lay, M, x, wq, fl, dy, row_group


def _words(wq, fl):
    return wq.astype(jnp.float32) * jnp.ldexp(1.0, -fl)[:, None, None]


def _meta(lay):
    return tuple(lay[k] for k in fg.LAYOUT_KEYS)


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_places_every_held_row_once(case):
    counts = CASES[case]
    group, lay, M, *_ = _setup(counts)
    dest = np.asarray(lay["dest"])
    held = np.asarray(group) < G
    assert len(set(dest[held])) == held.sum()          # one row each
    assert np.all(dest[~held] == M)                     # the rest dropped
    start, sizes = np.asarray(lay["start"]), np.asarray(lay["sizes"])
    assert np.all(start % TILE == 0) and np.all(sizes >= TILE)
    for g in range(G):
        rows = dest[np.asarray(group) == g]
        assert np.all((rows >= start[g]) & (rows < start[g] + counts[g]))
    assert int(lay["live"][0]) * TILE == sizes.sum() <= M
    assert list(np.asarray(lay["rows"])) == counts[:G]


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_dx_match_jnp(case):
    _, lay, M, x, wq, fl, dy, rg = _setup(CASES[case])
    w = _words(wq, fl)
    valid = (rg >= 0)[:, None]
    wr = w[jnp.maximum(rg, 0)]                          # (M, K, N)
    want = jnp.where(valid, jnp.einsum("mk,mkn->mn",
                                       x.astype(jnp.float32), wr), 0.0)
    got = fg.fxp_gmm(x, wq, fl, *_meta(lay), tile=TILE, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=1e-2, atol=0.5)
    assert np.all(np.asarray(got)[~np.asarray(valid[:, 0])] == 0)
    want_dx = jnp.where(valid, jnp.einsum("mn,mkn->mk",
                                          dy.astype(jnp.float32), wr), 0.0)
    got_dx = fg.gmm_dx(dy, wq, fl, *_meta(lay), tile=TILE, interpret=True)
    np.testing.assert_allclose(np.asarray(got_dx, np.float32), want_dx,
                               rtol=1e-2, atol=0.5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_dw_matches_jnp_and_empty_groups_read_zero(case):
    counts = CASES[case]
    _, lay, M, x, wq, fl, dy, rg = _setup(counts)
    # garbage in the padding rows must not reach dw
    pad = (rg < 0)[:, None]
    x = jnp.where(pad, jnp.bfloat16(jnp.nan), x)
    dy = jnp.where(pad, jnp.bfloat16(7.0), dy)
    got = fg.gmm_dw(x, dy, *_meta(lay), groups=G, tile=TILE, interpret=True)
    xf = jnp.where(pad, 0.0, x.astype(jnp.float32))
    dyf = jnp.where(pad, 0.0, dy.astype(jnp.float32))
    want = jnp.stack([jnp.einsum("mk,mn->kn",
                                 jnp.where((rg == g)[:, None], xf, 0.0), dyf)
                      for g in range(G)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)
    for g in range(G):
        if counts[g] == 0:
            assert np.all(np.asarray(got[g]) == 0.0)


def test_vjp_matches_the_xla_dispatch():
    """Through ``ops.fxp_gmm``: the kernels' forward, dx and the
    straight-through dw on ``wref`` equal ``ragged_dot`` on the dequantized
    words."""
    _, lay, M, x, wq, fl, dy, rg = _setup(CASES["empty_group"], seed=3)
    # the cotangent of the rows that no assignment fills is zero
    dy = jnp.where((rg >= 0)[:, None], dy, 0)
    sc = jnp.ldexp(1.0, -fl).astype(jnp.bfloat16).reshape(G, 1, 1)
    wref = jnp.zeros((G, K, N), jnp.bfloat16)
    xf = x.astype(jnp.float32)

    def loss(use_pallas, x, wref):
        y = ops.fxp_gmm(x, wq, sc, wref, lay, tile=TILE,
                        use_pallas=use_pallas, out_dtype=jnp.float32)
        return jnp.sum(y * dy.astype(jnp.float32))

    gk = jax.grad(lambda x, w: loss(True, x, w), argnums=(0, 1))(xf, wref)
    gx = jax.grad(lambda x, w: loss(False, x, w), argnums=(0, 1))(xf, wref)
    np.testing.assert_allclose(loss(True, xf, wref), loss(False, xf, wref),
                               rtol=1e-5)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=2e-2)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: loss(True, x, w),
                                    argnums=(0, 1)))(xf, wref).jaxpr
    names = jaxpr_tools.pallas_kernel_names(jaxpr)
    assert sorted(names) == ["fxp_gmm", "gmm_dw", "gmm_dx"]


def test_fl_of_reads_the_scale_exponent():
    fl = jnp.asarray([-3, 0, 4, 11, 20], jnp.int32)
    sc = jnp.ldexp(1.0, -fl).astype(jnp.bfloat16).reshape(-1, 1, 1)
    assert list(np.asarray(ops.fl_of_scale(sc))) == list(np.asarray(fl))


def test_kernel_names_stay_off_the_dense_regex():
    dense = re.compile(r"fxp_q?matmul|matmul_d[xw]|int8_matmul")
    grouped = re.compile(r"fxp_gmm|gmm_d[xw]")
    for name in ("fxp_gmm", "gmm_dx", "gmm_dw"):
        assert grouped.search(name) and not dense.search(name)


@pytest.mark.parametrize("rows,tile", [(100, 128), (2048, 256),
                                       (4096, 512), (65536, 512)])
def test_row_tile_from_rows_per_group(rows, tile):
    assert fg.row_tile(rows) == tile
