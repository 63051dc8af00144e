"""Pallas TPU kernels: grouped fixed-point matmul over rows sorted by expert.

A mixture-of-experts layer sends each token's rows to the experts it was
routed to. Here the rows of all held experts sit in one buffer ``x`` (M, K),
sorted by expert, and each expert g holds int8 words ``wq[g]`` (K, N) on its
own ⟨WL,FL⟩ grid (scale 2^-FL[g]). The three kernels compute

    fxp_gmm:  y[r] = x[r] @ (wq[g(r)] · 2^-FL[g(r)])          (M, N)
    gmm_dx:   dx[r] = dy[r] @ (wq[g(r)] · 2^-FL[g(r)])ᵀ       (M, K)
    gmm_dw:   dw[g] = Σ_{r in g} x[r]ᵀ dy[r]                 (G, K, N) f32

Layout (``row_layout``): each group starts on a row tile of ``tile`` rows
and owns at least one tile, so a tile belongs to exactly one group. Rows of
a group past its count are padding; the tiles after the last group's are
dead. The grid walks row tiles; what a tile needs is scalar prefetch:
``tile_group`` (each tile's group; a dead tile carries the last group),
``start`` and ``rows`` (each group's first row and row count), ``live``
(the number of tiles in use) and ``fl`` (each group's FL). The index maps
read the group's weight block from ``tile_group``; a dead tile maps every
operand to the block it already holds (no DMA), does no MXU work and writes
zeros. Rows past a group's count are zeroed in-register before and after
the MXU, so the kernels never depend on what the padding holds.

``gmm_dw`` walks the tiles innermost: each group's (K, N) block is
accumulated in f32 VMEM over the group's tiles, initialised at its first
tile and written at its last. Every group owns a tile, so every block is
written; a group with no rows writes an exact zero.

Blocks over K and N come from the dense rule (``fxp_matmul._dense_blocks``)
at M = ``tile``; the row tile comes from the rows a group is expected to
hold (``row_tile``). Operands go into the MXU as stored (bf16 activations,
int8 words cast to bf16), accumulating in f32, as the dense kernels do.

``fxp_gmm_vjp`` carries the custom VJP: dx is ``gmm_dx`` over the same
words, dw = ``gmm_dw`` lands whole on ``wref`` (the straight-through path to
the master weights), and the words and the layout take no cotangent.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fxp_matmul import _mask_tail, _pick_blocks, float0_like
from repro.kernels.sr_quantize import _pow2i

Array = jax.Array

LAYOUT_KEYS = ("tile_group", "start", "rows", "live")


def row_tile(rows_per_group: float) -> int:
    """Row tile for groups expected to hold ``rows_per_group`` rows each:
    the largest of 512, 256 and 128 that a group fills at least eight
    times, so the padding of its last tile (half a tile on average) stays
    within about 1/16 of its rows; 128 at fewer rows."""
    for t in (512, 256):
        if rows_per_group >= 8 * t:
            return t
    return 128


def row_layout(group: Array, groups: int, tile: int
               ) -> Tuple[Dict[str, Array], int]:
    """The sorted-row layout of ``group`` (A,), each entry a group in
    [0, groups) or ``groups`` for a row that goes to no group here.

    Returns ({"dest": (A,) buffer row of each entry (M for those of no
    group), "sizes": (G,) rows each group spans in the buffer, and the
    kernels' LAYOUT_KEYS, "rows" among them: (G,) rows per group}, M, the
    buffer's row count)."""
    A = group.shape[0]
    G = groups
    counts_all = jnp.zeros((G + 1,), jnp.int32).at[group].add(1)
    counts = counts_all[:G]
    sizes = jnp.maximum(-(-counts // tile), 1) * tile
    M = -(-A // tile) * tile + G * tile
    start = jnp.cumsum(sizes) - sizes
    order = jnp.argsort(group, stable=True)
    sorted_g = group[order]
    first = jnp.cumsum(counts_all) - counts_all           # in sorted order
    rank = jnp.arange(A, dtype=jnp.int32) - first[sorted_g]
    held = sorted_g < G
    dest_sorted = jnp.where(
        held, jnp.take(start, jnp.minimum(sorted_g, G - 1)) + rank, M)
    dest = jnp.zeros((A,), jnp.int32).at[order].set(dest_sorted)
    tiles = jnp.arange(M // tile, dtype=jnp.int32) * tile
    tg = jnp.searchsorted(start, tiles, side="right").astype(jnp.int32) - 1
    live = (jnp.sum(sizes) // tile).reshape(1).astype(jnp.int32)
    return {"dest": dest, "sizes": sizes, "start": start, "rows": counts,
            "tile_group": jnp.minimum(tg, G - 1), "live": live}, M


def _row_mask(x: Array, t, tile: int, end) -> Array:
    """Zero the rows of tile ``t`` at or past buffer row ``end``."""
    rows = t * tile + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < end, x, jnp.zeros_like(x))


def _params(kind: str, tile: int, K: int, N: int, a_dtype, b_dtype,
            out_dtype):
    _, bn, bk, params = _pick_blocks(kind, tile, K, N, None, None, None,
                                     a_dtype, b_dtype, out_dtype)
    return bn, bk, params


# ---------------------------------------------------------------------------
# Forward


def _fxp_gmm_kernel(tg_ref, start_ref, rows_ref, live_ref, fl_ref, x_ref,
                    w_ref, o_ref, acc_ref, *, nk: int, dims: tuple,
                    tile: int):
    K, N = dims
    t, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = tg_ref[t]
    live = t < live_ref[0]
    end = start_ref[g] + rows_ref[g]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        x = _row_mask(_mask_tail(x_ref[...], 1, k, K), t, tile, end)
        w = _mask_tail(w_ref[...].astype(x.dtype), 0, k, K)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        out = acc_ref[...] * _pow2i(-fl_ref[g])
        out = _mask_tail(_row_mask(out, t, tile, end), 1, j, N)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "interpret"))
def fxp_gmm(x: Array, wq: Array, fl: Array, tile_group: Array, start: Array,
            rows: Array, live: Array, *, tile: int, out_dtype=None,
            interpret: bool = False) -> Array:
    """y = x @ (wq[g] · 2^-fl[g]) row by row over the layout.  x: (M, K)
    float, M a multiple of ``tile``; wq: (G, K, N) int8; fl: (G,) int32."""
    M, K = x.shape
    G, K2, N = wq.shape
    assert K == K2 and M % tile == 0, (x.shape, wq.shape, tile)
    out_dtype = out_dtype or x.dtype
    bn, bk, params = _params("fwd", tile, K, N, x.dtype, wq.dtype, out_dtype)
    nt, nn, nk = M // tile, pl.cdiv(N, bn), pl.cdiv(K, bk)

    def x_map(t, j, k, tg, st, rw, lv, f):
        on = t < lv[0]
        return jnp.minimum(t, lv[0] - 1), jnp.where(on, k, nk - 1)

    def w_map(t, j, k, tg, st, rw, lv, f):
        on = t < lv[0]
        return tg[t], jnp.where(on, k, nk - 1), jnp.where(on, j, nn - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(nt, nn, nk),
        in_specs=[pl.BlockSpec((tile, bk), x_map),
                  pl.BlockSpec((None, bk, bn), w_map)],
        out_specs=pl.BlockSpec((tile, bn),
                               lambda t, j, k, *_: (t, j)),
        scratch_shapes=[pltpu.VMEM((tile, bn), jnp.float32)])
    kernel = functools.partial(_fxp_gmm_kernel, nk=nk, dims=(K, N),
                               tile=tile)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret, compiler_params=params, name="fxp_gmm",
    )(tile_group, start, rows, live, fl.astype(jnp.int32), x, wq)


# ---------------------------------------------------------------------------
# Backward


def _gmm_dx_kernel(tg_ref, start_ref, rows_ref, live_ref, fl_ref, dy_ref,
                   w_ref, dx_ref, acc_ref, *, nn: int, dims: tuple,
                   tile: int):
    """dx tile = Σ_n dy(t, n) @ w_g(j, n)ᵀ — the group's int8 (K, N) words
    read through a transposed index map."""
    K, N = dims
    t, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = tg_ref[t]
    live = t < live_ref[0]
    end = start_ref[g] + rows_ref[g]

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        dy = _row_mask(_mask_tail(dy_ref[...], 1, n, N), t, tile, end)
        w = _mask_tail(w_ref[...].astype(dy.dtype), 1, n, N)
        acc_ref[...] += jax.lax.dot_general(
            dy, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(n == nn - 1)
    def _done():
        out = acc_ref[...] * _pow2i(-fl_ref[g])
        out = _mask_tail(_row_mask(out, t, tile, end), 1, j, K)
        dx_ref[...] = out.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "out_dtype",
                                             "interpret"))
def gmm_dx(dy: Array, wq: Array, fl: Array, tile_group: Array, start: Array,
           rows: Array, live: Array, *, tile: int, out_dtype=None,
           interpret: bool = False) -> Array:
    """dx = dy @ (wq[g] · 2^-fl[g])ᵀ row by row.  dy: (M, N); wq: (G, K, N)
    int8; out (M, K)."""
    M, N = dy.shape
    G, K, N2 = wq.shape
    assert N == N2 and M % tile == 0, (dy.shape, wq.shape, tile)
    out_dtype = out_dtype or dy.dtype
    bn, bk, params = _params("dx", tile, K, N, dy.dtype, wq.dtype, out_dtype)
    nt, nkb, nn = M // tile, pl.cdiv(K, bk), pl.cdiv(N, bn)

    def dy_map(t, j, n, tg, st, rw, lv, f):
        on = t < lv[0]
        return jnp.minimum(t, lv[0] - 1), jnp.where(on, n, nn - 1)

    def w_map(t, j, n, tg, st, rw, lv, f):
        on = t < lv[0]
        return tg[t], jnp.where(on, j, nkb - 1), jnp.where(on, n, nn - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(nt, nkb, nn),
        in_specs=[pl.BlockSpec((tile, bn), dy_map),
                  pl.BlockSpec((None, bk, bn), w_map)],
        out_specs=pl.BlockSpec((tile, bk),
                               lambda t, j, n, *_: (t, j)),
        scratch_shapes=[pltpu.VMEM((tile, bk), jnp.float32)])
    kernel = functools.partial(_gmm_dx_kernel, nn=nn, dims=(K, N),
                               tile=tile)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, K), out_dtype),
        interpret=interpret, compiler_params=params, name="gmm_dx",
    )(tile_group, start, rows, live, fl.astype(jnp.int32), dy, wq)


def _gmm_dw_kernel(tg_ref, start_ref, rows_ref, live_ref, x_ref, dy_ref,
                   dw_ref, acc_ref, *, nt: int, dims: tuple, tile: int):
    K, N = dims
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = tg_ref[t]
    live = t < live_ref[0]
    end = start_ref[g] + rows_ref[g]
    nxt = tg_ref[jnp.minimum(t + 1, nt - 1)]
    last = (t + 1 == live_ref[0]) | (nxt != g)

    @pl.when(live & (t * tile == start_ref[g]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _acc():
        ct = jnp.promote_types(x_ref.dtype, dy_ref.dtype)
        x = _row_mask(x_ref[...].astype(ct), t, tile, end)
        dy = _row_mask(dy_ref[...].astype(ct), t, tile, end)
        acc_ref[...] += jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & last)
    def _done():
        dw_ref[...] = _mask_tail(_mask_tail(acc_ref[...], 0, i, K), 1, j, N)


@functools.partial(jax.jit, static_argnames=("groups", "tile", "interpret"))
def gmm_dw(x: Array, dy: Array, tile_group: Array, start: Array, rows: Array,
           live: Array, *, groups: int, tile: int,
           interpret: bool = False) -> Array:
    """dw[g] = Σ_{rows r of g} x[r]ᵀ dy[r] in f32.  x: (M, K); dy: (M, N);
    out (groups, K, N), exact zero for a group with no rows."""
    M, K = x.shape
    M2, N = dy.shape
    assert M == M2 and M % tile == 0, (x.shape, dy.shape, tile)
    bn, bk, params = _params("dw", tile, K, N, x.dtype, dy.dtype,
                             jnp.float32)
    nkb, nn, nt = pl.cdiv(K, bk), pl.cdiv(N, bn), M // tile

    def x_map(i, j, t, tg, st, rw, lv):
        return jnp.minimum(t, lv[0] - 1), i

    def dy_map(i, j, t, tg, st, rw, lv):
        return jnp.minimum(t, lv[0] - 1), j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(nkb, nn, nt),
        in_specs=[pl.BlockSpec((tile, bk), x_map),
                  pl.BlockSpec((tile, bn), dy_map)],
        out_specs=pl.BlockSpec((None, bk, bn),
                               lambda i, j, t, tg, *_: (tg[t], i, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)])
    kernel = functools.partial(_gmm_dw_kernel, nt=nt, dims=(K, N), tile=tile)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((groups, K, N), jnp.float32),
        interpret=interpret, compiler_params=params, name="gmm_dw",
    )(tile_group, start, rows, live, x, dy)


# ---------------------------------------------------------------------------
# custom_vjp rule


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm_diff(cfg, x, wq, fl, wref, tile_group, start, rows, live):
    del wref    # gradient receiver only
    tile, out_dtype, interpret, _ = cfg
    return fxp_gmm(x, wq, fl, tile_group, start, rows, live, tile=tile,
                   out_dtype=out_dtype, interpret=interpret)


def _gmm_diff_fwd(cfg, x, wq, fl, wref, tile_group, start, rows, live):
    return (_gmm_diff(cfg, x, wq, fl, wref, tile_group, start, rows, live),
            (x, wq, fl, tile_group, start, rows, live))


def _gmm_diff_bwd(cfg, res, dy):
    tile, _, interpret, wref_dtype = cfg
    x, wq, fl, tile_group, start, rows, live = res
    dx = gmm_dx(dy, wq, fl, tile_group, start, rows, live, tile=tile,
                out_dtype=x.dtype, interpret=interpret)
    dw = gmm_dw(x, dy, tile_group, start, rows, live, groups=wq.shape[0],
                tile=tile, interpret=interpret)
    meta = tuple(float0_like(a) for a in (tile_group, start, rows, live))
    return (dx, float0_like(wq), float0_like(fl), dw.astype(wref_dtype)
            ) + meta


_gmm_diff.defvjp(_gmm_diff_fwd, _gmm_diff_bwd)


def fxp_gmm_vjp(x: Array, wq: Array, fl: Array, wref: Array, layout: Dict,
                *, tile: int, out_dtype=None,
                interpret: bool = False) -> Array:
    """Differentiable grouped product over int8 words: forward ``fxp_gmm``,
    dx ``gmm_dx`` over the same words, and dw = ``gmm_dw`` landing on
    ``wref`` (G, K, N), which is never read. ``layout`` is
    ``row_layout``'s at the same ``tile``."""
    return _gmm_diff((tile, out_dtype, interpret, jnp.dtype(wref.dtype)),
                     x, wq, fl.astype(jnp.int32), wref,
                     *(layout[k] for k in LAYOUT_KEYS))
