"""Pallas TPU kernel: fused PushDown EDF ladder — all WL-candidate histograms
in one pass over the weights.

PushDown (alg. 3) compares the master weights' EDF against the EDF of the
weights re-quantized at every candidate word length. The XLA reference does
this as |ladder| = 18 independent quantize passes, each followed by *two*
scatter-add histograms (``jnp.zeros(bins).at[idx].add(1)``) — 18 reads of the
tensor and 36 scatters, the single most TPU-hostile pattern in the repo.

This kernel streams each (block_rows, 128) tile of the pre-subsampled weights
through VMEM **once** and, per tile:

  * bins the master values into the (T+1, r_upr) accumulator's row 0,
  * for each ladder candidate t (static unroll — WLs are compile-time, the
    range-derived FLs arrive per-call via SMEM): round-to-nearest quantizes
    the tile in-register and bins it into row 1+t,

with binning done as compares, no scatters anywhere: each 128-lane row of
the tile is tested against a column of bin ids (bins down the sublanes),
the hits accumulate in a (bins, 128) register tile across the rows, and one
lane reduction per histogram per tile folds them into the counts. The live
resolution r^l (runtime, SMEM) masks down the static r_upr-bin buffer;
padding lanes are masked by global element index so every histogram is
exact. One launch
replaces 18 quantize+histogram round trips; the KL/argmin epilogue over the
(T+1, r_upr) counts is O(T·r_upr) scalar work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sr_quantize import _pow2i

Array = jax.Array

LANE = 128


def _edf_ladder_kernel(scal_ref, meta_ref, fls_ref, x_ref, o_ref, acc_ref, *,
                       wl_ladder: tuple, r_pad: int, nsteps: int,
                       block_rows: int, cols: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo = scal_ref[0, 0]
    hi = scal_ref[0, 1]
    rf = meta_ref[0, 0].astype(jnp.float32)   # live bin count r^l
    n = meta_ref[0, 1]                        # valid element count
    span = jnp.maximum(hi - lo, 1e-12)
    # bin ids down the sublanes, tile elements along the lanes: each row
    # of the tile compares against every bin with no relayout (Mosaic has
    # no lane→sublane reshape). int32 iota: Mosaic builds no f32 iota.
    bins = jax.lax.broadcasted_iota(jnp.int32, (r_pad, 1),
                                    0).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row0 = pl.program_id(0) * block_rows

    def count(quant):
        """(r_pad, 1) counts of one histogram over this tile."""
        def row(r, acc):
            v = quant(x_ref[pl.ds(r, 1), :].astype(jnp.float32))  # (1, cols)
            # same expression order as pushdown._histogram for bit parity
            idx = jnp.clip(jnp.floor((v - lo) / span * rf), 0, rf - 1)
            valid = (row0 + r) * cols + lane < n
            return acc + jnp.where((idx == bins) & valid, 1.0, 0.0)

        acc = jax.lax.fori_loop(0, block_rows, row,
                                jnp.zeros((r_pad, cols), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    acc_ref[0] += count(lambda v: v)
    for t, wl in enumerate(wl_ladder):        # static unroll over the ladder
        scale = _pow2i(fls_ref[0, t])   # exact: exp2 is off an ulp at FL≳10
        qmax = float(2.0 ** (wl - 1) - 1.0)
        acc_ref[1 + t] += count(lambda v, scale=scale, qmax=qmax: jnp.clip(
            jnp.round(v * scale), -qmax - 1.0, qmax) / scale)

    @pl.when(pl.program_id(0) == nsteps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("wl_ladder", "r_upr",
                                             "block_rows", "interpret"))
def edf_ladder_hists(w: Array, fls: Array, r: Array, *, wl_ladder: tuple,
                     r_upr: int, block_rows: int = 64,
                     interpret: bool = False) -> Array:
    """Counts (1+T, r_upr): row 0 the master EDF of ``w``, row 1+t the EDF of
    ``w`` round-to-nearest quantized at ⟨wl_ladder[t], fls[t]⟩ — all over w's
    [min, max] range with ``r`` live bins inside the static r_upr buffer.

    w: 1-D pre-subsampled f32 weights; fls: (T,) int32 range-derived FLs;
    r: int32 live resolution.
    """
    wf = w.reshape(-1).astype(jnp.float32)
    n = wf.shape[0]
    cols = LANE
    if n >= 2 ** 31 - cols:                   # int32 element-index math
        raise ValueError(f"edf_ladder_hists: {n} elements overflow int32 "
                         "indexing — subsample first (pushdown.subsample)")
    lo, hi = jnp.min(wf), jnp.max(wf)
    rows = pl.cdiv(n, cols)
    pad = rows * cols - n
    w2 = jnp.pad(wf, (0, pad)).reshape(rows, cols)
    scal = jnp.stack([lo, hi]).reshape(1, 2)
    meta = jnp.stack([jnp.asarray(r, jnp.int32),
                      jnp.int32(n)]).reshape(1, 2)
    fls2 = fls.astype(jnp.int32).reshape(1, -1)
    T = len(wl_ladder)

    r_pad = pl.cdiv(r_upr, 8) * 8               # whole sublane tiles
    grid = (pl.cdiv(rows, block_rows),)
    kernel = functools.partial(_edf_ladder_kernel, wl_ladder=wl_ladder,
                               r_pad=r_pad, nsteps=grid[0],
                               block_rows=block_rows, cols=cols)
    hists = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),      # lo/hi (f32)
            pl.BlockSpec(memory_space=pltpu.SMEM),      # r, n (int32)
            pl.BlockSpec(memory_space=pltpu.SMEM),      # per-candidate FLs
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1 + T, r_pad, 1), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1 + T, r_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1 + T, r_pad, 1), jnp.float32)],
        interpret=interpret,
    )(scal, meta, fls2, w2)
    return hists[:, :r_upr, 0]
