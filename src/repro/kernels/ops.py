"""Jit'd dispatch wrappers over the Pallas kernels.

Each op picks the Pallas path on TPU (or when forced) and falls back to the
pure-jnp oracle otherwise; `interpret=True` is used automatically on CPU so
the kernels stay exercised (and tested) in this container.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.kernels import edf_ladder as _el
from repro.kernels import flash_attention as _fa
from repro.kernels import fxp_gmm as _fg
from repro.kernels import fxp_matmul as _fm
from repro.kernels import ref
from repro.kernels import sr_quantize as _sq

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sr_quantize(x: Array, u: Array, wl, fl, *, use_pallas: bool = False) -> Array:
    if use_pallas:
        return _sq.sr_quantize(x, u, jnp.asarray(wl, jnp.int32),
                               jnp.asarray(fl, jnp.int32),
                               interpret=not _on_tpu())
    return ref.ref_sr_quantize(x, u, wl, fl)


def _dim_spec(axes: tuple):
    return None if not axes else (axes[0] if len(axes) == 1 else axes)


def _fused_sharded(x: Array, seed: Array, extras, extra_lead, call,
                   sharding) -> Array:
    """shard_map-wrap ``call(x_loc, seed_loc, *extra_locs)`` over the leaf's
    NamedSharding. pallas_call has no SPMD partitioning rule — under plain
    GSPMD the kernel would be REPLICATED (all-gathering the f32 master), so
    the wrapper goes manual over every mesh axis the spec names and derives
    a per-shard seed by folding the linear shard index
    (``sr_quantize.fold_shard_seed``): the global stream is a pure function
    of ⟨seed, mesh layout⟩, bit-reproducible on any host
    (``ref.ref_sr_quantize_fused_sharded_words``). ``extra_lead[i]`` marks
    extras[i] as an (L,)-vector following the leaf's leading dim (stacked
    ⟨WL,FL⟩); other extras are replicated scalars. Callers must have
    checked even divisibility (``sharding.shard_grid``)."""
    mesh = sharding.mesh
    per_dim = shd.spec_dim_axes(sharding.spec, x.ndim)
    folded = tuple(a for axes in per_dim for a in axes)
    if not folded:                    # fully replicated: plain kernel call
        return call(x, seed, *extras)
    xspec = P(*[_dim_spec(a) for a in per_dim])
    lead = P(_dim_spec(per_dim[0]))

    def body(x_loc, seed_, *extra_locs):
        # Fold only the axes the spec names: devices along the remaining
        # (replication) axes hold identical blocks and must compute
        # identical words.
        idx = jnp.int32(0)
        for a in folded:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return call(x_loc, _sq.fold_shard_seed(seed_, idx), *extra_locs)

    in_specs = (xspec, P()) + tuple(lead if is_lead else P()
                                    for is_lead in extra_lead)
    # Manual over the WHOLE mesh (GSPMD refuses to partition a Mosaic
    # kernel along any auto axis; full-manual also runs eagerly) — unnamed
    # axes simply see replicated blocks.
    return shd.shard_map(body, mesh, axis_names=set(mesh.axis_names),
                         in_specs=in_specs, out_specs=xspec)(x, seed, *extras)


def sr_quantize_fused(x: Array, seed, wl, fl, *, use_pallas: bool = False,
                      sharding=None) -> Array:
    """SR quantize with in-kernel noise (no U[0,1) tensor in HBM), serving
    all three dispatch regimes of the 2-transfer path:

    * scalar ⟨wl, fl⟩           → ``sr_quantize_fused`` directly;
    * (L,)-vector ⟨wl, fl⟩      → the per-layer-stacked kernel (leading
      grid dim + SMEM precision vector, one launch for the whole stack);
    * ``sharding`` a NamedSharding with mesh axes in its spec → the kernel
      (stacked or not) wrapped in ``sharding.shard_map`` with per-shard
      folded seeds, so FSDP/TP leaves keep the 2-transfer path with zero
      collectives.

    The hardware PRNG is used on compiled TPU runs; interpret mode (CPU
    CI) uses the portable counter-hash stream; the non-Pallas fallback
    draws an explicit jax.random stream. All are deterministic per seed."""
    seed = jnp.asarray(seed, jnp.int32)
    wl = jnp.asarray(wl, jnp.int32)
    fl = jnp.asarray(fl, jnp.int32)
    stacked = bool(wl.ndim)
    if use_pallas:
        on_tpu = _on_tpu()

        def call(xv, sv, wlv, flv):
            if stacked:
                return _sq.sr_quantize_fused_stacked(
                    xv, sv, wlv, flv, interpret=not on_tpu, hw_prng=on_tpu)
            return _sq.sr_quantize_fused(xv, sv, wlv, flv,
                                         interpret=not on_tpu,
                                         hw_prng=on_tpu)

        if sharding is not None:
            return _fused_sharded(x, seed, (wl, fl), (stacked, stacked),
                                  call, sharding)
        return call(x, seed, wl, fl)
    if sharding is not None:
        # The jax.random fallback can honor neither the per-shard seed
        # contract nor the no-collective guarantee — refuse loudly rather
        # than silently re-introducing the f32 all-gather.
        raise ValueError("sr_quantize_fused: sharding= requires "
                         "use_pallas=True (the XLA fallback would gather "
                         "the master; use the noise+constraint path "
                         "instead)")
    if stacked:
        b = (wl.shape[0],) + (1,) * (x.ndim - 1)
        return ref.ref_sr_quantize_fused(x, seed, wl.reshape(b),
                                         fl.reshape(b))
    return ref.ref_sr_quantize_fused(x, seed, wl, fl)


def sr_quantize_fused_int8(x: Array, seed, fl, *, use_pallas: bool = False,
                           sharding=None) -> Array:
    """Int8-word flavor of :func:`sr_quantize_fused` for the native_int8 /
    packed path: returns the int8 fixed-point words (dequant = q8·2^-FL).
    Same three dispatch regimes (scalar / stacked (L,)-vector FL /
    shard_map-wrapped)."""
    seed = jnp.asarray(seed, jnp.int32)
    fl = jnp.asarray(fl, jnp.int32)
    stacked = bool(fl.ndim)
    if use_pallas:
        on_tpu = _on_tpu()

        def call(xv, sv, flv):
            if stacked:
                return _sq.sr_quantize_fused_stacked_int8(
                    xv, sv, flv, interpret=not on_tpu, hw_prng=on_tpu)
            return _sq.sr_quantize_fused_int8(xv, sv, flv,
                                              interpret=not on_tpu,
                                              hw_prng=on_tpu)

        if sharding is not None:
            return _fused_sharded(x, seed, (fl,), (stacked,), call, sharding)
        return call(x, seed, fl)
    if sharding is not None:
        raise ValueError("sr_quantize_fused_int8: sharding= requires "
                         "use_pallas=True (the XLA fallback would gather "
                         "the master; use the noise+constraint path "
                         "instead)")
    if stacked:
        b = (fl.shape[0],) + (1,) * (x.ndim - 1)
        return ref.ref_sr_quantize_fused_int8(x, seed, fl.reshape(b))
    return ref.ref_sr_quantize_fused_int8(x, seed, fl)


def edf_ladder_hists(w: Array, fls: Array, r, *, wl_ladder: tuple,
                     r_upr: int, use_pallas: bool = False) -> Array:
    """(1+T, r_upr) master + per-WL-candidate histograms in one data pass."""
    if use_pallas:
        return _el.edf_ladder_hists(w, fls, jnp.asarray(r, jnp.int32),
                                    wl_ladder=wl_ladder, r_upr=r_upr,
                                    interpret=not _on_tpu())
    return ref.ref_edf_ladder_hists(w, fls, jnp.asarray(r, jnp.int32),
                                    wl_ladder=wl_ladder, r_upr=r_upr)


def fxp_matmul(x: Array, wq: Array, scale: Array, *, use_pallas: bool = False,
               bias: Array | None = None) -> Array:
    """Differentiable on both paths: the Pallas route carries a custom VJP
    whose backward matmuls are themselves Pallas kernels (dx streams the
    same int8 weight tiles through a transposed index map; dw accumulates
    xᵀ@dy in f32 VMEM scratch), so jax.grad never falls back to a
    dequantized HBM weight copy.

    Masking contract: ANY ⟨M,K,N⟩ is accepted — primes included. Blocks
    are the shape rule's (``fxp_matmul._dense_blocks``) clamped to the
    dim, grids are ``pl.cdiv``, and partial boundary blocks are
    correct by construction: the forward and both backward kernels zero
    the contracted-dim tail lanes in-register before each MXU
    accumulation and zero-fill the valid slice on boundary writes
    (Pallas pads partial blocks with garbage/NaN). Aligned shapes trace
    to the exact unmasked kernels, so the masking is free there."""
    if use_pallas:
        out = _fm.fxp_matmul_vjp(x, wq, scale, interpret=not _on_tpu())
        if bias is not None:
            out = out + bias
        return out
    return ref.ref_fxp_matmul(x, wq, scale, bias)


def int8_matmul(xq: Array, wq: Array, sx: Array, sw: Array, *,
                use_pallas: bool = False) -> Array:
    if use_pallas:
        return _fm.int8_matmul_vjp(xq, wq, sx, sw, interpret=not _on_tpu())
    return ref.ref_int8_matmul(xq, wq, sx, sw)


def fxp_dense(x: Array, wq: Array, scale: Array, wref: Array, *,
              use_pallas: bool = False, out_dtype=None) -> Array:
    """The model's dense layer over MATERIALIZED int8 words (the packed
    ⟨q8, sc, wref⟩ container): differentiable with the straight-through
    weight cotangent — dx streams the same int8 tiles (transposed index
    map), dw = xᵀ@dy lands whole on ``wref`` (→ the master param via
    ``controller.strip_packed_grads``), and the scale gets a ZERO cotangent
    (it is controller state, exactly ``fixed_point.dequant_packed``'s
    rule) — so flipping dispatch never changes the optimizer step. The
    non-Pallas path is the XLA dequant-then-dot this replaces."""
    if use_pallas:
        return _fm.fxp_dense_vjp(x, wq, scale, wref, out_dtype=out_dtype,
                                 interpret=not _on_tpu())
    wv = wq.astype(jnp.float32) * jax.lax.stop_gradient(
        scale.astype(jnp.float32).reshape(())) + wref.astype(jnp.float32)
    out = jnp.dot(x.astype(jnp.float32), wv,
                  preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def fxp_gmm(x: Array, wq: Array, sc: Array, wref: Array, layout, *,
            tile: int, use_pallas: bool = False, out_dtype=None) -> Array:
    """The experts' grouped product over MATERIALIZED int8 words: ``x``
    (M, K) holds the rows of every held expert sorted by expert as
    ``fxp_gmm.row_layout`` laid them out at ``tile``; ``wq`` (G, K, N) int8
    with each expert's dequant scale ``sc`` (G, 1, 1) = 2^-FL; ``wref``
    (G, K, N) takes the straight-through weight cotangent. Under
    ``use_pallas`` the grouped kernels (``fxp_gmm`` / ``gmm_dx`` /
    ``gmm_dw``, each expert's FL as scalar prefetch); otherwise
    ``ragged_dot`` on the dequantized words over the layout's group
    spans."""
    if use_pallas:
        fl = fl_of_scale(sc)
        return _fg.fxp_gmm_vjp(x, wq, fl, wref, layout, tile=tile,
                               out_dtype=out_dtype, interpret=not _on_tpu())
    wv = wq.astype(jnp.float32) * jax.lax.stop_gradient(
        sc.astype(jnp.float32)) + wref.astype(jnp.float32)
    return ragged_dot(x, wv, layout["sizes"]).astype(out_dtype or x.dtype)


def fl_of_scale(sc: Array) -> Array:
    """FL of each exact power-of-two scale 2^-FL, read from its exponent
    bits: (G, ...) -> (G,) int32."""
    bits = jax.lax.bitcast_convert_type(
        sc.astype(jnp.float32).reshape(sc.shape[0]), jnp.int32)
    return 127 - ((bits >> 23) & 0xFF)


def ragged_dot(x: Array, w: Array, sizes: Array) -> Array:
    """``jax.lax.ragged_dot`` with f32 accumulation: row block g of ``x``
    (``sizes[g]`` rows, in order) times ``w[g]``; rows past the blocks
    give zeros. A program lowered for the CPU upcasts the operands, as
    ``models.common.einsum_f32`` does."""
    def dot(x, w):
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)

    def upcast(x, w):
        return dot(x.astype(jnp.float32), w.astype(jnp.float32))

    return jax.lax.platform_dependent(x, w, cpu=upcast, default=dot)


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int = 0, softcap: float = 0.0,
              scale: float | None = None, use_pallas: bool = False,
              bq: int = 512, bk: int = 512) -> Array:
    """Differentiable on both paths: the Pallas route carries a custom VJP
    (forward stashes the per-row logsumexp; backward is the standard
    recompute scheme as two more Pallas kernels, kernels/flash_attention
    ``_flash_dq_kernel`` / ``_flash_dkv_kernel``), so the differentiated
    training forward keeps the flash kernel instead of materializing the
    (Sq × Skv) logits in XLA.

    Masking contract: ANY Sq/Skv is accepted — primes included. bq/bk are
    clamped (never widened to the whole sequence), grids stay ``pl.cdiv``
    multi-block, and the garbage padding of partial boundary blocks is
    tail-masked inside all three kernels: q/k tail lanes read NEG_INF in
    the score path (excluded from the softmax max, the logsumexp and the
    per-row D), padded k/v/do lanes are zeroed before every MXU
    contraction, and boundary writes carry zeros in the padding lanes.
    Aligned shapes trace to the exact unmasked kernels (zero overhead);
    causal/window/GQA masking composes with the tail mask through the one
    shared ``_block_mask``, and the launches visit only the blocks it
    leaves live (``flash_attention.block_counts``)."""
    if use_pallas:
        return _fa.flash_attention_vjp(q, k, v, causal=causal, window=window,
                                       softcap=softcap, scale=scale,
                                       bq=bq, bk=bk, interpret=not _on_tpu())
    return ref.ref_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale)
