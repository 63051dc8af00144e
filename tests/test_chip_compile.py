"""Described-topology compiles of the main-path kernels at SmolLM-360M widths.

Interpret mode runs every kernel on the CPU, but it accepts programs the TPU
compiler refuses (unsupported casts, iotas of the wrong dtype, blocks that
break the (8, 128) tiling rule, too much VMEM). These tests lower each kernel
of the AdaPT train step with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology and compile it with the installed TPU compiler — no chip
is needed, and nothing runs. Widths are SmolLM-360M's (d=960, ff=2560, 15/5
heads of 64, vocab 49152) at seq 2048 and global batch 8, and the dense
products of the Granite-8B and Mellum2 cuts at the same rows, each with the
blocks and VMEM limit the shape rule picks; decode rows with a prime K compile
the tail-masked boundary blocks.

The topology is described inside a module fixture: only the worker that runs
this file loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import jaxpr_tools
from repro.core.pushdown import WL_LADDER
from repro.kernels import edf_ladder as el
from repro.kernels import flash_attention as fa
from repro.kernels import fxp_gmm as fg
from repro.kernels import fxp_matmul as fm
from repro.kernels import sr_quantize as sq

D, FF, H, HKV, DH, VOCAB, LAYERS = 960, 2560, 15, 5, 64, 49152, 32
BATCH, SEQ = 8, 2048
M = BATCH * SEQ
# (K, N) of every dense layer: q/o projections, k/v projections, MLP in, MLP
# out, LM head.
DENSE_SHAPES = [(D, D), (D, HKV * DH), (D, FF), (FF, D), (D, VOCAB)]
# The same for Granite-8B-Code's widths (d 4096, 8 kv heads of 128, ff 14336)
# with a quarter of its vocabulary, at the same M = 16,384 rows (seq 4096,
# global batch 4): the blocks the shape rule picks for wide products, and
# the VMEM limit it sets for them, must compile too.
G_D, G_KV, G_FF, G_VOCAB = 4096, 8 * 128, 14336, 12288
GRANITE_DENSE_SHAPES = [(G_D, G_D), (G_D, G_KV), (G_D, G_FF), (G_FF, G_D),
                        (G_D, G_VOCAB)]
# Mellum2's dense products (d 2304, 32/4 heads of 128, an eighth of its
# vocabulary): q, k/v, o and head, at the same M = 16,384 rows (seq 8192,
# global batch 2).
MEL_DENSE_SHAPES = [(2304, 4096), (2304, 512), (4096, 2304), (2304, 12288)]
# Mellum2's experts (d 2304, expert width 896), 32 held of 64, top-8, at
# seq 8192 and global batch 2: the grouped kernels over the buffer of
# 131,072 assignment rows laid out at the row tile the rule picks.
MEL_D, MEL_F, MEL_E, MEL_ROWS = 2304, 896, 32, 16384 * 8
MEL_SHAPES = [(MEL_D, MEL_F), (MEL_F, MEL_D)]
# Mellum2's attention (32/4 heads of 128) at seq 8192, global batch 2: its
# sliding-window slots (1024) run the banded flash launches.
MEL_B, MEL_S, MEL_H, MEL_HKV, MEL_DH, MEL_WINDOW = 2, 8192, 32, 4, 128, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip — keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the HLO text after
    asserting every kernel lowered to a Mosaic custom call."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return text


@pytest.mark.parametrize("hw_prng", [True, False])
@pytest.mark.parametrize("shape", [(D, VOCAB), (VOCAB, D)])
def test_sr_quantize_fused_int8(one_chip, shape, hw_prng):
    fn = functools.partial(sq.sr_quantize_fused_int8, interpret=False,
                           hw_prng=hw_prng)
    _compile(fn, _spec(one_chip, shape, jnp.float32),
             _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("hw_prng", [True, False])
@pytest.mark.parametrize("kn", [(D, HKV * DH), (FF, D)])
def test_sr_quantize_fused_stacked_int8(one_chip, kn, hw_prng):
    fn = functools.partial(sq.sr_quantize_fused_stacked_int8,
                           interpret=False, hw_prng=hw_prng)
    _compile(fn, _spec(one_chip, (LAYERS,) + kn, jnp.float32),
             _spec(one_chip, (), jnp.int32),
             _spec(one_chip, (LAYERS,), jnp.int32))


@pytest.mark.parametrize("per_layer", [False, True])
def test_edf_ladder_hists(one_chip, per_layer):
    """The PushDown ladder at the default 65536-element EDF subsample and
    r_upr = 150; ``per_layer`` vmaps it over the 32 stacked layers, as
    ``controller.precision_switch`` does."""
    fn = functools.partial(el.edf_ladder_hists, wl_ladder=WL_LADDER,
                           r_upr=150, interpret=False)
    lead = (LAYERS,) if per_layer else ()
    if per_layer:
        fn = jax.vmap(fn)
    _compile(fn, _spec(one_chip, lead + (65536,), jnp.float32),
             _spec(one_chip, lead + (len(WL_LADDER),), jnp.int32),
             _spec(one_chip, lead, jnp.int32))


@pytest.mark.parametrize("kn", DENSE_SHAPES + GRANITE_DENSE_SHAPES
                         + MEL_DENSE_SHAPES)
def test_fxp_dense_vjp_grad(one_chip, kn):
    K, N = kn

    def loss(x, wq, scale, wref):
        y = fm.fxp_dense_vjp(x, wq, scale, wref, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 3)),
             _spec(one_chip, (M, K), jnp.bfloat16),
             _spec(one_chip, (K, N), jnp.int8),
             _spec(one_chip, (), jnp.float32),
             _spec(one_chip, (K, N), jnp.bfloat16))


def test_flash_attention_vjp_grad(one_chip):
    def loss(q, k, v):
        o = fa.flash_attention_vjp(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             _spec(one_chip, (BATCH, SEQ, H, DH), jnp.bfloat16),
             _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16),
             _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16))


def test_flash_attention_forward(one_chip):
    """The serving/prefill forward (no lse output)."""
    fn = functools.partial(fa.flash_attention, causal=True, interpret=False)
    _compile(fn, _spec(one_chip, (BATCH, SEQ, H, DH), jnp.bfloat16),
             _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16),
             _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16))


def _flash_grad(causal, window):
    def loss(q, k, v):
        o = fa.flash_attention_vjp(q, k, v, causal=causal, window=window,
                                   interpret=False)
        return jnp.sum(o.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _flash_eqns(fn, *args):
    """{kernel name: pallas_call eqn} of the three flash launches."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return dict(zip(jaxpr_tools.pallas_kernel_names(jaxpr),
                    jaxpr_tools.pallas_eqns(jaxpr)))


def test_flash_attention_window_band(one_chip):
    """Mellum2's windowed slots: the forward, dQ and dK/dV launches walk
    only the band of live blocks (3 of 16 at 512-blocks, at most the
    ⌈(window + bq − 1) / bk⌉ + 1 the window allows), and compile."""
    nq = MEL_S // 512
    band = fa._band(nq, functools.partial(
        fa._kv_blocks, bq=512, bk=512, causal=True, window=MEL_WINDOW,
        q_offset=0, sq=MEL_S, skv=MEL_S))
    assert band == 3 <= min(nq, -(-(MEL_WINDOW + 511) // 512) + 1)
    args = (_spec(one_chip, (MEL_B, MEL_S, MEL_H, MEL_DH), jnp.bfloat16),
            _spec(one_chip, (MEL_B, MEL_S, MEL_HKV, MEL_DH), jnp.bfloat16),
            _spec(one_chip, (MEL_B, MEL_S, MEL_HKV, MEL_DH), jnp.bfloat16))
    fn = _flash_grad(True, MEL_WINDOW)
    grids = {name: tuple(eqn.params["grid_mapping"].grid)
             for name, eqn in _flash_eqns(fn, *args).items()}
    rep = MEL_H // MEL_HKV
    assert grids == {
        "_flash_kernel": (MEL_B, MEL_H, nq, band),
        "_flash_dq_kernel": (MEL_B, MEL_H, nq, band),
        "_flash_dkv_kernel": (MEL_B, MEL_HKV, nq, band * rep)}, grids
    _compile(fn, *args)


def test_flash_attention_noncausal_keeps_grid(one_chip):
    """No causal mask and no window: every block is live and fully live,
    so the launches keep the whole (B, H, nq, nk) grid and the kernels no
    gate but their init and done."""
    args = (_spec(one_chip, (BATCH, SEQ, H, DH), jnp.bfloat16),
            _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16),
            _spec(one_chip, (BATCH, SEQ, HKV, DH), jnp.bfloat16))
    fn = _flash_grad(False, 0)
    n = SEQ // 512
    eqns = _flash_eqns(fn, *args)
    grids = {name: tuple(eqn.params["grid_mapping"].grid)
             for name, eqn in eqns.items()}
    assert grids == {"_flash_kernel": (BATCH, H, n, n),
                     "_flash_dq_kernel": (BATCH, H, n, n),
                     "_flash_dkv_kernel": (BATCH, HKV, n, n * H // HKV)}
    for name, eqn in eqns.items():
        body = eqn.params["jaxpr"]
        assert jaxpr_tools.count_primitives(body, "cond") == 2, name
    _compile(fn, *args)


@pytest.mark.parametrize("kn", [(D, D), (D, VOCAB), (1031, FF)])
def test_fxp_matmul_decode_rows(one_chip, kn):
    """Serving decode: one row per slot, the four batcher slots vmapped
    (``ContinuousBatcher._decode_fn``)."""
    K, N = kn
    fn = jax.vmap(lambda x, wq, s: fm.fxp_matmul(x, wq, s, interpret=False),
                  in_axes=(0, None, None))
    _compile(fn, _spec(one_chip, (4, 1, K), jnp.bfloat16),
             _spec(one_chip, (K, N), jnp.int8),
             _spec(one_chip, (), jnp.float32))


@pytest.mark.parametrize("kn", MEL_SHAPES)
def test_grouped_kernels_vjp_grad(one_chip, kn):
    """``fxp_gmm`` forward, ``gmm_dx`` and ``gmm_dw`` at Mellum2's expert
    shapes (gate/up K 2304 / N 896, down K 896 / N 2304), 32 groups."""
    K, N = kn
    tile = fg.row_tile(MEL_ROWS / 64)
    M = -(-MEL_ROWS // tile) * tile + MEL_E * tile
    nt = M // tile

    def loss(x, wq, fl, wref, tg, start, rows, live):
        lay = dict(tile_group=tg, start=start, rows=rows, live=live)
        y = fg.fxp_gmm_vjp(x, wq, fl, wref, lay, tile=tile, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)
    text = _compile(jax.grad(loss, argnums=(0, 3)),
                    _spec(one_chip, (M, K), jnp.bfloat16),
                    _spec(one_chip, (MEL_E, K, N), jnp.int8),
                    i32(MEL_E), _spec(one_chip, (MEL_E, K, N), jnp.bfloat16),
                    i32(nt), i32(MEL_E), i32(MEL_E), i32(1))
    for name in ("fxp_gmm", "gmm_dx", "gmm_dw"):
        assert name in text, name


def test_remat_granularity_moe_period(one_chip, monkeypatch):
    """``remat="full"`` checkpoints a period with MoE slots slot by slot:
    the compiled step's temporaries fall below those of the whole-period
    checkpoint. The smoke Mellum2 model cut to one (windowed, full) period,
    at 8,192 tokens a step."""
    import dataclasses

    from repro.config import apply_overrides
    from repro.configs import get_smoke_config
    from repro.kernels import ops
    from repro.models import transformer
    from repro.train import train_loop

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_smoke_config("mellum2-12b")
    m = dataclasses.replace(cfg.model, num_layers=2,
                            attn_pattern=("local", "global"))
    cfg = apply_overrides(dataclasses.replace(cfg, model=m), [
        "quant.use_pallas=true", "quant.container_dtype=int8_packed",
        "train.remat=full", "train.accum_steps=1", "train.seq_len=4096",
        "train.global_batch=2"])
    assert transformer._checkpoint_slots("full", transformer.build_plan(m)[0])

    def temporaries(per_slot):
        monkeypatch.setattr(transformer, "_checkpoint_slots",
                            lambda remat, plan: per_slot)
        place = lambda tree: jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree)
        state = jax.eval_shape(lambda: train_loop.init_state(cfg))
        batch = {"tokens": _spec(one_chip, (2, 4096), jnp.int32)}
        step = jax.jit(train_loop.make_train_step(cfg), donate_argnums=0)
        compiled = step.lower(place(state), batch).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temporaries(True) < temporaries(False)
