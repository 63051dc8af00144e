"""The held-expert MoE layer, YaRN rope and the mellum2-12b smoke model
against the benchmark's plain reference (``bench/reference/moe_decoder.py``)
on seeded random weights, on the CPU: both dispatches of the layer (the
grouped kernels in interpret mode, ``ragged_dot``), the share test (the
shares of the experts sum to the uncut layer), the forward of the whole
model, the AdaPT train step's loss and gradients, and the precision switch
per (layer, expert)."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, gen  # noqa: E402
from bench.reference import moe_decoder as ref  # noqa: E402
from repro import jaxpr_tools  # noqa: E402
from repro.config import apply_overrides  # noqa: E402
from repro.configs import assigned_archs, get_smoke_config  # noqa: E402
from repro.core import fixed_point as fxp  # noqa: E402
from repro.models import common, moe, transformer  # noqa: E402
from repro.train import train_loop  # noqa: E402

SMOKE = get_smoke_config("mellum2-12b")
FL = 6          # the layer tests' words: 2^-6 steps, round to nearest


def ref_cfg(m):
    """The reference's configuration (config.json keys) of a program
    ``ModelConfig`` of the mellum family."""
    kinds = ["sliding_attention" if m.attn_pattern[i % len(m.attn_pattern)]
             == "local" else "full_attention" for i in range(m.num_layers)]
    return {
        "hidden_size": m.d_model, "num_attention_heads": m.num_heads,
        "num_key_value_heads": m.num_kv_heads,
        "head_dim": m.resolved_head_dim, "num_hidden_layers": m.num_layers,
        "vocab_size": m.vocab_size, "rms_norm_eps": m.norm_eps,
        "layer_types": kinds, "sliding_window": m.window_size,
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": m.rope_theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": m.rope_theta,
                "factor": m.yarn_factor,
                "original_max_position_embeddings": m.yarn_original_max,
                "beta_fast": m.yarn_beta_fast,
                "beta_slow": m.yarn_beta_slow,
                "attention_factor": m.yarn_attention_factor}},
        "num_experts_per_tok": m.experts_per_token,
        "num_experts": moe.held(m), "router_experts": m.num_experts,
        "expert_offset": m.expert_offset,
        "moe_intermediate_size": m.moe_d_ff}


def _model(held=0, offset=0):
    return dataclasses.replace(SMOKE.model, experts_held=held,
                               expert_offset=offset)


def _layer_params(m, seed=0):
    p = moe.init_layer(jax.random.PRNGKey(seed), m, 0)
    p["pre_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                            p["pre_norm"].shape)
    # weights on the words' grid, so that every dispatch reads the same
    for n in fxp.EXPERT_PARAM_NAMES:
        p[n] = jnp.round(p[n] * 2.0 ** FL) * 2.0 ** -FL
    return p


def _packed(p):
    """The expert matrices as the controller packs them (int8 words at FL,
    a zero straight-through receiver)."""
    out = dict(p)
    for n in fxp.EXPERT_PARAM_NAMES:
        E = p[n].shape[0]
        out[n] = {"q8": jnp.round(p[n] * 2.0 ** FL).astype(jnp.int8),
                  "sc": jnp.full((E, 1, 1), 2.0 ** -FL, jnp.bfloat16),
                  "wref": jnp.zeros(p[n].shape, jnp.bfloat16)}
    return out


def _x(seed=2, shape=(2, 24, 64)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _reference_layer(p, x, m):
    cfg = ref_cfg(m)
    B, S, D = x.shape
    h = ref._rms_norm(x, p["pre_norm"], m.norm_eps).reshape(B * S, D)
    w = ref.route(h, p["router"], cfg)
    return x + ref._experts(h, w, p, cfg).reshape(B, S, D)


def _program_layer(p, x, m, use_pallas):
    return moe.apply(_packed(p) if use_pallas else p, x, m,
                     use_pallas=use_pallas)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ragged_dot", "grouped_kernels"])
def test_layer_matches_reference(use_pallas):
    """A layer holding experts 2-5 of 8: output, routed-row counts and the
    gradients of x and of the expert matrices."""
    m = _model(held=4, offset=2)
    p, x = _layer_params(m), _x()
    r = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    with jax.default_matmul_precision("highest"):
        want = _reference_layer(p, x, m)
        got, (rows, largest) = _program_layer(p, x, m, use_pallas)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        chosen = jax.lax.top_k(common.rms_norm(x, p["pre_norm"]).reshape(
            -1, 64) @ p["router"], m.experts_per_token)[1]
        per = np.bincount(np.asarray(chosen).ravel(), minlength=8)[2:6]
        assert int(rows) == per.sum() and int(largest) == per.max()

        def f_ref(x, w):
            return jnp.sum(_reference_layer(dict(p, **w), x, m) * r)

        def f_prog(x, w):
            q = dict(_packed(p) if use_pallas else p)
            for n in fxp.EXPERT_PARAM_NAMES:
                if use_pallas:
                    q[n] = dict(q[n], wref=w[n].astype(jnp.bfloat16))
                else:
                    q[n] = w[n]
            return jnp.sum(moe.apply(q, x, m, use_pallas=use_pallas)[0] * r)

        w0 = {n: p[n] for n in fxp.EXPERT_PARAM_NAMES}
        if use_pallas:
            w0_prog = {n: jnp.zeros_like(p[n]) for n in w0}
        else:
            w0_prog = w0
        gr = jax.grad(f_ref, argnums=(0, 1))(x, w0)
        gp = jax.grad(f_prog, argnums=(0, 1))(x, w0_prog)
    np.testing.assert_allclose(gp[0], gr[0], rtol=1e-3, atol=1e-3)
    for n in w0:
        np.testing.assert_allclose(np.asarray(gp[1][n], np.float32),
                                   gr[1][n], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["ragged_dot", "grouped_kernels"])
def test_shares_sum_to_the_uncut_layer(use_pallas):
    """Four shares of two experts each, every one routing over all eight,
    add up to the layer that holds all eight; the residual is counted
    once."""
    full_m = _model()
    p, x = _layer_params(full_m), _x(seed=4)
    whole, (rows, _) = _program_layer(p, x, full_m, use_pallas)
    total, held = x, 0
    for off in range(0, 8, 2):
        m = _model(held=2, offset=off)
        share = dict(p, **{n: p[n][off:off + 2]
                           for n in fxp.EXPERT_PARAM_NAMES})
        y, (r, _) = _program_layer(share, x, m, use_pallas)
        total = total + (y - x)
        held += int(r)
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    assert held == int(rows) == x.shape[0] * x.shape[1] * 2


def test_yarn_inv_freq_matches_the_hand_formula():
    """Mellum2's full layers: head_dim 128, θ 500000, factor 16 over 8192
    positions, β 32 / 1 put the correction dims at 18 and 35."""
    d, theta, factor, orig = 128, 500000.0, 16.0, 8192

    def corr(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(corr(32.0)), math.ceil(corr(1.0))
    assert (low, high) == (18, 35)
    i = np.arange(d // 2)
    ext = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = ext / factor * ramp + ext * (1 - ramp)
    got = common.yarn_inv_freq(d, theta, factor, orig, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert np.allclose(got[:19], ext[:19], rtol=2e-6)
    assert np.allclose(got[35:], ext[35:] / factor, rtol=2e-6)
    m = dataclasses.replace(SMOKE.model, num_heads=1, head_dim=128)
    inv, scale = common.rope_for(m, full=True)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1)   # YaRN's own
    assert common.rope_for(m, full=False) == (None, 1.0)
    inv_r, scale_r = ref.inv_freq(ref_cfg(m), "full_attention")
    np.testing.assert_allclose(inv_r, want, rtol=2e-6)
    assert scale_r == scale


def test_smoke_layers_match_reference():
    """The smoke model's period (three windowed layers, then a YaRN one;
    experts 2-5 of 8 held) in float32 with unquantized weights: each slot
    of the program's plan, attention then experts, against the reference's
    layer of that kind."""
    m = _model(held=4, offset=2)
    plan, periods = transformer.build_plan(m)
    assert [s.window for s in plan] == [8, 8, 8, 0] and periods == 1
    shapes = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), m))
    params = gen.make_weights(gen.seed_key(5), shapes)
    params = jax.tree.map(lambda a: a if a.ndim >= 2 else a + 0.1, params)
    toks = gen.lm_tokens(jax.random.PRNGKey(1), 2, 24, m.vocab_size, 0.05)
    cfg = ref_cfg(m)
    pos = jnp.broadcast_to(jnp.arange(24)[None], toks.shape)
    x = params["embed"][toks]
    with jax.default_matmul_precision("highest"):
        for i, slot in enumerate(plan):
            attn = jax.tree.map(lambda a: a[0],
                                params["blocks"][transformer.slot_key(i, slot)])
            mp = jax.tree.map(lambda a: a[0],
                              params["blocks"][transformer.ffn_key(i, slot)])
            want = ref._layer(cfg, cfg["layer_types"][i], x, attn, mp)
            y, _ = transformer.attention.attend_full(attn, x, m, pos,
                                                     window=slot.window)
            got, _ = moe.apply(mp, y, m)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                       err_msg=f"slot {i}")
            x = want


def _smoke_cfg(use_pallas, held=4, offset=2):
    cfg = dataclasses.replace(SMOKE, model=_model(held, offset))
    return apply_overrides(cfg, [
        f"quant.use_pallas={str(use_pallas).lower()}",
        "quant.container_dtype=int8_packed", "quant.edf_sample=4096",
        "train.remat=full", "train.seq_len=16", "train.global_batch=2",
        "optimizer.name=asgd"])


@pytest.fixture(scope="module")
def kernel_step():
    """One AdaPT step of the smoke model on the grouped kernels from the
    benchmark's seeded weights: (cfg, params, tokens, new state,
    metrics)."""
    cfg = _smoke_cfg(True)
    shapes = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg.model))
    params = gen.make_weights(gen.seed_key(7), shapes)
    toks = gen.lm_tokens(jax.random.PRNGKey(3), 2, 16, cfg.model.vocab_size,
                         0.05)
    state = dict(train_loop.init_state(cfg), params=params)
    new, metrics = jax.jit(train_loop.make_train_step(cfg))(
        state, {"tokens": toks})
    return cfg, params, toks, new, metrics


def test_smoke_train_step_matches_reference(kernel_step):
    """Loss and per-leaf gradient norms of the smoke model's AdaPT step on
    the grouped kernels (words at <8,4>, drawn from another stream than the
    reference's) within the benchmark's tiny-cell limits of the
    reference's: the controller's record of each quantized leaf's gradient,
    the move over lr of the router and the norm gains."""
    cfg, params, toks, new, metrics = kernel_step
    lr = cfg.optimizer.lr
    grad = {}
    for path, w0 in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = "/".join(str(k.key) for k in path)
        ts = new["adapt"]["tensors"].get(p)
        if ts is not None:
            grad[p] = float(jnp.sqrt(jnp.sum(jnp.square(ts["norm_sum"]))))
        else:
            w1 = new["params"]
            for k in path:
                w1 = w1[k.key]
            grad[p] = float(jnp.sqrt(jnp.sum(jnp.square(w1 - w0)))) / lr
    order = {p: i for i, p in enumerate(sorted(grad))}
    recipe = {"init_wl": 8, "init_fl": 4, "lr": lr, "l1": 1e-6, "l2": 1e-5}
    with jax.default_matmul_precision("highest"):
        _, task, raw = jax.jit(lambda p, t, k: ref.train_step(
            p, t, k, ref_cfg(cfg.model), recipe, order))(
            params, toks, jax.random.PRNGKey(11))
    raw = {k: float(v) for k, v in raw.items()}
    read = check.readings(
        {"losses": [float(metrics["loss"])], "grad": grad, "change": grad},
        {"losses": [float(task)], "grad": raw, "change": raw})
    assert read["loss"] < 0.05, read
    assert read["grad_median"] < 0.2, read
    assert read["grad"] < 1.0, read
    assert 0 < int(metrics["moe_rows_max"]) <= int(metrics["moe_rows_held"])


def test_switch_sets_each_expert_alike_on_both_dispatches(kernel_step):
    """Every (layer, expert) gets its own <WL, FL>, and the switch sets the
    same pairs on the kernel and the XLA dispatch."""
    cfg, _, _, state, _ = kernel_step
    tensors = {p: ts for p, ts in state["adapt"]["tensors"].items()
               if fxp.is_expert_param(p)}     # the experts' switch alone
    assert len(tensors) == 12
    assert tensors["blocks/s0_moe/we_gate"]["wl"].shape == (1, 4)
    full = dict(state, adapt=dict(state["adapt"], tensors={
        p: dict(ts, count=jnp.maximum(ts["count"], ts["lb"]))
        for p, ts in tensors.items()}))
    out = {}
    for pallas in (True, False):
        c = apply_overrides(cfg, [f"quant.use_pallas={str(pallas).lower()}"])
        out[pallas] = jax.jit(train_loop.make_precision_switch(c))(full)
    moved = 0
    for p, ts in out[True]["adapt"]["tensors"].items():
        other = out[False]["adapt"]["tensors"][p]
        np.testing.assert_array_equal(ts["wl"], other["wl"], err_msg=p)
        np.testing.assert_array_equal(ts["fl"], other["fl"], err_msg=p)
        moved += int(jnp.sum(ts["fl"] != tensors[p]["fl"]))
    assert moved > 0      # the experts' FLs do move at the switch


def test_kernel_path_has_no_dequantized_expert_products():
    """Under use_pallas the differentiated step runs every expert product
    on the grouped kernels (forward, dx, dw for each of the three
    matrices of the four MoE slots) and XLA computes no expert product."""
    cfg = _smoke_cfg(True)
    state = jax.eval_shape(lambda: train_loop.init_state(cfg))
    batch = jax.eval_shape(lambda: train_loop.make_batch(cfg, 0))
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, batch).jaxpr
    names = jaxpr_tools.pallas_kernel_names(jaxpr)
    assert names.count("gmm_dx") == names.count("gmm_dw") == 12
    assert names.count("fxp_gmm") >= 12
    assert jaxpr_tools.count_primitives(jaxpr, "ragged_dot") == 0
    xla = _smoke_cfg(False)
    jx = jax.make_jaxpr(train_loop.make_train_step(xla))(state, batch).jaxpr
    assert jaxpr_tools.count_pallas_calls(jx, "gmm") == 0
    assert jaxpr_tools.count_primitives(jx, "ragged_dot") >= 12


@pytest.mark.parametrize("arch", [
    a for a in assigned_archs()
    if len(transformer.build_plan(get_smoke_config(a).model)[0]) > 1])
def test_full_remat_checkpoints_moe_periods_by_slot(arch):
    """``remat="full"`` checkpoints each slot of a period with MoE slots on
    its own, and a period of other slots whole, as it did before the MoE
    layer held experts (``tests/test_chip_compile.py`` compares the two
    granularities' memory)."""
    m = get_smoke_config(arch).model
    plan, _ = transformer.build_plan(m)
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), m))
    kw = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    if m.cross_attn_every:
        kw["memory"] = jax.ShapeDtypeStruct(
            (2, m.num_image_tokens, m.d_model), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, kw: transformer.forward(p, m, remat="full", **kw))(
            params, kw)
    moe_period = any(slot.ffn == "moe" for slot in plan)
    assert jaxpr_tools.count_primitives(jaxpr, "remat") == \
        (len(plan) if moe_period else 1)
