"""Dense-layer kernel-path suite: the quantized kernels as the MODEL's
default data path (not a sidecar).

Covers, bottom-up:
  * the straight-through dense VJP (``fxp_dense_vjp``): dw = xᵀ@dy lands
    whole on the master receiver, scale cotangent zero;
  * controller emission: unpack_tree(keep_dense=...) keeps dense packed
    dicts intact for the kernels, and strip_packed_grads reads their
    cotangents off "wref";
  * the acceptance criteria: a jitted tiny-config train step lowers EVERY
    dense layer (7 in-scan + head) to Pallas fwd+dx+dw with ZERO
    dequantized-weight XLA matmuls (jaxpr-asserted), and loss/grad-norm
    trajectory parity vs the XLA dispatch within the
    test_vjp_differential.py tolerances;
  * the serve path: Engine over the packed tree, RTN words shared with
    training, finite logits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import jaxpr_tools
from repro.config import ModelConfig, load_config
from repro.core import controller
from repro.core import fixed_point as fxp
from repro.kernels import fxp_matmul as fm
from repro.kernels import ops, ref
from repro.train import train_loop

KEY = jax.random.PRNGKey(7)

TOL = dict(rtol=2e-4, atol=2e-4)


def _close(got, want, msg="", tol=TOL):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **tol, err_msg=msg)


def test_fxp_dense_grad_straight_through():
    """Materialized-words dense VJP: dwref = xᵀ@dy (whole, cast to the
    receiver dtype), dscale = 0 (controller state), dx streams int8."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (40, 56), jnp.float32)
    wq = jax.random.randint(k2, (56, 24), -128, 128, jnp.int8)
    cot = jax.random.normal(k3, (40, 24), jnp.float32)
    sc = jnp.bfloat16(1 / 32)
    wref = jnp.zeros((56, 24), jnp.bfloat16)
    gx, gs, gr = jax.grad(lambda x, s, r: jnp.sum(
        fm.fxp_dense_vjp(x, wq, s, r, interpret=True) * cot),
        (0, 1, 2))(x, sc, wref)
    _close(gx, ref.ref_matmul_dx(cot, wq, jnp.float32(1 / 32)))
    assert float(jnp.asarray(gs, jnp.float32)) == 0.0
    assert gr.dtype == jnp.bfloat16
    _close(gr, ref.ref_matmul_dw(x, cot), tol=dict(rtol=3e-2, atol=3e-2))


def test_dense_vjp_jaxpr_kernels():
    """Differentiated op-level jaxprs contain the expected fwd + bwd
    Pallas kernels."""
    x = jnp.zeros((32, 64), jnp.float32)
    wq = jnp.zeros((64, 32), jnp.int8)
    wref = jnp.zeros((64, 32), jnp.bfloat16)

    j1 = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(ops.fxp_dense(
        x, wq, jnp.float32(0.5), wref, use_pallas=True))))(x).jaxpr
    assert jaxpr_tools.count_pallas_calls(j1, "_fxp_matmul_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j1, "_matmul_dx_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j1, "_matmul_dw_kernel") == 1


# ---------------------------------------------------------------------------
# Controller emission + unpack/strip round trip


def _tiny_packed_cfg(use_pallas=True, sr=True, interval=1000):
    cfg = load_config("tiny", overrides=[
        "quant.container_dtype=int8_packed", "quant.max_wl=8",
        "quant.init_wl=8", "quant.init_fl=4",
        f"quant.stochastic_rounding={'true' if sr else 'false'}"])
    return dataclasses.replace(
        cfg,
        quant=dataclasses.replace(cfg.quant, use_pallas=use_pallas),
        train=dataclasses.replace(cfg.train, adapt_interval=interval,
                                  log_every=1))


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device mesh (the multidevice-4 "
                           "CI entry forces 4 host devices)")
def test_packed_dense_sharded_mesh_refused():
    """A dense leaf sharded over a REAL (>1-device) mesh under use_pallas
    must refuse loudly: the dense kernels cannot be partitioned by GSPMD,
    so proceeding would silently replicate every launch (all-gathering
    operands) — the opposite of what the packed container exists for."""
    import numpy as np_
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg = _tiny_packed_cfg()
    state = train_loop.init_state(cfg)
    mesh = Mesh(np_.array(jax.devices()[:2]), ("data",))
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), state["params"])
    shardings["head"] = NamedSharding(mesh, P("data", None))
    with pytest.raises(ValueError, match="dense kernel path"):
        controller.quantize_params_packed(
            state["params"], state["adapt"], cfg.quant, key=KEY,
            shardings=shardings)
    # the guard is generic over Sharding types, not a NamedSharding
    # whitelist — any other >1-device split placement must refuse too
    class _SplitSharding:
        device_set = frozenset(jax.devices()[:2])
        is_fully_replicated = False

    shardings["head"] = _SplitSharding()
    with pytest.raises(ValueError, match="dense kernel path"):
        controller.quantize_params_packed(
            state["params"], state["adapt"], cfg.quant, key=KEY,
            shardings=shardings)


def test_unpack_and_strip_both_flavors():
    """Dense and non-dense packed leaves: unpack_tree(keep_dense=True)
    keeps the dense dict for the kernels and dequantizes the rest;
    strip_packed_grads reads every cotangent off "wref"."""
    cfg = _tiny_packed_cfg()
    state = train_loop.init_state(cfg)
    qp = controller.quantize_params_packed(state["params"], state["adapt"],
                                           cfg.quant, key=KEY)
    assert fxp.is_packed(qp["head"]) and fxp.is_packed(qp["embed"])
    kept = fxp.unpack_tree(qp, keep_dense=True)
    assert fxp.is_packed(kept["head"])                 # dense rides through
    assert not fxp.is_packed(kept["embed"])            # non-dense unpacked
    full = fxp.unpack_tree(qp)
    h = qp["head"]
    want = h["q8"].astype(jnp.float32) * h["sc"].astype(jnp.float32)
    _close(full["head"], want, msg="unpack == words · scale")
    # strip: every packed dict's grad comes from its wref
    fake = jax.tree_util.tree_map(jnp.ones_like, qp)
    fake["head"]["wref"] = jnp.full_like(qp["head"]["wref"], 2.0)
    stripped = controller.strip_packed_grads(fake)
    assert stripped["head"].shape == state["params"]["head"].shape
    assert stripped["embed"].shape == state["params"]["embed"].shape
    assert bool(jnp.all(stripped["head"] == 2.0))


# ---------------------------------------------------------------------------
# Acceptance: jitted train step lowers every dense layer to Pallas
# fwd+dx+dw with zero dequantized-weight XLA matmuls


def _dense_weight_shapes(params):
    """All 2-D shapes a dequantized dense weight (or its transpose) could
    present to an XLA dot in the scan body / head matmul."""
    shapes = set()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        p = controller.path_str(path)
        if not fxp.is_dense_param(p) or leaf.ndim not in (2, 3):
            continue
        s = leaf.shape[-2:]
        shapes.add(s)
        shapes.add(s[::-1])
    return shapes


# 7 dense layers in the scanned block (wq wk wv wo wi_gate wi_up wo) + head
N_DENSE = 8


def test_train_step_lowers_all_dense_layers():
    cfg = _tiny_packed_cfg()
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, batch).jaxpr
    for kern in ("_fxp_matmul_kernel", "_matmul_dx_kernel",
                 "_matmul_dw_kernel"):
        n = jaxpr_tools.count_pallas_calls(jaxpr, kern)
        assert n == N_DENSE, (kern, n)
    # zero dequantized-weight XLA matmuls: no float dot consumes a tensor
    # of a dense weight's (or its transpose's) shape
    forbidden = _dense_weight_shapes(state["params"])
    bad = [(l, r, dt) for l, r, dt in jaxpr_tools.dot_general_shapes(jaxpr)
           if r in forbidden and dt != jnp.int8]
    assert not bad, bad


def test_train_step_xla_dispatch_has_no_dense_kernels():
    cfg = _tiny_packed_cfg(use_pallas=False)
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, batch).jaxpr
    assert jaxpr_tools.count_pallas_calls(jaxpr) == 0
    # ... and the dequantized dots ARE there (the contrast that makes the
    # zero-dequantized-matmul assertion above meaningful)
    forbidden = _dense_weight_shapes(state["params"])
    hits = [r for _, r, dt in jaxpr_tools.dot_general_shapes(jaxpr)
            if r in forbidden and dt != jnp.int8]
    assert hits


def test_train_trajectory_parity_dense_kernels_vs_xla():
    """4 real optimizer steps, SR off (RTN words are bit-identical across
    both dispatches): loss/grad-norm trajectories agree within the
    test_vjp_differential.py tolerances."""
    hist = {}
    for name, up in {"xla": False, "mat": True}.items():
        cfg = _tiny_packed_cfg(use_pallas=up, sr=False)
        state = train_loop.init_state(cfg)
        step = jax.jit(train_loop.make_train_step(cfg))
        rows = []
        for i in range(4):
            state, m = step(state, train_loop.make_batch(cfg, i))
            rows.append((float(m["loss"]), float(m["grad_norm"])))
        hist[name] = rows
    for (l_x, g_x), (l_p, g_p) in zip(hist["xla"], hist["mat"]):
        np.testing.assert_allclose(l_p, l_x, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(g_p, g_x, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Other model families through the dense kernel path


def _family_cfg(model: ModelConfig):
    cfg = _tiny_packed_cfg()
    cfg = dataclasses.replace(cfg, model=model)
    return dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, seq_len=32, global_batch=4))


def test_hybrid_ssm_family_dense_kernels():
    """mamba2-style hybrid: the SSM in/out projections ride the kernel
    path; conv_w / dynamics params keep their use-site dequant."""
    m = ModelConfig(name="tiny-hyb", family="hybrid", num_layers=2,
                    d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                    vocab_size=128, layer_pattern=("attn", "mamba"),
                    ssm_state=16, ssm_head_dim=32)
    cfg = _family_cfg(m)
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    state, metrics = step(state, train_loop.make_batch(cfg, 0))
    assert bool(jnp.isfinite(metrics["loss"]))
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, train_loop.make_batch(cfg, 1)).jaxpr
    # period = (attn, mamba): wq wk wv wo + mlp(3) + ssm in/out + head = 10
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_fxp_matmul_kernel") == 10


def test_moe_family_dense_kernels():
    """MoE: router is excluded (f32), the expert matrices keep the
    materialized container and go to the grouped kernels as int8 words,
    and the shared dense layers take the dense kernel path."""
    m = ModelConfig(name="tiny-moe", family="moe", num_layers=2,
                    d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                    vocab_size=128, num_experts=4, experts_per_token=2,
                    moe_d_ff=64)
    cfg = _family_cfg(m)
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    state, metrics = step(state, train_loop.make_batch(cfg, 0))
    assert bool(jnp.isfinite(metrics["loss"]))
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, train_loop.make_batch(cfg, 1)).jaxpr
    # attn wq wk wv wo + head = 5 (the FFN is MoE: its experts run on the
    # grouped kernels, dx and dw once per expert matrix of each layer)
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_fxp_matmul_kernel") == 5
    assert jaxpr_tools.count_pallas_calls(jaxpr, "gmm_dw") == 3


# ---------------------------------------------------------------------------
# Serving shares the path


def test_engine_serves_packed_dense_path():
    from repro.serve import engine as eng
    cfg = _tiny_packed_cfg()
    state = train_loop.init_state(cfg)
    e = eng.Engine(cfg, state["params"], state["adapt"])
    # serving materializes the words once, at load
    assert fxp.is_packed(e.qparams["head"])
    toks = jnp.zeros((2, 8), jnp.int32)
    out, logits = e.generate(toks, 4)
    assert out.shape == (2, 4)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # prefill logits match the XLA-dispatch engine: same RTN words, so the
    # residual difference is the bf16 forward chain (flash vs masked
    # attention reduction order) — bf16-chain tolerance as in
    # test_vjp_differential.TOL
    cfg_x = _tiny_packed_cfg(use_pallas=False)
    e2 = eng.Engine(cfg_x, state["params"], state["adapt"])
    l1, _ = e._prefill(e.qparams, toks, None)
    l2, _ = e2._prefill(e2.qparams, toks, None)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(l1, -1)),
                                  np.asarray(jnp.argmax(l2, -1)))


def test_continuous_batcher_dense_kernel_path():
    """The scheduler shares the serving dispatch: its vmapped decode step
    threads use_pallas, so the batcher drains requests through the fxp
    dense kernels (vmapped pallas_call) and produces tokens."""
    from repro.serve.scheduler import ContinuousBatcher
    cfg = _tiny_packed_cfg()
    state = train_loop.init_state(cfg)
    b = ContinuousBatcher(cfg, state["params"], state["adapt"], slots=2,
                          max_context=32)
    b.submit([1, 2, 3], max_new_tokens=4)
    done = b.run_until_drained(max_steps=40)
    assert len(done) == 1 and len(done[0].output) == 4
