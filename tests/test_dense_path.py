"""Dense-layer kernel-path suite: the quantized kernels as the MODEL's
default data path (not a sidecar).

Covers, bottom-up:
  * the quantize-prologue kernels (``fxp_qmatmul`` / ``matmul_qdx``):
    SR words bit-identical to the materialized ``sr_quantize_fused_int8``
    stream on 2-D leaves, RTN bit-identical to ``jnp.round``, fwd/grad
    parity vs XLA autodiff of the straight-through oracle across odd /
    prime / multi-block shapes;
  * the straight-through dense VJPs (``fxp_dense_vjp`` / ``fxp_qdense_vjp``):
    dw = xᵀ@dy lands whole on the master receiver, scale cotangent zero;
  * controller emission: dense-consumed leaves become prologue dicts under
    use_pallas + dense_prologue (packed dicts otherwise), non-dense leaves
    keep the materialized container; unpack_tree(keep_dense=...) and
    strip_packed_grads agree on both flavors;
  * the acceptance criteria: a jitted tiny-config train step lowers EVERY
    dense layer (7 in-scan + head) to Pallas fwd+dx+dw with ZERO
    dequantized-weight XLA matmuls (jaxpr-asserted), loss/grad-norm
    trajectory parity vs the XLA dispatch within the
    test_vjp_differential.py tolerances, and the prologue variant still
    fires on steps traced after a precision switch;
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


# ---------------------------------------------------------------------------
# Quantize-prologue kernels


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 53, 29),
                                   (127, 257, 131)])
@pytest.mark.parametrize("fl", [0, 4, 7])
def test_fxp_qmatmul_words_match_materialized(m, k, n, fl):
    """The prologue's SR word draw for a 2-D master is bit-identical to
    ``sr_quantize_fused_int8``'s PORTABLE stream (the one CPU CI runs):
    quantize-in-prologue and materialize-then-matmul are the same function
    of ⟨master, seed, FL⟩ wherever both draw portably. (Compiled TPU
    materialized words use the hardware PRNG — same distribution only.)"""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, m * 31 + fl))
    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32)
    seed = jnp.int32(m * 1009 + fl)
    wq = ref.ref_sr_quantize_fused_int8_words(w, seed, fl)
    want = ref.ref_fxp_matmul(x, wq, jnp.ldexp(jnp.float32(1.0), -fl))
    got = fm.fxp_qmatmul(x, w, seed, jnp.int32(fl), jnp.int32(1),
                         bm=32, bn=32, bk=32, interpret=True)
    _close(got, want, msg=f"fl={fl}")


def test_fxp_qmatmul_rtn_matches_round():
    """mode=0 is round-half-even — bit-identical words to the XLA packed
    path's ``jnp.round`` (ties included: the half-integer grid points)."""
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (24, 48), jnp.float32)
    w = jax.random.normal(k2, (48, 40), jnp.float32)
    # force exact ties onto the 2^-FL half grid for a few entries
    w = w.at[0, :8].set(jnp.arange(8, dtype=jnp.float32) / 16.0 + 1.0 / 32.0)
    fl = jnp.int32(4)
    wq = jnp.clip(jnp.round(w * 16.0), -128, 127).astype(jnp.int8)
    want = ref.ref_fxp_matmul(x, wq, jnp.float32(1 / 16))
    got = fm.fxp_qmatmul(x, w, jnp.int32(0), fl, jnp.int32(0),
                         bm=16, bn=16, bk=16, interpret=True)
    _close(got, want)


@pytest.mark.parametrize("m,k,n", [(16, 32, 16), (37, 53, 29),
                                   (100, 70, 50)])
@pytest.mark.parametrize("mode", [0, 1])
def test_qdense_grad_parity(m, k, n, mode):
    """jax.grad through the prologue VJP vs XLA autodiff of the
    straight-through oracle: dx via the dequantized words, dw = xᵀ@dy."""
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, m + mode), 3)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32)
    cot = jax.random.normal(k3, (m, n), jnp.float32)
    seed, fl = jnp.int32(99), jnp.int32(5)

    gp = jax.grad(lambda x, w: jnp.sum(
        ops.fxp_qdense(x, w, seed, fl, jnp.int32(mode), use_pallas=True)
        * cot), (0, 1))(x, w)
    gr = jax.grad(lambda x, w: jnp.sum(
        ref.ref_fxp_qdense(x, w, seed, fl, mode) * cot), (0, 1))(x, w)
    _close(gp[0], gr[0], msg=f"dx mode={mode}")
    _close(gp[1], gr[1], msg=f"dw mode={mode}")
    # the straight-through dw is exactly xᵀ@dy
    _close(gp[1], ref.ref_matmul_dw(x, cot), msg="dw straight-through")


def test_qdense_fwd_bwd_word_agreement_multiblock():
    """fwd and dx tile the weight DIFFERENTLY (K- vs N-innermost grids);
    the index-hash stream must give them identical words anyway — dx from
    the Pallas VJP equals dy @ dequant(words)ᵀ of the forward's words."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (64, 96), jnp.float32)
    w = jax.random.normal(k2, (96, 80), jnp.float32)
    cot = jax.random.normal(k3, (64, 80), jnp.float32)
    seed, fl = jnp.int32(3), jnp.int32(6)
    gx = jax.grad(lambda x: jnp.sum(
        fm.fxp_qdense_vjp(x, w, seed, fl, jnp.int32(1), bm=32, bn=16,
                          bk=32, interpret=True) * cot))(x)
    wq = ref.ref_sr_quantize_fused_int8_words(w, seed, 6)
    want = jnp.dot(cot, (wq.astype(jnp.float32) / 64.0).T)
    _close(gx, want)


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
    Pallas kernels (and the prologue pair for the qdense flavor)."""
    x = jnp.zeros((32, 64), jnp.float32)
    wq = jnp.zeros((64, 32), jnp.int8)
    w = jnp.zeros((64, 32), jnp.float32)
    wref = jnp.zeros((64, 32), jnp.bfloat16)

    j1 = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(ops.fxp_dense(
        x, wq, jnp.float32(0.5), wref, use_pallas=True))))(x).jaxpr
    assert jaxpr_tools.count_pallas_calls(j1, "_fxp_matmul_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j1, "_matmul_dx_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j1, "_matmul_dw_kernel") == 1

    j2 = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(ops.fxp_qdense(
        x, w, jnp.int32(1), jnp.int32(4), jnp.int32(1),
        use_pallas=True))))(x).jaxpr
    assert jaxpr_tools.count_pallas_calls(j2, "_fxp_qmatmul_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j2, "_matmul_qdx_kernel") == 1
    assert jaxpr_tools.count_pallas_calls(j2, "_matmul_dw_kernel") == 1


# ---------------------------------------------------------------------------
# Controller emission + unpack/strip round trip


def _tiny_packed_cfg(prologue, use_pallas=True, sr=True, interval=1000):
    cfg = load_config("tiny", overrides=[
        "quant.container_dtype=int8_packed", "quant.max_wl=8",
        "quant.init_wl=8", "quant.init_fl=4",
        f"quant.stochastic_rounding={'true' if sr else 'false'}"])
    return dataclasses.replace(
        cfg,
        quant=dataclasses.replace(cfg.quant, use_pallas=use_pallas,
                                  dense_prologue=prologue),
        train=dataclasses.replace(cfg.train, adapt_interval=interval,
                                  log_every=1))


def test_controller_emits_prologue_leaves():
    cfg = _tiny_packed_cfg(prologue=True)
    state = train_loop.init_state(cfg)
    qp = controller.quantize_params_packed(state["params"], state["adapt"],
                                           cfg.quant, key=KEY)
    blocks = qp["blocks"]
    # stacked dense leaf → prologue dict with (L,) metadata
    wq = blocks["s0_attn"]["wq"]
    assert fxp.is_qdense(wq)
    L = state["params"]["blocks"]["s0_attn"]["wq"].shape[0]
    assert wq["seed"].shape == wq["flq"].shape == wq["mode"].shape == (L,)
    assert int(wq["mode"][0]) == 1                     # SR mode
    # per-layer seeds differ (folded layer index)
    assert int(wq["seed"][0]) != int(wq["seed"][1])
    # unstacked dense leaf (head) → prologue dict with scalar metadata
    assert fxp.is_qdense(qp["head"]) and qp["head"]["flq"].shape == ()
    # non-dense quantized leaf (embed) keeps the materialized container
    assert fxp.is_packed(qp["embed"])
    # RTN (serving / SR off): mode 0
    qp_r = controller.quantize_params_packed(state["params"], state["adapt"],
                                             cfg.quant, key=None)
    assert int(qp_r["head"]["mode"]) == 0


def test_prologue_excludes_sharded_leaves():
    """An explicitly-sharded dense leaf must NOT become a prologue dict
    (pallas_call has no SPMD rule — a mesh would gather the f32 master
    into every launch); it keeps the 1-byte packed container. Replicated
    placements stay eligible."""
    import numpy as np_
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg = _tiny_packed_cfg(prologue=True)
    state = train_loop.init_state(cfg)
    mesh = Mesh(np_.array(jax.devices()[:1]), ("data",))
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), state["params"])
    head_sharded = dict(shardings)
    head_sharded["head"] = NamedSharding(mesh, P("data", None))
    qp = controller.quantize_params_packed(
        state["params"], state["adapt"], cfg.quant, key=KEY,
        shardings=head_sharded)
    assert fxp.is_packed(qp["head"])           # sharded → materialized
    assert fxp.is_qdense(qp["blocks"]["s0_attn"]["wq"])  # replicated → ok


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
    cfg = _tiny_packed_cfg(prologue=False)
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
    cfg = _tiny_packed_cfg(prologue=True)
    state = train_loop.init_state(cfg)
    qp = controller.quantize_params_packed(state["params"], state["adapt"],
                                           cfg.quant, key=KEY)
    kept = fxp.unpack_tree(qp, keep_dense=True)
    assert fxp.is_qdense(kept["head"])                 # dense rides through
    assert not fxp.is_packed(kept["embed"])            # non-dense unpacked
    full = fxp.unpack_tree(qp)
    h = qp["head"]
    want = (ref.ref_qdense_words(h["wm"], h["seed"], h["flq"], h["mode"])
            .astype(jnp.float32) * jnp.ldexp(jnp.float32(1.0), -h["flq"]))
    _close(full["head"], want, msg="qdense_view == dequant of stream words")
    # strip: qdense grads land on wm, packed grads on wref
    fake = jax.tree_util.tree_map(jnp.ones_like, qp)
    stripped = controller.strip_packed_grads(fake)
    assert stripped["head"].shape == state["params"]["head"].shape
    assert stripped["embed"].shape == state["params"]["embed"].shape


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


@pytest.mark.parametrize("prologue", [False, True])
def test_train_step_lowers_all_dense_layers(prologue):
    cfg = _tiny_packed_cfg(prologue)
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, batch).jaxpr
    fwd = "_fxp_qmatmul_kernel" if prologue else "_fxp_matmul_kernel"
    dx = "_matmul_qdx_kernel" if prologue else "_matmul_dx_kernel"
    for kern in (fwd, dx, "_matmul_dw_kernel"):
        n = jaxpr_tools.count_pallas_calls(jaxpr, kern)
        assert n == N_DENSE, (kern, n)
    # the OTHER flavor is absent — no double dispatch
    other = "_fxp_matmul_kernel" if prologue else "_fxp_qmatmul_kernel"
    assert jaxpr_tools.count_pallas_calls(jaxpr, other) == 0
    # zero dequantized-weight XLA matmuls: no float dot consumes a tensor
    # of a dense weight's (or its transpose's) shape
    forbidden = _dense_weight_shapes(state["params"])
    bad = [(l, r, dt) for l, r, dt in jaxpr_tools.dot_general_shapes(jaxpr)
           if r in forbidden and dt != jnp.int8]
    assert not bad, bad


def test_train_step_xla_dispatch_has_no_dense_kernels():
    cfg = _tiny_packed_cfg(prologue=False, use_pallas=False)
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
    all three dispatches): loss/grad-norm trajectories agree within the
    test_vjp_differential.py tolerances."""
    hist = {}
    for name, (up, pro) in {"xla": (False, False), "mat": (True, False),
                            "pro": (True, True)}.items():
        cfg = _tiny_packed_cfg(pro, use_pallas=up, sr=False)
        state = train_loop.init_state(cfg)
        step = jax.jit(train_loop.make_train_step(cfg))
        rows = []
        for i in range(4):
            state, m = step(state, train_loop.make_batch(cfg, i))
            rows.append((float(m["loss"]), float(m["grad_norm"])))
        hist[name] = rows
    for variant in ("mat", "pro"):
        for (l_x, g_x), (l_p, g_p) in zip(hist["xla"], hist[variant]):
            np.testing.assert_allclose(l_p, l_x, rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(g_p, g_x, rtol=2e-2, atol=2e-2)


def test_prologue_fires_across_precision_switch():
    """Steps traced before AND after a precision switch keep the prologue
    kernels (freshly re-quantized layers never materialize words in HBM:
    the new ⟨WL,FL⟩ flows in as data, the graph — and its Pallas calls —
    never change), and training stays finite through the switch."""
    cfg = _tiny_packed_cfg(prologue=True, interval=2)
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    switch = jax.jit(train_loop.make_precision_switch(cfg))
    for i in range(5):
        state, m = step(state, train_loop.make_batch(cfg, i))
        assert bool(jnp.isfinite(m["loss"])), i
        if (i + 1) % 2 == 0:
            state = switch(state)
    # the step traced against post-switch state still runs the prologue
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, train_loop.make_batch(cfg, 5)).jaxpr
    assert jaxpr_tools.count_pallas_calls(
        jaxpr, "_fxp_qmatmul_kernel") == N_DENSE


# ---------------------------------------------------------------------------
# Other model families through the dense kernel path


def _family_cfg(model: ModelConfig, prologue: bool):
    cfg = _tiny_packed_cfg(prologue)
    cfg = dataclasses.replace(cfg, model=model)
    return dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, seq_len=32, global_batch=4))


@pytest.mark.parametrize("prologue", [False, True])
def test_hybrid_ssm_family_dense_kernels(prologue):
    """mamba2-style hybrid: the SSM in/out projections ride the kernel
    path; conv_w / dynamics params keep their use-site dequant."""
    m = ModelConfig(name="tiny-hyb", family="hybrid", num_layers=2,
                    d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                    vocab_size=128, layer_pattern=("attn", "mamba"),
                    ssm_state=16, ssm_head_dim=32)
    cfg = _family_cfg(m, prologue)
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    state, metrics = step(state, train_loop.make_batch(cfg, 0))
    assert bool(jnp.isfinite(metrics["loss"]))
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, train_loop.make_batch(cfg, 1)).jaxpr
    fwd = "_fxp_qmatmul_kernel" if prologue else "_fxp_matmul_kernel"
    # period = (attn, mamba): wq wk wv wo + mlp(3) + ssm in/out + head = 10
    assert jaxpr_tools.count_pallas_calls(jaxpr, fwd) == 10


def test_moe_family_dense_kernels():
    """MoE: router is excluded (f32), the expert matrices keep the
    materialized container and go to the grouped kernels as int8 words,
    and the shared dense layers take the dense kernel path."""
    m = ModelConfig(name="tiny-moe", family="moe", num_layers=2,
                    d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                    vocab_size=128, num_experts=4, experts_per_token=2,
                    moe_d_ff=64)
    cfg = _family_cfg(m, True)
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    state, metrics = step(state, train_loop.make_batch(cfg, 0))
    assert bool(jnp.isfinite(metrics["loss"]))
    jaxpr = jax.make_jaxpr(train_loop.make_train_step(cfg))(
        state, train_loop.make_batch(cfg, 1)).jaxpr
    # attn wq wk wv wo + head = 5 (the FFN is MoE: its experts run on the
    # grouped kernels, dx and dw once per expert matrix of each layer)
    assert jaxpr_tools.count_pallas_calls(jaxpr, "_fxp_qmatmul_kernel") == 5
    assert jaxpr_tools.count_pallas_calls(jaxpr, "gmm_dw") == 3


# ---------------------------------------------------------------------------
# Serving shares the path


def test_engine_serves_packed_dense_path():
    from repro.serve import engine as eng
    cfg = _tiny_packed_cfg(prologue=True)
    state = train_loop.init_state(cfg)
    e = eng.Engine(cfg, state["params"], state["adapt"])
    # serving ALWAYS materializes the words once at load, even with
    # dense_prologue on — weights are static, so holding the f32 master
    # to re-draw words per decode step would be pure overhead
    assert fxp.is_packed(e.qparams["head"])
    assert not any(fxp.is_qdense(l) for l in jax.tree_util.tree_leaves(
        e.qparams, is_leaf=fxp.is_qdense))
    toks = jnp.zeros((2, 8), jnp.int32)
    out, logits = e.generate(toks, 4)
    assert out.shape == (2, 4)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # prefill logits match the XLA-dispatch engine: same RTN words, so the
    # residual difference is the bf16 forward chain (flash vs masked
    # attention reduction order) — bf16-chain tolerance as in
    # test_vjp_differential.TOL
    cfg_x = _tiny_packed_cfg(prologue=False, use_pallas=False)
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
    cfg = _tiny_packed_cfg(prologue=False)
    state = train_loop.init_state(cfg)
    b = ContinuousBatcher(cfg, state["params"], state["adapt"], slots=2,
                          max_context=32)
    b.submit([1, 2, 3], max_new_tokens=4)
    done = b.run_until_drained(max_steps=40)
    assert len(done) == 1 and len(done[0].output) == 4
