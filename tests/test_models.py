"""Per-architecture smoke tests + model-level consistency properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import load_config
from repro.configs import assigned_archs, get_smoke_config
from repro.models import ssm, transformer
from repro.serve.engine import _merge_prefill_caches

ARCHS = assigned_archs()


def _inputs(m, B=2, S=16, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    kw = {}
    if m.is_encoder:
        kw["embeds"] = jax.random.normal(key, (B, S, m.d_model))
    else:
        kw["tokens"] = jax.random.randint(key, (B, S), 0, m.vocab_size)
    if m.cross_attn_every:
        kw["memory"] = jax.random.normal(
            key, (B, m.num_image_tokens, m.d_model))
    return kw


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    m = get_smoke_config(arch).model
    params = transformer.init_params(jax.random.PRNGKey(0), m)
    B, S = 2, 16
    logits = transformer.forward(params, m, **_inputs(m, B, S))
    assert logits.shape == (B, S, m.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """One quantized train step on the reduced config: loss finite, params
    move, no NaNs anywhere in the state."""
    from repro.train import train_loop
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, seq_len=16, global_batch=2))
    state = train_loop.init_state(cfg)
    step = jax.jit(train_loop.make_train_step(cfg))
    batch = train_loop.make_batch(cfg, 0)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    for leaf in jax.tree_util.tree_leaves(new_state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                        jax.tree_util.tree_leaves(new_state["params"])))
    assert moved


# Router logits may differ between the batched forward and the step-wise
# decode by bf16 rounding of the hidden state (up to ~0.03 on the smoke
# models); a token whose k-th and (k+1)-th logits lie closer than TIE may
# pick other experts on the two paths, and nothing causally after it then
# has to agree.
TIE = 0.06


def _record_routing(monkeypatch):
    """Patch ``moe.route`` to log each call's f32 router logits and chosen
    experts, in execution order (layer by layer; step by step in decode)."""
    from repro.models import moe
    real, log = moe.route, []

    def route(tokens, router, k):
        weights, chosen = real(tokens, router, k)
        logits = jnp.dot(tokens.astype(jnp.float32),
                         router.astype(jnp.float32))
        jax.debug.callback(
            lambda l, c: log.append((np.asarray(l), np.asarray(c))),
            logits, chosen, ordered=True)
        return weights, chosen

    monkeypatch.setattr(moe, "route", route)
    return log


def _routing(log, order):
    """Stack logged calls to (layers, B, S, ...) arrays of logits and sorted
    chosen experts; ``order`` turns the flat call list into that layout."""
    jax.effects_barrier()
    logits = order(np.stack([l for l, _ in log]))
    chosen = order(np.stack([np.sort(c, axis=-1) for _, c in log]))
    log.clear()
    return logits, chosen


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not get_smoke_config(a).model.is_encoder])
def test_decode_matches_forward(arch, monkeypatch):
    m = get_smoke_config(arch).model
    if m.num_experts:  # compare dropless-to-dropless
        m = dataclasses.replace(m, capacity_factor=16.0)
        log = _record_routing(monkeypatch)
    params = transformer.init_params(jax.random.PRNGKey(0), m)
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, m.vocab_size)
    kw = {}
    if m.cross_attn_every:
        kw["memory"] = jax.random.normal(jax.random.PRNGKey(2),
                                         (B, m.num_image_tokens, m.d_model))
    full = transformer.forward(params, m, tokens=toks, **kw)
    if m.num_experts:
        f_logits, f_chosen = _routing(
            log, lambda a: a.reshape(a.shape[0], B, S, -1))
    caches = transformer.init_caches(m, B, S, dtype=jnp.float32)
    if m.cross_attn_every:
        from repro.models import attention
        plan, np_ = transformer.build_plan(m)
        for i, slot in enumerate(plan):
            if slot.kind == "cross":
                key_name = transformer.slot_key(i, slot)
                ks, vs = [], []
                for pidx in range(np_):
                    p = jax.tree.map(lambda a: a[pidx],
                                     params["blocks"][key_name])
                    k_, v_ = attention.project_memory(
                        p, kw["memory"].astype(jnp.bfloat16), m)
                    ks.append(k_)
                    vs.append(v_)
                caches[key_name] = {"k": jnp.stack(ks).astype(jnp.float32),
                                    "v": jnp.stack(vs).astype(jnp.float32)}
    outs = []
    for t in range(S):
        logits, caches = transformer.decode_step(params, m, toks[:, t],
                                                 caches, jnp.int32(t))
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    # bf16 blockwise compute: tolerance scales with how much the decode path
    # re-orders accumulations (mamba recurrence, MoE dispatch, cross-attn)
    if any(k == "mamba" for k in m.layer_pattern):
        tol = 0.15
    else:
        tol = 0.05   # bf16 block compute: contraction order differs between
                     # the batched forward and the step-wise decode einsums
    compared = np.ones((B, S), bool)
    if m.num_experts:
        # decode logs (S steps x layers) calls of (B, ...) each
        d_logits, d_chosen = _routing(
            log, lambda a: np.moveaxis(a.reshape(S, -1, *a.shape[1:]), 0, 2))
        k = m.experts_per_token
        top = -np.sort(-f_logits, axis=-1)
        margin = top[..., k - 1] - top[..., k]                  # (L, B, S)
        differs = np.any(f_chosen != d_chosen, axis=-1)         # (L, B, S)
        for b in range(B):
            flips = np.nonzero(differs[:, b].any(axis=0))[0]
            if flips.size:
                # the first position routed apart sits on a near-tie, at the
                # first layer it parts; later positions attend to it
                t = flips[0]
                layer = np.nonzero(differs[:, b, t])[0][0]
                assert margin[layer, b, t] < TIE, (layer, b, t)
                compared[b, t:] = False
        assert compared.sum() >= B * S // 2
        assert np.all(np.abs(f_logits - d_logits)[:, compared] < TIE / 2)
    err = np.abs(np.asarray(dec - full, np.float32)).max(axis=-1)
    assert float(err[compared].max()) < tol


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not get_smoke_config(a).model.is_encoder
                                  and not get_smoke_config(a).model.cross_attn_every])
def test_prefill_then_decode(arch):
    m = get_smoke_config(arch).model
    if m.num_experts:
        m = dataclasses.replace(m, capacity_factor=16.0)
    params = transformer.init_params(jax.random.PRNGKey(0), m)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              m.vocab_size)
    full = transformer.forward(params, m, tokens=toks)
    logits_pref, pref = transformer.prefill(params, m, toks[:, :S],
                                            cache_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits_pref - full[:, S - 1]))) < 0.1
    gen = transformer.init_caches(m, B, S + 4, dtype=jnp.float32)
    gen = _merge_prefill_caches(gen, pref, S)
    logits_dec, _ = transformer.decode_step(params, m, toks[:, S], gen,
                                            jnp.int32(S))
    assert float(jnp.max(jnp.abs(logits_dec - full[:, S]))) < 0.1


def test_ssd_chunked_equals_recurrent():
    cfg = get_smoke_config("mamba2-780m").model
    p = jax.tree.map(lambda a: a[0], ssm.init_layer(jax.random.PRNGKey(1),
                                                    cfg, 1))
    B, S = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model),
                          jnp.float32)
    ref, final_cache = ssm.apply(p, x, cfg, return_state=True)
    cache = jax.tree.map(lambda a: a[0],
                         ssm.init_cache(cfg, B, 1, dtype=jnp.float32))
    outs = []
    for t in range(S):
        y, cache = ssm.apply_decode(p, x[:, t:t + 1], cfg, cache)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(dec - ref))) < 1e-4
    # state handoff: chunked final state == recurrent final state
    assert float(jnp.max(jnp.abs(cache["ssm"] - final_cache["ssm"]))) < 1e-4


@pytest.mark.parametrize("s", [5, 8, 13, 16, 24])
def test_ssd_chunk_boundary_independence(s):
    """Chunked SSD result must not depend on the chunk size (pads included)."""
    cfg = get_smoke_config("mamba2-780m").model
    p = jax.tree.map(lambda a: a[0], ssm.init_layer(jax.random.PRNGKey(1),
                                                    cfg, 1))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, s, cfg.d_model))
    outs = []
    for chunk in (4, 8, 64):
        c = dataclasses.replace(cfg, ssm_chunk=chunk)
        outs.append(ssm.apply(p, x, c))
    for o in outs[1:]:
        assert float(jnp.max(jnp.abs(o - outs[0]))) < 1e-4


def test_plan_periodicity():
    checks = {
        "granite-8b": (1, 36), "gemma2-2b": (2, 13), "zamba2-7b": (3, 27),
        "mamba2-780m": (1, 48), "mixtral-8x22b": (1, 56),
        "llama-3.2-vision-11b": (5, 8), "hubert-xlarge": (1, 48),
    }
    for arch, (period, np_) in checks.items():
        m = load_config(arch).model
        plan, got_np = transformer.build_plan(m)
        assert (len(plan), got_np) == (period, np_), arch


def test_full_configs_match_assignment():
    spec = {
        "granite-8b": dict(num_layers=36, d_model=4096, num_heads=32,
                           num_kv_heads=8, d_ff=14336, vocab_size=49152),
        "gemma2-2b": dict(num_layers=26, d_model=2304, num_heads=8,
                          num_kv_heads=4, d_ff=9216, vocab_size=256000),
        "llama3.2-3b": dict(num_layers=28, d_model=3072, num_heads=24,
                            num_kv_heads=8, d_ff=8192, vocab_size=128256),
        "smollm-360m": dict(num_layers=32, d_model=960, num_heads=15,
                            num_kv_heads=5, d_ff=2560, vocab_size=49152),
        "zamba2-7b": dict(num_layers=81, d_model=3584, num_heads=32,
                          num_kv_heads=32, d_ff=14336, vocab_size=32000,
                          ssm_state=64),
        "mixtral-8x22b": dict(num_layers=56, d_model=6144, num_heads=48,
                              num_kv_heads=8, d_ff=16384, vocab_size=32768,
                              num_experts=8, experts_per_token=2),
        "arctic-480b": dict(num_layers=35, d_model=7168, num_heads=56,
                            num_kv_heads=8, d_ff=4864, vocab_size=32000,
                            num_experts=128, experts_per_token=2),
        "llama-3.2-vision-11b": dict(num_layers=40, d_model=4096,
                                     num_heads=32, num_kv_heads=8,
                                     d_ff=14336, vocab_size=128256),
        "hubert-xlarge": dict(num_layers=48, d_model=1280, num_heads=16,
                              num_kv_heads=16, d_ff=5120, vocab_size=504),
        "mamba2-780m": dict(num_layers=48, d_model=1536, vocab_size=50280,
                            ssm_state=128),
    }
    for arch, fields in spec.items():
        m = load_config(arch).model
        for k, v in fields.items():
            assert getattr(m, k) == v, f"{arch}.{k}: {getattr(m, k)} != {v}"
