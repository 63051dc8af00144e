"""Serving engine for AdaPT-trained (fully quantized, sparsified) models.

The paper's headline inference claim (tab. 6: SU 1.52–3.56, SZ 0.36–0.60)
rests on the trained network *staying* quantized after training — unlike
MuPPET, which emits float32. This engine consumes the AdaPT controller's
final ⟨WL,FL⟩ map, quantizes the weights ONCE at load, and serves from the
quantized copy; the float32 master is never shipped.

Two jitted entry points (also the dry-run's serve-shape targets):
  * ``prefill_step``  — prompt → (first logits, KV/SSM caches)
  * ``decode_step``   — one token for every sequence in the batch

``Engine`` wraps them with greedy/temperature sampling and batched request
padding. Fault tolerance: the engine is stateless between calls (caches are
caller-held), so a failed replica is replaced by re-prefilling on a healthy
one — no checkpoint needed for serving.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import Config
from repro.core import controller
from repro.models import transformer

Array = jax.Array


def quantize_for_serving(params, adapt_state, qcfg, max_wl=None):
    """One-shot weight quantization at the final ⟨WL,FL⟩ (deterministic —
    nearest rounding; SR is a training-time device). ``max_wl`` optionally
    clamps every tensor's word length first (AdaBits-style degraded
    serving; see ``controller.clamp_adapt_state``).

    With ``container_dtype="int8_packed"`` the engine serves from the SAME
    packed tree format the train step uses — dense layers feed int8 words
    straight to the fxp Pallas kernels via models/common.dense, so train
    and serve share one code path and one word draw (RTN is bit-identical
    across dispatches). Serving always materializes the words once, at
    load."""
    if not adapt_state or not adapt_state.get("tensors"):
        return params
    if max_wl is not None:
        adapt_state = controller.clamp_adapt_state(adapt_state, max_wl)
    if qcfg.container_dtype == "int8_packed":
        return controller.quantize_params_packed(params, adapt_state, qcfg,
                                                 key=None)
    return controller.quantize_params(params, adapt_state, qcfg, key=None)


def quantize_serving_levels(params, adapt_state, qcfg, levels):
    """Pre-materialize one quantized word set per serving word length
    (AdaBits: one set of trained weights served at multiple bit-widths).
    Returns {wl: qparams} for ``levels`` (descending WL, levels[0] = full
    precision). Every level is produced by the same deterministic
    requantization with the controller state WL-clamped, so all trees are
    STRUCTURALLY IDENTICAL (same treedef, leaf shapes, and dtypes) — the
    batcher swaps the active tree between decode steps and the jitted
    decode never recompiles. Structural identity is asserted here, at
    load, rather than discovered as a recompile at peak load.

    Without controller state there is nothing to requantize: the single
    passthrough tree is returned under levels[0]."""
    levels = tuple(levels)
    if not levels:
        raise ValueError("quantize_serving_levels: empty level ladder")
    if not adapt_state or not adapt_state.get("tensors"):
        return {levels[0]: quantize_for_serving(params, adapt_state, qcfg)}
    out = {wl: quantize_for_serving(params, adapt_state, qcfg, max_wl=wl)
           for wl in levels}
    ref_struct = jax.tree_util.tree_structure(out[levels[0]])
    ref_leaves = jax.tree_util.tree_leaves(out[levels[0]])
    for wl in levels[1:]:
        if jax.tree_util.tree_structure(out[wl]) != ref_struct:
            raise AssertionError(
                f"serving level WL={wl} produced a different pytree "
                "structure than the full-precision level — swapping it in "
                "would recompile the decode step")
        for a, b in zip(ref_leaves, jax.tree_util.tree_leaves(out[wl])):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise AssertionError(
                    f"serving level WL={wl}: leaf {a.shape}/{a.dtype} vs "
                    f"{b.shape}/{b.dtype} — precision swap would recompile")
    return out


def make_prefill(cfg: Config):
    m = cfg.model

    def prefill_step(qparams, tokens, memory=None):
        return transformer.prefill(qparams, m, tokens, memory=memory,
                                   use_pallas=cfg.quant.use_pallas)

    return prefill_step


def make_decode(cfg: Config):
    m = cfg.model

    def decode_step(qparams, token, caches, t):
        return transformer.decode_step(qparams, m, token, caches, t,
                                       use_pallas=cfg.quant.use_pallas)

    return decode_step


def sample(logits: Array, key: Array, temperature: float = 0.0) -> Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1
                                  ).astype(jnp.int32)


class Engine:
    """Minimal batched serving engine over the quantized model."""

    def __init__(self, cfg: Config, params, adapt_state: Optional[dict] = None):
        self.cfg = cfg
        self.qparams = quantize_for_serving(params, adapt_state or {},
                                            cfg.quant)
        self._prefill = jax.jit(make_prefill(cfg))
        self._decode = jax.jit(make_decode(cfg), donate_argnums=2)

    def generate(self, tokens: Array, max_new_tokens: int, *,
                 memory: Optional[Array] = None, temperature: float = 0.0,
                 seed: int = 0) -> Tuple[Array, Array]:
        """tokens: (B, S) prompt batch (right-aligned, same length).
        Returns (generated (B, max_new), last logits)."""
        B, S = tokens.shape
        context = S + max_new_tokens
        caches = transformer.init_caches(self.cfg.model, B, context)
        logits, pref_caches = self._prefill(self.qparams, tokens, memory)
        caches = _merge_prefill_caches(caches, pref_caches, S)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = sample(logits, key, temperature)
        for i in range(max_new_tokens):
            out.append(tok)
            if i == max_new_tokens - 1:
                break
            t = jnp.int32(S + i)
            logits, caches = self._decode(self.qparams, tok, caches, t)
            tok = sample(logits, jax.random.fold_in(key, i), temperature)
        return jnp.stack(out, axis=1), logits


def _merge_prefill_caches(full: Dict[str, Any], pref: Dict[str, Any],
                          prompt_len: int) -> Dict[str, Any]:
    """Embed prefill caches (sized to the prompt) into the generation-sized
    cache buffers. Positions keep their slot = pos %% C invariant because the
    full cache length C' >= prompt length and slots are re-derived from t."""
    merged = {}
    for key, slot_cache in full.items():
        p = pref[key]
        if "ssm" in slot_cache:                       # mamba: shapes equal
            merged[key] = jax.tree.map(lambda a, b: b.astype(a.dtype),
                                       slot_cache, p)
            continue
        dst_k, src_k = slot_cache["k"], p["k"]
        C_dst, C_src = dst_k.shape[2], src_k.shape[2]
        if C_dst == C_src:
            merged[key] = {"k": src_k.astype(dst_k.dtype),
                           "v": p["v"].astype(dst_k.dtype)}
            continue
        # re-layout: source slot s held position pos = roll-layout of the
        # prompt; rewrite into destination slot pos % C_dst.
        pos = jnp.arange(prompt_len - C_src, prompt_len, dtype=jnp.int32)
        src_slot = pos % C_src
        dst_slot = pos % C_dst
        k = dst_k.at[:, :, dst_slot].set(src_k[:, :, src_slot].astype(dst_k.dtype))
        v = slot_cache["v"].at[:, :, dst_slot].set(
            p["v"][:, :, src_slot].astype(dst_k.dtype))
        merged[key] = {"k": k, "v": v}
    return merged
