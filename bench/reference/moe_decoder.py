"""Plain float32 reference of the AdaPT train step of a mixture-of-experts
decoder, on one chip's share of the experts.

Written from the architecture (the configuration file) and the AdaPT
recipe, with nothing from the program under test; it is the judge of the
benchmark's MoE training cells.

Model (Mellum 2 as its config.json gives it): pre-norm RMSNorm with a
``1 + gain`` scale; grouped-query attention, causal, each layer of kind
``layer_types[l]``: a ``sliding_attention`` layer sees the last
``sliding_window`` positions (itself included) and takes plain rotary
embedding at θ; a ``full_attention`` layer sees every earlier position and
takes YaRN rotary embedding (frequencies blended between θ^(-2i/d) and
θ^(-2i/d)/factor by a ramp between the correction dims, cos and sin scaled
by the attention factor). Every MLP is sparse: the router (float32, as
given) scores all ``router_experts`` experts, each token takes its top
``num_experts_per_tok`` and a softmax over those logits. This chip holds
experts ``expert_offset`` to ``expert_offset + num_experts - 1``: each held
expert runs on every token, densely, and its SwiGLU output is weighted by
the token's routing weight for it (0 where the token did not choose it);
what the other experts would add is left out. Untied head, mean
next-token cross entropy.

Departures from the published model, each absent from its config.json: no
q/k norm, no load-balancing loss, no MTP head.

AdaPT step: ``dense_decoder``'s recipe (words with stochastic rounding on
every weight matrix, each expert's matrix its own; the router and the norm
gains read as they are; the residual stream rounded after each layer;
elastic net over the weight matrices; each weight matrix's gradient divided
by its own norm, each expert's by its own; the router and the norm gains
take their gradient as it is; plain SGD).

Everything runs in float32 under ``jax.default_matmul_precision("highest")``.
Memory: the rows of a step go through together (the activation grids frame
all of them), and what a step at the cells' sizes would hold at once is
rematerialised piece by piece: each layer, each block of queries, each
expert and each row's logits.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_decoder import (QUERY_BLOCK, _act_fl, _rms_norm,
                                           leaf_norms, quantize_act, words)


def is_word_matrix(path: str, shape) -> bool:
    """Matrices read as words: every weight matrix but the router."""
    return len(shape) >= 2 and "norm" not in path and "router" not in path


def _leaf_key(key, path: str, order: Dict[str, int]):
    return jax.random.fold_in(key, order[path])


def inv_freq(cfg, kind: str):
    """(inverse frequencies (d/2,), cos/sin scale) of a layer kind."""
    rope = cfg["rope_parameters"][kind]
    d = cfg["head_dim"]
    theta = float(rope["rope_theta"])
    ext = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return jnp.asarray(ext, jnp.float32), 1.0
    assert rope["rope_type"] == "yarn", rope
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def corr(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = ext / factor * ramp + ext * (1 - ramp)
    return jnp.asarray(inv, jnp.float32), float(rope["attention_factor"])


def _rope(x, inv, scale):
    S, half = x.shape[1], inv.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None]
    sin = (jnp.sin(ang) * scale)[None, :, None]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(q, k, v, window: int):
    """Causal attention (within ``window`` positions where it is > 0) in
    blocks of queries. q: (B, S, Hkv, G, D); k, v: (B, S, Hkv, D)."""
    S, D = q.shape[1], q.shape[-1]
    bq = min(QUERY_BLOCK, S)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k) * scale
        rows = i * bq + jnp.arange(bq)[:, None]
        cols = jnp.arange(S)[None, :]
        seen = cols <= rows
        if window > 0:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    out = jax.lax.map(block, jnp.arange(S // bq))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def route(h, router, cfg):
    """(T, router_experts) routing weights: the softmax over each token's
    top-k logits, 0 elsewhere."""
    logits = h @ router
    k = cfg["num_experts_per_tok"]
    kth = jax.lax.top_k(logits, k)[0][:, -1:]
    chosen = logits >= kth
    z = jnp.where(chosen, logits, -jnp.inf)
    return jax.nn.softmax(z, axis=-1)


def _experts(h, weights, moe, cfg):
    """Σ over held experts e of weights[:, e] · SwiGLU_e(h), each expert on
    every row. h: (T, d)."""
    off = cfg["expert_offset"]
    held = jax.lax.dynamic_slice_in_dim(weights, off, cfg["num_experts"],
                                        axis=1)

    @jax.checkpoint
    def expert(wg, wu, wd, w):
        return w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd)

    def one(acc, xs):   # the sum's carry is no input of the checkpoint
        return acc + expert(*xs), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (moe["we_gate"], moe["we_up"], moe["we_down"],
                           held.T))
    return out


def _layer(cfg, kind: str, x, attn, moe):
    B, S, d = x.shape
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    inv, scale = inv_freq(cfg, kind)
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    h = _rms_norm(x, attn["pre_norm"], eps)
    q = _rope((h @ attn["wq"]).reshape(B, S, H, D), inv, scale)
    k = _rope((h @ attn["wk"]).reshape(B, S, Hkv, D), inv, scale)
    v = (h @ attn["wv"]).reshape(B, S, Hkv, D)
    q = q.reshape(B, S, Hkv, H // Hkv, D)
    x = x + _attention(q, k, v, window).reshape(B, S, H * D) @ attn["wo"]
    h = _rms_norm(x, moe["pre_norm"], eps).reshape(B * S, d)
    weights = route(h, moe["router"], cfg)
    return x + _experts(h, weights, moe, cfg).reshape(B, S, d)


def _slot_words(tree, group, key, order, fl, period):
    out = {}
    for name, w in tree.items():
        path = f"blocks/{group}/{name}"
        if is_word_matrix(path, w.shape):
            k = jax.random.fold_in(_leaf_key(key, path, order), period)
            out[name] = words(w, k, fl)
        else:
            out[name] = w
    return out


def _slots(cfg) -> int:
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p
    return len(kinds)


def _chunk_loss(params, tokens, key, cfg, recipe, order):
    """Summed next-token cross entropy of the rows ``tokens`` (c, S), the
    residual stream rounded after each layer on the grid framing these
    rows."""
    wl, fl = recipe["init_wl"], recipe["init_fl"]
    emb = words(params["embed"], _leaf_key(key, "embed", order), fl)
    x = emb[tokens]
    blocks = params["blocks"]
    P = _slots(cfg)
    kinds = cfg["layer_types"]
    for l in range(cfg["num_hidden_layers"]):
        period, i = divmod(l, P)
        attn = jax.tree.map(lambda a: a[period], blocks[f"s{i}_attn"])
        moe = jax.tree.map(lambda a: a[period], blocks[f"s{i}_moe"])

        @jax.checkpoint
        def run(x, attn, moe, kind=kinds[l], i=i, period=period):
            x = _layer(cfg, kind, x,
                       _slot_words(attn, f"s{i}_attn", key, order, fl, period),
                       _slot_words(moe, f"s{i}_moe", key, order, fl, period))
            return quantize_act(x, wl, _act_fl(x, wl))

        x = run(x, attn, moe)
    head = words(params["head"], _leaf_key(key, "head", order), fl)
    x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def row(b):   # one row's logits at a time
        logp = jax.nn.log_softmax(x[b, :-1] @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tokens[b, 1:, None],
                                            axis=-1))

    return jnp.sum(jax.lax.map(row, jnp.arange(tokens.shape[0])))


def regularizer(params, key, recipe, order):
    """alpha |w_hat|_1 + beta / 2 |w_hat|^2 over every word matrix."""
    fl, a, b = recipe["init_fl"], recipe["l1"], recipe["l2"]
    total = jnp.float32(0.0)
    for n in ("embed", "head"):
        w = words(params[n], _leaf_key(key, n, order), fl)
        total = total + a * jnp.sum(jnp.abs(w)) + 0.5 * b * jnp.sum(w * w)
    for group, tree in params["blocks"].items():
        for period in range(next(iter(tree.values())).shape[0]):
            sl = jax.tree.map(lambda t: t[period], tree)
            for name, w in _slot_words(sl, group, key, order, fl,
                                       period).items():
                if is_word_matrix(f"blocks/{group}/{name}", w.shape):
                    total = total + a * jnp.sum(jnp.abs(w)) \
                        + 0.5 * b * jnp.sum(w * w)
    return total


def loss_fn(params, tokens, key, cfg, recipe, order: Dict[str, int]):
    """(full loss, task loss) of the quantized model on ``tokens`` (one
    shard: the activation grids frame all of its rows, so the rows go
    through in one chunk)."""
    B, S = tokens.shape
    task = _chunk_loss(params, tokens, key, cfg, recipe, order) / (B * (S - 1))
    return task + regularizer(params, key, recipe, order), task


def train_step(params, tokens, key, cfg, recipe, order):
    """One AdaPT step: (new params, task loss, raw gradient norms)."""
    (_, task), grads = jax.value_and_grad(
        lambda p: loss_fn(p, tokens, key, cfg, recipe, order),
        has_aux=True)(params)
    raw = leaf_norms(grads)
    lr = recipe["lr"]

    def update(path, w, g):
        p = "/".join(str(k.key) for k in path)
        if is_word_matrix(p, w.shape):
            expert = p.rsplit("/", 1)[-1] in ("we_gate", "we_up", "we_down")
            axes = tuple(range(w.ndim - 2, w.ndim)) if expert else None
            g = g / jnp.maximum(jnp.sqrt(jnp.sum(g * g, axis=axes,
                                                 keepdims=expert)), 1e-12)
        return w - lr * g

    return jax.tree_util.tree_map_with_path(update, params, grads), task, raw


def run(params, batches: List, keys: List, cfg, recipe,
        order: Dict[str, int]) -> Tuple[list, Dict, object]:
    """Drive ``len(batches)`` steps from ``params`` on one device. Returns
    (task losses, first step's raw gradient norms by leaf, final
    params)."""
    step = jax.jit(lambda p, t, k: train_step(p, t, k, cfg, recipe, order),
                   donate_argnums=0)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for tokens, key in zip(batches, keys):
            params, task, raw = step(params, tokens, key)
            losses.append(float(task))
            if first is None:
                first = {k: float(v) for k, v in raw.items()}
    return losses, first, params
