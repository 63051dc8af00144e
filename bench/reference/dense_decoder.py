"""Plain float32 reference of the AdaPT train step of a dense decoder.

Written from the architecture and the AdaPT recipe, with nothing from the
program under test; it is the judge of the benchmark's training cells.

Model (llama family, as the configuration file gives it): pre-norm RMSNorm
with a ``1 + gain`` scale, rotary embedding over split halves, grouped-query
causal attention (query head j reads key/value head j // (H / Hkv)), SwiGLU
MLP, untied head, mean next-token cross entropy.

AdaPT step, as the configuration states it:

* every weight matrix (embedding, projections, head) is read as words on
  the <WL, FL> grid, w_hat = clip(SR(w 2^FL), int8) 2^-FL, with stochastic
  rounding SR(x) = floor(x + u), u ~ U[0, 1) drawn here from its own stream;
  norm gains are read as they are;
* after each layer the residual stream is rounded to nearest on the
  <WL, FL_a> grid with FL_a = WL - 1 - ceil(log2 max|x|)+, the maximum taken
  over the rows of one data-parallel shard; gradients pass straight through;
* the loss adds alpha |w_hat|_1 + beta / 2 |w_hat|^2 over the weight
  matrices, and its gradient with respect to w_hat is applied to w (the
  straight-through estimator);
* each weight matrix's gradient is divided by its L2 norm, then
  w <- w - lr g (plain SGD; norm gains take their gradient as it is).

Everything runs in float32 under ``jax.default_matmul_precision("highest")``.
Memory: the loss and its gradient go a chunk of rows at a time
(``chunk_rows``) through a scan over layers with a rematerialised body,
attention in blocks of queries and the logits one row at a time, so that a
step of the configurations at their timed sizes fits one chip. Where a
chunk is smaller than a data-parallel shard, the activation grids come
first from a forward pass over whole shards.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def is_matrix(path: str, shape) -> bool:
    return len(shape) >= 2 and "norm" not in path


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, theta):
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., 2 * half:]], axis=-1)


def _pow2(e):
    return jnp.ldexp(jnp.float32(1.0), jnp.asarray(e, jnp.int32))


def words(w, key, fl):
    """w read as int8 words at 2^-fl, stochastically rounded; the gradient
    passes straight through to w."""
    u = jax.random.uniform(key, w.shape, jnp.float32)
    q = jnp.clip(jnp.floor(w * _pow2(fl) + u), -128.0, 127.0) * _pow2(-fl)
    return w + jax.lax.stop_gradient(q - w)


def _act_fl(x, wl):
    """FL of the activation grid that frames max|x|."""
    amax = jnp.max(jnp.abs(jax.lax.stop_gradient(x)))
    il = jnp.maximum(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-12))), 0.0)
    return wl - 1 - il.astype(jnp.int32)


def quantize_act(x, wl, fl):
    """Nearest rounding of the residual stream onto <wl, fl>; the gradient
    passes straight through."""
    top = _pow2(wl - 1)
    q = jnp.clip(jnp.round(x * _pow2(fl)), -top, top - 1.0) * _pow2(-fl)
    return x + jax.lax.stop_gradient(q - x)


def _attention(q, k, v):
    """Causal attention in blocks of queries, one block at a time. q: (B, S,
    Hkv, G, D), the G query heads that share a key/value head side by side;
    k, v: (B, S, Hkv, D)."""
    S, D = q.shape[1], q.shape[-1]
    bq = min(QUERY_BLOCK, S)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k) * scale
        rows = i * bq + jnp.arange(bq)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    out = jax.lax.map(block, jnp.arange(S // bq))   # (nb, B, bq, Hkv, G, D)
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _layer(cfg, x, attn, mlp):
    B, S, _ = x.shape
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms_norm(x, attn["pre_norm"], eps)
    q = _rope((h @ attn["wq"]).reshape(B, S, H, D), theta)
    k = _rope((h @ attn["wk"]).reshape(B, S, Hkv, D), theta)
    v = (h @ attn["wv"]).reshape(B, S, Hkv, D)
    q = q.reshape(B, S, Hkv, H // Hkv, D)
    x = x + _attention(q, k, v).reshape(B, S, H * D) @ attn["wo"]
    h = _rms_norm(x, mlp["pre_norm"], eps)
    return x + (jax.nn.silu(h @ mlp["wi_gate"]) * (h @ mlp["wi_up"])) \
        @ mlp["wo"]


def _leaf_key(key, path: str, order: Dict[str, int]):
    return jax.random.fold_in(key, order[path])


def _layer_words(params_slice, group, key, order, fl, i):
    out = {}
    for name, w in params_slice.items():
        path = f"blocks/{group}/{name}"
        if "norm" in name:
            out[name] = w
        else:
            k = jax.random.fold_in(_leaf_key(key, path, order), i)
            out[name] = words(w, k, fl)
    return out


def _run_layers(params, x, key, cfg, recipe, order, act):
    """The layer stack; ``act(x, i)`` -> (x, fl) rounds layer i's output."""
    fl = recipe["init_fl"]
    blocks = params["blocks"]

    @jax.checkpoint
    def body(x, xs):
        attn, mlp, i = xs
        x = _layer(cfg, x, _layer_words(attn, "s0_attn", key, order, fl, i),
                   _layer_words(mlp, "s0_mlp", key, order, fl, i))
        return act(x, i)

    return jax.lax.scan(body, x, (blocks["s0_attn"], blocks["s0_mlp"],
                                  jnp.arange(cfg["num_hidden_layers"])))


def act_fls(params, tokens, key, cfg, recipe, shards, order):
    """(layers, shards) FLs of the activation grids: the forward over all
    rows, each shard's range taken over its own rows."""
    wl = recipe["init_wl"]
    B = tokens.shape[0]
    emb = words(params["embed"], _leaf_key(key, "embed", order),
                recipe["init_fl"])

    def act(x, i):
        xs = x.reshape((shards, B // shards) + x.shape[1:])
        fls = jax.vmap(lambda v: _act_fl(v, wl))(xs)
        q = jax.vmap(lambda v, f: quantize_act(v, wl, f))(xs, fls)
        return q.reshape(x.shape), fls

    _, fls = _run_layers(params, emb[tokens], key, cfg, recipe, order, act)
    return fls


def _chunk_loss(params, tokens, fls, key, cfg, recipe, order):
    """Summed next-token cross entropy of the rows ``tokens`` (c, S) of one
    shard, its activations rounded on that shard's per-layer FLs; with
    ``fls`` None the rows are the whole shard and each FL is taken from
    them as the pass goes."""
    wl, fl = recipe["init_wl"], recipe["init_fl"]
    emb = words(params["embed"], _leaf_key(key, "embed", order), fl)

    def act(x, i):
        f = _act_fl(x, wl) if fls is None else fls[i]
        return quantize_act(x, wl, f), None

    x, _ = _run_layers(params, emb[tokens], key, cfg, recipe, order, act)
    head = words(params["head"], _leaf_key(key, "head", order), fl)
    x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def row(b):   # one row's logits at a time
        logp = jax.nn.log_softmax(x[b, :-1] @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tokens[b, 1:, None],
                                            axis=-1))

    return jnp.sum(jax.lax.map(row, jnp.arange(tokens.shape[0])))


def chunk_rows(cfg, rows: int, seq: int, budget: int = 2 ** 28) -> int:
    """The most rows of a shard, dividing it, whose widest activation
    (seq x max(d, ff) float32 per row) stays within ``budget`` bytes."""
    width = max(cfg["hidden_size"], cfg["intermediate_size"])
    best = 1
    for c in range(1, rows + 1):
        if rows % c == 0 and c * seq * width * 4 <= budget:
            best = c
    return best


def regularizer(params, key, recipe, order):
    """alpha |w_hat|_1 + beta / 2 |w_hat|^2 over every weight matrix."""
    fl, a, b = recipe["init_fl"], recipe["l1"], recipe["l2"]

    def elastic(w_hat):
        return a * jnp.sum(jnp.abs(w_hat)) + 0.5 * b * jnp.sum(w_hat * w_hat)

    total = sum(elastic(words(params[n], _leaf_key(key, n, order), fl))
                for n in ("embed", "head"))

    def body(r, xs):
        attn, mlp, i = xs
        for group, src in (("s0_attn", attn), ("s0_mlp", mlp)):
            for name, w in _layer_words(src, group, key, order, fl,
                                        i).items():
                if "norm" not in name:
                    r = r + elastic(w)
        return r, None

    blocks = params["blocks"]
    layers = blocks["s0_attn"]["wq"].shape[0]
    r, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                        (blocks["s0_attn"], blocks["s0_mlp"],
                         jnp.arange(layers)))
    return total + r


def loss_fn(params, tokens, key, cfg, recipe, shards: int = 1,
            order: Dict[str, int] | None = None):
    """(full loss, task loss) of the quantized model on ``tokens``. The
    loss and its gradient go a chunk of rows at a time, so that a step fits
    one chip; each activation grid frames the range of its shard's rows."""
    order = order or {}
    B, S = tokens.shape
    per_shard = B // shards
    c = chunk_rows(cfg, per_shard, S)
    fls = None
    if c < per_shard:   # a forward over whole shards first, for the ranges
        fls = jax.lax.stop_gradient(
            act_fls(params, tokens, key, cfg, recipe, shards, order))

    def chunk(total, j):
        tok = jax.lax.dynamic_slice_in_dim(tokens, j * c, c, axis=0)
        f = None if fls is None else jax.lax.dynamic_index_in_dim(
            fls, (j * c) // per_shard, axis=1, keepdims=False)
        return total + _chunk_loss(params, tok, f, key, cfg, recipe,
                                   order), None

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0), jnp.arange(B // c))
    task = total / (B * (S - 1))
    return task + regularizer(params, key, recipe, order), task


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{path: L2 norm} of a params tree (stacked leaves as one)."""
    return {"/".join(str(k.key) for k in p): jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32))))
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_step(params, tokens, key, cfg, recipe, shards, order, axis=None):
    """One AdaPT step: returns (new params, task loss, raw gradient norms).
    Under ``shard_map`` over ``axis`` each device holds one shard of rows
    and the loss and gradients are averaged over the devices."""
    (_, task), grads = jax.value_and_grad(
        lambda p: loss_fn(p, tokens, key, cfg, recipe, shards, order),
        has_aux=True)(params)
    if axis is not None:
        task, grads = jax.lax.pmean((task, grads), axis)
    raw = leaf_norms(grads)
    lr = recipe["lr"]

    def update(path, w, g):
        p = "/".join(str(k.key) for k in path)
        if is_matrix(p, w.shape):
            g = g / jnp.maximum(jnp.sqrt(jnp.sum(g * g)), 1e-12)
        return w - lr * g

    new = jax.tree_util.tree_map_with_path(update, params, grads)
    return new, task, raw


def run(params, batches: List, keys: List, cfg, recipe, shards: int,
        order: Dict[str, int], devices=None) -> Tuple[list, Dict, object]:
    """Drive ``len(batches)`` steps from ``params``, the activation range
    taken per ``shards`` of rows. Given ``shards`` devices, each takes one
    shard. Returns (task losses, first step's raw gradient norms by leaf,
    final params on the first device)."""
    if devices is not None and shards > 1 and len(devices) >= shards:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(devices[:shards]), ("rows",))
        fn = jax.shard_map(
            lambda p, t, k: train_step(p, t, k, cfg, recipe, 1, order,
                                       axis="rows"),
            mesh=mesh, in_specs=(P(), P("rows"), P()),
            out_specs=(P(), P(), P()), check_vma=False)
        rep = NamedSharding(mesh, P())
        step = jax.jit(fn, in_shardings=(rep, NamedSharding(mesh, P("rows")),
                                         rep), donate_argnums=0)
        params = jax.device_put(params, rep)
    else:
        step = jax.jit(lambda p, t, k: train_step(p, t, k, cfg, recipe,
                                                  shards, order),
                       donate_argnums=0)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for tokens, key in zip(batches, keys):
            params, task, raw = step(params, tokens, key)
            losses.append(float(task))
            if first is None:
                first = {k: float(v) for k, v in raw.items()}
    if devices is not None:
        params = jax.device_put(params, devices[0])
    return losses, first, params
