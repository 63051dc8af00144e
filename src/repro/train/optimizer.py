"""Optimizers: ASGD (the paper's AdaPT-SGD), plain SGD, Adam (ablation), with
the paper's reduce-on-plateau (ROP) scheduler as jit-safe state.

ASGD = SGD where (paper §3.3/§3.4):
  * gradients of quantized tensors are L2-normalized per tensor
    ("we normalize gradients to limit weight growth and reduce chances of
    weights becoming unrepresentable after an update step"),
  * the loss already carries L1/L2/P regularizers (see core/sparsity.py).

The learning rate lives in the optimizer state (a traced scalar), so ROP
reductions never recompile the step.
"""
from __future__ import annotations

from typing import Any, Dict, Set, Tuple

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig

Array = jax.Array
PyTree = Any


def init_opt_state(params: PyTree, ocfg: OptimizerConfig) -> Dict[str, Any]:
    state: Dict[str, Any] = {
        "lr": jnp.float32(ocfg.lr),
        "step": jnp.int32(0),
        "rop_best": jnp.float32(jnp.inf),
        "rop_bad": jnp.int32(0),
    }
    if ocfg.name == "adam":
        state["m"] = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        state["v"] = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    elif ocfg.momentum > 0.0:
        state["mom"] = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return state


def _normalize(g: Array, axes=None) -> Array:
    n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)), axis=axes,
                         keepdims=axes is not None))
    return (g / jnp.maximum(n, 1e-12)).astype(g.dtype)


def normalize_grads(grads: PyTree, quantized_paths: Set[str]) -> PyTree:
    """Per-tensor L2 normalization on AdaPT-quantized tensors (paper §3.3).
    A stacked MoE expert leaf (L, E, K, N) is L·E matrices, each divided
    by its own norm."""
    from repro.core import fixed_point as fxp
    from repro.core.controller import path_str

    def visit(path, g):
        p = path_str(path)
        if p not in quantized_paths:
            return g
        if fxp.is_expert_param(p) and g.ndim == 4:
            return _normalize(g, axes=(2, 3))
        return _normalize(g)

    return jax.tree_util.tree_map_with_path(visit, grads)


def clip_by_global_norm(grads: PyTree, max_norm: float) -> PyTree:
    if max_norm <= 0:
        return grads
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree_util.tree_leaves(grads))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def apply_updates(params: PyTree, grads: PyTree, state: Dict[str, Any],
                  ocfg: OptimizerConfig) -> Tuple[PyTree, Dict[str, Any]]:
    lr = state["lr"]
    step = state["step"] + 1
    new_state = dict(state, step=step)
    if ocfg.name == "adam":
        b1, b2, eps = ocfg.beta1, ocfg.beta2, ocfg.adam_eps
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2)
                         * jnp.square(g.astype(jnp.float32)), state["v"], grads)
        t = step.astype(jnp.float32)
        corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        upd = jax.tree.map(lambda m_, v_: corr * m_ / (jnp.sqrt(v_) + eps), m, v)
        new_state.update(m=m, v=v)
    elif ocfg.momentum > 0.0:
        mom = jax.tree.map(
            lambda mo, g: ocfg.momentum * mo + g.astype(jnp.float32),
            state["mom"], grads)
        upd = mom
        new_state["mom"] = mom
    else:
        upd = grads
    params = jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) - lr * u.astype(jnp.float32)
                      ).astype(p.dtype), params, upd)
    return params, new_state


def rop_update(state: Dict[str, Any], loss: Array,
               ocfg: OptimizerConfig) -> Dict[str, Any]:
    """Reduce-on-plateau: lr *= factor after `patience` steps without a
    `threshold` improvement (paper §4.1 uses torch's ReduceLROnPlateau)."""
    improved = loss < state["rop_best"] - ocfg.rop_threshold
    best = jnp.minimum(state["rop_best"], loss)
    bad = jnp.where(improved, 0, state["rop_bad"] + 1)
    reduce_now = bad >= ocfg.rop_patience
    lr = jnp.where(reduce_now, state["lr"] * ocfg.rop_factor, state["lr"])
    bad = jnp.where(reduce_now, 0, bad)
    return dict(state, lr=lr, rop_best=best, rop_bad=bad)
