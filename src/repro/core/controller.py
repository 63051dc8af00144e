"""PrecisionController: AdaPT per-tensor state machine (paper alg. 1 & 2).

State layout (a plain dict pytree → trivially checkpointable):

    state = {
      "tensors": { path: {
          "wl":       int32 P     word length
          "fl":       int32 P     fractional length
          "lb":       int32 P     lookback
          "res":      int32 P     EDF resolution
          "count":    int32 P     optimizer steps in current window
          "norm_sum": f32   P     Σ‖g_k‖₂ over window
          "grad_sum": bf16  like param     Σ g_k over window
          "sp":       f32   P     non-zero fraction at last switch
      }},
      "strategy":  int32 ()                 st ∈ {0:min, 1:mean, 2:max}
      "loss_hist": f32 (H,)                 ring buffer
      "loss_ptr":  int32 ()
      "loss_seen": int32 ()
    }

P, the precision's shape, is () for a leaf with one ⟨WL,FL⟩; leaves with a
leading scanned-layer dim L (the "blocks" stack) carry per-layer precision,
P = (L,), and MoE expert stacks (L, E, K, N) one per (layer, expert), P =
(L, E), each expert's matrix its own tensor. Everything is vmapped over
those dims. The hot ``train_step`` only *reads* wl/fl and *writes* the
accumulators; ``precision_switch`` (PushDown + PushUp + adaptation) runs
every ``adapt_interval`` steps on the same jit graph regardless of which
tensors actually switch (masked updates).
"""
from __future__ import annotations

import re
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import sharding as shd
from repro.config import QuantConfig
from repro.core import fixed_point as fxp
from repro.core import pushdown, pushup
from repro.kernels import ops as kops

Array = jax.Array
PyTree = Any

STACKED_PREFIXES = ("blocks", "layers")


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def is_quantized_leaf(path: str, leaf: Array, qcfg: QuantConfig) -> bool:
    """Weights matrices/conv kernels are quantized; vectors, norms, routers,
    SSM dynamics params are not (DESIGN.md §4)."""
    if leaf.ndim < 2:
        return False
    low = path.lower()
    return not any(pat in low for pat in qcfg.exclude)


def is_stacked(path: str) -> bool:
    return path.split("/", 1)[0] in STACKED_PREFIXES


def _per_layer_shape(path: str, leaf: Array):
    """Shape of the leaf's precision: (L,) for a stacked leaf, (L, E) for a
    stacked expert leaf, () otherwise."""
    if not (is_stacked(path) and leaf.ndim >= 3):
        return ()
    if fxp.is_expert_param(path) and leaf.ndim == 4:
        return leaf.shape[:2]
    return (leaf.shape[0],)


def _reduce_axes(path: str, leaf: Array):
    if _per_layer_shape(path, leaf):
        return tuple(range(1, leaf.ndim))
    return tuple(range(leaf.ndim))


# ---------------------------------------------------------------------------
# Init


def init_adapt_state(params: PyTree, qcfg: QuantConfig) -> Dict[str, Any]:
    tensors = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        p = path_str(path)
        if not is_quantized_leaf(p, leaf, qcfg):
            continue
        ps = _per_layer_shape(p, leaf)
        mk = lambda v, dt: jnp.full(ps, v, dt)
        tensors[p] = {
            "wl": mk(qcfg.init_wl, jnp.int32),
            "fl": mk(qcfg.init_fl, jnp.int32),
            "lb": mk(qcfg.lb_lwr, jnp.int32),
            "res": mk(qcfg.r_lwr, jnp.int32),
            "count": mk(0, jnp.int32),
            "norm_sum": mk(0.0, jnp.float32),
            "grad_sum": jnp.zeros(leaf.shape, jnp.bfloat16),
            "sp": mk(1.0, jnp.float32),
        }
    st0 = {"min": 0, "mean": 1, "max": 2}[qcfg.strategy]
    return {
        "tensors": tensors,
        "strategy": jnp.int32(st0),
        "loss_hist": jnp.zeros((qcfg.loss_hist_len,), jnp.float32),
        "loss_ptr": jnp.int32(0),
        "loss_seen": jnp.int32(0),
    }


# ---------------------------------------------------------------------------
# Per-step accumulation (cheap; lives inside train_step)


def accumulate(state: Dict[str, Any], grads: PyTree, loss: Array) -> Dict[str, Any]:
    flat = dict(
        (path_str(p), g) for p, g in jax.tree_util.tree_flatten_with_path(grads)[0])
    tensors = {}
    for path, ts in state["tensors"].items():
        g = flat[path].astype(jnp.float32)
        axes = tuple(range(ts["wl"].ndim, g.ndim))
        gn = jnp.sqrt(jnp.sum(g * g, axis=axes) + 1e-30)
        tensors[path] = {
            **ts,
            "norm_sum": ts["norm_sum"] + gn,
            "grad_sum": (ts["grad_sum"].astype(jnp.float32) + g).astype(jnp.bfloat16),
            "count": ts["count"] + 1,
        }
    h = state["loss_hist"]
    ptr = state["loss_ptr"]
    h = h.at[ptr].set(loss.astype(jnp.float32))
    return {
        **state,
        "tensors": tensors,
        "loss_hist": h,
        "loss_ptr": (ptr + 1) % h.shape[0],
        "loss_seen": state["loss_seen"] + 1,
    }


# ---------------------------------------------------------------------------
# Precision switch (PushDown + PushUp, masked per tensor/layer)


def _avg_lookback(state) -> Array:
    lbs = [jnp.mean(ts["lb"].astype(jnp.float32)) for ts in state["tensors"].values()]
    return jnp.mean(jnp.stack(lbs)) if lbs else jnp.float32(0.0)


def _loss_stats(state, lb_avg: Array):
    """(avg loss over last ⌈lb_avg⌉ entries, most recent loss) from the ring."""
    h = state["loss_hist"]
    n = h.shape[0]
    ptr = state["loss_ptr"]                       # next write slot
    seen = jnp.minimum(state["loss_seen"], n)
    k = jnp.clip(jnp.ceil(lb_avg).astype(jnp.int32), 1, seen)
    idx = (ptr - 1 - jnp.arange(n)) % n           # most recent first
    vals = h[idx]
    mask = (jnp.arange(n) < k).astype(jnp.float32)
    avg = jnp.sum(vals * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return avg, vals[0]


def _switch_tensor(ts: Dict[str, Array], w: Array, strategy: Array,
                   qcfg: QuantConfig) -> Dict[str, Array]:
    """PushDown + PushUp for one tensor (possibly per-layer-stacked)."""
    per_layer = bool(ts["wl"].shape)

    def one(w_slice, wl, fl, lb, res, count, norm_sum, gsum_norm, sp):
        should = count >= lb
        ds = pushup.gradient_diversity(norm_sum, gsum_norm)
        flat = pushdown.subsample(w_slice.reshape(-1).astype(jnp.float32),
                                  qcfg.edf_sample)
        wl_min, fl_min = pushdown.push_down(
            flat, res, r_upr=qcfg.r_upr, eps_kl=qcfg.eps_kl,
            max_wl=qcfg.max_wl, use_pallas=qcfg.use_pallas)
        wl_new, fl_new = pushup.push_up(
            wl_min, fl_min, ds, strategy, buff=qcfg.buff, max_wl=qcfg.max_wl)
        lb_new = pushup.adapt_lookback(lb, ds, lb_lwr=qcfg.lb_lwr,
                                       lb_upr=qcfg.lb_upr, gamma=qcfg.gamma)
        res_new = pushup.adapt_resolution(res, lb_new, lb_lwr=qcfg.lb_lwr,
                                          lb_upr=qcfg.lb_upr,
                                          r_lwr=qcfg.r_lwr, r_upr=qcfg.r_upr)
        # measure sparsity of the quantized-at-new-precision weights
        qw = fxp.quantize(flat, wl_new, fl_new, u=None)
        sp_new = fxp.sparsity(qw)
        pick = lambda a, b: jnp.where(should, a, b)
        return (pick(wl_new, wl), pick(fl_new, fl), pick(lb_new, lb),
                pick(res_new, res), pick(jnp.int32(0), count),
                pick(jnp.float32(0.0), norm_sum), pick(sp_new, sp))

    gsum = ts["grad_sum"].astype(jnp.float32)
    if per_layer:
        # one vmap over every (layer[, expert]) tensor of the stack
        lead = ts["wl"].shape
        flat = lambda a: a.reshape((-1,) + a.shape[len(lead):])
        axes = tuple(range(1, gsum.ndim - len(lead) + 1))
        gflat = flat(gsum)
        gsum_norm = jnp.sqrt(jnp.sum(gflat * gflat, axis=axes) + 1e-30)
        outs = jax.vmap(one)(flat(w), *(flat(ts[k]) for k in (
            "wl", "fl", "lb", "res", "count", "norm_sum")), gsum_norm,
            flat(ts["sp"]))
        outs = tuple(o.reshape(lead) for o in outs)
    else:
        gsum_norm = jnp.sqrt(jnp.sum(gsum * gsum) + 1e-30)
        outs = one(w, ts["wl"], ts["fl"], ts["lb"], ts["res"],
                   ts["count"], ts["norm_sum"], gsum_norm, ts["sp"])
    wl, fl, lb, res, count, norm_sum, sp = outs
    should = ts["count"] >= ts["lb"]
    bshape = should.shape + (1,) * (gsum.ndim - should.ndim)
    grad_sum = jnp.where(should.reshape(bshape), 0.0, gsum).astype(jnp.bfloat16)
    return {"wl": wl, "fl": fl, "lb": lb, "res": res, "count": count,
            "norm_sum": norm_sum, "grad_sum": grad_sum, "sp": sp}


def precision_switch(state: Dict[str, Any], params: PyTree,
                     qcfg: QuantConfig) -> Dict[str, Any]:
    """Alg. 2: AdaptStrategy, then per tensor Adapt{Lookback,Resolution} +
    PushDown + PushUp where the window is full."""
    with jax.named_scope("adapt.switch"):
        lb_avg = _avg_lookback(state)
        loss_avg, loss_now = _loss_stats(state, lb_avg)
        strategy = pushup.adapt_strategy(state["strategy"], loss_avg,
                                         loss_now)
        flat = dict((path_str(p), w) for p, w in
                    jax.tree_util.tree_flatten_with_path(params)[0])
        tensors = {
            path: _switch_tensor(ts, flat[path].astype(jnp.float32),
                                 strategy, qcfg)
            for path, ts in state["tensors"].items()
        }
    return {**state, "tensors": tensors, "strategy": strategy}


# ---------------------------------------------------------------------------
# Quantized copy for the forward pass (alg. 1 ln. 9-11)


def _leaf_key(key: Array, path: str) -> Array:
    # stable per-path fold; cheap non-cryptographic hash of the path string
    h = 0
    for ch in path:
        h = (h * 131 + ord(ch)) % (2 ** 31 - 1)
    return jax.random.fold_in(key, h)


def _leaf_seed(key: Array, path: str) -> Array:
    """int32 scalar seed for the in-kernel PRNG, derived from the per-leaf
    key so determinism-per-⟨step key, path⟩ is preserved."""
    return jax.random.randint(_leaf_key(key, path), (), 0, 2 ** 31 - 1,
                              jnp.int32)


def _use_fused_prng(qcfg: QuantConfig, key, wl: Array, leaf: Array,
                    sharding=None) -> bool:
    """True when ``leaf`` can take the 2-transfer in-kernel-PRNG quantize.
    All three dispatch regimes are served by ``kernels.ops``: scalar-⟨WL,FL⟩
    leaves hit ``sr_quantize_fused`` directly; per-layer-stacked leaves
    (wl of shape (L,)) hit the stacked kernel (leading per-layer grid dim,
    SMEM precision vector); explicitly-sharded leaves are wrapped in
    ``sharding.shard_map`` with per-shard folded seeds (pallas_call has no
    SPMD partitioning rule, so without the wrapper GSPMD would REPLICATE
    the kernel and all-gather the f32 master). Remaining exclusions:

    * round-to-nearest mode (no step key / stochastic_rounding off) — the
      fused kernel is an SR kernel; RTN stays on the deterministic XLA path;
    * placements that are not a NamedSharding (no mesh/spec to map);
    * sharded leaves whose sharded dims don't divide evenly over their mesh
      axes — shard_map needs equal blocks, so those keep the XLA
      noise+constraint path."""
    if not (qcfg.use_pallas and qcfg.fused_prng and qcfg.stochastic_rounding
            and key is not None):
        return False
    if wl.ndim > 1 or (wl.ndim == 1 and wl.shape[0] != leaf.shape[0]):
        return False
    if sharding is None:
        return True
    if not isinstance(sharding, NamedSharding):
        return False
    return shd.shard_grid(leaf.shape, sharding.spec, sharding.mesh) is not None


def quantize_params(params: PyTree, state: Dict[str, Any], qcfg: QuantConfig,
                    key: Array | None = None, dtype=jnp.float32,
                    shardings: PyTree | None = None) -> PyTree:
    """Return the quantized copy L̂ of the master params (grid values in a
    ``dtype`` container). Non-quantized leaves are passed through in
    ``dtype``.

    ``shardings``: optional NamedSharding tree (same structure as params).
    On the XLA path the SR noise is constrained to each tensor's sharding —
    without this GSPMD resolves (sharded master × replicated noise) by
    ALL-GATHERING the f32 master before quantizing (measured: the entire
    5.6 TiB/step arctic gather volume ran in f32 regardless of container
    dtype; §Perf). With ``use_pallas`` + ``fused_prng``, eligible leaves
    (see ``_use_fused_prng``) skip the noise tensor entirely — drawn inside
    the kernel, one fewer param-sized HBM round trip — including per-layer-
    stacked leaves (one stacked-kernel launch per leaf) and evenly-sharded
    leaves (shard_map-wrapped kernel, per-shard seeds, zero collectives).

    ``dtype=jnp.int8`` emits the native-int8 path: round(w·2^FL) lives as an
    int8 tensor in the graph (exact for WL≤8), dequantized to bf16 at the
    consumer — FSDP/TP weight movement happens on 1-byte payloads.
    """
    tensors = state["tensors"]
    int8 = dtype == jnp.int8
    out_dtype = jnp.bfloat16 if int8 else dtype
    flat_sh = None
    if shardings is not None:
        flat_sh = dict(
            (path_str(p), s) for p, s in
            jax.tree_util.tree_flatten_with_path(shardings)[0])

    def visit(path, leaf):
        p = path_str(path)
        if p not in tensors:
            return leaf.astype(out_dtype)
        ts = tensors[p]
        wl, fl = ts["wl"], ts["fl"]
        sh = flat_sh.get(p) if flat_sh is not None else None
        if _use_fused_prng(qcfg, key, wl, leaf, sh):
            # single-pass Pallas kernel, noise drawn in-register: the only
            # param-sized HBM traffic is leaf-in / quantized-out.
            seed = _leaf_seed(key, p)
            if int8:
                q8 = kops.sr_quantize_fused_int8(leaf, seed, fl,
                                                 use_pallas=True, sharding=sh)
                # exact 2^-FL (bf16-representable): bf16 exp2 is off by up
                # to ~3% and NOT a power of two — fixed_point.pow2i
                sc = fxp.pow2i(-fl).astype(jnp.bfloat16)
                if fl.shape:
                    sc = sc.reshape(fl.shape + (1,) * (leaf.ndim - fl.ndim))
                return q8.astype(jnp.bfloat16) * sc
            return kops.sr_quantize_fused(leaf, seed, wl, fl, use_pallas=True,
                                          sharding=sh).astype(out_dtype)
        if wl.shape:  # stacked: broadcast (L[, E]) -> (L[, E], 1, ...)
            bshape = wl.shape + (1,) * (leaf.ndim - wl.ndim)
            wl = wl.reshape(bshape)
            fl = fl.reshape(bshape)
        u = None
        if qcfg.stochastic_rounding and key is not None:
            u = fxp.uniform_noise_like(_leaf_key(key, p), leaf)
            if flat_sh is not None and p in flat_sh:
                u = jax.lax.with_sharding_constraint(u, flat_sh[p])
        if int8:
            scale = fxp.pow2i(fl)
            x = leaf.astype(jnp.float32) * scale
            q = fxp.stochastic_round(x, u) if u is not None else jnp.round(x)
            q = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)
            return q.astype(jnp.bfloat16) * fxp.pow2i(-fl).astype(jnp.bfloat16)
        return fxp.quantize(leaf, wl, fl, u=u).astype(out_dtype)

    return jax.tree_util.tree_map_with_path(visit, params)


# ---------------------------------------------------------------------------
# Packed int8 wire format (native_int8 / §Perf): the quantized copy travels
# the mesh as int8 + per-layer scale; dequant happens AFTER the per-layer
# FSDP gather (inside the scan body), so weight movement costs 1 byte/param
# instead of 4 (f32 container) — AdaPT's low-bit forward applied to the
# *interconnect*. Gradients route through a custom_vjp to a bf16 reference
# tensor that the forward never reads (so it is DCE'd — no extra traffic).


def quantize_params_packed(params: PyTree, state: Dict[str, Any],
                           qcfg: QuantConfig, key: Array | None = None,
                           shardings: PyTree | None = None) -> PyTree:
    """Lazy packed tree: quantized leaves become {"q8", "sc", "wref"} dicts
    (see fixed_point.PACKED_KEYS); consumers call fxp.unpack_tree AT the use
    site — inside the scanned layer body, after the per-layer gather — so
    weights cross the mesh as int8 (4× less than the f32 container).
    Differentiate w.r.t. this tree: cotangents land on each "wref"."""
    tensors = state["tensors"]
    flat_sh = None
    if shardings is not None:
        flat_sh = dict(
            (path_str(p), s) for p, s in
            jax.tree_util.tree_flatten_with_path(shardings)[0])

    def _sc_for(p, leaf, fl):
        """Dequant scale 2^-FL, shaped so the scan can slice it: per-layer
        (L,)-FL leaves get (L, 1, ...); a per-TENSOR ⟨WL,FL⟩ on a scanned
        leaf (e.g. an (L, nh) d_skip, too flat for per-layer treatment)
        still needs the leading scan dim — a bare scalar would crash
        lax.scan's leading-axis slicing."""
        sc = fxp.pow2i(-fl).astype(jnp.bfloat16)
        if fl.shape:
            return sc.reshape(fl.shape + (1,) * (leaf.ndim - fl.ndim))
        if is_stacked(p) and leaf.ndim >= 2:
            return jnp.broadcast_to(sc.reshape((1,) * leaf.ndim),
                                    (leaf.shape[0],) + (1,) * (leaf.ndim - 1))
        return sc

    def visit(path, leaf):
        p = path_str(path)
        if p not in tensors:
            return leaf.astype(jnp.bfloat16)
        ts = tensors[p]
        fl = ts["fl"]
        sh = flat_sh.get(p) if flat_sh is not None else None
        if (qcfg.use_pallas and fxp.is_dense_param(p) and sh is not None
                and len(sh.device_set) > 1 and not sh.is_fully_replicated):
            # The dense Pallas kernels have no SPMD partitioning rule: a
            # >1-device-sharded dense leaf fed to them would be silently
            # REPLICATED by GSPMD (all-gathering every operand into every
            # launch). Refuse loudly instead of regressing quietly — the
            # shard_map wrapper for the dense matmuls is the open ROADMAP
            # item; until then mesh runs keep use_pallas off.
            raise ValueError(
                f"quantize_params_packed: dense leaf '{p}' is sharded over "
                "a multi-device mesh while quant.use_pallas is on — the "
                "dense kernel path (models/common.dense → fxp kernels) "
                "cannot be partitioned by GSPMD and would replicate every "
                "launch. Disable quant.use_pallas for mesh runs (ROADMAP: "
                "shard_map wrapper for the dense matmul kernels).")
        if fl.ndim == 2 and sh is None and _use_fused_prng(
                qcfg, key, fl.reshape(-1), leaf.reshape(
                    (-1,) + leaf.shape[2:])):
            # an (L, E) expert stack: one launch of the stacked kernel over
            # its L·E matrices, each with its own FL
            q8 = kops.sr_quantize_fused_int8(
                leaf.reshape((-1,) + leaf.shape[2:]), _leaf_seed(key, p),
                fl.reshape(-1), use_pallas=True).reshape(leaf.shape)
            return {"q8": q8, "sc": _sc_for(p, leaf, fl),
                    "wref": jnp.zeros(leaf.shape, jnp.bfloat16)}
        if _use_fused_prng(qcfg, key, fl, leaf, sh):
            # in-kernel PRNG: the int8 words are produced in one pass with
            # no noise operand — the packed wire payload never sees f32.
            # Sharded leaves come back from the shard_map wrapper already
            # laid out on the mesh; only wref needs the constraint.
            q8 = kops.sr_quantize_fused_int8(leaf, _leaf_seed(key, p), fl,
                                             use_pallas=True, sharding=sh)
            sc = _sc_for(p, leaf, fl)
            wref = jnp.zeros(leaf.shape, jnp.bfloat16)
            if sh is not None:
                wref = jax.lax.with_sharding_constraint(wref, sh)
            return {"q8": q8, "sc": sc, "wref": wref}
        if fl.shape:
            fl = fl.reshape(fl.shape + (1,) * (leaf.ndim - fl.ndim))
        u = None
        if qcfg.stochastic_rounding and key is not None:
            u = fxp.uniform_noise_like(_leaf_key(key, p), leaf)
            if flat_sh is not None and p in flat_sh:
                u = jax.lax.with_sharding_constraint(u, flat_sh[p])
        scale = fxp.pow2i(fl)
        x = leaf.astype(jnp.float32) * scale
        q = fxp.stochastic_round(x, u) if u is not None else jnp.round(x)
        q8 = jnp.clip(q, -128.0, 127.0).astype(jnp.int8)
        sc = _sc_for(p, leaf, ts["fl"])
        wref = jnp.zeros(leaf.shape, jnp.bfloat16)
        if flat_sh is not None and p in flat_sh:
            q8 = jax.lax.with_sharding_constraint(q8, flat_sh[p])
            wref = jax.lax.with_sharding_constraint(wref, flat_sh[p])
        return {"q8": q8, "sc": sc, "wref": wref}

    return jax.tree_util.tree_map_with_path(visit, params)


def strip_packed_grads(grads: PyTree) -> PyTree:
    """Grad tree of a packed qparams tree → plain per-param grads. A
    packed dict's cotangent lives in its "wref" (q8 carries float0)."""
    return jax.tree_util.tree_map(
        lambda g: g["wref"] if fxp.is_packed(g) else g, grads,
        is_leaf=fxp.is_packed)


def clamp_adapt_state(state: Dict[str, Any], max_wl) -> Dict[str, Any]:
    """AdaBits-style (1912.09666) serve-time view of the controller state:
    every tensor's WL clamped to ``max_wl``, FL reduced by the same amount
    so the integer range (max|w| representability) is preserved and only
    fractional LSBs are dropped — the same set of master weights served at
    a coarser grid. Tensors already at or below ``max_wl`` are untouched.
    Returns a NEW state dict; the trained controller state is never
    mutated, and the result has the same pytree structure/dtypes as the
    input, so quantized copies produced from different clamp levels are
    structurally identical (swap without recompiling)."""
    max_wl = jnp.int32(max_wl)
    tensors = {}
    for path, ts in state["tensors"].items():
        wl = ts["wl"]
        new_wl = jnp.minimum(wl, max_wl)
        tensors[path] = {**ts, "wl": new_wl, "fl": ts["fl"] - (wl - new_wl)}
    return {**state, "tensors": tensors}


def snapshot(state: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Host-side summary {path: {wl, fl, sp, lb, res}} for logging and the
    paper's analytical performance model (eq. 6–9 need lb and r too)."""
    out = {}
    for path, ts in state["tensors"].items():
        out[path] = {
            "wl": jax.device_get(ts["wl"]),
            "fl": jax.device_get(ts["fl"]),
            "sp": jax.device_get(ts["sp"]),
            "lb": jax.device_get(ts["lb"]),
            "res": jax.device_get(ts["res"]),
        }
    return out
