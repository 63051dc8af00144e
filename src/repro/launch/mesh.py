"""Production mesh + logical-axis rule sets + parameter shardings.

Mesh (per task spec):
    single-pod: (16, 16)      axes ("data", "model")        — 256 chips
    multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Rule sets map the model code's logical axes (see repro/sharding.py) to mesh
axes per input-shape kind:
    train / prefill / decode: batch→(pod,data), heads/ff/experts/vocab→model
    long-context decode (batch=1): the KV-cache *sequence* axis takes the
    data axis instead (you cannot shard a batch of 1).

Parameter shardings are name-based (megatron TP): column-parallel in-proj,
row-parallel out-proj, vocab-sharded embedding/head, expert-parallel MoE.
Tensors bigger than ``FSDP_THRESHOLD`` elements additionally fold the data
axis into a free dimension (2-D weight sharding) — without this the ≥100B
configs (arctic-480b, mixtral-8x22b) cannot fit HBM; XLA re-gathers one
scanned layer at a time inside the loop, which is exactly the FSDP schedule.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.config import Config

# elements; ~256 MiB in bf16. Above this a weight also shards over "data".
FSDP_THRESHOLD = 128 * 1024 * 1024


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> Mesh:
    """Mesh with Auto axes: the model code's logical-axis constraints
    (``sharding.shard``) are GSPMD hints, which only Auto axes accept —
    ``jax.make_mesh`` defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh() -> Mesh:
    """1-device mesh with the same axis names (tests / local smoke)."""
    return make_mesh((1, 1), ("data", "model"))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_rules(cfg: Config, mesh: Mesh, kind: str) -> Dict[str, tuple]:
    """Logical→physical rules for activations inside the model code."""
    dp = dp_axes(mesh)
    e = cfg.model.num_experts
    msize = mesh.shape["model"]
    expert_parallel = e > 0 and e % msize == 0
    heads_ok = _div(cfg.model.num_heads, msize)
    ssa = cfg.mesh.seq_shard_attn
    q_seq = ("model",) if (ssa == "on" or (ssa == "auto" and not heads_ok)) \
        else ()
    pad_heads = 0
    if ssa == "pad" and not heads_ok:
        # pad q/k/v heads up to the next model-axis multiple inside the
        # attention einsums: ≤(pad/H) extra FLOPs, but fully head-sharded —
        # avoids both replication AND the q-seq resharding cliffs (§Perf).
        pad_heads = ((cfg.model.num_heads + msize - 1) // msize) * msize
        q_seq = ()
    rules = {
        "batch": dp,
        "seq": (),
        "q_seq": q_seq,
        "heads": ("model",) if (heads_ok or pad_heads) else (),
        "#pad_heads_to": pad_heads or None,
        "kv_heads": ("model",) if _div(cfg.model.num_kv_heads, msize) else (),
        "ff": () if expert_parallel else ("model",),
        "experts": ("model",) if expert_parallel else (),
        "vocab": ("model",),
        "embed": (),
    }
    rules.setdefault("kv_seq", ())
    if kind == "decode" and cfg.mesh.decode_kv_shard == "seq" and \
            not _div(cfg.model.num_kv_heads, msize):
        # split-KV decode: cache sequence carries the model axis; heads
        # stay local (only softmax stats / 1-token outputs cross chips)
        rules["kv_seq"] = ("model",)
        rules["heads"] = ()
    if kind == "long":
        # batch=1: shard the KV/sequence axis over data instead
        rules["batch"] = ()
        rules["kv_seq"] = dp
    if cfg.train.tp_reduce_dtype == "bfloat16":
        rules["#tp_reduce_bf16"] = True
    return rules


def _div(n: int, k: int) -> bool:
    return n > 0 and n % k == 0


# ---------------------------------------------------------------------------
# Parameter shardings (name-based)


def _fits(shape, dim: int, n: int) -> bool:
    """Shard ``dim`` over an axis of size ``n``? Never over a size-1 axis:
    it splits nothing, and naming it would still move the leaf onto the
    shard_map-wrapped quantize with a folded per-shard seed — a different
    SR stream from the same weights on one device."""
    return n > 1 and shape[dim] % n == 0 and shape[dim] >= n


def param_pspec(path: str, shape: Tuple[int, ...], cfg: Config, mesh: Mesh,
                *, fsdp: Optional[bool] = None) -> P:
    """PartitionSpec for one parameter tensor.

    ``fsdp=None`` folds the data axis in automatically for huge tensors;
    True/False force it (the ZeRO master-shard flag / dry-run ablations).
    """
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    name = path.split("/")[-1]
    parts: list = [None] * len(shape)

    def col(dim):   # shard output/column dim over model
        if _fits(shape, dim, msize):
            parts[dim] = "model"

    e = cfg.model.num_experts
    expert_parallel = e > 0 and e % msize == 0

    if name == "embed":
        col(0)                                   # vocab rows
    elif name == "head":
        col(len(shape) - 1)                      # vocab cols
    elif name in ("wk", "wv"):
        # when kv heads don't divide the TP degree the (S, hkv·dh)→
        # (S, hkv, dh) reshape cannot keep a col-sharding and the K/V
        # activations get all-gathered every layer (~30 GiB/step on
        # granite-8b, kv=8 on 16-way — §Perf h3/h4). kv_proj="replicate"
        # keeps the small wk/wv replicated instead (no gathers, redundant
        # kv-proj compute).
        if _fits((cfg.model.num_kv_heads,), 0, msize) or \
                cfg.mesh.kv_proj != "replicate":
            col(len(shape) - 1)
    elif name in ("wq", "wi_gate", "wi_up", "in_proj"):
        col(len(shape) - 1)
    elif name in ("wo", "out_proj"):
        col(len(shape) - 2)                      # row-parallel (contraction)
    elif name == "conv_w":
        col(len(shape) - 1)                      # depthwise channels
    elif name in ("we_gate", "we_up", "we_down"):
        edim = len(shape) - 3
        if expert_parallel:
            parts[edim] = "model"
        else:                                    # TP inside each expert
            fdim = (len(shape) - 1 if name != "we_down" else len(shape) - 2)
            col(fdim)
    elif name == "router" or len(shape) < 2:
        pass                                     # replicated
    elif name == "w" and len(shape) == 4:
        pass                                     # conv kernels (CNN): DP only
    elif name == "w":
        col(len(shape) - 1)

    size = int(np.prod(shape))
    want_fsdp = fsdp if fsdp is not None else size >= FSDP_THRESHOLD
    if want_fsdp:
        for dim in range(len(shape) - 1, -1, -1):
            if parts[dim] is None and _fits(shape, dim, dsize) and \
                    shape[dim] >= dsize:
                parts[dim] = "data"
                break
    return P(*parts)


def state_shardings(state_shapes, cfg: Config, mesh: Mesh, *,
                    zero: Optional[bool] = None):
    """NamedShardings for the full train-state pytree (params + opt + adapt).

    ``zero`` controls data-axis folding for master/opt/adapt tensors
    (defaults to cfg.train.zero_shard or automatic-by-size)."""
    from repro.core.controller import path_str
    if zero is None:
        zero = {"auto": None, "on": True, "off": False}.get(
            cfg.train.fsdp, None)
        if cfg.train.zero_shard:
            zero = True

    def visit(path, leaf):
        p = path_str(path)
        shape = leaf.shape
        if not shape:
            return NamedSharding(mesh, P())
        if p.startswith("params/") or p.startswith("stats/"):
            spec = param_pspec(p.split("/", 1)[1], shape, cfg, mesh, fsdp=zero)
        elif p.startswith("opt/m/") or p.startswith("opt/v/") or \
                p.startswith("opt/mom/"):
            spec = param_pspec(p.split("/", 2)[2], shape, cfg, mesh, fsdp=zero)
        elif p.startswith("adapt/tensors/") and p.endswith("/grad_sum"):
            tensor_path = p[len("adapt/tensors/"):-len("/grad_sum")]
            spec = param_pspec(tensor_path, shape, cfg, mesh, fsdp=zero)
        else:
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(visit, state_shapes)


def packed_slice_specs(param_shapes, cfg: Config, mesh: Mesh) -> Dict:
    """TP-only NamedShardings for the PER-PERIOD slice of each stacked
    weight (leading period dim dropped) + full specs for unstacked tensors.
    Consumed by fxp.unpack_tree via the '#packed_slice_specs' rules flag to
    pin int8 weight gathers (see that docstring)."""
    from repro.core.controller import is_stacked, path_str
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        p = path_str(path)
        if len(leaf.shape) < 2:
            continue
        if is_stacked(p) and len(leaf.shape) >= 3:
            spec = param_pspec(p, leaf.shape[1:], cfg, mesh, fsdp=False)
            key = p.split("/", 1)[1]          # body sees paths sans "blocks/"
        else:
            spec = param_pspec(p, leaf.shape, cfg, mesh, fsdp=False)
            key = p
        out[key] = NamedSharding(mesh, spec)
    return out


def param_shardings(param_shapes, cfg: Config, mesh: Mesh, *,
                    fsdp: Optional[bool] = None):
    """NamedShardings for a bare parameter pytree (serving / dry-run)."""
    from repro.core.controller import path_str

    def visit(path, leaf):
        if not leaf.shape:
            return NamedSharding(mesh, P())
        return NamedSharding(
            mesh, param_pspec(path_str(path), leaf.shape, cfg, mesh, fsdp=fsdp))

    return jax.tree_util.tree_map_with_path(visit, param_shapes)


def batch_shardings(batch_shapes, mesh: Mesh, kind: str = "train"):
    dp = dp_axes(mesh)
    spec_dp = dp if len(dp) > 1 else (dp[0] if dp else None)

    def visit(leaf):
        parts = [None] * len(leaf.shape)
        if parts:
            parts[0] = spec_dp
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(visit, batch_shapes)


def cache_shardings(cache_shapes, cfg: Config, mesh: Mesh, kind: str):
    """Decode caches: (NP, B, C, H, D) — batch over data (decode) or cache
    seq over data (long, batch=1); kv heads over model when divisible."""
    msize = mesh.shape["model"]
    dp = dp_axes(mesh)
    spec_dp = dp if len(dp) > 1 else (dp[0] if dp else None)

    split_kv = cfg.mesh.decode_kv_shard == "seq"

    def visit(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        shape = leaf.shape
        parts: list = [None] * len(shape)
        if name in ("k", "v") and len(shape) == 5:
            NPd, B, C, H, D = shape
            if kind == "long" and B == 1:
                if C % max(_n(dp_size(mesh)), 1) == 0:
                    parts[2] = spec_dp
            else:
                parts[1] = spec_dp
            if H % msize == 0:
                parts[3] = "model"
            elif split_kv and C % msize == 0:
                # split-KV decode: kv heads can't shard → shard the cache
                # sequence over model; attention reduces per-head softmax
                # stats instead of gathering the cache (§Perf lever)
                parts[2] = "model"
        elif name == "conv" and len(shape) == 4:     # (NP,B,K,C)
            if kind != "long":
                parts[1] = spec_dp
            if shape[3] % msize == 0:
                parts[3] = "model"
        elif name == "ssm" and len(shape) == 5:      # (NP,B,H,P,N)
            if kind != "long":
                parts[1] = spec_dp
            if shape[2] % msize == 0:
                parts[2] = "model"
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(visit, cache_shapes)


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _n(x: int) -> int:
    return x


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
