"""Logical-axis sharding: model code annotates tensors with *logical* axes
("batch", "seq", "heads", "ff", "experts", "vocab", "embed"); the launcher
installs a rule set mapping logical → physical mesh axes for the current
(mesh × input-shape) combination. Outside any rule context every annotation
is a no-op, so models run unmodified on a single CPU device.

Rule sets (see launch/mesh.py):
  train/prefill/decode: batch → ("pod","data"), heads/ff/experts/vocab → "model"
  long-context decode:  seq(kv) → ("pod","data")  (batch=1 → shard the cache)
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array

_RULES: contextvars.ContextVar[Optional[Tuple[Mesh, Dict[str, tuple]]]] = \
    contextvars.ContextVar("repro_sharding_rules", default=None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Dict[str, tuple]):
    """rules: logical axis name -> tuple of mesh axis names (or ())."""
    token = _RULES.set((mesh, dict(rules)))
    try:
        yield
    finally:
        _RULES.reset(token)


def active() -> bool:
    return _RULES.get() is not None


def current_mesh() -> Optional[Mesh]:
    ctx = _RULES.get()
    return ctx[0] if ctx else None


def spec(*logical_axes: Optional[str]) -> Optional[P]:
    """PartitionSpec for a tensor whose dims carry these logical names."""
    ctx = _RULES.get()
    if ctx is None:
        return None
    _, rules = ctx
    parts = []
    used = set()
    for name in logical_axes:
        axes = rules.get(name, ()) if name else ()
        # a mesh axis may appear at most once in a spec
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
        else:
            parts.append(tuple(axes))
    return P(*parts)


def shard(x: Array, *logical_axes: Optional[str]) -> Array:
    """Annotate ``x`` (len(logical_axes) == x.ndim) if rules are active."""
    ctx = _RULES.get()
    if ctx is None:
        return x
    mesh, _ = ctx
    s = spec(*logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))


def named_sharding(*logical_axes: Optional[str]) -> Optional[NamedSharding]:
    ctx = _RULES.get()
    if ctx is None:
        return None
    mesh, _ = ctx
    return NamedSharding(mesh, spec(*logical_axes))


def shard_map(f, mesh: Mesh, *, axis_names, in_specs, out_specs,
              check: bool = False):
    """``jax.shard_map`` manual ONLY over ``axis_names`` (auto over the rest
    of the mesh)."""
    return jax.shard_map(f, mesh=mesh, axis_names=set(axis_names),
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=check)


def spec_dim_axes(spec, ndim: int) -> Tuple[tuple, ...]:
    """Per-dim tuples of mesh-axis names of a PartitionSpec, padded to
    ``ndim`` dims (PartitionSpecs may be shorter than the rank; missing and
    ``None`` entries mean replicated)."""
    entries = tuple(spec) if spec is not None else ()
    entries = entries[:ndim] + (None,) * (ndim - len(entries))
    return tuple(() if e is None else ((e,) if isinstance(e, str)
                                       else tuple(e)) for e in entries)


def shard_grid(shape, spec, mesh: Mesh) -> Optional[Tuple[int, ...]]:
    """Per-dim shard counts of an array of ``shape`` under (spec, mesh), or
    None when a sharded dim does not divide evenly over its mesh axes —
    shard_map needs equal blocks, so uneven leaves are ineligible for the
    shard_map-wrapped kernels."""
    grid = []
    for d, axes in enumerate(spec_dim_axes(spec, len(shape))):
        k = 1
        for a in axes:
            k *= mesh.shape[a]
        if shape[d] % k:
            return None
        grid.append(k)
    return tuple(grid)


def strip_axes(rules: Dict[str, tuple], axes) -> Dict[str, tuple]:
    """Rules with the given mesh axes removed (e.g. inside a shard_map that
    is manual over 'pod', constraints may only name auto axes)."""
    out = {}
    for k, v in rules.items():
        out[k] = tuple(a for a in v if a not in axes) \
            if isinstance(v, tuple) else v
    return out


def flag(name: str):
    """Read an out-of-band flag stashed in the rules dict (keys starting
    with '#'); None outside a rules context. Used for mesh-dependent compute
    policies (e.g. '#tp_reduce_bf16') that model code must see at trace
    time without threading config through every layer call."""
    ctx = _RULES.get()
    if ctx is None:
        return None
    return ctx[1].get(name)


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 outside rules).
    Model code uses this to pick shard-aligned internal layouts (e.g. the
    MoE group-limited dispatch groups)."""
    ctx = _RULES.get()
    if ctx is None:
        return 1
    mesh, rules = ctx
    n = 1
    for a in rules.get(logical, ()):
        n *= mesh.shape[a]
    return n
