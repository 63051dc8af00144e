"""Tiny jaxpr structure readers shared by the structural tests and the
quant microbenchmark — the fast-path perf claims ("no materialized noise
operand", "no scatter-add histograms", "≤2 param-sized kernel operands")
are read off the traced program, so they hold on any backend.
"""
from __future__ import annotations

from jax.extend.core import ClosedJaxpr, Jaxpr

# RNG primitives whose param-sized outputs would mean a materialized
# noise tensor (jax.random.uniform lowers to these under jit).
RNG_PRIMS = ("threefry", "random_bits", "random_seed", "random_wrap")


def subjaxprs(v):
    """All jaxprs nested inside one eqn-params value."""
    if isinstance(v, ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [s for x in v for s in subjaxprs(x)]
    return []


def iter_eqns(jaxpr):
    """Depth-first over every eqn, descending into sub-jaxprs (scan/cond/
    pjit/custom_vjp bodies and anything else carried in eqn params)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                yield from iter_eqns(sub)


def rng_eqns_of_size(jaxpr, min_size: int):
    """RNG eqns producing an output of ≥ min_size elements."""
    return [eqn for eqn in iter_eqns(jaxpr)
            if any(r in eqn.primitive.name for r in RNG_PRIMS)
            and any(getattr(ov.aval, "size", 0) >= min_size
                    for ov in eqn.outvars)]


def count_primitives(jaxpr, name_substr: str) -> int:
    return sum(name_substr in eqn.primitive.name for eqn in iter_eqns(jaxpr))


def pallas_eqns(jaxpr):
    """Every pallas_call eqn, descending into scan/cond/pjit/custom-vjp
    bodies — the raw material for "no silent XLA fallback" assertions."""
    return [eqn for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def pallas_kernel_names(jaxpr):
    """Kernel-function name per pallas_call eqn (e.g. '_flash_kernel',
    '_flash_dq_kernel'): the call's explicit ``name`` if one was given,
    else the traced kernel body's function name."""
    return [eqn.params.get("name")
            or eqn.params["jaxpr"].debug_info.func_name
            for eqn in pallas_eqns(jaxpr)]


def count_pallas_calls(jaxpr, name_substr: str = "") -> int:
    """pallas_call eqns whose kernel name contains ``name_substr`` ('' =
    all). The structural contract behind quant.use_pallas: the jitted,
    DIFFERENTIATED forward must contain the expected forward and backward
    kernels — a silent fallback to XLA shows up here as a zero."""
    return sum(name_substr in n for n in pallas_kernel_names(jaxpr))


def pallas_grids(jaxpr):
    """Grid tuple per pallas_call eqn (same order as ``pallas_eqns``).
    Backs the VMEM-boundedness assertions: a tail-masked kernel on a
    prime dim must show a MULTI-block grid (``pl.cdiv`` of the clamp),
    never a whole-dim single block."""
    return [tuple(eqn.params["grid_mapping"].grid)
            for eqn in pallas_eqns(jaxpr)]


def pallas_block_shapes(jaxpr):
    """Per pallas_call eqn, each operand's block shape (inputs then
    outputs, same order as ``pallas_eqns``). With tail masking the chosen
    block must equal min(requested, dim) — reading it off the traced
    program pins the no-whole-dim-fallback contract on any backend."""
    return [[tuple(getattr(b, "block_size", b) for b in bm.block_shape)
             for bm in eqn.params["grid_mapping"].block_mappings]
            for eqn in pallas_eqns(jaxpr)]


def iter_xla_eqns(jaxpr):
    """Like ``iter_eqns`` but does NOT descend into pallas_call bodies —
    the view of what XLA itself executes (a kernel's in-register
    dot_general on a whole-dim block is not an XLA matmul)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                yield from iter_xla_eqns(sub)


def dot_general_shapes(jaxpr):
    """(lhs shape, rhs shape, rhs dtype) per XLA dot_general eqn
    (descending into scan/cond/pjit/custom-vjp bodies but not into Pallas
    kernels). Backs the dense-path contract: with the fxp kernels wired
    into models/common.dense, NO dot_general in the differentiated train
    step may consume a float operand of a dense weight's shape — a
    dequantized HBM weight copy shows up here as a (K, N)-shaped f32/bf16
    rhs (tests/test_dense_path.py)."""
    out = []
    for eqn in iter_xla_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        out.append((tuple(lhs.shape), tuple(rhs.shape), rhs.dtype))
    return out


# Gather-shaped collectives whose param-sized outputs would mean the f32
# master (or its quantized copy) is being reassembled across the mesh —
# exactly what the shard_map-wrapped quantize exists to prevent. psum/
# pmean are deliberately absent: scalar reductions are fine.
COLLECTIVE_PRIMS = ("all_gather", "all_to_all")


def collective_eqns_of_size(jaxpr, min_size: int):
    """Gather-type collective eqns producing an output of ≥ min_size
    elements (descends into shard_map/pjit bodies via iter_eqns)."""
    return [eqn for eqn in iter_eqns(jaxpr)
            if any(p in eqn.primitive.name for p in COLLECTIVE_PRIMS)
            and any(getattr(ov.aval, "size", 0) >= min_size
                    for ov in eqn.outvars)]
