"""Sharding rules / parameter specs / HLO cost walker."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.config import load_config
from repro.launch import mesh as mesh_lib
from repro.roofline import hlo_costs
from repro.roofline.analysis import roofline_terms


def test_shard_noop_without_rules():
    x = jnp.ones((4, 4))
    assert sharding.shard(x, "batch", None) is x
    assert sharding.axis_size("batch") == 1


def test_rules_resolve_specs():
    mesh = mesh_lib.make_cpu_mesh()
    with sharding.use_rules(mesh, {"batch": ("data",), "ff": ("model",)}):
        assert sharding.spec("batch", None, "ff") == P("data", None, "model")
        assert sharding.axis_size("batch") == 1   # cpu mesh is 1×1
        x = jnp.ones((4, 4))
        y = sharding.shard(x, "batch", "ff")
        assert y.shape == x.shape


def test_duplicate_mesh_axis_suppressed():
    mesh = mesh_lib.make_cpu_mesh()
    with sharding.use_rules(mesh, {"batch": ("data",), "seq": ("data",)}):
        # "data" may appear only once in a spec
        assert sharding.spec("batch", "seq") == P("data", None)


class _FakeMesh:
    """Shape-only stand-in so spec tests don't allocate 256 devices."""
    def __init__(self, shape):
        self.shape = shape


@pytest.mark.parametrize("arch", ["granite-8b", "mixtral-8x22b",
                                  "arctic-480b", "mamba2-780m"])
def test_param_pspec_rules(arch):
    cfg = load_config(arch)
    mesh = _FakeMesh({"data": 16, "model": 16})
    # column-parallel QKV / in_proj → last dim on model
    p = mesh_lib.param_pspec("blocks/s0_attn/wq", (36, 4096, 4096), cfg, mesh)
    assert p[-1] == "model"
    # row-parallel out-proj → contraction dim on model
    p = mesh_lib.param_pspec("blocks/s0_attn/wo", (36, 4096, 4096), cfg, mesh)
    assert p[-2] == "model"
    # vocab-sharded embedding
    p = mesh_lib.param_pspec("embed", (49152, 4096), cfg, mesh)
    assert p[0] == "model"
    # routers replicated
    p = mesh_lib.param_pspec("blocks/s0_moe/router", (35, 7168, 128), cfg,
                             mesh)
    assert all(x is None for x in p)


def test_param_pspec_moe_ep_vs_tp():
    mesh = _FakeMesh({"data": 16, "model": 16})
    arctic = load_config("arctic-480b")
    mixtral = load_config("mixtral-8x22b")
    # arctic: 128 experts % 16 == 0 → expert-parallel
    p = mesh_lib.param_pspec("blocks/s0_moe/we_gate", (35, 128, 7168, 4864),
                             arctic, mesh)
    assert p[1] == "model"
    # mixtral: 8 experts % 16 != 0 → TP on the ff dim instead
    p = mesh_lib.param_pspec("blocks/s0_moe/we_gate", (56, 8, 6144, 16384),
                             mixtral, mesh)
    assert p[1] is None and p[-1] == "model"
    # big tensors additionally fold the data axis (FSDP)
    assert "data" in tuple(p)


def test_param_pspec_divisibility_fallback():
    cfg = load_config("smollm-360m")
    mesh = _FakeMesh({"data": 16, "model": 16})
    # 15 heads × 64 = 960 divisible → projection still sharded
    p = mesh_lib.param_pspec("blocks/s0_attn/wq", (32, 960, 960), cfg, mesh)
    assert p[-1] == "model"
    # odd dims fall back to replication rather than failing
    p = mesh_lib.param_pspec("blocks/s0_attn/wq", (32, 7, 7), cfg, mesh)
    assert all(x is None for x in p)


def test_make_rules_head_divisibility():
    granite = load_config("granite-8b")
    smollm = load_config("smollm-360m")
    mesh = _FakeMesh({"data": 16, "model": 16})
    mesh.axis_names = ("data", "model")
    r = mesh_lib.make_rules(granite, mesh, "train")
    assert r["heads"] == ("model",)
    r = mesh_lib.make_rules(smollm, mesh, "train")
    assert r["heads"] == ()          # 15 % 16 — replicate (baseline)
    assert r["q_seq"] == ()          # off by default
    import dataclasses
    smollm2 = dataclasses.replace(
        smollm, mesh=dataclasses.replace(smollm.mesh, seq_shard_attn="auto"))
    r = mesh_lib.make_rules(smollm2, mesh, "train")
    assert r["q_seq"] == ("model",)  # hillclimb lever


def test_long_rules_shard_kv_seq():
    cfg = load_config("mamba2-780m", "long_500k")
    mesh = _FakeMesh({"data": 16, "model": 16})
    mesh.axis_names = ("data", "model")
    r = mesh_lib.make_rules(cfg, mesh, "long")
    assert r["batch"] == () and r["kv_seq"] == ("data",)


# ---------------------------------------------------------------------------
# HLO cost walker


def test_walker_counts_scan_trips():
    def scanned(x, ws):
        def b(h, w):
            return jnp.dot(h, w,
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype), None
        h, _ = jax.lax.scan(b, x, ws)
        return h

    x = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.bfloat16)
    c = jax.jit(scanned).lower(x, ws).compile()
    r = hlo_costs.module_costs(c.as_text())
    assert r["flops"] == pytest.approx(8 * 2 * 128 ** 3, rel=1e-6)
    assert r["dynamic_loops"] == 0


def test_walker_nested_loops():
    def nested(x):
        def outer(h, _):
            def inner(h2, _):
                return jnp.dot(h2, h2,
                               preferred_element_type=jnp.float32
                               ).astype(h2.dtype), None
            h, _ = jax.lax.scan(inner, h, None, length=4)
            return h, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(nested).lower(x).compile()
    r = hlo_costs.module_costs(c.as_text())
    assert r["flops"] == pytest.approx(12 * 2 * 64 ** 3, rel=1e-6)


def test_walker_xla_costanalysis_disagrees():
    """Documents WHY the walker exists: XLA counts loop bodies once."""
    def scanned(x, ws):
        def b(h, w):
            return jnp.dot(h, w,
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype), None
        h, _ = jax.lax.scan(b, x, ws)
        return h

    x = jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.bfloat16)
    c = jax.jit(scanned).lower(x, ws).compile()
    xla_flops = c.cost_analysis()["flops"]
    walker_flops = hlo_costs.module_costs(c.as_text())["flops"]
    # XLA reports ~1 loop body (plus small elementwise terms); the walker
    # counts all 8 trips of the matmul.
    assert walker_flops == pytest.approx(8 * 2 * 128 ** 3, rel=1e-6)
    assert xla_flops < walker_flops / 4


def test_roofline_terms_math():
    rec = {"cost": {"flops": 197e12, "bytes accessed": 819e9},
           "collectives": {"total": 50e9}}
    t = roofline_terms(rec)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)


def test_collective_shape_bytes():
    from repro.roofline.analysis import _shape_bytes
    assert _shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert _shape_bytes("(bf16[4,4], f32[2])") == 4 * 4 * 2 + 2 * 4
