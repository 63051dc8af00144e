"""The MoE training cell: its real train step, built as ``bench/moe_train.py``
builds it and compiled for a described TPU v5e, fits one chip; the work
arithmetic of ``bench/work/moe_decoder.py`` against hand counts; and its
shares read 100% and no more for a run at the chip's peaks.

(``test_bench_fit.py`` builds every cell through ``lm_train``, so for this
cell it compiles a dense stand-in; the fit of the real step is here.)
"""
import importlib.util
import json
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import trace as tr
from conftest import ROOT

CELL = "mellum2-12b-4l.train-s8k"
HBM_GIB = 15.75          # what a v5e chip gives a program


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


work = _load("bench/work/moe_decoder.py", "bench_work_moe_decoder")


def _cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {c["name"]: c for c in bench["workloads"]}[CELL]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg = json.loads((ROOT / files[w["config"]]).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
    return cfg, traffic


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def for_the_chip():
    """Steer the kernels to Mosaic, and keep the persistent cache off: a
    described-topology compile cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import ops
    prev_tpu, prev_cache = ops._on_tpu, jax.config.jax_enable_compilation_cache
    ops._on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    ops._on_tpu = prev_tpu
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


def test_real_step_fits_one_chip(topo, for_the_chip):
    from bench import moe_train
    from repro.train import train_loop

    cfg, traffic = _cell()
    pcfg = moe_train.program_config(cfg, traffic)
    assert pcfg.model.experts_held == 32 and pcfg.model.num_experts == 64
    one = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    state = jax.eval_shape(lambda: train_loop.init_state(pcfg))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["global_batch"], traffic["seq_len"]), jnp.int32)}
    step = jax.jit(train_loop.make_train_step(pcfg), donate_argnums=0)
    compiled = step.lower(placed(state), placed(batch)).compile()
    text = compiled.as_text()
    for kernel in ("fxp_gmm", "gmm_dx", "gmm_dw", "flash_attention"):
        assert kernel in text, kernel
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 2 ** 30
    assert used <= HBM_GIB, f"{CELL}: {used:.2f} GiB on one chip"


def test_work_matches_hand_counts():
    """Mellum2's cut at seq 8192, global batch 2 (16,384 tokens):
    attention projections 2304 x 4096 x 2 + 2304 x 512 x 2 = 21,233,664
    per layer, x4, and the head 2304 x 12288 = 28,311,552; 32 held experts
    of 3 x 2304 x 896 = 198,180,864 per layer; held rows 16,384 x 8 x 32 /
    64 = 65,536 per layer; attention pairs 8192^2 / 2 on the full layer,
    8192 x 1024 - 1024^2 / 2 on each windowed one. Forward TFLOP:
    projections 2.78, head 0.93, experts 3.25, attention 1.10 + 0.77."""
    cfg, traffic = _cell()
    assert sum(k * n * c for _, k, n, c in work.dense_shapes(cfg)) == \
        4 * 21233664 + 28311552
    tokens = traffic["global_batch"] * traffic["seq_len"]
    assert work.held_rows(cfg, tokens) == 65536
    w = work.step_work(cfg, traffic["global_batch"], traffic["seq_len"])
    assert w["dense_int8_ops"] == 6 * tokens * (4 * 21233664 + 28311552)
    assert w["expert_int8_ops"] == 6 * 65536 * (3 * 2304 * 896) * 4
    pairs = 8192 ** 2 / 2 + 3 * (8192 * 1024 - 1024 ** 2 / 2)
    assert w["attention_bf16_flops"] == 6 * 2 * 2 * 32 * 128 * pairs
    fwd = {"proj": 2 * tokens * 4 * 21233664, "head": 2 * tokens * 28311552,
           "experts": 2 * 65536 * 3 * 2304 * 896 * 4,
           "full": 2 * 2 * 2 * 32 * 128 * 8192 ** 2 / 2,
           "windowed": 2 * 2 * 2 * 32 * 128 * 3 * (8192 * 1024 - 1024 ** 2
                                                    / 2)}
    got = {k: round(v / 1e12, 2) for k, v in fwd.items()}
    assert got == {"proj": 2.78, "head": 0.93, "experts": 3.25,
                   "full": 1.1, "windowed": 0.77}
    # each expert pass reads every held expert's words once
    (ops, fwd_b), (_, dx_b), (_, dw_b) = work.expert_passes(cfg, 10)[:3]
    assert ops == 2 * 10 * 2304 * 896
    assert fwd_b == dx_b == (10 * 2304 + 10 * 896) * 2 + 32 * 2304 * 896
    assert dw_b == (10 * 2304 + 10 * 896) * 2 + 32 * 2304 * 896 * 2


def test_no_share_exceeds_100_at_the_peak():
    """A run whose dense, grouped and flash kernels each take exactly their
    least time, back to back, reads 100% on every share; twice as slow
    reads half."""
    cfg, traffic = _cell()
    p = json.loads((ROOT / "bench" / "peaks.json").read_text()
                   )["devices"]["TPU v5 lite"]
    B, S = traffic["global_batch"], traffic["seq_len"]
    steps = 2
    int8, hbm = p["int8_ops_per_s"], p["hbm_bytes_per_s"]
    dense_s = steps * work.least_time(work.dense_passes(cfg, B * S), int8,
                                      hbm)
    gmm_s = steps * work.least_time(
        work.expert_passes(cfg, work.held_rows(cfg, B * S)), int8, hbm)
    attn_s = steps * work.least_time(work.attention_passes(cfg, B, S),
                                     p["bf16_flops_per_s"], hbm)

    def ctx(scale):
        t = [0.0]

        def op(name, s):
            t[0] += scale * s
            return tr.Op(name, t[0] - scale * s, t[0], True)

        dev = tr.Device(ops=[op("fxp_matmul", dense_s), op("fxp_gmm", gmm_s),
                             op("flash_attention", attn_s)])
        return SimpleNamespace(trace=tr.Trace({0: dev}, []),
                               window=(0.0, t[0]), devices=[dev], cfg=cfg,
                               traffic=traffic, peaks=p, work=work,
                               steps=steps, switches=0, chips=1, notes=[])

    for metric in ("fxp_dense.roofline", "flash_attention.roofline",
                   "moe_grouped.roofline", "moe_step.mfu"):
        mod = _load(f"bench/metrics/{metric}.py",
                    "m_" + re.sub(r"\W", "_", metric))
        assert mod.read(ctx(1.0)) == pytest.approx(100.0, rel=1e-9), metric
        assert mod.read(ctx(2.0)) == pytest.approx(50.0, rel=1e-9), metric


def test_readers_find_nothing_in_a_dense_cell():
    """The MoE readers return nothing, and raise nothing, for a cell whose
    family has no experts."""
    dense = _load("bench/work/dense_decoder.py", "bench_work_dense_decoder")
    ctx = SimpleNamespace(work=dense, steps=2, window=(0.0, 1.0))
    for metric in ("moe_grouped.roofline", "moe_step.mfu"):
        mod = _load(f"bench/metrics/{metric}.py",
                    "m_" + re.sub(r"\W", "_", metric))
        assert mod.read(ctx) is None
