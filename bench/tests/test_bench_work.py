"""The work and peak arithmetic of the benchmark, against hand counts."""
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import trace as tr  # noqa: E402


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


work = _load("bench/work/dense_decoder.py", "bench_work_dense_decoder")
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256, "tie_word_embeddings": False}


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# (configuration, batch, seq, dense matrix parameters, dense ops, attention
# FLOPs), counted by hand:
#   tiny:  per layer 64*64*2 + 64*32*2 + 64*128*3 = 36864, x2 layers, head
#          64*256 = 16384 -> 90112; attention 6 B H S^2 D per layer.
#   smollm-360m: 960*960*2 + 960*320*2 + 960*2560*3 = 9830400 per layer,
#          x32 = 314572800, head 960*49152 = 47185920 -> 361758720.
#   granite-8b-4l: 4096*4096*2 + 4096*1024*2 + 4096*14336*3 = 218103808,
#          x4 = 872415232, head 4096*12288 = 50331648 -> 922746880.
HAND = [
    (TINY, 4, 64, 90112, 6 * 90112 * 256, 6 * 4 * 4 * 64 ** 2 * 16 * 2),
    ("smollm-360m", 8, 2048, 361758720, 6 * 361758720 * 16384,
     6 * 8 * 15 * 2048 ** 2 * 64 * 32),
    ("granite-8b-4l", 4, 4096, 922746880, 6 * 922746880 * 16384,
     6 * 4 * 32 * 4096 ** 2 * 128 * 4),
]


@pytest.mark.parametrize("cfg,batch,seq,params,dense,attn", HAND,
                         ids=["tiny", "smollm-360m", "granite-8b-4l"])
def test_step_work_matches_hand_counts(cfg, batch, seq, params, dense, attn):
    cfg = _cfg(cfg) if isinstance(cfg, str) else cfg
    assert sum(k * n * c for _, k, n, c in work.dense_shapes(cfg)) == params
    w = work.step_work(cfg, batch, seq)
    assert w["dense_int8_ops"] == dense
    assert w["attention_bf16_flops"] == attn


def test_smollm_step_work_in_tera_operations():
    """35.6 T dense operations and 6.2 TFLOP of attention per step."""
    w = work.step_work(_cfg("smollm-360m"), 8, 2048)
    assert round(w["dense_int8_ops"] / 1e12, 1) == 35.6
    assert round(w["attention_bf16_flops"] / 1e12, 1) == 6.2


def test_bytes_count_each_operand_once():
    (ops, fwd), (_, dx), (_, dw) = work.dense_passes(
        {**TINY, "num_hidden_layers": 1}, 10)[:3]
    assert ops == 2 * 10 * 64 * 64
    assert fwd == (10 * 64 + 10 * 64) * 2 + 64 * 64
    assert dx == fwd
    assert dw == (10 * 64 + 10 * 64 + 64 * 64) * 2


def test_unknown_device_kind_raises():
    run = _load("bench/run.py", "bench_run_for_peaks")
    assert run.device_peaks(ROOT, "TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        run.device_peaks(ROOT, "TPU v9 imaginary")


def _ctx(cfg, traffic, dense_s, attn_s, window_s, steps=2, chips=1):
    """A trace in which the dense and attention kernels take exactly the
    given seconds, one after the other, inside a window of ``window_s``."""
    dev = tr.Device(ops=[tr.Op("fxp_matmul", 0.0, dense_s, True),
                         tr.Op("flash_attention", dense_s, dense_s + attn_s,
                               True)])
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text()
                       )["devices"]["TPU v5 lite"]
    return SimpleNamespace(trace=tr.Trace({0: dev}, []),
                           window=(0.0, window_s), devices=[dev] * chips,
                           cfg=cfg, traffic=traffic, peaks=peaks, work=work,
                           steps=steps, switches=0, chips=chips, notes=[])


@pytest.mark.parametrize("name,batch,seq", [("smollm-360m", 8, 2048),
                                            ("granite-8b-4l", 4, 4096)])
def test_no_share_exceeds_100_at_the_peak(name, batch, seq):
    """An implementation that runs every pass at the chip's peak reads 100%
    and no more, on every share."""
    cfg = _cfg(name)
    traffic = {"global_batch": batch, "seq_len": seq}
    p = json.loads((ROOT / "bench" / "peaks.json").read_text()
                   )["devices"]["TPU v5 lite"]
    steps = 2
    dense_s = steps * work.least_time(work.dense_passes(cfg, batch * seq),
                                      p["int8_ops_per_s"],
                                      p["hbm_bytes_per_s"])
    attn_s = steps * work.least_time(work.attention_passes(cfg, batch, seq),
                                     p["bf16_flops_per_s"],
                                     p["hbm_bytes_per_s"])
    ideal = dense_s + attn_s
    ctx = _ctx(cfg, traffic, dense_s, attn_s, ideal, steps)
    for metric in ("fxp_dense.roofline", "flash_attention.roofline",
                   "step.mfu"):
        mod = _load(f"bench/metrics/{metric}.py",
                    "m_" + re.sub(r"\W", "_", metric))
        value = mod.read(ctx)
        assert value == pytest.approx(100.0, rel=1e-9), metric
    # twice as slow reads half
    slow = _ctx(cfg, traffic, 2 * dense_s, 2 * attn_s, 2 * ideal, steps)
    mod = _load("bench/metrics/fxp_dense.roofline.py", "m_dense_slow")
    assert mod.read(slow) == pytest.approx(50.0, rel=1e-9)
