"""The trace reduction, on a trace recorded on a TPU v5e and trimmed.

``data/v5e_smollm_switch.xplane.pb``: the traced run of
``smollm-360m.train-s2k`` cut to the 219 ms around the one precision switch
(the end of one train step, the switch, the start of the next): the
device plane's ``XLA Modules`` and ``XLA Ops`` lines and the host's
``bench.*`` spans, with event stats dropped.
"""
import re
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "v5e_smollm_switch.xplane.pb"
DENSE = re.compile(r"fxp_q?matmul|matmul_d[xw]|int8_matmul")


@pytest.fixture(scope="module")
def recorded():
    t = tr.load(str(DATA))
    dev = t.devices[0]
    return t, dev, (min(o.start for o in dev.ops), max(o.end for o in dev.ops))


def _plain(pattern=None, kernels_only=False):
    """The same sums by a plain pass over the profiler's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    total, n = 0.0, 0
    for e in line.events:
        name = e.name.split(" = ")[0].lstrip("%")
        if kernels_only and " custom-call(" not in e.name:
            continue
        if pattern is None or pattern.search(name):
            total += e.duration_ns * 1e-9
            n += 1
    return total, n


def test_planes_and_names(recorded):
    t, dev, _ = recorded
    assert list(t.devices) == [0]
    assert {m[0] for m in dev.modules} >= {"jit_train_step",
                                           "jit_bench_precision_switch"}
    bases = {o.base for o in dev.ops}
    assert {"fxp_matmul", "matmul_dw", "flash_attention",
            "vmap_jit_edf_ladder_hists__"} <= bases
    assert "while" not in bases           # loops enclose, they are not ops
    assert t.span("bench.window") is not None


def test_switch_program_time(recorded):
    _, dev, window = recorded
    runs = tr.module_runs(dev, "bench_precision_switch", window)
    assert runs == [pytest.approx(0.138995287, abs=1e-9)]


def test_kernel_time_matches_a_plain_pass(recorded):
    _, dev, window = recorded
    got = tr.kernel_time(dev, DENSE, window)
    want = _plain(DENSE, kernels_only=True)
    assert got[1] == want[1] == 27
    assert got[0] == pytest.approx(want[0], rel=1e-9)
    assert got[0] == pytest.approx(0.03392755, rel=1e-6)
    flash = tr.kernel_time(dev, re.compile("flash_attention"), window)
    assert flash[1] == 3


def test_busy_and_idle(recorded):
    t, dev, window = recorded
    busy = tr.busy(dev, window)
    assert 0 < busy <= window[1] - window[0]
    assert busy == pytest.approx(0.218971943, rel=1e-6)
    idle = sum(g for _, g in tr.idle_gaps(t, dev, window, n=10 ** 6))
    assert idle == pytest.approx(window[1] - window[0] - busy, abs=1e-9)


def test_interval_arithmetic():
    assert tr.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.clip([(0, 2), (5, 9)], (1, 6)) == [(1, 2), (5, 6)]
    assert tr.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]
