"""Device time by named scope (``bench/scopes.py``), on a trace recorded on
a TPU v5e and trimmed.

``data/v5e_smollm_scopes.xplane.pb``: a traced run of
``smollm-360m.train-s2k`` with the program's ``adapt.*`` scopes, cut to the
30 ms before the precision switch, the switch, 25 ms after it, and the turn
of the next step from the head's first op to 20 ms after the loss's first:
the device plane's ``XLA Modules`` and ``XLA Ops`` lines with each op's
``tf_op`` metadata stat (the one that carries the scope path), and the
host's ``bench.*`` spans.
"""
import collections
import importlib.util
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import trace as tr
from conftest import ROOT

DATA = Path(__file__).parent / "data"
SCOPED = DATA / "v5e_smollm_scopes.xplane.pb"
UNSCOPED = DATA / "v5e_smollm_switch.xplane.pb"    # stats dropped
ALL = {"quantize", "forward", "layers", "head", "loss", "regularize",
       "accumulate", "update", "switch"}
METRICS = {"quantize.ms": ({"quantize"}, True),
           "layers.xla_ms": ({"layers"}, False),
           "head_loss.xla_ms": ({"head", "loss"}, False),
           "controller.accumulate_ms": ({"accumulate"}, True),
           "update.ms": ({"regularize", "update"}, True)}


def _fields(buf):
    """{field number: [values]} of one protobuf message, by the wire format
    alone: varints as ints, length-delimited fields as bytes."""
    out, i = collections.defaultdict(list), 0

    def varint(i):
        v = shift = 0
        while True:
            b = buf[i]
            v |= (b & 0x7F) << shift
            i, shift = i + 1, shift + 7
            if b < 0x80:
                return v, i

    while i < len(buf):
        key, i = varint(i)
        kind = key & 7
        if kind == 0:
            v, i = varint(i)
        elif kind == 1:
            v, i = buf[i:i + 8], i + 8
        elif kind == 2:
            n, i = varint(i)
            v, i = buf[i:i + n], i + n
        elif kind == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind}")
        out[key >> 3].append(v)
    return out


def _plain(path):
    """[(op name, start s, end s, op_name path)] of device 0's XLA Ops: times
    and names from ``ProfileData``, each event's ``tf_op`` from the file's
    bytes read field by field (xplane.proto: XSpace.planes = 1; XPlane
    name 2, lines 3, event_metadata 4, stat_metadata 5; XLine name 2,
    events 4; XEvent metadata_id 1; XEventMetadata stats 5; XStat
    metadata_id 1, str_value 5; XStatMetadata name 2)."""
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(str(path)).planes
                 if p.name == "/device:TPU:0")
    events = list(next(l for l in plane.lines if l.name == "XLA Ops").events)
    raw = next(p for p in map(_fields, _fields(path.read_bytes())[1])
               if p[2] == [b"/device:TPU:0"])
    stat_names = {}
    for entry in map(_fields, raw[5]):
        stat_names[entry[1][0]] = _fields(entry[2][0])[2][0].decode()
    op_names = {}
    for entry in map(_fields, raw[4]):
        stats = map(_fields, _fields(entry[2][0]).get(5, []))
        op_names[entry[1][0]] = next(
            (s[5][0].decode() for s in stats
             if stat_names[s[1][0]] == "tf_op"), "")
    line = next(l for l in map(_fields, raw[3]) if l[2] == [b"XLA Ops"])
    ids = [_fields(e)[1][0] for e in line[4]]
    assert len(ids) == len(events)
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             op_names[i]) for e, i in zip(events, ids)]


@pytest.fixture(scope="module")
def recorded():
    dev = scopes.load(str(SCOPED))[0]
    return dev, (min(o.start for o in dev.ops), max(o.end for o in dev.ops))


def _ctx(window, chips=1, steps=1):
    return SimpleNamespace(window=window, chips=chips, steps=steps, notes=[])


def test_every_scope_is_read(recorded):
    dev, _ = recorded
    assert {o.scope for o in dev.ops} - {None} == ALL
    kernels = {(o.scope, o.base) for o in dev.ops if o.kernel}
    assert ("quantize", "sr_quantize_fused_stacked_int8") in kernels
    assert ("head", "fxp_matmul") in kernels
    assert any(s == "switch" and "edf_ladder" in b for s, b in kernels)


def test_scope_sums_match_a_plain_pass(recorded):
    dev, window = recorded
    want = collections.Counter()
    for name, start, end, op_name in _plain(SCOPED):
        base = tr.op_base(name)
        if base not in tr.CONTAINERS:
            want[(scopes.innermost(op_name),
                  " custom-call(" in name)] += end - start
    got = collections.Counter()
    for o in dev.ops:
        got[(o.scope, o.kernel)] += o.end - o.start
    assert set(got) == set(want)
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-12), key
    ctx = _ctx(window)
    ctx.scoped = [dev]
    for scope in ALL:
        assert scopes.ms_per_step(ctx, {scope}) == pytest.approx(
            1e3 * (want[(scope, True)] + want[(scope, False)]), rel=1e-9)
        xla = scopes.ms_per_step(ctx, {scope}, kernels=False)
        if want[(scope, False)]:
            assert xla == pytest.approx(1e3 * want[(scope, False)], rel=1e-9)


def test_times_line_up_with_the_trace_reduction(recorded):
    dev, _ = recorded
    plain = tr.load(str(SCOPED)).devices[0]
    assert [(o.base, o.start, o.end, o.kernel) for o in dev.ops] == \
        [(o.base, o.start, o.end, o.kernel) for o in plain.ops]
    assert dev.modules == plain.modules


def test_innermost_scope():
    assert scopes.innermost("jit(train_step)/transpose(jvp(adapt.forward))"
                            "/adapt.layers/while/body/mul:") == "layers"
    assert scopes.innermost("jit(train_step)/transpose(jvp(adapt.loss))"
                            "/log_softmax") == "loss"
    assert scopes.innermost("jit(feed)/jit(_uniform)/slice:") is None
    assert scopes.innermost("jit(train_step)/not_adapt.layers/x") is None


def test_coverage_counts_only_the_train_step(recorded):
    dev, window = recorded
    ops = scopes.step_ops(dev, (0.0, 1e9))
    runs = [(s, e) for n, s, e in dev.modules if n == "jit_train_step"]
    assert ops and all(any(s <= o.start and o.end <= e for s, e in runs)
                       for o in ops)
    assert not any(o.scope == "switch" for o in ops)
    total = sum(o.end - o.start for o in ops)
    bare = sum(o.end - o.start for o in ops if o.scope is None)
    note = scopes.coverage_note([dev], (0.0, 1e9))
    assert note.startswith(f"scopes: {100.0 * (1 - bare / total)}% ")


def _checkout(tmp_path, trace_file):
    """A checkout whose ``.bench_trace`` holds ``trace_file``."""
    dest = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    dest.mkdir(parents=True)
    shutil.copy(trace_file, dest / "host.xplane.pb")
    return tmp_path


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers_parse_once_and_note_coverage(tmp_path, monkeypatch,
                                              recorded):
    dev, window = recorded
    monkeypatch.setattr(scopes, "ROOT", _checkout(tmp_path, SCOPED))
    ctx = _ctx(window, steps=2)
    for name, (wanted, kernels) in METRICS.items():
        got = _reader(name).read(ctx)
        hits = [o for o in dev.ops if o.scope in wanted
                and (kernels or not o.kernel)]
        assert got == pytest.approx(
            1e3 * sum(o.end - o.start for o in hits) / 2, rel=1e-9), name
        assert got > 0
    assert len(ctx.notes) == 1 and ctx.notes[0].startswith("scopes: ")


def test_readers_find_nothing_in_a_trace_without_scopes(tmp_path,
                                                        monkeypatch):
    """A program without the scopes (the stats-free recording stands for
    one): every reader gives None and nothing raises."""
    monkeypatch.setattr(scopes, "ROOT", _checkout(tmp_path, UNSCOPED))
    dev = tr.load(str(UNSCOPED)).devices[0]
    ctx = _ctx((min(o.start for o in dev.ops), max(o.end for o in dev.ops)))
    assert all(_reader(name).read(ctx) is None for name in METRICS)
    assert ctx.notes[0].startswith("scopes: 0.0% ")
