"""Device time by the program's named scopes, read from a traced run.

The train step and the precision switch put their work under
``jax.named_scope("adapt.<name>")``. The compiler keeps the scope path in
each instruction's ``op_name``, and a TPU trace carries it as the ``tf_op``
stat of the op's event metadata on the device plane (read by hand from a v5e
trace of the train step), e.g.
``jit(train_step)/transpose(jvp(adapt.forward))/adapt.layers/while/body/...``.
An op belongs to its innermost ``adapt.`` scope; ops outside every scope
have none. ``jax.profiler.ProfileData`` does not give event metadata stats,
so this module parses the ``.xplane.pb`` itself, with the subset of the
XPlane schema (``tsl/profiler/protobuf/xplane.proto``) declared below.

The trace is found by ``bench/run.py``'s rule: the newest ``.xplane.pb``
under ``<checkout>/.bench_trace/``. Times are seconds from the start of the
trace, truncated to whole nanoseconds as ``ProfileData`` gives them, so
they line up with ``bench/trace.py``'s.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Set

from bench import trace as tr

ROOT = Path(__file__).resolve().parents[1]
SCOPE = re.compile(r"(?<![\w.])adapt\.([a-z_]+)")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
STEP_PROGRAM = "jit_train_step"

# (field, number, type, repeated) of the messages read; a map field is read
# as the repeated key/value entries it is on the wire.
_INT64, _UINT64, _STRING = 3, 4, 9
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STRING, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, _STRING, False), ("timestamp_ns", 3, _INT64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _INT64, False),
               ("offset_ps", 2, _INT64, False),
               ("duration_ps", 3, _INT64, False)],
    "XStat": [("metadata_id", 1, _INT64, False),
              ("str_value", 5, _STRING, False),
              ("ref_value", 7, _UINT64, False)],
    "XEventMetadata": [("name", 2, _STRING, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, _STRING, False)],
    "EventMetadataEntry": [("key", 1, _INT64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _INT64, False),
                          ("value", 2, "XStatMetadata", False)],
}


def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="bench/xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number, label=(
                F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
            if isinstance(typ, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


@dataclass
class ScopedOp(tr.Op):
    scope: Optional[str]    # innermost adapt. scope, without the prefix


def innermost(op_name: str) -> Optional[str]:
    """``.../transpose(jvp(adapt.forward))/adapt.layers/...`` -> ``layers``."""
    hits = SCOPE.findall(op_name)
    return hits[-1] if hits else None


def load(path: str) -> Dict[int, tr.Device]:
    """{chip: Device} of a ``.xplane.pb`` file, each op with its scope."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices: Dict[int, tr.Device] = {}
    for plane in space.planes:
        m = _DEVICE.match(plane.name)
        if not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            op_name = ""
            for s in e.value.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    op_name = s.str_value or stat_names.get(s.ref_value, "")
            meta[e.key] = (e.value.name, innermost(op_name))
        dev = devices.setdefault(int(m.group(1)), tr.Device())
        for line in plane.lines:
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                name, scope = meta.get(ev.metadata_id, ("", None))
                start_ns = (t0 + ev.offset_ps) // 1000
                start = start_ns * 1e-9
                end = (start_ns + ev.duration_ps // 1000) * 1e-9
                if line.name == "XLA Modules":
                    dev.modules.append((name.split("(")[0], start, end))
                elif line.name == "XLA Ops":
                    base = tr.op_base(name)
                    if base not in tr.CONTAINERS:
                        dev.ops.append(ScopedOp(
                            base, start, end, " custom-call(" in name, scope))
    for dev in devices.values():
        dev.ops.sort(key=lambda o: o.start)
        dev.modules.sort(key=lambda m: m[1])
    return devices


def devices(ctx) -> List[tr.Device]:
    """The run's chips as ``bench/run.py``'s ``trace_context`` takes them
    (the first ``ctx.chips`` with ops), with scoped ops. The trace is parsed
    once per run and kept on ``ctx``; the first call notes the scopes'
    coverage in ``ctx.notes``."""
    got = getattr(ctx, "scoped", None)
    if got is None:
        path = tr.find_xplane(str(ROOT / ".bench_trace"))
        found = load(path)
        ids = sorted(d for d, dev in found.items() if dev.ops)[:ctx.chips]
        got = ctx.scoped = [found[d] for d in ids]
        ctx.notes.append(coverage_note(got, ctx.window))
    return got


def ms_per_step(ctx, scopes: Set[str], kernels: bool = True
                ) -> Optional[float]:
    """Device milliseconds per traced step of the ops whose innermost scope
    is in ``scopes`` (custom calls left out unless ``kernels``), the mean
    over chips; None where no such op ran."""
    lo, hi = ctx.window
    per, found = [], 0
    for dev in devices(ctx):
        hits = [o for o in dev.ops if o.scope in scopes
                and (kernels or not o.kernel) and lo <= o.start
                and o.end <= hi]
        found += len(hits)
        per.append(sum(o.end - o.start for o in hits))
    if not found or ctx.steps <= 0:
        return None
    return 1e3 * sum(per) / len(per) / ctx.steps


def step_ops(dev: tr.Device, window) -> List[ScopedOp]:
    """The ops that ran inside the train step's runs in ``window``."""
    runs = [(s, e) for n, s, e in dev.modules
            if n == STEP_PROGRAM and s >= window[0] and e <= window[1]]
    return [o for o in dev.ops if any(s <= o.start and o.end <= e
                                      for s, e in runs)]


def coverage_note(devs: List[tr.Device], window, top: int = 5) -> str:
    """The share of device time in the train step's runs that carries an
    ``adapt.`` scope (mean over chips), and the largest unscoped op bases
    on the first chip."""
    shares, unscoped = [], {}
    for i, dev in enumerate(devs):
        ops = step_ops(dev, window)
        total = sum(o.end - o.start for o in ops)
        bare = [o for o in ops if o.scope is None]
        if total > 0:
            shares.append(1 - sum(o.end - o.start for o in bare) / total)
        if i == 0:
            for o in bare:
                unscoped[o.base] = unscoped.get(o.base, 0.0) + o.end - o.start
    share = 100.0 * sum(shares) / len(shares) if shares else 0.0
    largest = sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
    return (f"scopes: {share}% of device time in {STEP_PROGRAM} runs carries "
            f"an adapt. scope; largest unscoped op bases on device 0 (s): "
            f"{largest}")
