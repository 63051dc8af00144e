"""Reduction of a JAX profiler trace (``.xplane.pb``) to device timelines.

What a TPU trace holds (read by hand from a v5e trace of the train step):

* one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules``
  (one event per program run, named ``jit_<function>(<hash>)``) and
  ``XLA Ops`` (one event per HLO instruction, named by its HLO text:
  ``%fxp_matmul.149 = bf16[...] custom-call(...)``). Loops (``while``)
  appear as an event around the events of their body;
* the host plane ``/host:CPU``, whose ``python`` line carries the
  ``jax.profiler.TraceAnnotation`` spans the benchmark opens (``bench.*``)
  on the same clock as the device events.

Times here are in seconds from the start of the trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

# Events that only enclose other events, or mark an async op's ends.
CONTAINERS = {"while", "conditional", "call"}
_BASE = re.compile(r"%([\w\-]+?)(?:\.\d+)?(?:\.clone)? = ")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def op_base(name: str) -> str:
    """``%fxp_matmul.149 = ...`` -> ``fxp_matmul``."""
    m = _BASE.match(name)
    return m.group(1) if m else name.split("(")[0]


@dataclass
class Op:
    base: str
    start: float
    end: float
    kernel: bool      # a custom call: a Pallas (Mosaic) kernel


@dataclass
class Device:
    ops: List[Op] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Tuple[str, float, float]]    # bench.* spans

    def span(self, name: str) -> Optional[Interval]:
        """First and last instant of the host spans called ``name``."""
        hits = [(s, e) for n, s, e in self.host if n == name]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)


def load(path: str, host_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb`` file into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    host = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules += [(e.name.split("(")[0],
                                     e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                                    for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        base = op_base(e.name)
                        if base in CONTAINERS:
                            continue
                        dev.ops.append(Op(base, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9,
                                          " custom-call(" in e.name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events
                         if e.name.startswith(host_prefix)]
    for dev in devices.values():
        dev.ops.sort(key=lambda o: o.start)
        dev.modules.sort(key=lambda m: m[1])
    return Trace(devices, sorted(host, key=lambda h: h[1]))


def find_xplane(directory: str) -> str:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(hits, key=os.path.getmtime)


# ---------------------------------------------------------------------------
# Interval arithmetic


def merge(intervals) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(intervals, window: Interval) -> List[Interval]:
    """Stretches of ``window`` that no interval covers."""
    out, cur = [], window[0]
    for s, e in merge(clip(intervals, window)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


# ---------------------------------------------------------------------------
# Readings over one device in one window


def busy(dev: Device, window: Interval) -> float:
    return length(clip([(o.start, o.end) for o in dev.ops], window))


def kernel_time(dev: Device, pattern: re.Pattern, window: Interval
                ) -> Tuple[float, int]:
    """(seconds, calls) of the kernels whose op name matches ``pattern``."""
    hits = [o for o in dev.ops if o.kernel and pattern.search(o.base)
            and o.start >= window[0] and o.end <= window[1]]
    return sum(o.end - o.start for o in hits), len(hits)


def module_runs(dev: Device, name: str, window: Interval) -> List[float]:
    """Durations of the runs of the program ``jit_<name>`` in ``window``."""
    return [e - s for n, s, e in dev.modules
            if n == f"jit_{name}" and s >= window[0] and e <= window[1]]


def top_ops(dev: Device, window: Interval, n: int = 10):
    tot: Dict[str, float] = {}
    for o in dev.ops:
        if o.start >= window[0] and o.end <= window[1]:
            tot[o.base] = tot.get(o.base, 0.0) + (o.end - o.start)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, dev: Device, window: Interval, n: int = 10):
    """The longest device gaps in ``window``, each named by the host span
    that overlaps it most (``host:other`` where none does)."""
    out = []
    for s, e in gaps([(o.start, o.end) for o in dev.ops], window):
        best, most = "host:other", 0.0
        for name, hs, he in trace.host:
            if name == "bench.window":
                continue
            ov = min(e, he) - max(s, hs)
            if ov > most:
                best, most = name, ov
        out.append((best, e - s))
    return sorted(out, key=lambda g: -g[1])[:n]
