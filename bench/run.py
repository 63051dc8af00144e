#!/usr/bin/env python3
"""Benchmark of the AdaPT training stack on the chips of this machine.

    python3 bench/run.py --workload smollm-360m.train-s2k --seed 7 \
        --seconds 45 --trace 0

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell names a configuration (``bench/configs/<name>.json``) and
a traffic mix (``bench/traffic/<traffic>.json``); the mix's ``kind`` names
the module that runs it (``bench/<kind>.py``); the configuration's
``family`` names the work functions (``bench/work/<family>.py``) and the
plain reference
(``bench/reference/<family>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``; the limits that decide ``correct`` are in
``bench/limits/<cell>.json`` and the chips' peaks in ``bench/peaks.json``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
``log_every`` steps that hold the first precision switch. Every run checks
the first three steps against the plain reference, and the compiled
precision switch against the same switch on the XLA dispatch. The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error. Without a TPU, or with fewer
chips than the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


def process_start() -> float:
    """Wall-clock time at which this process started (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return time.time()


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache`` (the path is part of the cache's key), for
    every program however small, so that only a checkout's first run
    compiles. Set in the environment before JAX is imported, which is when
    JAX reads it; where JAX is already imported, in its config too."""
    settings = {"jax_compilation_cache_dir": str(root / ".jax_cache"),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0}
    for name, value in settings.items():
        os.environ[name.upper()] = str(value)
    if "jax" in sys.modules:
        import jax
        for name, value in settings.items():
            jax.config.update(name, value)


def resolve(root: Path, workload: str):
    """(benchmark, cell, configuration, traffic) for a cell's name."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(root / entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    if traffic["data_parallel"] != cell["chips"]:
        raise ValueError(f"{workload}: traffic {cell['traffic']} runs on "
                         f"{traffic['data_parallel']} chips, cell asks "
                         f"{cell['chips']}")
    return bench, cell, cfg, traffic


def per_layer(root: Path, bench, workload: str, ctx) -> dict:
    """Read every per-layer metric listed for this cell; a reader that
    finds nothing returns None and its metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is None:
            continue
        if m["unit"] == "%" and ("roofline" in m["name"] or "mfu" in m["name"]) \
                and value > 100.0:
            raise ValueError(f"{m['name']} reads {value}% > 100%: the work "
                             "is counted too high or the time misses part "
                             "of it")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def kernel_patterns(root: Path, bench):
    pats = []
    for m in bench["per_layer"]:
        mod = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        if getattr(mod, "KERNEL", None) is not None:
            pats.append(mod.KERNEL)
    return pats


def device_peaks(root: Path, kind: str) -> dict:
    """The peaks of a chip by its ``device_kind``; an unknown kind is an
    error, never a default."""
    peaks = load_json(Path(root) / "bench" / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return peaks[kind]


def trace_context(root, bench, cfg, traffic, trace_dir, stats, kind, chips):
    from bench import trace as tr

    t = tr.load(tr.find_xplane(str(trace_dir)))
    span = t.span("bench.window")
    if span is None:
        raise RuntimeError("the trace holds no bench.window span")
    devs = sorted(d for d, dev in t.devices.items() if dev.ops)[:chips]
    if len(devs) < chips:
        raise RuntimeError(f"trace holds ops on {len(devs)} devices, "
                           f"{chips} expected")
    work = load_module(root / "bench" / "work" / f"{cfg['family']}.py",
                       "bench_work_" + cfg["family"])
    return SimpleNamespace(
        trace=t, window=span, devices=[t.devices[d] for d in devs],
        cfg=cfg, traffic=traffic, peaks=device_peaks(root, kind), work=work,
        steps=stats["traced"]["steps"], switches=stats["traced"]["switches"],
        chips=chips, notes=[])


def main(argv=None, *, root: Path = ROOT, require_accelerator: bool = True,
         step_wrapper=None, switch_wrapper=None) -> int:
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root)

    bench, cell_entry, cfg, traffic = resolve(root, args.workload)
    chips = cell_entry["chips"]
    use_cache(root)
    import jax
    devs = jax.devices()
    if require_accelerator and (devs[0].platform != "tpu"
                                or len(devs) < chips):
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    for p in (root, root / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    from bench import check

    mix = importlib.import_module(f"bench.{traffic['kind']}")
    cell = mix.Cell(cfg, traffic, step_wrapper=step_wrapper,
                    switch_wrapper=switch_wrapper)
    state = cell.fresh_state(args.seed)
    state, prog = cell.first_steps(state, args.seed)
    state, prog["switch"] = cell.check_switch(state)   # warms the switch
    jax.block_until_ready(state)
    setup_s = time.time() - started

    trace_dir = None
    if args.trace:
        trace_dir = root / ".bench_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    state, stats = cell.window(state, args.seed, args.seconds,
                               mix.FIRST_STEPS,
                               None if trace_dir is None else str(trace_dir))
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in cell.devices)
    # the allocator's peak leaves out what the compiled step holds as
    # temporaries; the step's own analysis counts them
    step_bytes = cell.step_bytes()
    peak = max(in_use, step_bytes or 0)
    del state
    t_ref = time.perf_counter()
    ref = cell.reference(args.seed)
    reference_s = time.perf_counter() - t_ref
    read = check.readings(prog, ref)
    limits = check.load_limits(str(root), args.workload)
    ok, rows = check.judge(read, limits)
    ok = ok and stats["failed"] == 0

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": peak,
              "memory_step_bytes": step_bytes,
              "memory_peak_bytes_in_use": in_use}
    result = {"correct": ok, "attempted": stats["steps"],
              "failed": stats["failed"]}
    if args.trace:
        ctx = trace_context(root, bench, cfg, traffic, trace_dir, stats,
                            dev.device_kind, len(cell.devices))
        from bench import trace as tr
        metrics = per_layer(root, bench, args.workload, ctx)
        device["busy_s"] = sum(tr.busy(d, ctx.window) for d in ctx.devices
                               ) / len(ctx.devices)
        device["window_s"] = ctx.window[1] - ctx.window[0]
        pats = kernel_patterns(root, bench)
        stray = [o for o in ctx.devices[0].ops if o.kernel
                 and ctx.window[0] <= o.start < ctx.window[1]
                 and not any(p.search(o.base) for p in pats)]
        by = {}
        for o in stray:
            by[o.base] = by.get(o.base, 0.0) + o.end - o.start
        for line in ctx.notes:
            print(f"bench: {line}", file=sys.stderr)
        top = sorted(by.items(), key=lambda kv: -kv[1])
        print(f"bench: unattributed kernel time on device 0: "
              f"{sum(by.values())} s {top}", file=sys.stderr)
        breakdown = {
            "device_ops": [[n, s] for n, s in
                           tr.top_ops(ctx.devices[0], ctx.window)],
            "idle_gaps": [[n, s] for n, s in
                          tr.idle_gaps(ctx.trace, ctx.devices[0],
                                       ctx.window)]}
    else:
        tokens = stats["steps"] * traffic["global_batch"] * traffic["seq_len"]
        metrics = {"train_tokens_per_s": {"value": tokens / stats["wall_s"],
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"steps": stats["steps"], "wall_s": stats["wall_s"],
                        "switches": stats["switches"],
                        "compiles": stats["compiles"],
                        "compile_s": cell.compile_s,
                        "reference_s": reference_s, "losses": stats["losses"],
                        "program": prog, "reference": ref}
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    print(f"bench: {args.workload} seed {args.seed}: {stats['steps']} steps "
          f"in {stats['wall_s']:.3f} s, {stats['switches']} switches, "
          f"{stats['compiles']} compiles in the window, setup {setup_s:.3f} s,"
          f" {read['leaves']} leaves compared, {read['switch_moved']} of the"
          f" switch's <WL,FL> pairs moved, step memory {step_bytes} bytes,"
          f" allocator peak {in_use} bytes", file=sys.stderr)
    for r in rows:
        print(f"check {r['name']} {r['value']} limit {r['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    raise SystemExit(main())
