"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --shape train_4k --override quant.mode=simulate --dry-steps 3
    PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 30 \
        --trace-dir /tmp/prof --trace-steps 20:25

On a real TPU pod this process runs per host (jax.distributed.initialize is
called when the coordinator env vars are present); in this container it runs
single-process on CPU. Fault-tolerance wiring: checkpoint manager with
atomic resume, preemption guard, step watchdog.
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.config import load_config
from repro.launch.cache import use_compile_cache
from repro.train import train_loop
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import (Heartbeat, PreemptionGuard,
                                         StepWatchdog)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics-dir", default="",
                    help="write JSONL step/switch telemetry here")
    ap.add_argument("--override", action="append", default=[])
    ap.add_argument("--trace-dir", default="",
                    help="write a jax.profiler trace (.xplane.pb) here")
    ap.add_argument("--trace-steps", default="", metavar="A:B",
                    help="with --trace-dir: trace steps A to B-1")
    args = ap.parse_args(argv)
    trace = None
    if args.trace_dir or args.trace_steps:
        try:
            first, stop = (int(v) for v in args.trace_steps.split(":"))
        except ValueError:
            ap.error("--trace-steps takes A:B, two step numbers")
        if not args.trace_dir or stop <= first:
            ap.error("--trace-dir and --trace-steps A:B (A < B) go together")
        trace = (args.trace_dir, first, stop)
    use_compile_cache()

    if "COORDINATOR_ADDRESS" in os.environ:   # multi-host entry
        jax.distributed.initialize()

    if args.smoke:
        from repro.configs import get_smoke_config
        from repro.config import apply_overrides, with_shape
        cfg = get_smoke_config(args.arch)
        if args.shape:
            cfg = with_shape(cfg, args.shape)
        cfg = apply_overrides(cfg, args.override)
    else:
        cfg = load_config(args.arch, args.shape, overrides=args.override)

    state = None
    mgr = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir,
                                keep=cfg.train.keep_checkpoints,
                                async_save=cfg.train.async_checkpoint)
        if args.resume and mgr.latest_step() is not None:
            template = train_loop.init_state(cfg)
            state = mgr.restore(template)
            print(f"[train] resumed from step {int(state['step'])}")

    watchdog = StepWatchdog(factor=cfg.train.straggler_factor,
                            on_straggler=lambda s, dt, med: print(
                                f"[watchdog] straggler step {s}: "
                                f"{dt:.2f}s vs median {med:.2f}s"))

    metrics_logger = None
    if args.metrics_dir:
        from repro.train.metrics import MetricsLogger
        metrics_logger = MetricsLogger(args.metrics_dir,
                                       run_name=args.arch.replace("/", "_"))

    telemetry: list = []
    # the guard + heartbeat are wired INTO the loop: SIGTERM mid-run saves
    # a final checkpoint at the interrupted step and returns early, rather
    # than being noticed only after all steps complete
    with PreemptionGuard() as guard:
        state, history = train_loop.train(
            cfg, steps=args.steps, state=state, checkpoint_mgr=mgr,
            watchdog=watchdog, telemetry=telemetry,
            metrics_logger=metrics_logger, preemption_guard=guard,
            heartbeat=Heartbeat(), trace=trace)
    if metrics_logger is not None:
        metrics_logger.log_event("finished", steps=int(state["step"]))
        metrics_logger.close()
    if mgr is not None:
        mgr.save(state, step=int(state["step"]))
        mgr.wait()
    if history:
        print(f"[train] done: step={history[-1]['step']} "
              f"loss={history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
