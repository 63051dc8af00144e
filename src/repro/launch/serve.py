"""Serving launcher: load a checkpoint (or fresh init), quantize once at the
AdaPT controller's final ⟨WL,FL⟩, and serve batched generation requests.

Batch mode (default) drives the simple ``Engine``:

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --tokens 16 \
        --batch 4 --max-new 8

Continuous mode (``--continuous``) drives the overload-robust
``ContinuousBatcher`` — admission control, deadlines, a durable request
journal, and AdaBits-style precision degradation under queue pressure
(docs/serving.md):

    PYTHONPATH=src python -m repro.launch.serve --arch tiny --continuous \
        --requests 16 --max-new 8 --journal /tmp/serve.journal \
        --override serve.max_queue=8 serve.degrade_high_watermark=4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import load_config
from repro.launch.cache import use_compile_cache
from repro.serve.engine import Engine
from repro.train import train_loop
from repro.train.checkpoint import CheckpointManager


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batcher with admission control, "
                         "journal, and precision degradation")
    ap.add_argument("--requests", type=int, default=16,
                    help="[continuous] synthetic requests to submit")
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="[continuous] per-request deadline in seconds")
    ap.add_argument("--journal", default="",
                    help="[continuous] durable request journal path")
    ap.add_argument("--no-degrade", action="store_true",
                    help="[continuous] disable the precision policy")
    ap.add_argument("--override", action="append", default=[])
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.smoke:
        from repro.configs import get_smoke_config
        from repro.config import apply_overrides
        cfg = apply_overrides(get_smoke_config(args.arch), args.override)
    else:
        cfg = load_config(args.arch, overrides=args.override)

    state = train_loop.init_state(cfg)
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir)
        state = mgr.restore(state)
        print(f"[serve] restored step {int(state['step'])}")

    if args.continuous:
        return _serve_continuous(cfg, state, args)

    engine = Engine(cfg, state["params"], state["adapt"])
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (args.batch, args.tokens), 0,
                                 cfg.model.vocab_size)
    t0 = time.perf_counter()
    out, _ = engine.generate(prompts, args.max_new,
                             temperature=args.temperature)
    dt = time.perf_counter() - t0
    toks = args.batch * args.max_new
    print(f"[serve] generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")
    print("[serve] sample:", [int(t) for t in out[0][:16]])
    return 0


def _serve_continuous(cfg, state, args):
    from repro.serve.policy import PrecisionPolicy
    from repro.serve.scheduler import ContinuousBatcher, DrainTimeout

    policy = (None if args.no_degrade
              else PrecisionPolicy.from_config(cfg.serve))
    cb = ContinuousBatcher(cfg, state["params"], state["adapt"],
                           policy=policy, journal_path=args.journal)
    key = jax.random.PRNGKey(1)
    plen = min(args.tokens, cb.max_context - 1)
    for r in range(args.requests):
        prompt = [int(t) for t in jax.random.randint(
            jax.random.fold_in(key, r), (plen,), 0, cfg.model.vocab_size)]
        cb.submit(prompt, max_new_tokens=args.max_new,
                  temperature=args.temperature,
                  timeout=args.timeout or None)
    t0 = time.perf_counter()
    try:
        done = cb.run_until_drained()
    except DrainTimeout as e:
        print(f"[serve] DRAIN TIMEOUT: stranded rids {sorted(e.unfinished)}")
        done = e.done
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    print(f"[serve] {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s incl. compile)")
    print(f"[serve] stats: {dict(cb.stats)}")
    if policy is not None and cb.wl_trace:
        print(f"[serve] WL trace: start={cb.wl_trace[0]} "
              f"min={min(cb.wl_trace)} end={cb.wl_trace[-1]} "
              f"switches={cb.stats.get('precision_switches', 0)}")
    by_status = {}
    for r in done:
        by_status.setdefault(r.status.value, []).append(r.rid)
    for status, rids in sorted(by_status.items()):
        print(f"[serve]   {status}: {len(rids)}")
    if cb.journal is not None:
        cb.journal.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
