#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

    python3 bench/calibrate.py --workload smollm-360m.train-s2k \
        --seeds 101,102,103 --fault-seeds 3 --out cal.jsonl

In one process, on the cell's own sizes and compiled step, for each seed:

* ``sound``: the program as the configuration states it, against the plain
  reference, with the set-up's check of the precision switch;
* ``control_wl<n>``: the program with its own lower-precision path switched
  on — every word length clamped to n bits by
  ``controller.clamp_adapt_state``, the serving path's AdaBits view, which
  keeps each tensor's range and drops 8 - n fractional bits — against the
  same reference, for each n of ``--control-wls``;

and, on the first ``--fault-seeds`` seeds, faults planted in the reference
put in the program's place: half of the batch left out (the mean taken over
the rest) and, on a cell over several chips, the exchange between chips
left out (each chip's update from its own rows only). A state left unchanged
reads 1 on ``change`` by construction and needs no run.

Each reading is written as one JSON line; the benchmark's own runs do not
run this. Exits nonzero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None, *, root: Path = ROOT, require_accelerator=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--control-wls", default="7,6,4")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = Path(root)
    for p in (root, root / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import check, run

    _, entry, cfg, traffic = run.resolve(root, args.workload)
    run.use_cache(root)
    import jax
    devs = jax.devices()
    if require_accelerator and (devs[0].platform != "tpu"
                                or len(devs) < entry["chips"]):
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.core import controller
    import importlib
    mix = importlib.import_module(f"bench.{traffic['kind']}")
    cell = mix.Cell(cfg, traffic)
    clamps = {wl: jax.jit(lambda s, wl=wl: dict(
        s, adapt=controller.clamp_adapt_state(s["adapt"], wl)),
        donate_argnums=0)
        for wl in (int(w) for w in args.control_wls.split(",") if w)}
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a")

    def emit(kind, seed, read, seconds):
        row = {"workload": args.workload, "kind": kind, "seed": seed,
               **read, "seconds": seconds}
        out.write(json.dumps(row) + "\n")
        out.flush()
        print(json.dumps(row), flush=True)

    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        state, prog = cell.first_steps(cell.fresh_state(seed), seed)
        state, prog["switch"] = cell.check_switch(state)
        del state
        t1 = time.perf_counter()
        ctl = {}
        for wl, clamp in clamps.items():
            state, ctl[wl] = cell.first_steps(clamp(cell.fresh_state(seed)),
                                              seed)
            del state
        t2 = time.perf_counter()
        ref = cell.reference(seed)
        t3 = time.perf_counter()
        emit("sound", seed, check.readings(prog, ref), t1 - t0)
        for wl, read in ctl.items():
            emit(f"control_wl{wl}", seed, check.readings(read, ref),
                 (t2 - t1) / len(ctl))
        emit("reference", seed, {"losses": ref["losses"]}, t3 - t2)
        if n < args.fault_seeds:
            half = cell.reference(seed, rows=traffic["global_batch"] // 2)
            emit("half_batch", seed, check.readings(half, ref), 0.0)
            if cell.chips > 1:
                own = cell.reference(seed, rows=traffic["global_batch"]
                                     // cell.chips, shards=1)
                emit("no_exchange", seed, check.readings(own, ref), 0.0)
    out.close()
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    raise SystemExit(main())
