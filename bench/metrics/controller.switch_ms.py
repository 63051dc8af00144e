"""controller.switch_ms: device time per run of the precision switch, the
program the benchmark compiles as ``jit_bench_precision_switch``, the mean
over chips and runs in the traced window."""
from bench import trace as tr

PROGRAM = "bench_precision_switch"


def read(ctx):
    runs = [r for d in ctx.devices
            for r in tr.module_runs(d, PROGRAM, ctx.window)]
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
