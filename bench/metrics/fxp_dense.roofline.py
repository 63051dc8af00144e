"""fxp_dense.roofline: the least time of every dense product's forward, dx
and dw passes in the traced steps (each the larger of its operations at the
int8 peak and its bytes at HBM bandwidth), over the summed device time of
the dense kernels (names matching ``KERNEL``), the mean over chips."""
import re

from bench import trace as tr

KERNEL = re.compile(r"fxp_q?matmul|matmul_d[xw]|int8_matmul")


def read(ctx):
    t = ctx.traffic
    rows = t["global_batch"] * t["seq_len"] // ctx.chips
    passes = ctx.work.dense_passes(ctx.cfg, rows)
    least = ctx.steps * ctx.work.least_time(
        passes, ctx.peaks["int8_ops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    spent = [tr.kernel_time(d, KERNEL, ctx.window)[0] for d in ctx.devices]
    if ctx.steps <= 0 or min(spent) <= 0:
        return None
    return 100.0 * least / (sum(spent) / len(spent))
