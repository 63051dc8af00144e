"""moe_grouped.roofline: the least time of every expert product's forward,
dx and dw passes in the traced steps, at the rows the shapes lead one to
expect for the held experts (tokens x k x held / routed experts; each pass
the larger of its operations at the int8 peak and its bytes at HBM
bandwidth), over the summed device time of the grouped kernels (names
matching ``KERNEL``), the mean over chips."""
import re

from bench import trace as tr

KERNEL = re.compile(r"fxp_gmm|gmm_d[xw]")


def read(ctx):
    w = ctx.work
    if not hasattr(w, "expert_passes"):
        return None
    t = ctx.traffic
    tokens = t["global_batch"] * t["seq_len"] // ctx.chips
    passes = w.expert_passes(ctx.cfg, w.held_rows(ctx.cfg, tokens))
    least = ctx.steps * w.least_time(
        passes, ctx.peaks["int8_ops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    spent = [tr.kernel_time(d, KERNEL, ctx.window)[0] for d in ctx.devices]
    if ctx.steps <= 0 or min(spent) <= 0:
        return None
    return 100.0 * least / (sum(spent) / len(spent))
