"""flash_attention.roofline: the least time of causal attention's six
products per layer in the traced steps (bf16 peak, or HBM bandwidth where
that bounds), over the summed device time of the attention kernels (names
matching ``KERNEL``), the mean over chips."""
import re

from bench import trace as tr

KERNEL = re.compile(r"flash_attention")


def read(ctx):
    t = ctx.traffic
    seqs = t["global_batch"] // ctx.chips
    passes = ctx.work.attention_passes(ctx.cfg, seqs, t["seq_len"])
    least = ctx.steps * ctx.work.least_time(
        passes, ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    spent = [tr.kernel_time(d, KERNEL, ctx.window)[0] for d in ctx.devices]
    if ctx.steps <= 0 or min(spent) <= 0:
        return None
    return 100.0 * least / (sum(spent) / len(spent))
