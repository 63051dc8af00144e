"""device.idle_share: the share of the traced window in which no operation
ran on a chip, the mean over the cell's chips."""
from bench import trace as tr


def read(ctx):
    lo, hi = ctx.window
    if hi <= lo:
        return None
    busy = [tr.busy(d, ctx.window) for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
