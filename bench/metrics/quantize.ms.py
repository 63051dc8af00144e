"""quantize.ms: device time per traced step of the ops under the program's
``adapt.quantize`` scope (master weights to words: the SR-quantize kernels
and the XLA around them), the mean over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"quantize"})
