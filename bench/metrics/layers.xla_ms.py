"""layers.xla_ms: device time per traced step of the XLA ops (no kernel)
under the program's ``adapt.layers`` scope: norms, rope, SiLU, residuals,
activation quantize and layout copies of the decoder layers, forward, remat
recompute and backward. The dense and flash kernels there are the
rooflines' time. The mean over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"layers"}, kernels=False)
