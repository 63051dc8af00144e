"""moe.route_ms: device time per traced step of the XLA ops (no kernel)
under the program's ``adapt.moe_route`` scope: router logits, top-k, the
sort of the assignments by expert, the gather of rows into the experts'
buffer and the weighted combine back to the tokens, forward, remat
recompute and backward. The mean over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"moe_route"}, kernels=False)
