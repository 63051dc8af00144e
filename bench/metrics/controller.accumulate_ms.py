"""controller.accumulate_ms: device time per traced step of the ops under
the program's ``adapt.accumulate`` scope (the controller's per-step
gradient statistics), the mean over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"accumulate"})
