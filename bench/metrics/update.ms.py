"""update.ms: device time per traced step of the ops under the program's
``adapt.regularize`` (elastic net and precision penalty, with their
gradient) and ``adapt.update`` scopes (normalize, clip, learning-rate
schedule, update, the metrics' gradient norm), the mean over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"regularize", "update"})
