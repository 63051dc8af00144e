"""head_loss.xla_ms: device time per traced step of the XLA ops (no kernel)
under the program's ``adapt.head`` and ``adapt.loss`` scopes: the final
norm, softcap, log-softmax and NLL over float32 logits, forward and
backward. The head's dense kernels are the dense roofline's time. The mean
over chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, {"head", "loss"}, kernels=False)
