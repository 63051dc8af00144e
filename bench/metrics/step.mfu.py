"""step.mfu: the least time the chips need for the traced steps' model
work at their peaks, over the traced window's length.

Work is counted from the configuration's shapes with nothing recomputed
(``bench/work/<family>.py``): each dense pass at the int8 peak, the highest
this chip offers for a product, each attention pass at the bf16 peak, and
either at HBM bandwidth where its bytes take longer. The conventional
figure (all products at the bf16 peak) goes on an earlier line.
"""


def read(ctx):
    lo, hi = ctx.window
    if ctx.steps <= 0 or hi <= lo:
        return None
    t, p, w = ctx.traffic, ctx.peaks, ctx.work
    seqs = t["global_batch"] // ctx.chips
    least = ctx.steps * (
        w.least_time(w.dense_passes(ctx.cfg, seqs * t["seq_len"]),
                     p["int8_ops_per_s"], p["hbm_bytes_per_s"])
        + w.least_time(w.attention_passes(ctx.cfg, seqs, t["seq_len"]),
                       p["bf16_flops_per_s"], p["hbm_bytes_per_s"]))
    work = w.step_work(ctx.cfg, t["global_batch"], t["seq_len"])
    flops = ctx.steps * (work["dense_int8_ops"] + work["attention_bf16_flops"])
    ctx.notes.append(f"step.mfu at the bf16 peak alone: "
                     f"{100.0 * flops / ((hi - lo) * ctx.chips * p['bf16_flops_per_s'])}"
                     f" % ({flops / ctx.steps} FLOP per step, {ctx.steps} "
                     f"steps in {hi - lo} s)")
    return 100.0 * least / (hi - lo)
