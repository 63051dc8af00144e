"""moe_step.mfu: ``step.mfu``'s share for a mixture-of-experts step: the
least time the chips need for the traced steps' model work at their peaks,
over the traced window's length, with the experts' passes counted at the
rows the shapes lead one to expect for the held experts.

Work from the configuration's shapes with nothing recomputed
(``bench/work/<family>.py``): each dense and expert pass at the int8 peak,
each attention pass at the bf16 peak, either at HBM bandwidth where its
bytes take longer. The conventional figure (all products at the bf16 peak)
goes on an earlier line.
"""


def read(ctx):
    lo, hi = ctx.window
    w = ctx.work
    if ctx.steps <= 0 or hi <= lo or not hasattr(w, "expert_passes"):
        return None
    t, p = ctx.traffic, ctx.peaks
    seqs = t["global_batch"] // ctx.chips
    tokens = seqs * t["seq_len"]
    int8 = (w.dense_passes(ctx.cfg, tokens)
            + w.expert_passes(ctx.cfg, w.held_rows(ctx.cfg, tokens)))
    least = ctx.steps * (
        w.least_time(int8, p["int8_ops_per_s"], p["hbm_bytes_per_s"])
        + w.least_time(w.attention_passes(ctx.cfg, seqs, t["seq_len"]),
                       p["bf16_flops_per_s"], p["hbm_bytes_per_s"]))
    work = w.step_work(ctx.cfg, seqs, t["seq_len"])
    flops = ctx.steps * sum(work.values())
    ctx.notes.append(f"moe_step.mfu at the bf16 peak alone: "
                     f"{100.0 * flops / ((hi - lo) * ctx.chips * p['bf16_flops_per_s'])}"
                     f" % ({flops / ctx.steps} FLOP per step, {ctx.steps} "
                     f"steps in {hi - lo} s)")
    return 100.0 * least / (hi - lo)
