#!/usr/bin/env python3
"""Smoke run of the AdaPT train step, with its Pallas kernels compiled for
the TPU, at SmolLM-360M width (32 layers, d=960, 15/5 heads, vocab 49152,
seq 2048, random weights from a seed).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data parallel over four chips only

One chip, through the normal entry points:
  0. the hardware-PRNG quantize words (on the SR grid, unbiased,
     deterministic per seed) and compiled kernels with partial boundary
     blocks (finite, on their oracles); each attention slot's live and
     total flash blocks;
  1. the step-0 logits and loss against the plain float32 reference
     (``models/reference.py``, matmuls at "highest" precision) on the
     dequantized words the step draws;
  2. six steps through ``train_loop.train`` with a precision switch (the
     EDF-ladder kernel) every two steps — every loss finite;
  3. three round-to-nearest steps against the XLA dispatch
     (``quant.use_pallas=false``);
  4. four greedy requests (32-token prompts, 16 new tokens) through
     ``ContinuousBatcher`` from the trained state — every one ends ``ok``.

``--chips 4``: the same step data parallel on a ("data", "model") = (4, 1)
mesh against the one-chip run of the same global batch, and nothing else.

Each result line names the device it ran on. The last line of standard
output is a JSON object with ``ok`` and the device. With no TPU the script
exits nonzero before doing any work and prints no result.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
OVERRIDES = ["quant.use_pallas=true", "quant.container_dtype=int8_packed",
             "train.seq_len=2048", "train.accum_steps=1",
             "train.adapt_interval=2", "train.remat=full", "train.log_every=1"]
# The largest that fits one v5e (15.75 GiB): the step takes 2.31 GiB of
# arguments and 9.27 GiB of temporaries at 8, 14.49 GiB at 16.
GLOBAL_BATCH = 8
TRAIN_STEPS = 6
TIMED_STEPS = 3
# Tolerances, fixed before the first chip run. The logits bound is twice
# what bf16 activations through 32 layers gave against the reference on the
# CPU (rel. L2 0.038 with either dispatch); a wrong kernel gives ~1.4.
LOGITS_REL_L2 = 0.08
LOSS_ABS = 1e-2
# f32 kernels against f32 oracles at "highest": the MXU's f32 passes leave
# ~1e-3; a garbage boundary block is off by order one
TAIL_REL_L2 = 1e-2
# the RTN trajectory tolerances of tests/test_dense_path.py
RTN_LOSS = dict(rtol=2e-3, atol=2e-3)
RTN_GRAD = dict(rtol=2e-2, atol=2e-2)


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def log(label, msg):
    print(f"[{label}] {msg}", flush=True)


def smollm_config(*extra):
    from repro.config import load_config
    return load_config("smollm-360m", overrides=OVERRIDES + [
        f"train.global_batch={GLOBAL_BATCH}", *extra])


def count_custom_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def _allclose(a, b, rtol, atol):
    return abs(a - b) <= atol + rtol * abs(b)


# ---------------------------------------------------------------------------
# One chip


def kernel_phase(label):
    """What interpret mode cannot show: the hardware-PRNG words and the
    compiled kernels' partial boundary blocks."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import flash_attention as fa
    from repro.kernels import fxp_matmul as fm
    from repro.kernels import ref
    from repro.kernels import sr_quantize as sq

    def sr_stats(q, x, fl):
        """(every word is the floor or ceil of x·2^fl, clipped to int8;
        mean rounding error in units of the word's LSB)."""
        s = x * jnp.ldexp(jnp.float32(1.0), fl)
        lo = jnp.clip(jnp.floor(s), -128, 127)
        hi = jnp.clip(jnp.floor(s) + 1, -128, 127)
        qf = q.astype(jnp.float32)
        inside = (s > -128) & (s < 127)
        err = jnp.where(inside, qf - s, 0.0)
        return (jnp.all((qf == lo) | (qf == hi)),
                jnp.sum(err) / jnp.sum(inside))

    x = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (960, 2560))
    quant = jax.jit(lambda x, seed: sq.sr_quantize_fused_int8(
        x, seed, jnp.int32(9), hw_prng=True))
    q = quant(x, jnp.int32(7))
    on_grid, bias = sr_stats(q, x, 9)
    same = bool(jnp.all(quant(x, jnp.int32(7)) == q))
    moved = float(jnp.mean(quant(x, jnp.int32(8)) != q))
    xs = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (4, 960, 320))
    fls = jnp.array([6, 7, 8, 9], jnp.int32)
    qs = jax.jit(lambda x: sq.sr_quantize_fused_stacked_int8(
        x, jnp.int32(3), fls, hw_prng=True))(xs)
    stacked = [sr_stats(qs[i], xs[i], fls[i]) for i in range(4)]
    # SR error per word is within (-1, 1) with variance ≤ 1/4: 8σ bound
    limit = 8 * 0.5 / x.size ** 0.5
    log(label, f"hw_prng words: on_grid={bool(on_grid)} mean_err_lsb="
        f"{float(bias):.2e} (limit {limit:.1e}) same_seed_identical={same} "
        f"new_seed_moved={moved:.3f} stacked_on_grid="
        f"{[bool(g) for g, _ in stacked]}")
    check(bool(on_grid) and all(bool(g) for g, _ in stacked),
          "hw_prng word off the SR grid")
    check(abs(float(bias)) <= limit and all(
        abs(float(b)) <= 8 * 0.5 / (960 * 320) ** 0.5 for _, b in stacked),
        "hw_prng rounding is biased")
    check(same and moved > 0.1, "hw_prng stream not deterministic per seed")

    # partial boundary blocks in every dim: 1000 = 3·256 + 232 = 512 + 488
    # (the shape rule would take 1000 whole, so the blocks are requested)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    xm = jax.random.normal(k1, (1000, 1000))
    wq = jax.random.randint(k2, (1000, 1000), -128, 128).astype(jnp.int8)
    sc = jnp.float32(2.0 ** -7)
    dy = jax.random.normal(k3, (1000, 1000))
    blocks = dict(bm=256, bn=256, bk=512)

    def mm(x):
        return fm.fxp_dense_vjp(x, wq, sc, jnp.zeros(wq.shape, jnp.float32),
                                **blocks)

    y, vjp = jax.vjp(mm, xm)
    dx, = vjp(dy)
    dw = jax.grad(lambda w: jnp.sum(fm.fxp_dense_vjp(
        xm, wq, sc, w, **blocks) * dy))(jnp.zeros(wq.shape, jnp.float32))
    q = jax.random.normal(k4, (1, 1000, 3, 64))
    kv = jax.random.normal(k1, (1, 1000, 1, 64))
    o, avjp = jax.vjp(lambda a, b, c: fa.flash_attention_vjp(a, b, c), q, kv,
                      kv)
    grads = avjp(o)
    with jax.default_matmul_precision("highest"):
        want = [ref.ref_fxp_matmul(xm, wq, sc), ref.ref_matmul_dx(dy, wq, sc),
                ref.ref_matmul_dw(xm, dy), ref.ref_attention(q, kv, kv)]
        want += list(ref.ref_attention_grads(q, kv, kv, o))
    got = [y, dx, dw, o, *grads]
    rels = [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
            for g, w in zip(got, want)]
    finite = all(bool(jnp.all(jnp.isfinite(g))) for g in got)
    log(label, f"tail blocks (1000-wide, blocks 256/512): finite={finite} "
        f"rel_l2 fwd/dx/dw={rels[:3]} attn o/dq/dk/dv={rels[3:]}")
    check(finite, "non-finite output from a compiled tail block")
    check(max(rels) <= TAIL_REL_L2, f"tail-block output off the oracle: "
          f"{rels}")


def reference_phase(label, cfg, state, batch):
    """Step-0 logits and loss of the system against the float32 reference,
    on the words the step-0 quantize draws. Returns the reference loss."""
    import jax
    import jax.numpy as jnp

    from repro.core import controller
    from repro.models import reference, transformer

    m = cfg.model
    key0 = jax.random.fold_in(state["rng"], state["step"])
    qp = jax.jit(lambda p, a: controller.quantize_params_packed(
        p, a, cfg.quant, key0))(state["params"], state["adapt"])
    awl = transformer.act_wl_from_state(state["adapt"])

    def compare(qp, tokens):
        w = reference.dequantize(qp)
        got = transformer.forward(qp, m, tokens=tokens, use_pallas=True,
                                  remat=cfg.train.remat)
        with jax.default_matmul_precision("highest"):
            want = reference.forward(w, m, tokens)
            loss = reference.lm_loss(
                reference.forward(w, m, tokens, awl["s0_attn"]), tokens)
        rel = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
        return rel, loss, jnp.all(jnp.isfinite(got))

    t0 = time.perf_counter()
    rel, loss, finite = jax.jit(compare)(qp, batch["tokens"])
    rel, loss = float(rel), float(loss)
    log(label, f"reference: logits rel_l2={rel:.6f} (limit {LOGITS_REL_L2}) "
        f"ref_loss={loss:.6f} ({time.perf_counter() - t0:.1f} s incl. "
        "compile)")
    check(bool(finite), "non-finite step-0 logits")
    check(rel <= LOGITS_REL_L2, f"step-0 logits off the reference: {rel}")
    return loss


def train_phase(label, cfg, state):
    import jax

    from repro import jaxpr_tools
    from repro.train import train_loop

    batch = train_loop.make_batch(cfg, 0)
    step = jax.jit(train_loop.make_train_step(cfg), donate_argnums=0)
    t0 = time.perf_counter()
    lowered = step.lower(state, batch)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    n_kernels = count_custom_calls(compiled.as_text())
    log(label, f"train step: lower_s={t1 - t0:.2f} compile_s={t2 - t1:.2f} "
        f"tpu_custom_call={n_kernels}")
    check(n_kernels > 0, "the compiled train step holds no Mosaic kernel")
    switch = train_loop.make_precision_switch(cfg)
    n_edf = jaxpr_tools.count_pallas_calls(
        jax.make_jaxpr(switch)(state).jaxpr, "_edf_ladder_kernel")
    check(n_edf > 0, "precision switch does not run the EDF-ladder kernel")

    telemetry = []
    state, history = train_loop.train(
        cfg, steps=TRAIN_STEPS, state=state, step_fn=compiled,
        telemetry=telemetry, log=lambda s: log(label, s))
    losses = [h["loss"] for h in history]
    check(len(losses) == TRAIN_STEPS and
          all(l == l and abs(l) != float("inf") for l in losses),
          f"non-finite training loss: {losses}")
    check(len(telemetry) >= 2, f"only {len(telemetry)} precision switches")
    wls = sorted({int(w) for snap in telemetry[-1:]
                  for t in snap.values() for w in t["wl"].reshape(-1)})
    log(label, f"train: {TRAIN_STEPS} steps, losses={losses}, "
        f"precision switches={len(telemetry)} (EDF kernels per switch="
        f"{n_edf}), WLs after the last={wls}")

    state = timed_steps(label, cfg, compiled, state, TRAIN_STEPS)
    return state, history[0]["loss"]


def timed_steps(label, cfg, step, state, first, batch_sharding=None):
    """Median of ``TIMED_STEPS`` steps, each waited for; returns the
    state."""
    import jax

    from repro.train import train_loop

    times = []
    for i in range(first, first + TIMED_STEPS):
        b = train_loop.make_batch(cfg, i)
        if batch_sharding is not None:
            b = jax.device_put(b, batch_sharding)
        jax.block_until_ready(b)
        t0 = time.perf_counter()
        state, metrics = step(state, b)
        jax.block_until_ready(metrics)
        times.append(time.perf_counter() - t0)
    stats = jax.devices()[0].memory_stats() or {}
    tokens = cfg.train.global_batch * cfg.train.seq_len
    med = statistics.median(times)
    log(label, f"steady step_s={med:.4f} (median of {TIMED_STEPS}: "
        f"{[round(t, 4) for t in times]}) tokens/s={tokens / med:.0f} "
        f"peak_bytes_in_use(device 0)="
        f"{stats.get('peak_bytes_in_use', 'n/a')}")
    return state


def rtn_phase(label):
    """Round-to-nearest words are bit-identical across dispatches, so the
    kernel path and the XLA path must follow the same trajectory."""
    from repro.train import train_loop

    runs = {}
    for use_pallas in (True, False):
        cfg = smollm_config("quant.stochastic_rounding=false",
                            "train.adapt_interval=1000",
                            f"quant.use_pallas={str(use_pallas).lower()}")
        t0 = time.perf_counter()
        _, runs[use_pallas] = train_loop.train(cfg, steps=3,
                                               log=lambda s: None)
        log(label, f"rtn use_pallas={use_pallas}: "
            f"losses={[h['loss'] for h in runs[use_pallas]]} "
            f"grad_norms={[h['grad_norm'] for h in runs[use_pallas]]} "
            f"({time.perf_counter() - t0:.1f} s incl. compile)")
    for hp, hx in zip(runs[True], runs[False]):
        check(_allclose(hp["loss"], hx["loss"], **RTN_LOSS),
              f"RTN loss, kernels vs XLA: {hp['loss']} vs {hx['loss']}")
        check(_allclose(hp["grad_norm"], hx["grad_norm"], **RTN_GRAD),
              f"RTN grad norm: {hp['grad_norm']} vs {hx['grad_norm']}")


def serve_phase(label, cfg, state):
    import jax

    from repro.data import synthetic
    from repro.serve.scheduler import ContinuousBatcher, Status

    cb = ContinuousBatcher(cfg, state["params"], state["adapt"], slots=4)
    prompts = synthetic.lm_tokens(jax.random.PRNGKey(cfg.train.seed + 1), 4,
                                  32, cfg.model.vocab_size)
    for p in prompts.tolist():
        cb.submit(p, max_new_tokens=16)
    t0 = time.perf_counter()
    done = cb.run_until_drained(max_steps=1000)
    dt = time.perf_counter() - t0
    statuses = [r.status for r in done]
    log(label, f"serve: {len(done)} requests, statuses="
        f"{[s.value for s in statuses]}, "
        f"tokens={[len(r.output) for r in done]}, {dt:.1f} s incl. compile")
    check(len(done) == 4 and all(s == Status.OK for s in statuses),
          f"served requests did not all end ok: {statuses}")
    check(all(len(r.output) == 16 for r in done), "short generations")


def attention_blocks(label, cfg):
    """Each distinct attention slot's flash blocks at the step's sequence
    (512-blocks, what ``ops.attention`` launches with): live, fully live
    (computed without the mask) and all."""
    from repro.kernels import flash_attention as fa
    from repro.models import transformer

    s = cfg.train.seq_len
    slots, _ = transformer.build_plan(cfg.model)
    for w in sorted({sl.window for sl in slots if sl.kind == "attn"}):
        live, full, total = fa.block_counts(s, s, 512, 512, causal=True,
                                            window=w)
        log(label, f"flash blocks, window {w or 'full'} at seq {s}: live "
            f"{live}/{total}, fully live {full}")


def one_chip(label):
    from repro.train import train_loop

    kernel_phase(label)
    cfg = smollm_config()
    attention_blocks(label, cfg)
    state = train_loop.init_state(cfg)
    ref_loss = reference_phase(label, cfg, state,
                               train_loop.make_batch(cfg, 0))
    state, loss0 = train_phase(label, cfg, state)
    log(label, f"step-0 loss={loss0:.6f} vs reference {ref_loss:.6f} "
        f"(limit {LOSS_ABS})")
    check(abs(loss0 - ref_loss) <= LOSS_ABS, "step-0 loss off the reference")
    serve_phase(label, cfg, state)
    del state
    rtn_phase(label)


# ---------------------------------------------------------------------------
# Four chips


def gathers_feeding_kernels(hlo: str):
    """(all-gathers, Mosaic calls with an all-gathered operand) in an HLO
    module's text; an operand counts when it is an all-gather or a
    copy/bitcast/fusion of one."""
    from repro.roofline import hlo_costs

    ops = {op.name: op for comp in hlo_costs.parse_module(hlo).values()
           for op in comp.ops}

    def operands(op):
        return re.findall(r"%([\w.\-]+)", op.line.split(f" {op.kind}(", 1)[1])

    def gathered(name, depth=2):
        op = ops.get(name)
        if op is None:
            return False
        if op.kind.startswith("all-gather"):
            return True
        return depth > 0 and op.kind in ("copy", "bitcast", "fusion") and \
            any(gathered(x, depth - 1) for x in operands(op))

    gathers = [n for n, op in ops.items() if op.kind.startswith("all-gather")]
    fed = [n for n, op in ops.items()
           if 'custom_call_target="tpu_custom_call"' in op.line
           and any(gathered(x) for x in operands(op))]
    return gathers, fed


def four_chips(label):
    import jax

    from repro.launch import mesh as mesh_lib
    from repro.train import train_loop

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    cfg = smollm_config("train.adapt_interval=1000")
    mesh = mesh_lib.make_mesh((4, 1), ("data", "model"))
    state = train_loop.init_state(cfg)
    batch = train_loop.make_batch(cfg, 0)
    step, _, state_sh, batch_sh = train_loop.data_parallel_step(
        cfg, mesh, jax.eval_shape(lambda: state),
        jax.eval_shape(lambda: batch))
    state = jax.device_put(state, state_sh)
    t0 = time.perf_counter()
    compiled = step.lower(state, jax.device_put(batch, batch_sh)).compile()
    hlo = compiled.as_text()
    gathers, fed = gathers_feeding_kernels(hlo)
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "dp4_train_step.hlo.txt").write_text(hlo)
    log(label, f"dp (4,1) train step: compile_s={time.perf_counter() - t0:.2f}"
        f" tpu_custom_call={count_custom_calls(hlo)} all-gathers="
        f"{len(gathers)} kernels fed by an all-gather={len(fed)}")
    state, dp = train_loop.train(cfg, steps=3, state=state,
                                 step_fn=compiled, mesh=mesh,
                                 log=lambda s: log(label, "dp " + s))
    timed_steps(label, cfg, compiled, state, 3, batch_sh)
    del state

    dev = jax.devices()[0]
    one_label = f"{dev.platform} {dev.device_kind} x1"
    state = train_loop.init_state(cfg)
    one_step = jax.jit(train_loop.make_train_step(cfg),
                       donate_argnums=0).lower(state, batch).compile()
    state, one = train_loop.train(
        cfg, steps=3, state=state, step_fn=one_step,
        log=lambda s: log(one_label, "one-chip " + s))
    timed_steps(one_label, cfg, one_step, state, 3)
    for hd, ho in zip(dp, one):
        check(_allclose(hd["loss"], ho["loss"], **RTN_LOSS),
              f"dp loss {hd['loss']} vs one chip {ho['loss']}")
        check(_allclose(hd["grad_norm"], ho["grad_norm"], **RTN_GRAD),
              f"dp grad norm {hd['grad_norm']} vs one chip {ho['grad_norm']}")
    log(label, "dp matches the one-chip run: losses "
        f"{[h['loss'] for h in dp]} vs {[h['loss'] for h in one]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); this smoke run needs a TPU",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.cache import use_compile_cache
    cache = use_compile_cache()
    count = len(jax.devices()) if args.chips == 4 else 1
    label = f"{dev.platform} {dev.device_kind} x{count}"
    log(label, f"compile cache: {cache}")
    try:
        if args.chips == 4:
            four_chips(label)
        else:
            one_chip(label)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED on {label}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
