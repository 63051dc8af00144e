"""AdaPT-SGD training loop (paper alg. 1), unified over all model families.

Hot path = ``train_step`` (jit):
    1. L̂ = Quantize(L, Q)           — master→quantized copy at current ⟨WL,FL⟩
    2. Ĝ, L = ForwardPass(L̂, batch) — loss incl. elastic-net + P penalty,
                                       grads taken AT the quantized weights
                                       (straight-through to the master copy)
    3. controller.accumulate         — windowed gradient-diversity stats
    4. SGDBackwardsPass(L, Ĝ)        — grad-normalize → ROP → optimizer on L

Cold path = ``precision_switch`` (jit, every `adapt_interval` steps):
    PushDown + PushUp + strategy/lookback/resolution adaptation (alg. 2).

The step never branches on ⟨WL,FL⟩ values — they are traced int32 arrays —
so precision switches never recompile (DESIGN.md §5.2).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import Config
from repro.core import controller, sparsity
from repro.core import fixed_point as fxp
from repro.data import synthetic
from repro.models import cnn, transformer
from repro.quant import qsgd
from repro.train import optimizer as opt_lib

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# State


def init_state(cfg: Config, key: Optional[Array] = None) -> Dict[str, Any]:
    key = key if key is not None else jax.random.PRNGKey(cfg.train.seed)
    m = cfg.model
    if m.family == "cnn":
        init_fn, _ = cnn.MODELS[m.name.replace("-smoke", "")]
        width = 0.25 if m.name.endswith("smoke") else 1.0
        params, stats = init_fn(key, num_classes=m.vocab_size, width=width)
    else:
        params = transformer.init_params(key, m)
        stats = {}
    adapt = (controller.init_adapt_state(params, cfg.quant)
             if cfg.quant.mode != "off" else {"tensors": {}})
    return {
        "params": params,
        "stats": stats,
        "opt": opt_lib.init_opt_state(params, cfg.optimizer),
        "adapt": adapt,
        "step": jnp.int32(0),
        "rng": key,
    }


# ---------------------------------------------------------------------------
# Family-specific loss


def _task_loss(cfg: Config, qparams, stats, batch, act_wl=None,
               train: bool = True):
    """Returns (task_loss, aux dict). aux may carry new stats / accuracy."""
    m = cfg.model
    if m.family == "cnn":
        _, fwd = cnn.MODELS[m.name.replace("-smoke", "")]
        with jax.named_scope("adapt.forward"):
            logits, new_stats = fwd(qparams, stats, batch["images"], train)
        with jax.named_scope("adapt.loss"):
            loss = cnn.ce_loss(logits, batch["labels"])
        return loss, {"stats": new_stats,
                      "acc": cnn.accuracy(logits, batch["labels"])}
    kwargs = {}
    if m.is_encoder:
        kwargs["embeds"] = batch["embeds"]
        targets, shift = batch["labels"], False
    else:
        kwargs["tokens"] = batch["tokens"]
        targets, shift = batch["tokens"], True
    if m.cross_attn_every:
        kwargs["memory"] = batch["memory"]
    # This forward sits under value_and_grad; every forward kernel carries
    # a custom VJP whose backward passes are themselves Pallas kernels, so
    # quant.use_pallas covers the differentiated train step end to end:
    # flash attention (_flash_dq/_dkv_kernel) AND the dense layers — with
    # container_dtype="int8_packed" the packed leaves survive to
    # models/common.dense, which streams int8 weight tiles into the fxp
    # matmul kernels (dx via the same tiles transposed, straight-through
    # dw = xᵀ@dy onto the master; tests/test_dense_path.py pins fwd+dx+dw
    # per dense layer and zero dequantized-weight XLA matmuls), and the MoE
    # experts through the grouped kernels (kernels/ops.fxp_gmm). Remaining
    # exclusions: dynamic-window attention slots (traced window → masked
    # XLA path in attend_full), the CNN family's conv forward, and
    # quantized leaves no kernel consumes (embed/conv weights — dequantized
    # at their use site).
    with jax.named_scope("adapt.forward"):
        out = transformer.forward(qparams, m, act_wl=act_wl,
                                  use_pallas=cfg.quant.use_pallas,
                                  remat=cfg.train.remat,
                                  with_moe_rows=bool(m.num_experts), **kwargs)
    logits, aux = out if m.num_experts else (out, {})
    with jax.named_scope("adapt.loss"):
        loss = transformer.lm_loss(logits, targets, shift=shift)
    return loss, {"stats": stats, **aux}


# ---------------------------------------------------------------------------
# Train step


def make_train_step(cfg: Config, qparam_shardings=None,
                    dp_axes: Tuple[str, ...] = ()) -> Callable:
    """``dp_axes``: mesh axes of a ``shard_map`` the step runs under, each
    device on its own batch shard; loss and gradients are averaged over
    them before the update (``data_parallel_step``).

    ``qparam_shardings``: optional NamedSharding tree for the quantized
    copy. Without it GSPMD may resolve the (sharded master × replicated SR
    noise) elementwise quantize to a REPLICATED output — i.e. all-gather the
    f32 master instead of the small quantized container (measured on
    granite-8b: the 96 GiB/step gather didn't shrink under a bf16 container
    until this constraint pinned it; EXPERIMENTS.md §Perf). Under
    ``quant.use_pallas`` + ``quant.fused_prng``, eligible leaves —
    unsharded, per-layer-stacked, AND evenly-sharded (the kernel wraps
    itself in sharding.shard_map with per-shard seeds, since pallas_call
    cannot be partitioned by GSPMD) — draw the SR noise inside the
    quantize kernel: no noise tensor, one fewer param-sized HBM round
    trip, zero collectives. Only unevenly-sharded or RTN-mode leaves keep
    the noise+constraint XLA path (controller._use_fused_prng)."""
    qcfg, ocfg, tcfg = cfg.quant, cfg.optimizer, cfg.train

    def train_step(state: Dict[str, Any], batch: Dict[str, Array]
                   ) -> Tuple[Dict[str, Any], Dict[str, Array]]:
        step_key = jax.random.fold_in(state["rng"], state["step"])
        params = state["params"]
        adapt = state["adapt"]

        act_wl = None
        packed = False
        if qcfg.mode != "off":
            with jax.named_scope("adapt.quantize"):
                qkey = step_key if qcfg.stochastic_rounding else None
                if qcfg.container_dtype == "int8_packed" and \
                        cfg.model.family != "cnn":
                    # native-int8 wire format: weights cross the mesh as int8,
                    # dequantized inside the scan body after the per-layer
                    # gather (§Perf / DESIGN §3)
                    packed = True
                    qparams = controller.quantize_params_packed(
                        params, adapt, qcfg, qkey, shardings=qparam_shardings)
                else:
                    container = {"bfloat16": jnp.bfloat16,
                                 "int8": jnp.int8}.get(qcfg.container_dtype,
                                                       jnp.float32)
                    qparams = controller.quantize_params(
                        params, adapt, qcfg, qkey, dtype=container,
                        shardings=qparam_shardings)
                    if qparam_shardings is not None:
                        qparams = jax.lax.with_sharding_constraint(
                            qparams, qparam_shardings)
                if cfg.model.family != "cnn" and qcfg.quantize_activations:
                    act_wl = transformer.act_wl_from_state(adapt)
        else:
            qparams = params

        def loss_fn(qp, mb):
            task, aux = _task_loss(cfg, qp, state["stats"], mb, act_wl)
            if qcfg.mode != "off":
                # reg terms on an eagerly-unpacked view: elementwise +
                # scalar reductions only, so it stays fully sharded (no
                # gathers); its cotangents add onto the same wrefs.
                with jax.named_scope("adapt.regularize"):
                    reg_tree = fxp.unpack_tree(qp) if packed else qp
                    full = sparsity.adapt_loss(
                        task, reg_tree, adapt, alpha=ocfg.l1, beta=ocfg.l2,
                        penalty_coef=ocfg.penalty_coef, max_wl=qcfg.max_wl)
            else:
                full = task
            return full, (task, aux)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True,
                                     allow_int=packed)
        strip = controller.strip_packed_grads if packed else (lambda g: g)

        def compute_grads(qp, b):
            if tcfg.accum_steps > 1:
                # microbatch scan: live activations shrink by accum_steps
                # while the global batch (AdaPT's per-batch semantics) stays.
                mb_batch = _microbatch(b, tcfg.accum_steps)

                def accum_body(carry, mb):
                    g_acc, l_acc, t_acc = carry
                    (loss, (task, aux)), g = grad_fn(qp, mb)
                    g_acc = jax.tree.map(
                        lambda a, x: a + x.astype(a.dtype), g_acc, strip(g))
                    return (g_acc, l_acc + loss, t_acc + task), aux

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, _accum_dtype(tcfg)), params)
                (g, loss, task), auxes = jax.lax.scan(
                    accum_body, (g0, jnp.float32(0.0), jnp.float32(0.0)),
                    mb_batch)
                inv = 1.0 / tcfg.accum_steps
                g = jax.tree.map(lambda x: (x * inv).astype(jnp.float32), g)
                aux = jax.tree.map(lambda a: a[-1], auxes)
                if "moe_rows_held" in aux:   # counted over the microbatches
                    aux.update(moe_rows_held=jnp.sum(auxes["moe_rows_held"]),
                               moe_rows_max=jnp.max(auxes["moe_rows_max"]))
                return loss * inv, task * inv, aux, g
            (loss, (task, aux)), g = grad_fn(qp, b)
            return loss, task, aux, strip(g)

        if tcfg.qsgd_pod_compression:
            # grads stay pod-local inside a shard_map manual over "pod"
            # (auto over data/model); the cross-pod reduce ships int8 (QSGD)
            # — 4× less traffic on the slowest links (quant/qsgd.py).
            from repro import sharding as shd
            from jax.sharding import PartitionSpec as P
            mesh = shd.current_mesh()
            rules = shd.strip_axes(
                dict(shd._RULES.get()[1]), ("pod",))

            def pod_local(qp, b):
                with shd.use_rules(mesh, rules):
                    loss, task, aux, g = compute_grads(qp, b)
                g = qsgd.psum_compressed(g, step_key, "pod", tcfg.qsgd_bits)
                npods = jax.lax.psum(1, "pod")
                g = jax.tree.map(lambda x: x / npods, g)
                return (jax.lax.pmean(loss, "pod"),
                        jax.lax.pmean(task, "pod"), aux, g)

            loss, task, aux, grads = shd.shard_map(
                pod_local, mesh, axis_names={"pod"},
                in_specs=(P(), P("pod")), out_specs=P(),
                check=False)(qparams, batch)
        else:
            loss, task, aux, grads = compute_grads(qparams, batch)
        if dp_axes:       # equal shards: the mean of means is the global mean
            with jax.named_scope("adapt.grad_sync"):
                loss, task, grads = jax.lax.pmean((loss, task, grads),
                                                  dp_axes)
                if "moe_rows_held" in aux:
                    aux = dict(aux, moe_rows_held=jax.lax.psum(
                        aux["moe_rows_held"], dp_axes), moe_rows_max=
                        jax.lax.pmax(aux["moe_rows_max"], dp_axes))

        if qcfg.mode != "off":
            with jax.named_scope("adapt.accumulate"):
                adapt = controller.accumulate(adapt, grads, task)
        with jax.named_scope("adapt.update"):
            if qcfg.mode != "off":
                grads = opt_lib.normalize_grads(grads, set(adapt["tensors"]))
            grads = opt_lib.clip_by_global_norm(grads, ocfg.grad_clip)
            opt = opt_lib.rop_update(state["opt"], task, ocfg)
            params, opt = opt_lib.apply_updates(params, grads, opt, ocfg)
            grad_norm = _global_norm(grads)

        metrics = {"loss": task, "full_loss": loss, "lr": opt["lr"],
                   "grad_norm": grad_norm}
        for k in ("acc", "moe_rows_held", "moe_rows_max"):
            if k in aux:
                metrics[k] = aux[k]
        new_state = {
            "params": params,
            "stats": aux.get("stats", state["stats"]),
            "opt": opt,
            "adapt": adapt,
            "step": state["step"] + 1,
            "rng": state["rng"],
        }
        return new_state, metrics

    return train_step


def _microbatch(batch: Dict[str, Array], accum: int) -> Dict[str, Array]:
    """(B, ...) → (accum, B/accum, ...), microbatch dim sharded like batch."""
    from repro import sharding

    def visit(a):
        mb = a.reshape((accum, a.shape[0] // accum) + a.shape[1:])
        return sharding.shard(mb, None, "batch", *([None] * (a.ndim - 1)))

    return jax.tree.map(visit, batch)


def _accum_dtype(tcfg):
    return jnp.bfloat16 if tcfg.accum_dtype == "bfloat16" else jnp.float32


def _global_norm(grads) -> Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)))


def make_precision_switch(cfg: Config) -> Callable:
    qcfg = cfg.quant

    def precision_switch(state: Dict[str, Any]) -> Dict[str, Any]:
        adapt = controller.precision_switch(state["adapt"], state["params"],
                                            qcfg)
        return dict(state, adapt=adapt)

    return precision_switch


# ---------------------------------------------------------------------------
# Data dispatch


def make_batch(cfg: Config, step: int) -> Dict[str, Array]:
    if cfg.model.family == "cnn":
        return synthetic.cifar_batch(cfg.model.vocab_size,
                                     cfg.train.global_batch, step,
                                     cfg.train.seed)
    return synthetic.lm_batch(cfg, step)


# ---------------------------------------------------------------------------
# Data parallelism over a mesh


def data_parallel_step(cfg: Config, mesh, state_shapes, batch_shapes):
    """The train step and the precision switch, data parallel over
    ``mesh``: the state is placed by launch/mesh's ``state_shardings`` and
    must come out replicated (every parameter below ``FSDP_THRESHOLD`` and
    no model-axis split), the batch is split over the data axes by
    ``batch_shardings``. GSPMD cannot partition a Mosaic kernel, so each
    device runs the whole step on its batch shard inside ``shard_map``
    (manual over every mesh axis) and the step averages loss and gradients
    over the data axes; the activation-quantize ranges are per shard.
    Returns (step, switch, state shardings, batch shardings), jitted with
    the state donated."""
    from jax.sharding import PartitionSpec as P

    from repro import sharding
    from repro.launch import mesh as mesh_lib
    state_sh = mesh_lib.state_shardings(state_shapes, cfg, mesh)
    split = [jax.tree_util.keystr(path) for path, s in
             jax.tree_util.tree_flatten_with_path(state_sh)[0]
             if not s.is_fully_replicated]
    if split:
        raise ValueError(f"data_parallel_step: state leaves {split[:3]} are "
                         "sharded; data parallelism needs them replicated")
    batch_sh = mesh_lib.batch_shardings(batch_shapes, mesh)
    dp = mesh_lib.dp_axes(mesh)
    axes = set(mesh.axis_names)
    step = sharding.shard_map(
        make_train_step(cfg, dp_axes=dp), mesh, axis_names=axes,
        in_specs=(P(), P(dp)), out_specs=(P(), P()))
    switch = sharding.shard_map(make_precision_switch(cfg), mesh,
                                axis_names=axes, in_specs=P(),
                                out_specs=P())
    return (jax.jit(step, in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None), donate_argnums=0),
            jax.jit(switch, in_shardings=(state_sh,),
                    out_shardings=state_sh, donate_argnums=0),
            state_sh, batch_sh)


# ---------------------------------------------------------------------------
# Host-side driver (one process; data parallel when given a mesh)


def train(cfg: Config, *, steps: Optional[int] = None,
          state: Optional[Dict[str, Any]] = None,
          checkpoint_mgr=None, watchdog=None,
          log: Callable[[str], None] = print,
          telemetry: Optional[list] = None,
          metrics_logger=None, preemption_guard=None,
          heartbeat=None, mesh=None,
          step_fn: Optional[Callable] = None,
          trace: Optional[Tuple[str, int, int]] = None
          ) -> Tuple[Dict[str, Any], list]:
    """Run the loop; returns (state, history). ``telemetry`` (if a list)
    collects per-switch controller snapshots for the paper's perf model;
    ``metrics_logger`` (train.metrics.MetricsLogger) streams JSONL.

    ``dt`` (in ``history``, ``metrics_logger.log_step`` and the
    ``watchdog``'s samples) is the wall time per step between two host
    syncs: the loss reads every ``log_every`` steps. It covers everything
    between the two reads (batches, steps, switches, checkpoint saves) over
    the steps in between; the first also covers the step's compile.

    ``trace``: (directory, first, stop) profiles steps ``first`` to
    ``stop - 1`` into ``directory`` with ``jax.profiler``. Each step is a
    ``StepTraceAnnotation`` ("train") holding the host spans
    ``train.batch``, ``train.step``, ``train.switch``, ``train.log_read``
    and ``train.checkpoint``, on the device trace's clock; the device ops
    carry the step's ``adapt.*`` scopes.

    ``mesh``: train data parallel over it (``data_parallel_step``); state
    and batches are placed on it. ``step_fn``: an already-compiled step
    (``jax.jit(...).lower(...).compile()`` of the same program) to run in
    place of the one built here.

    ``preemption_guard`` (fault_tolerance.PreemptionGuard): checked after
    every step — a SIGTERM triggers one final checkpoint save (when a
    ``checkpoint_mgr`` is present) and a clean early return, honoring the
    preempt→final-checkpoint contract INSIDE the loop rather than after
    all ``steps`` complete. ``heartbeat`` (fault_tolerance.Heartbeat)
    emits liveness lines on its own interval."""
    steps = steps if steps is not None else cfg.train.steps
    if state is None:
        state = init_state(cfg)
    batch_sh = None
    if mesh is not None:
        jitted, switch_fn, state_sh, batch_sh = data_parallel_step(
            cfg, mesh, jax.eval_shape(lambda: state),
            jax.eval_shape(lambda: make_batch(cfg, 0)))
        state = jax.device_put(state, state_sh)
    else:
        jitted = jax.jit(make_train_step(cfg), donate_argnums=0)
        switch_fn = jax.jit(make_precision_switch(cfg), donate_argnums=0)
    step_fn = step_fn or jitted
    if cfg.quant.mode == "off":
        switch_fn = None
    interval = cfg.train.adapt_interval or cfg.quant.lb_lwr

    tp = jax.profiler
    trace_dir, trace_first, trace_stop = trace or (None, -1, -1)
    tracing = False
    history = []
    start_step = int(state["step"])
    t_sync, synced = time.perf_counter(), start_step
    try:
        for i in range(start_step, start_step + steps):
            if i == trace_first:
                tp.start_trace(trace_dir)
                tracing = True
            with tp.StepTraceAnnotation("train", step_num=i):
                with tp.TraceAnnotation("train.batch"):
                    batch = make_batch(cfg, i)
                    if batch_sh is not None:
                        batch = jax.device_put(batch, batch_sh)
                with tp.TraceAnnotation("train.step"):
                    state, metrics = step_fn(state, batch)
                if switch_fn is not None and (i + 1) % interval == 0:
                    with tp.TraceAnnotation("train.switch"):
                        state = switch_fn(state)
                        if telemetry is not None or \
                                metrics_logger is not None:
                            snap = controller.snapshot(state["adapt"])
                            if telemetry is not None:
                                telemetry.append(snap)
                            if metrics_logger is not None:
                                metrics_logger.log_switch(i + 1, snap)
                if (i + 1) % max(cfg.train.log_every, 1) == 0:
                    with tp.TraceAnnotation("train.log_read"):
                        m = {k: float(v) for k, v in metrics.items()}
                    now = time.perf_counter()
                    dt = (now - t_sync) / (i + 1 - synced)
                    t_sync, synced = now, i + 1
                    if watchdog is not None:
                        watchdog.observe(i, dt)
                    history.append({"step": i + 1, **m, "dt": dt})
                    if metrics_logger is not None:
                        metrics_logger.log_step(i + 1, m, dt=dt)
                    log(f"step {i + 1:5d} loss={m['loss']:.4f} "
                        f"lr={m['lr']:.4g} "
                        + (f"acc={m['acc']:.3f} " if "acc" in m else "")
                        + f"({dt * 1e3:.0f} ms)")
                if checkpoint_mgr is not None and \
                        cfg.train.checkpoint_every and \
                        (i + 1) % cfg.train.checkpoint_every == 0:
                    with tp.TraceAnnotation("train.checkpoint"):
                        checkpoint_mgr.save(state, step=i + 1)
                if heartbeat is not None:
                    heartbeat.beat(i + 1,
                                   extra=f"loss={float(metrics['loss']):.4f}")
                if preemption_guard is not None and \
                        preemption_guard.requested:
                    log(f"[preempt] SIGTERM at step {i + 1}: saving final "
                        "checkpoint and exiting")
                    if checkpoint_mgr is not None:
                        with tp.TraceAnnotation("train.checkpoint"):
                            checkpoint_mgr.save(state, step=i + 1)
                            checkpoint_mgr.wait()
                    break
            if tracing and i + 1 == trace_stop:
                jax.block_until_ready(state)
                tp.stop_trace()
                tracing = False
    finally:
        if tracing:
            tp.stop_trace()
    return state, history
