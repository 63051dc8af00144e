"""Training cells (traffic ``kind: lm_train``): the AdaPT train step of a
dense decoder, driven in the order of ``train_loop.train``.

Set-up builds one object, ``Cell``: the compiled train step and precision
switch (from the persistent compile cache after the first run), the state
made on the device from the seed, and the batch feed. It drives that state
through the first three steps with the window's own call and feed, keeping
what the reference is compared on, then runs the window's compiled switch
once on that state with every tensor's window counted full, beside the same
switch on the XLA dispatch, and puts the controller state back as it was.
It hands the same state to the window. The window runs, for each step i:
the batch of step i made on the device from the seed, the step, the switch
after every ``adapt_interval`` steps, and a host read of the loss every
``log_every`` steps. After the window the program's state is freed and the
plain reference runs the same three steps.
"""
from __future__ import annotations

import math
import re
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

FIRST_STEPS = 3


def program_config(cfg: Dict, traffic: Dict):
    """The program's ``Config`` for a configuration file and a traffic
    mix."""
    from repro.config import Config, ModelConfig, apply_overrides

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only silu MLPs are run")
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError(f"{cfg['name']}: the program has no bias terms")
    model = ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], act_fn="silu")
    r = cfg["recipe"]
    overrides = list(cfg["program"]) + [
        f"quant.init_wl={r['init_wl']}", f"quant.init_fl={r['init_fl']}",
        f"optimizer.lr={r['lr']}", f"optimizer.l1={r['l1']}",
        f"optimizer.l2={r['l2']}",
        f"train.seq_len={traffic['seq_len']}",
        f"train.global_batch={traffic['global_batch']}",
        f"train.adapt_interval={traffic['adapt_interval']}",
        f"train.log_every={traffic['log_every']}"]
    return apply_overrides(Config(arch=cfg["name"], model=model), overrides)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under a name of the benchmark's, so that its program is found
    in a trace as ``jit_<name>``."""
    def wrapper(*args):
        return fn(*args)
    wrapper.__name__ = wrapper.__qualname__ = name
    return wrapper


SWITCH_NAME = "bench_precision_switch"


def precisions(adapt) -> Dict:
    """{tensor: (wl, fl)} of a controller state."""
    return {p: (ts["wl"], ts["fl"]) for p, ts in adapt["tensors"].items()}


class Cell:
    """One training cell on the devices JAX gives this process."""

    def __init__(self, cfg: Dict, traffic: Dict, *,
                 step_wrapper: Optional[Callable] = None,
                 switch_wrapper: Optional[Callable] = None):
        from repro.config import apply_overrides
        from repro.models import transformer
        from repro.train import train_loop

        self.cfg, self.traffic = cfg, traffic
        self.pcfg = program_config(cfg, traffic)
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        self.vocab = cfg["vocab_size"]
        self.chips = traffic["data_parallel"]
        self.interval = traffic["adapt_interval"]
        self.log_every = traffic["log_every"]
        self.param_shapes = jax.eval_shape(
            lambda: transformer.init_params(jax.random.PRNGKey(0),
                                            self.pcfg.model))
        self.paths = gen.leaf_paths(self.param_shapes)
        pcfg = self.pcfg

        def make_state(seed_words):
            params = gen.make_weights(
                jax.random.fold_in(seed_words, gen.WEIGHTS),
                self.param_shapes)
            rng = jax.random.fold_in(seed_words, gen.PROGRAM_RNG)
            # the program's own init of everything but the weights; its
            # weights are not used and XLA drops them
            return dict(train_loop.init_state(pcfg, rng), params=params)

        def feed(seed_words, i):
            return {"tokens": gen.step_tokens(
                jax.random.fold_in(seed_words, gen.TOKENS), i, self.batch,
                self.seq, self.vocab, traffic["noise"])}

        xla_switch = train_loop.make_precision_switch(
            apply_overrides(pcfg, ["quant.use_pallas=false"]))

        def switch_words(state):
            """Each quantized tensor's <WL, FL> after the switch on the XLA
            dispatch, the judge of the program's."""
            return precisions(xla_switch(state)["adapt"])

        words = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step_no = jax.ShapeDtypeStruct((), jnp.int32)
        state_shapes = jax.eval_shape(make_state, words)
        batch_shapes = jax.eval_shape(feed, words, step_no)
        if self.chips > 1:
            from repro.launch import mesh as mesh_lib
            if len(jax.devices()) < self.chips:
                raise RuntimeError(f"{self.chips} devices needed, "
                                   f"{len(jax.devices())} found")
            self.mesh = mesh_lib.make_mesh((self.chips, 1),
                                           ("data", "model"))
            step, switch, state_sh, batch_sh = train_loop.data_parallel_step(
                pcfg, self.mesh, state_shapes, batch_shapes)
            make_state_j = jax.jit(make_state, out_shardings=state_sh)
            feed_j = jax.jit(feed, out_shardings=batch_sh)
            switch_j = jax.jit(_named(switch, SWITCH_NAME),
                               in_shardings=(state_sh,),
                               out_shardings=state_sh, donate_argnums=0)
            words_j = jax.jit(switch_words, in_shardings=(state_sh,))
            self.devices = list(self.mesh.devices.flat)
            self.state_sh, self.batch_sh = state_sh, batch_sh
        else:
            self.mesh = None
            step = jax.jit(train_loop.make_train_step(pcfg),
                           donate_argnums=0)
            make_state_j, feed_j = jax.jit(make_state), jax.jit(feed)
            switch_j = jax.jit(_named(train_loop.make_precision_switch(pcfg),
                                      SWITCH_NAME), donate_argnums=0)
            words_j = jax.jit(switch_words)
            self.devices = [jax.devices()[0]]
        t0 = time.perf_counter()
        self.make_state = make_state_j.lower(words).compile()
        self.feed = feed_j.lower(words, step_no).compile()
        self.step = self.compiled_step = step.lower(
            state_shapes, batch_shapes).compile()
        self.switch = switch_j.lower(state_shapes).compile()
        self.switch_words = words_j.lower(state_shapes).compile()
        self.compile_s = time.perf_counter() - t0
        if step_wrapper is not None:
            self.step = step_wrapper(self.step, self)
        if switch_wrapper is not None:
            self.switch = switch_wrapper(self.switch, self)
        self.quantized = sorted(state_shapes["adapt"]["tensors"])
        self._change = jax.jit(self._change_norms)
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self._full = jax.jit(lambda t: {p: jnp.maximum(c, lb)
                                        for p, (c, lb) in t.items()})

    # -- pieces -------------------------------------------------------------

    def _change_norms(self, params, seed_words):
        """{leaf: |params - the seed's initial weights|}, the initial
        weights made again leaf by leaf rather than kept."""
        init = gen.make_weights(jax.random.fold_in(seed_words, gen.WEIGHTS),
                                self.param_shapes)
        diff = jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b))), params, init)
        return {"/".join(str(k.key) for k in p): v for p, v in
                jax.tree_util.tree_flatten_with_path(diff)[0]}

    def change_norms(self, params, seed_words) -> Dict[str, float]:
        return {k: float(v) for k, v in
                jax.device_get(self._change(params, seed_words)).items()}

    def fresh_state(self, seed: int):
        return self.make_state(gen.seed_key(seed))

    def first_steps(self, state, seed: int):
        """Drive ``state`` through steps 0..2 with the window's call and
        feed. Returns (state, {"losses", "grad", "change"})."""
        words = gen.seed_key(seed)
        losses, grad = [], {}
        for i in range(FIRST_STEPS):
            state, m = self.step(state, self.feed(words, jnp.int32(i)))
            losses.append(float(m["loss"]))
            if i == 0:
                lr = float(state["opt"]["lr"])
                moved = self.change_norms(state["params"], words)
                sums = jax.device_get({p: state["adapt"]["tensors"][p]
                                       ["norm_sum"] for p in self.quantized})
                for p in self.paths:
                    if p in sums:   # the controller's record of |g| per layer
                        grad[p] = float(np.sqrt(np.sum(
                            np.square(np.asarray(sums[p], np.float64)))))
                    else:           # taken whole: w1 = w0 - lr g
                        grad[p] = moved[p] / lr
        change = self.change_norms(state["params"], words)
        return state, {"losses": losses, "grad": grad, "change": change}

    def check_switch(self, state):
        """Run the window's compiled switch once on ``state`` with every
        tensor's window counted full, as at the switch the cadence calls
        for, and the same switch on the XLA dispatch. The controller state
        goes back to what it was (the switch moves no weight), so the window
        keeps ``train_loop.train``'s order. Returns (state, {"program",
        "xla", "before"}), each {tensor: [[wl, fl], ...]}."""
        adapt = state["adapt"]
        kept = self._copy(adapt)
        counts = self._full({p: (ts["count"], ts["lb"])
                             for p, ts in adapt["tensors"].items()})
        full = dict(state, adapt=dict(adapt, tensors={
            p: dict(ts, count=counts[p])
            for p, ts in adapt["tensors"].items()}))
        want = self.switch_words(full)
        before = precisions(kept)
        out = self.switch(full)               # donates ``full``
        got = precisions(out["adapt"])
        words = jax.device_get({"program": got, "xla": want,
                                "before": before})
        return dict(out, adapt=kept), {
            k: {p: np.stack([np.ravel(wl), np.ravel(fl)], 1).tolist()
                for p, (wl, fl) in v.items()} for k, v in words.items()}

    def step_bytes(self) -> Optional[int]:
        """Device memory of the compiled train step on one chip: arguments,
        outputs and temporaries, less what the donated state shares."""
        m = self.compiled_step.memory_analysis()
        if m is None:
            return None
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)

    # -- the window ---------------------------------------------------------

    def window(self, state, seed: int, seconds: float, first: int,
               trace_dir: Optional[str] = None):
        """Run steps from ``first`` until ``seconds`` have passed; with
        ``trace_dir``, trace the ``log_every`` steps that hold the first
        switch. Returns (state, stats)."""
        words = gen.seed_key(seed)
        tp = jax.profiler
        lo = None
        if trace_dir is not None:
            s1 = ((first // self.interval) + 1) * self.interval - 1
            lo = max(first, (s1 // self.log_every) * self.log_every)
            hi = lo + self.log_every
        compiles = []
        listener = _compile_listener(compiles)
        losses, switches, traced = [], 0, None
        i = first
        span = None
        t0 = time.perf_counter()
        try:
            while True:
                if i == lo:
                    tp.start_trace(trace_dir)
                    span = tp.TraceAnnotation("bench.window")
                    span.__enter__()
                    traced = {"steps": 0, "switches": 0}
                with tp.TraceAnnotation("bench.batch"):
                    batch = self.feed(words, jnp.int32(i))
                with tp.TraceAnnotation("bench.step"):
                    state, m = self.step(state, batch)
                if (i + 1) % self.interval == 0:
                    with tp.TraceAnnotation("bench.switch"):
                        state = self.switch(state)
                    switches += 1
                    if span is not None:
                        traced["switches"] += 1
                if (i + 1) % self.log_every == 0:
                    with tp.TraceAnnotation("bench.log_read"):
                        losses.append(float(m["loss"]))
                i += 1
                if span is not None:
                    traced["steps"] += 1
                    if i == hi:
                        jax.block_until_ready(state)
                        span.__exit__(None, None, None)
                        span = None
                        tp.stop_trace()
                        lo = -1
                if time.perf_counter() - t0 >= seconds and lo in (None, -1):
                    break
            jax.block_until_ready((state, m))
        finally:
            if span is not None:
                span.__exit__(None, None, None)
                tp.stop_trace()
            _drop_listener(listener)
        wall = time.perf_counter() - t0
        steps = i - first
        return state, {"steps": steps, "wall_s": wall, "switches": switches,
                       "losses": losses, "compiles": len(compiles),
                       "traced": traced,
                       "failed": sum(not math.isfinite(v) for v in losses)}

    # -- the reference ------------------------------------------------------

    def reference(self, seed: int, rows: Optional[int] = None,
                  shards: Optional[int] = None) -> Dict:
        """The plain reference's readings for the same three steps, on the
        first ``rows`` rows of each batch (all by default), with the
        activation range taken per ``shards`` of rows (per chip by
        default, as the data-parallel step takes it)."""
        from bench.reference import dense_decoder as ref

        words = gen.seed_key(seed)
        dev = jax.devices()[0]
        rows = rows or self.batch
        with jax.default_device(dev):
            params = jax.jit(lambda w: gen.make_weights(
                jax.random.fold_in(w, gen.WEIGHTS), self.param_shapes))(words)
            feed = jax.jit(lambda w, i: gen.step_tokens(
                jax.random.fold_in(w, gen.TOKENS), i, self.batch, self.seq,
                self.vocab, self.traffic["noise"])[:rows])
            batches = [feed(words, jnp.int32(i)) for i in range(FIRST_STEPS)]
            sr = jax.random.fold_in(words, gen.REFERENCE_SR)
            keys = [jax.random.fold_in(sr, i) for i in range(FIRST_STEPS)]
            order = {p: i for i, p in enumerate(self.paths)}
            losses, grad, params = ref.run(
                params, batches, keys, self.cfg, self.cfg["recipe"],
                shards or min(self.chips, rows), order, self.devices)
            change = self.change_norms(params, words)
        return {"losses": losses, "grad": grad, "change": change}


def _compile_listener(sink: list):
    def on_event(name, *args, **kwargs):
        if re.search(r"backend_compile", name):
            sink.append(name)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return on_event


def _drop_listener(fn):
    jax.monitoring.unregister_event_duration_listener(fn)
