"""Training cells of a mixture-of-experts decoder (traffic ``kind:
moe_train``): the AdaPT train step of a decoder whose every MLP is sparse,
on one chip's share of the experts, driven as ``bench/lm_train.py`` drives
a dense decoder.

The cell reuses ``lm_train.Cell`` (first steps, switch check, window, step
memory) with the program's ``Config`` built from the configuration's MoE
keys, and the plain reference ``bench/reference/moe_decoder.py``. It keeps
each window step's routing counters (``moe_rows_held``, ``moe_rows_max``)
on the device and reads them after the window: the held rows over what the
shapes lead one to expect (tokens x k x held / routed experts) and the
largest expert's rows over the mean go to standard error, since the
per-layer readers count held rows from shapes.
"""
from __future__ import annotations

import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, lm_train

FIRST_STEPS = lm_train.FIRST_STEPS


def program_config(cfg: Dict, traffic: Dict):
    """The program's ``Config`` for a MoE configuration file and a traffic
    mix."""
    from repro.config import Config, ModelConfig, apply_overrides

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only silu experts are run")
    if cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: the program has no bias terms")
    if not cfg.get("norm_topk_prob", False):
        raise ValueError(f"{cfg['name']}: the program takes the softmax "
                         "over the chosen logits (norm_topk_prob)")
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"{cfg['name']}: layer types {sorted(set(kinds))}")
    rope = cfg["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if slide["rope_type"] != "default" or full["rope_type"] != "yarn" or \
            full["rope_theta"] != slide["rope_theta"]:
        raise ValueError(f"{cfg['name']}: rope {rope}")
    model = ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        attn_pattern=tuple("local" if k == "sliding_attention" else "global"
                           for k in kinds),
        window_size=cfg["sliding_window"], rope_theta=slide["rope_theta"],
        yarn_factor=full["factor"],
        yarn_original_max=full["original_max_position_embeddings"],
        yarn_beta_fast=full["beta_fast"], yarn_beta_slow=full["beta_slow"],
        yarn_attention_factor=full["attention_factor"],
        norm_eps=cfg["rms_norm_eps"],
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


class Cell(lm_train.Cell):
    """One MoE training cell on one chip."""

    def __init__(self, cfg: Dict, traffic: Dict, *,
                 step_wrapper: Optional[Callable] = None,
                 switch_wrapper: Optional[Callable] = None):
        from repro.config import apply_overrides
        from repro.models import transformer
        from repro.train import train_loop

        if traffic["data_parallel"] != 1:
            raise ValueError("a moe_train cell runs on one chip")
        self.cfg, self.traffic = cfg, traffic
        self.pcfg = pcfg = program_config(cfg, traffic)
        self.batch, self.seq = traffic["global_batch"], traffic["seq_len"]
        self.vocab = cfg["vocab_size"]
        self.chips = 1
        self.interval = traffic["adapt_interval"]
        self.log_every = traffic["log_every"]
        self.param_shapes = jax.eval_shape(
            lambda: transformer.init_params(jax.random.PRNGKey(0),
                                            pcfg.model))
        self.paths = gen.leaf_paths(self.param_shapes)

        def make_state(seed_words):
            params = gen.make_weights(
                jax.random.fold_in(seed_words, gen.WEIGHTS),
                self.param_shapes)
            rng = jax.random.fold_in(seed_words, gen.PROGRAM_RNG)
            return dict(train_loop.init_state(pcfg, rng), params=params)

        def feed(seed_words, i):
            return {"tokens": gen.step_tokens(
                jax.random.fold_in(seed_words, gen.TOKENS), i, self.batch,
                self.seq, self.vocab, traffic["noise"])}

        xla_switch = train_loop.make_precision_switch(
            apply_overrides(pcfg, ["quant.use_pallas=false"]))

        def switch_words(state):
            return lm_train.precisions(xla_switch(state)["adapt"])

        words = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step_no = jax.ShapeDtypeStruct((), jnp.int32)
        state_shapes = jax.eval_shape(make_state, words)
        batch_shapes = jax.eval_shape(feed, words, step_no)
        self.mesh = None
        self.devices = [jax.devices()[0]]
        step = jax.jit(train_loop.make_train_step(pcfg), donate_argnums=0)
        switch = jax.jit(lm_train._named(
            train_loop.make_precision_switch(pcfg), lm_train.SWITCH_NAME),
            donate_argnums=0)
        t0 = time.perf_counter()
        self.make_state = jax.jit(make_state).lower(words).compile()
        self.feed = jax.jit(feed).lower(words, step_no).compile()
        self.compiled_step = step.lower(state_shapes, batch_shapes).compile()
        self.switch = switch.lower(state_shapes).compile()
        self.switch_words = jax.jit(switch_words).lower(state_shapes).compile()
        self.compile_s = time.perf_counter() - t0
        self.routed: List = []
        compiled = self.compiled_step

        def step_counted(state, batch):
            state, m = compiled(state, batch)
            self.routed.append((m["moe_rows_held"], m["moe_rows_max"]))
            return state, m

        self.step = step_counted
        if step_wrapper is not None:
            self.step = step_wrapper(self.step, self)
        if switch_wrapper is not None:
            self.switch = switch_wrapper(self.switch, self)
        self.quantized = sorted(state_shapes["adapt"]["tensors"])
        self._change = jax.jit(self._change_norms)
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self._full = jax.jit(lambda t: {p: jnp.maximum(c, lb)
                                        for p, (c, lb) in t.items()})

    def window(self, state, seed: int, seconds: float, first: int,
               trace_dir: Optional[str] = None):
        """``lm_train.Cell.window``; then the routing counters of its steps,
        kept on the device, are read and noted on standard error."""
        self.routed = []
        state, stats = super().window(state, seed, seconds, first, trace_dir)
        self.note_routing(self.routed)
        return state, stats

    def note_routing(self, routed) -> Dict[str, float]:
        counts = np.asarray(jax.device_get(routed), np.float64)
        if not counts.size:
            return {}
        m = self.pcfg.model
        layers = m.num_layers
        expect = (self.batch * self.seq * m.experts_per_token * layers
                  * m.experts_held / m.num_experts)
        mean_expert = counts[:, 0] / (layers * m.experts_held)
        read = {"held_over_expected": float(np.mean(counts[:, 0]) / expect),
                "largest_over_mean": float(np.max(counts[:, 1] /
                                                  mean_expert))}
        print(f"bench: routing over {len(counts)} window steps: held rows / "
              f"shapes' expectation {read['held_over_expected']}, largest "
              f"expert's rows / mean {read['largest_over_mean']}",
              file=sys.stderr)
        return read

    def reference(self, seed: int, rows: Optional[int] = None,
                  shards: Optional[int] = None) -> Dict:
        """The plain MoE reference's readings for the same three steps, on
        the first ``rows`` rows of each batch (all by default)."""
        from bench.reference import moe_decoder as ref

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
            losses, grad, params = ref.run(params, batches, keys, self.cfg,
                                           self.cfg["recipe"], order)
            change = self.change_norms(params, words)
        return {"losses": losses, "grad": grad, "change": change}
