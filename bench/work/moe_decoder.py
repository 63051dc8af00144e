"""The work of one train step of a mixture-of-experts decoder on one chip's
share of the experts (Mellum 2's layout: every MLP sparse, sliding-window
and full attention layers), from the configuration's shapes alone.

Counts are the algorithm's, as ``dense_decoder.py`` counts them: every
product of the forward pass once, and its two backward products (dx, dw),
nothing recomputed. A causal attention product counts the (query, key)
pairs it may see: half the square on a full layer, S·w − w²/2 on a layer
with a window of w. The experts count the rows routed to held experts;
the caller gives them (their expectation from shapes is ``held_rows``).
Elementwise work (norms, rotary, SwiGLU, routing, softmax, the loss) is not
counted.

Keys read from a configuration file: hidden_size, num_attention_heads,
num_key_value_heads, head_dim, num_hidden_layers, vocab_size,
tie_word_embeddings, layer_types, sliding_window, moe_intermediate_size,
num_experts (held), router_experts, num_experts_per_tok.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.work.dense_decoder import BF16, INT8, least_time  # noqa: F401


def dense_shapes(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, how many per step-token row) of every dense product:
    the four attention projections of each layer and the untied head."""
    d = cfg["hidden_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    out = [("wq", d, h * dh, layers), ("wk", d, hkv * dh, layers),
           ("wv", d, hkv * dh, layers), ("wo", h * dh, d, layers)]
    if not cfg.get("tie_word_embeddings", False):
        out.append(("head", d, cfg["vocab_size"], 1))
    return out


def _passes(m: float, k: int, n: int, words: int) -> List[Tuple[float, float]]:
    """(ops, bytes) of forward, dx and dw of an (m, k) x (k, n) product
    whose weights are ``words`` int8 (k, n) matrices, each read once."""
    ops = 2.0 * m * k * n
    fwd = (m * k + m * n) * BF16 + words * k * n * INT8
    dx = (m * n + m * k) * BF16 + words * k * n * INT8
    dw = (m * k + m * n) * BF16 + words * k * n * BF16
    return [(ops, fwd), (ops, dx), (ops, dw)]


def dense_passes(cfg: Dict, tokens: int) -> List[Tuple[float, float]]:
    """(operations, bytes) of each pass of each dense product over
    ``tokens`` rows, as ``dense_decoder.dense_passes`` counts them."""
    out = []
    for _, k, n, count in dense_shapes(cfg):
        out += _passes(tokens, k, n, 1) * count
    return out


def held_rows(cfg: Dict, tokens: int) -> float:
    """Rows one layer routes to the held experts, expected from shapes:
    tokens x k x held / routed experts."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_experts"])


def expert_passes(cfg: Dict, rows: float) -> List[Tuple[float, float]]:
    """(operations, bytes) of the gate, up and down products' forward, dx
    and dw over ``rows`` rows of each layer (each row one held expert's),
    every held expert's words read once a pass."""
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    per_layer = (_passes(rows, d, f, e) * 2) + _passes(rows, f, d, e)
    return per_layer * cfg["num_hidden_layers"]


def _pairs(seq: int, window: int) -> float:
    """(query, key) pairs a causal layer sees, as the algorithm counts
    them: S²/2 in full, S·w − w²/2 within a window w < S."""
    if window <= 0 or window >= seq:
        return seq * seq / 2
    return seq * window - window * window / 2


def attention_passes(cfg: Dict, batch: int, seq: int
                     ) -> List[Tuple[float, float]]:
    """(operations, bytes) per layer of attention over ``batch`` sequences
    of ``seq``: six products of 2 B H D per (query, key) pair seen (forward
    Q K^T and P V; backward dP, dS^T Q, dS K, P^T dO); bytes: q, k, v, o,
    do, dq, dk, dv in bf16, once each."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rows = batch * seq * dh * BF16
    bytes_ = rows * (h + 2 * hkv) * 2 + rows * h * 2
    out = []
    for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        w = cfg["sliding_window"] if kind == "sliding_attention" else 0
        out.append((6 * 2.0 * batch * h * dh * _pairs(seq, w), bytes_))
    return out


def step_work(cfg: Dict, batch: int, seq: int) -> Dict[str, float]:
    """Operations of one train step, by the peak that bounds them, with the
    experts at their expected held rows."""
    tokens = batch * seq
    dense = sum(o for o, _ in dense_passes(cfg, tokens))
    experts = sum(o for o, _ in expert_passes(cfg, held_rows(cfg, tokens)))
    attn = sum(o for o, _ in attention_passes(cfg, batch, seq))
    return {"dense_int8_ops": dense, "expert_int8_ops": experts,
            "attention_bf16_flops": attn}
