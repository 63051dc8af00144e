"""The work of one train step of a dense decoder (llama family), from the
configuration's shapes alone.

Counts are the algorithm's: every product of the forward pass once, and the
two products of its backward pass (dx and dw), with nothing recomputed. A
causal attention product counts half of its square. Elementwise work (norms,
rotary, SwiGLU, softmax, the loss) is not counted.

Keys read from a configuration file: hidden_size, intermediate_size,
num_attention_heads, num_key_value_heads, head_dim, num_hidden_layers,
vocab_size.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16, INT8 = 2, 1


def dense_shapes(cfg: Dict) -> List[Tuple[str, int, int, int]]:
    """(name, K, N, how many per step-token row) of every dense product:
    the seven projections of each layer and the untied head."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    out = [("wq", d, h * dh, layers), ("wk", d, hkv * dh, layers),
           ("wv", d, hkv * dh, layers), ("wo", h * dh, d, layers),
           ("wi_gate", d, ff, layers), ("wi_up", d, ff, layers),
           ("mlp_wo", ff, d, layers)]
    if not cfg.get("tie_word_embeddings", False):
        out.append(("head", d, cfg["vocab_size"], 1))
    return out


def dense_passes(cfg: Dict, tokens: int) -> List[Tuple[float, float]]:
    """(operations, bytes) of each pass of each dense product over
    ``tokens`` rows: forward y = x w (int8 words in, bf16 activations),
    dx = dy w^T and dw = x^T dy. Bytes are each operand read once and the
    result written once."""
    m = tokens
    out = []
    for _, k, n, count in dense_shapes(cfg):
        ops = 2.0 * m * k * n
        fwd = (m * k + m * n) * BF16 + k * n * INT8
        dx = (m * n + m * k) * BF16 + k * n * INT8
        dw = (m * k + m * n + k * n) * BF16
        out += [(ops, fwd), (ops, dx), (ops, dw)] * count
    return out


def attention_passes(cfg: Dict, batch: int, seq: int
                     ) -> List[Tuple[float, float]]:
    """(operations, bytes) per layer of causal attention over ``batch``
    sequences of ``seq``: forward Q K^T and P V, backward dP = dO V^T,
    dS^T Q, dS K and P^T dO, each 2 B H S^2 D halved by the causal mask.
    Bytes: q, k, v, o, do, dq, dk, dv in bf16, once each."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = 6 * (2.0 * batch * h * seq * seq * dh) / 2
    rows = batch * seq * dh * BF16
    bytes_ = rows * (h + 2 * hkv) * 2 + rows * h * 2
    return [(ops, bytes_)] * cfg["num_hidden_layers"]


def step_work(cfg: Dict, batch: int, seq: int) -> Dict[str, float]:
    """Operations of one train step, by the peak that bounds them."""
    dense = sum(o for o, _ in dense_passes(cfg, batch * seq))
    attn = sum(o for o, _ in attention_passes(cfg, batch, seq))
    return {"dense_int8_ops": dense, "attention_bf16_flops": attn}


def least_time(passes, ops_per_s: float, bytes_per_s: float) -> float:
    """Seconds a chip at its peaks needs: each pass bound by the larger of
    its operations at ``ops_per_s`` and its bytes at ``bytes_per_s``."""
    return sum(max(o / ops_per_s, b / bytes_per_s) for o, b in passes)
