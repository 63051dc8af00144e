"""Inputs made from ``--seed``: PRNG keys, the token stream and the weights.

The benchmark makes these itself, so that the plain reference and the
program under test start from the same numbers without the reference taking
anything the program made. Everything here is a pure function of the seed.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key (uint32[2]) from a seed of up to 64 bits."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jnp.asarray(np.array([(seed >> 32) & 0xFFFFFFFF,
                                 seed & 0xFFFFFFFF], np.uint32))


# Salts that keep the streams drawn from one seed apart.
WEIGHTS, TOKENS, PROGRAM_RNG, REFERENCE_SR = 1, 2, 3, 4


def lm_tokens(key: jax.Array, batch: int, seq: int, vocab: int,
              noise: float) -> jax.Array:
    """Per-row "stride induction" sequences, t_i = (start + i * stride) mod V
    with a share ``noise`` of uniform corruption: every row has its own
    start and stride, so the rows of a batch differ. A copy of the
    program's ``data/synthetic.lm_tokens``."""
    ks = jax.random.split(key, 4)
    start = jax.random.randint(ks[0], (batch, 1), 0, vocab)
    stride = jax.random.randint(ks[1], (batch, 1), 1, max(vocab // 4, 2))
    idx = jnp.arange(seq, dtype=jnp.int32)[None, :]
    toks = (start + idx * stride) % vocab
    corrupt = jax.random.bernoulli(ks[2], noise, (batch, seq))
    rand = jax.random.randint(ks[3], (batch, seq), 0, vocab)
    return jnp.where(corrupt, rand, toks).astype(jnp.int32)


def step_tokens(seed_stream: jax.Array, step, batch: int, seq: int,
                vocab: int, noise: float) -> jax.Array:
    """The batch of train step ``step`` (traced or static)."""
    return lm_tokens(jax.random.fold_in(seed_stream, step), batch, seq,
                     vocab, noise)


def _fan_in(path: str, shape) -> int:
    # an embedding table (V, D) is read one row per token: its fan-in is D
    return shape[-1] if path.endswith("embed") else shape[-2]


def leaf_init(key: jax.Array, path: str, shape) -> jax.Array:
    """Fan-in truncated-normal scaling (the AdaPT paper's TNVS, s = 1):
    N(0, 1/n_in) cut at +-sqrt(3/n_in). Gains of norms start at 0 (the
    models scale by 1 + gain)."""
    if len(shape) < 2 or re.search(r"norm", path):
        return jnp.zeros(shape, jnp.float32)
    n = _fan_in(path, shape)
    cut = 3.0 ** 0.5
    return (jax.random.truncated_normal(key, -cut, cut, shape, jnp.float32)
            / n ** 0.5)


def leaf_paths(shapes) -> list:
    """Sorted '/'-joined paths of a (nested dict) tree of shapes."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        out.append("/".join(str(k.key) for k in path))
    return sorted(out)


def make_weights(seed_stream: jax.Array, shapes):
    """The weights for a tree of ``jax.ShapeDtypeStruct`` (float32), each
    leaf from its own fold of the stream by its place in sorted path
    order. Call under ``jax.jit`` so they are made on the device."""
    order = {p: i for i, p in enumerate(leaf_paths(shapes))}

    def visit(path, s):
        p = "/".join(str(k.key) for k in path)
        return leaf_init(jax.random.fold_in(seed_stream, order[p]), p,
                         s.shape)

    return jax.tree_util.tree_map_with_path(visit, shapes)
