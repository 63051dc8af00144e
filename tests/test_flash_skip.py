"""Flash attention block skipping: the live-block predicate against
``_block_mask``.

A launch visits only the (q, kv) blocks in which the causal/window/tail mask
admits a pair, and runs the mask only on blocks it cuts. The helpers that
decide this (``_kv_blocks``, ``_q_blocks``, ``_block_full``) must agree with
a brute-force evaluation of ``_block_mask`` block by block: a block is live
iff the mask holds any True, fully live iff it holds no False. Shapes cover
primes (tail lanes), Sq < Skv (prefill against a cache), Sq > Skv (dead
rows), windows inside one block, across several and past the sequence.
"""
import itertools

import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import flash_attention as fa

SHAPES = [  # (sq, skv, bq, bk)
    (16, 16, 4, 4),
    (13, 13, 4, 4),       # primes: tail blocks in both dims
    (17, 31, 4, 8),       # Sq < Skv, primes
    (8, 32, 4, 4),        # prefill offset 24
    (31, 17, 8, 4),       # Sq > Skv: dead rows, primes
    (40, 12, 8, 8),       # dead q blocks
    (24, 24, 8, 4),       # bq ≠ bk
]
WINDOWS = [0, 1, 3, 5, 12, 64]


def _brute(sq, skv, bq, bk, causal, window):
    """{(iq, ik): (any, all)} of ``_block_mask`` over every block."""
    m = dict(bq=bq, bk=bk, causal=causal, window=window, q_offset=skv - sq,
             sq=sq, skv=skv)
    out = {}
    for iq, ik in itertools.product(range(pl.cdiv(sq, bq)),
                                    range(pl.cdiv(skv, bk))):
        blk = np.asarray(fa._block_mask(iq, ik, **m))
        out[iq, ik] = bool(blk.any()), bool(blk.all())
    return m, out


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,skv,bq,bk", SHAPES)
def test_live_ranges_match_block_mask(sq, skv, bq, bk, causal, window):
    m, brute = _brute(sq, skv, bq, bk, causal, window)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(skv, bk)
    for iq in range(nq):
        lo, hi = fa._kv_blocks(iq, **m)
        live = {ik for ik in range(nk) if brute[iq, ik][0]}
        assert set(range(lo, hi)) == live, (iq, lo, hi, sorted(live))
        assert 0 <= lo < nk and lo <= hi <= nk
    for ik in range(nk):
        lo, hi = fa._q_blocks(ik, **m)
        live = {iq for iq in range(nq) if brute[iq, ik][0]}
        assert set(range(lo, hi)) == live, (ik, lo, hi, sorted(live))
        assert 0 <= lo < nq and lo <= hi <= nq
    for (iq, ik), (_, every) in brute.items():
        assert bool(fa._block_full(iq, ik, **m)) == every, (iq, ik)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("sq,skv,bq,bk", SHAPES)
def test_band_covers_live_blocks(sq, skv, bq, bk, window):
    """The innermost grid extent holds every q block's live kv blocks (and
    every kv block's live q blocks), never exceeds the grid, and under a
    causal window is at most ⌈(window + bq − 1) / bk⌉ + 1 blocks."""
    m = dict(bq=bq, bk=bk, causal=True, window=window, q_offset=skv - sq,
             sq=sq, skv=skv)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(skv, bk)
    kv_band = fa._band(nq, lambda i: fa._kv_blocks(i, **m))
    q_band = fa._band(nk, lambda i: fa._q_blocks(i, **m))
    assert 1 <= kv_band <= nk and 1 <= q_band <= nq
    assert all(hi - lo <= kv_band
               for lo, hi in (fa._kv_blocks(i, **m) for i in range(nq)))
    if window:
        assert kv_band <= min(nk, pl.cdiv(window + bq - 1, bk) + 1)
        assert q_band <= min(nq, pl.cdiv(window + bk - 1, bq) + 1)
    else:
        assert kv_band == nk


@pytest.mark.parametrize("lo,hi,band", [(0, 3, 5), (2, 4, 4), (0, 0, 2),
                                        (3, 4, 1), (1, 6, 5)])
def test_dead_steps_name_the_previous_block(lo, hi, band):
    """Grid step j of a band names its live block; every dead step past
    the live range names the block its predecessor holds (no copy), and an
    empty range names a valid block."""
    names = [fa._step_block(lo, hi, j) for j in range(band)]
    for j, name in enumerate(names):
        if lo + j < hi:
            assert name == lo + j
        else:
            assert name == (names[j - 1] if j else lo)


@pytest.mark.parametrize("s,window,want", [
    (2048, 0, (10, 6, 16)),          # SmolLM, causal
    (4096, 0, (36, 28, 64)),         # Granite, causal
    (8192, 0, (136, 120, 256)),      # Mellum, full layer
    (8192, 1024, (45, 15, 256)),     # Mellum, windowed layer
])
def test_block_counts_at_the_cells(s, window, want):
    """Live, fully live and all blocks of one head at 512 × 512 blocks,
    the benchmark cells' attention shapes."""
    assert fa.block_counts(s, s, 512, 512, causal=True,
                           window=window) == want


def test_block_counts_match_brute_force():
    for (sq, skv, bq, bk), causal, window in itertools.product(
            SHAPES[:4], [True, False], [0, 3, 12]):
        _, brute = _brute(sq, skv, bq, bk, causal, window)
        want = (sum(a for a, _ in brute.values()),
                sum(e for _, e in brute.values()), len(brute))
        assert fa.block_counts(sq, skv, bq, bk, causal=causal,
                               window=window) == want
