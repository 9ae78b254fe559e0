"""PrIM NW — Needleman-Wunsch global sequence alignment (paper §4.10).

Decomposition: the (m+1)×(n+1) score matrix is tiled into large 2D blocks;
the host iterates over block anti-diagonals; blocks on one diagonal are
distributed across banks; after each diagonal the host retrieves each block's
last row/column and feeds them to the next diagonal (the inter-DPU pattern
that dominates NW in the paper, Key Obs. 16).

TPU-native block kernel: the row-sequential dependency is vectorized with the
cummax trick — row[j] = cummax(t[k] + gap·k) − gap·j — so each block row is
one VPU-wide associative scan instead of a scalar loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.banked import BankGrid
from .common import PhaseTimer, sync

MATCH, MISMATCH, GAP = 1, -1, 1    # +1 match, -1 mismatch, -1 per gap


def ref(s1: np.ndarray, s2: np.ndarray) -> np.ndarray:
    """Full DP score matrix S[(m+1),(n+1)] (numpy gold)."""
    m, n = len(s1), len(s2)
    S = np.zeros((m + 1, n + 1), np.int32)
    S[0, :] = -GAP * np.arange(n + 1)
    S[:, 0] = -GAP * np.arange(m + 1)
    for i in range(1, m + 1):
        for j in range(1, n + 1):
            sub = MATCH if s1[i - 1] == s2[j - 1] else MISMATCH
            S[i, j] = max(S[i - 1, j - 1] + sub,
                          S[i - 1, j] - GAP, S[i, j - 1] - GAP)
    return S


def _nw_block(top, left, corner, s1b, s2b):
    """One (Bx, By) DP block given boundaries. top: (By,), left: (Bx,),
    corner: scalar = S[top-left-1, left-1]."""
    By = top.shape[0]

    def row_step(prev_full, inp):
        # prev_full: (By+1,) = S[i-1, -1..By-1]
        c1, lft = inp
        sub = jnp.where(c1 == s2b, MATCH, MISMATCH)
        t = jnp.maximum(prev_full[:-1] + sub, prev_full[1:] - GAP)
        v = jnp.concatenate([lft[None], t])              # (By+1,)
        u = v + GAP * jnp.arange(By + 1)
        row = jax.lax.associative_scan(jnp.maximum, u)[1:] - \
            GAP * (jnp.arange(By) + 1)
        new_prev = jnp.concatenate([lft[None], row])   # S[i, -1..By-1]
        return new_prev, row

    prev0 = jnp.concatenate([corner[None], top])
    _, rows = jax.lax.scan(row_step, prev0, (s1b, left))
    return rows                                           # (Bx, By)


def pim(grid: BankGrid, s1: np.ndarray, s2: np.ndarray, block: int = 32):
    """Returns the full score matrix (boundaries exchanged via host each
    block-diagonal, per the paper)."""
    t = PhaseTimer()
    m, n = len(s1), len(s2)
    Bx = By = block
    nbx, nby = -(-m // Bx), -(-n // By)
    mp, np_ = nbx * Bx, nby * By
    s1p = np.concatenate([s1, np.full(mp - m, -1, s1.dtype)])
    s2p = np.concatenate([s2, np.full(np_ - n, -2, s2.dtype)])
    S = np.zeros((mp + 1, np_ + 1), np.int32)
    S[0, :] = -GAP * np.arange(np_ + 1)
    S[:, 0] = -GAP * np.arange(mp + 1)

    n_banks = grid.n_banks
    kernel = jax.vmap(_nw_block)
    compute_blocks = grid.bank_local(
        lambda tt, ll, cc, aa, bb: kernel(tt[0], ll[0], cc[0],
                                          aa[0], bb[0])[None])

    for d in range(nbx + nby - 1):
        cells = [(bi, d - bi) for bi in range(max(0, d - nby + 1),
                                              min(nbx, d + 1))]
        per = -(-len(cells) // n_banks)
        padded = cells + [cells[-1]] * (per * n_banks - len(cells))
        with t.phase("inter_dpu"):
            tops = np.stack([S[bi * Bx, bj * By + 1: bj * By + By + 1]
                             for bi, bj in padded])
            lefts = np.stack([S[bi * Bx + 1: bi * Bx + Bx + 1, bj * By]
                              for bi, bj in padded])
            corners = np.array([S[bi * Bx, bj * By] for bi, bj in padded],
                               np.int32)
            s1bs = np.stack([s1p[bi * Bx: bi * Bx + Bx] for bi, bj in padded])
            s2bs = np.stack([s2p[bj * By: bj * By + By] for bi, bj in padded])
            shape = (n_banks, per)
            dev = [sync(grid.to_banks(a.reshape(shape + a.shape[1:])))
                   for a in (tops, lefts, corners.astype(np.int32),
                             s1bs, s2bs)]
        with t.phase("dpu"):
            blocks = sync(compute_blocks(*dev))
        with t.phase("dpu_cpu"):
            host_blocks = grid.from_banks(blocks).reshape(
                (-1, Bx, By))[: len(cells)]
        for (bi, bj), blk in zip(cells, host_blocks):
            S[bi * Bx + 1: bi * Bx + Bx + 1,
              bj * By + 1: bj * By + By + 1] = blk
    return S[: m + 1, : n + 1], t.times
