"""RapidRAID (n, k) generator matrix, from the paper (arXiv 1207.6744 §IV).

Before archival the k blocks o_1..o_k sit as two overlapped replicas on n
nodes: node i holds o_i when i < k and o_(i-(n-k)) when i >= n-k (0-based).
The chain then computes, node by node,

    x_(i,i+1) = x_(i-1,i) + sum over blocks o_j on node i of psi * o_j  (Eq. 3)
    c_i       = x_(i-1,i) + sum over blocks o_j on node i of xi  * o_j  (Eq. 4)

with one coefficient per (node, block) slot; the last node forwards
nothing. The coefficients are nonzero field elements drawn from the
configuration's code seed (§V-A: random coefficients over GF(2^16) make
the code MDS with high probability): every psi first, in slot order, then
every xi, each ``numpy.random.default_rng(code_seed).integers(1, 2^l)``.
"""
from __future__ import annotations

import numpy as np


def _held(n: int, k: int) -> list[list[int]]:
    return [([i] if i < k else []) + ([i - (n - k)] if i >= n - k else [])
            for i in range(n)]


def generator(cfg: dict) -> np.ndarray:
    """(n, k) coefficients of the coded blocks c_0..c_(n-1) over o."""
    n, k, l = cfg["n"], cfg["k"], cfg["l"]
    held = _held(n, k)
    slots = sum(len(h) for h in held)
    rng = np.random.default_rng(cfg["code_seed"])
    psi = [int(v) for v in rng.integers(1, 1 << l, size=slots - len(held[-1]))]
    xi = [int(v) for v in rng.integers(1, 1 << l, size=slots)]
    G = np.zeros((n, k), dtype=np.int64)
    x = np.zeros(k, dtype=np.int64)       # coefficients of x_(i-1,i)
    for i, blocks in enumerate(held):
        G[i] = x
        for b in blocks:
            G[i, b] ^= xi.pop(0)
        if i < n - 1:
            for b in blocks:
                x[b] ^= psi.pop(0)
    return G


def repair_reads(cfg: dict, lost: list[int]) -> int:
    """Coded blocks a repair must read: any k independent survivors."""
    return cfg["k"]
