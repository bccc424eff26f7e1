"""Locally repairable code LRC(k, g, r): the layout of Windows Azure
Storage's LRC(12,2,2) (Huang et al., USENIX ATC 2012, §2).

The k data fragments are stored as they are (rows 0..k-1). They are split
into ``local_groups`` contiguous groups, the first groups taking one more
fragment when k does not divide evenly; row k+g is the XOR of group g.
The remaining n-k-g rows are global parities over all k fragments. Azure
constructs its global coefficients; this configuration assumes nonzero
coefficients drawn from the code seed instead, one row at a time, each
``numpy.random.default_rng(code_seed).integers(1, 2^l, size=k)``.
"""
from __future__ import annotations

import numpy as np


def groups(cfg: dict) -> list[list[int]]:
    k, g = cfg["k"], cfg["local_groups"]
    sizes = [k // g + (1 if i < k % g else 0) for i in range(g)]
    starts = np.cumsum([0] + sizes)
    return [list(range(starts[i], starts[i + 1])) for i in range(g)]


def generator(cfg: dict) -> np.ndarray:
    n, k, l = cfg["n"], cfg["k"], cfg["l"]
    grp = groups(cfg)
    G = np.zeros((n, k), dtype=np.int64)
    G[:k] = np.eye(k, dtype=np.int64)
    for g, members in enumerate(grp):
        G[k + g, members] = 1
    rng = np.random.default_rng(cfg["code_seed"])
    for r in range(k + len(grp), n):
        G[r] = rng.integers(1, 1 << l, size=k, dtype=np.int64)
    return G


def repair_reads(cfg: dict, lost: list[int]) -> int:
    """One lost data fragment or local parity reads the rest of its group
    and the group parity; anything else reads k fragments."""
    k = cfg["k"]
    if len(lost) == 1 and lost[0] < k + cfg["local_groups"]:
        for g, members in enumerate(groups(cfg)):
            if lost[0] in members or lost[0] == k + g:
                return len(members)
    return k
