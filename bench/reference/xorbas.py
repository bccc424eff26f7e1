"""HDFS-Xorbas LRC(10,6,5): the locally repairable code of Facebook's HDFS
warehouse (Sathiamoorthy et al., "XORing Elephants: Novel Erasure Codes for
Big Data", VLDB 2013, §2).

A stripe of k data blocks X1..Xk is stored as it is (rows 0..k-1) and split
into ``local_groups`` = 2 contiguous groups of k/2 (5 and 5). Row k+g is
group g's XOR parity S_g. The remaining rows are the Reed-Solomon parities
P1..Pm (m = n-k-2 = 4). The paper picks the code so that S1 + S2 + S3 = 0,
where S3 is the XOR parity of P1..Pm: S3 is implied and never stored. This
configuration draws P1..P(m-1) from the code seed the way the Azure
reference draws its globals, one row at a time, each
``numpy.random.default_rng(code_seed).integers(1, 2^l, size=k)``, and sets
Pm = 1 + P1 + ... + P(m-1) coefficient by coefficient, so that
P1 + ... + Pm = X1 + ... + Xk = S1 + S2.

Every single lost block is therefore the XOR of 5 others: a data block of
its group's other members and S_g, S_g of its group's members, Pi of the
other P's with S1 and S2.
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
    first = k + len(grp)
    rng = np.random.default_rng(cfg["code_seed"])
    for r in range(first, n - 1):
        G[r] = rng.integers(1, 1 << l, size=k, dtype=np.int64)
    G[n - 1] = 1
    for r in range(first, n - 1):
        G[n - 1] ^= G[r]
    return G


def repair_reads(cfg: dict, lost: list[int]) -> int:
    """One lost block reads 5: the rest of its group and the group's
    parity, where the P's group has S1 and S2 for its implied parity.
    More than one lost block reads k."""
    n, k = cfg["n"], cfg["k"]
    if len(lost) != 1:
        return k
    grp = groups(cfg)
    row = lost[0]
    for g, members in enumerate(grp):
        if row in members or row == k + g:
            return len(members)
    return n - k - 1                  # the other P's, S1 and S2
