"""Locally Repairable Code (LRC) family — local XOR groups + global parities.

Following Sathiamoorthy et al. ("XORing Elephants", PAPERS.md): the k data
blocks are stored systematically and split into ``g`` contiguous local
groups; each group gets one XOR parity (coefficient 1 over GF(2^l), i.e. a
plain XOR of the group members), and the remaining ``n - k - g`` rows are
global parities with seeded random nonzero coefficients over all k blocks.

Layout of the n codeword rows:

  rows 0..k-1        data blocks (systematic)
  rows k..k+g-1      local XOR parities, one per group
  rows k+g..n-1      global parities

The family's point: a SINGLE lost shard whose local group is otherwise
intact is repaired by XORing the surviving group members + group parity —
``repair_plan`` returns only those helpers (≤ locality shards, an all-ones
R row), and because the plan flows through the same pipelined repair chain
as RapidRAID, the distributed repair provably touches only the local group.
The code is NOT MDS: some (n-k)-loss patterns are undecodable, which is
the storage/locality trade the Monte Carlo in ``core/churn.py`` quantifies.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from repro.core import gf
from repro.core.codes import base


def num_groups(n: int, k: int) -> int:
    """Default group count: roughly half the parity budget goes local."""
    return max(1, min(k, math.ceil((n - k) / 2)))


@dataclasses.dataclass(frozen=True)
class LRCCode(base.ErasureCode):
    n: int
    k: int
    l: int = 16
    seed: int = 0

    family = "lrc"

    def __post_init__(self):
        if not 1 <= self.k < self.n:
            raise ValueError(f"need 1 <= k < n, got (n={self.n}, k={self.k})")
        if self.n - self.k < self.num_groups() + 1:
            raise ValueError(
                f"(n={self.n}, k={self.k}) leaves no room for a global "
                f"parity next to {self.num_groups()} local groups")

    def num_groups(self) -> int:
        """The number of local groups (the family's geometry)."""
        return num_groups(self.n, self.k)

    @functools.cached_property
    def groups(self) -> tuple[tuple[int, ...], ...]:
        """Contiguous data-block groups; group gi's parity is row k + gi."""
        return tuple(tuple(int(b) for b in part)
                     for part in np.array_split(np.arange(self.k),
                                                self.num_groups()))

    @property
    def n_local(self) -> int:
        return len(self.groups)

    @property
    def n_global(self) -> int:
        return self.n - self.k - self.n_local

    @property
    def locality(self) -> int:
        """Max shards read to repair one lost data/local-parity shard."""
        return max(len(grp) for grp in self.groups)

    @functools.cached_property
    def G(self) -> np.ndarray:
        dt = gf.WORD_DTYPE[self.l]
        G = np.zeros((self.n, self.k), dtype=dt)
        G[:self.k] = np.eye(self.k, dtype=dt)
        for gi, grp in enumerate(self.groups):
            G[self.k + gi, list(grp)] = 1  # XOR parity
        G[self.k + self.n_local:] = self.global_rows()
        return G

    def global_rows(self) -> np.ndarray:
        """(n_global, k) global parity coefficients: nonzero, drawn from the
        seed one row at a time."""
        rng = np.random.default_rng(self.seed)
        return np.stack([rng.integers(1, 1 << self.l, size=self.k,
                                      dtype=np.int64)
                         for _ in range(self.n_global)])

    def row_group(self, row: int) -> int | None:
        """Local group index of a data/local-parity row; None for globals."""
        if row < self.k:
            for gi, grp in enumerate(self.groups):
                if row in grp:
                    return gi
            raise AssertionError(row)
        if row < self.k + self.n_local:
            return row - self.k
        return None

    def group_rows(self, gi: int) -> tuple[int, ...]:
        """All codeword rows of group gi: its data members + its parity."""
        return tuple(self.groups[gi]) + (self.k + gi,)

    def repair_plan(self, missing, alive):
        """Locality-aware plan: one lost shard with an intact group is
        rebuilt by XOR over the other group rows; anything else falls back
        to the generic global plan."""
        missing = list(missing)
        alive = list(alive)
        if len(missing) == 1:
            gi = self.row_group(missing[0])
            if gi is not None:
                helpers = [r for r in self.group_rows(gi) if r != missing[0]]
                if all(r in alive for r in helpers):
                    R = np.ones((1, len(helpers)),
                                dtype=gf.WORD_DTYPE[self.l])
                    return helpers, R
        return base.matrix_repair_plan(self, missing, alive)

    def repair_transfer_words(self, block_words: int) -> int:
        return self.locality * block_words


def make(n: int, k: int, l: int = 16, seed: int = 0) -> LRCCode:
    return LRCCode(n=n, k=k, l=l, seed=seed)


@dataclasses.dataclass(frozen=True)
class XorbasCode(LRCCode):
    """Facebook HDFS-Xorbas LRC (Sathiamoorthy et al., VLDB 2013, §2).

    Two local groups, each with an XOR parity (S1, S2), and n - k - 2 global
    parities P1..Pm. The last global is set so that P1 + ... + Pm equals
    S1 + S2 (P_m = 1 + P1 + ... + P_{m-1} entrywise): the global group's
    own XOR parity S3 = S1 + S2 is implied and never stored, and a single
    lost global is rebuilt from the other globals and S1, S2. So EVERY
    single lost row, a parity included, is repaired from ``locality``
    others with an all-ones R row. For (16,10): 2 groups of 5, 4 globals,
    locality 5. A seed is valid when P_m has no zero coefficient (checked
    here) and every (n-k-2)-loss pattern decodes (distance n-k-1, the
    paper's 5; checked by the tests for the seed a deployment states); the
    construction does not search for one.
    """

    family = "xorbas"

    def __post_init__(self):
        super().__post_init__()
        if self.n_global < 2:
            raise ValueError(
                f"xorbas (n={self.n}, k={self.k}) needs at least 2 global "
                f"parities next to its 2 local groups")

    def num_groups(self) -> int:
        return 2

    def global_rows(self) -> np.ndarray:
        rows = super().global_rows()
        rows[-1] = np.bitwise_xor.reduce(rows[:-1], axis=0) ^ 1
        if not rows[-1].all():
            raise ValueError(
                f"xorbas seed {self.seed}: the implied-parity global row "
                f"has a zero coefficient; state another code seed")
        return rows

    @property
    def locality(self) -> int:
        return max(super().locality, self.n_global + 1)

    def repair_plan(self, missing, alive):
        """A single lost global is the XOR of the other globals and the
        local parities; everything else as the LRC plan."""
        missing = list(missing)
        if len(missing) == 1 and self.row_group(missing[0]) is None:
            helpers = [r for r in range(self.k, self.n) if r != missing[0]]
            if set(helpers) <= set(alive):
                return helpers, np.ones((1, len(helpers)),
                                        dtype=gf.WORD_DTYPE[self.l])
        return super().repair_plan(missing, alive)


def make_xorbas(n: int, k: int, l: int = 16, seed: int = 0) -> XorbasCode:
    return XorbasCode(n=n, k=k, l=l, seed=seed)
