"""What the timed path produced, set against the plain reference.

Every code this benchmark runs is linear and positionwise: coded word t of
row r is the GF(2^l) combination, with the generator's row r, of word t of
the k data blocks. Addition in GF(2^l) is XOR, so XOR-folding any set of
word positions commutes with the code: the fold of a coded row equals the
generator row applied to the folds of the data rows. That lets every
stored word be checked at the cost of reading it once:

* ``seg``: the XOR of each of ``FOLDS`` contiguous segments of the row;
* ``res``: the XOR of each residue class of positions modulo ``FOLDS``,
  which catches words moved inside a segment;
* ``win``: sampled windows, compared word for word (the first, the last
  and ``RANDOM_WINDOWS`` drawn from the seed).

A data row's folds are taken once per object; a stored row's folds are
taken from its file right after the operation that wrote it. The
comparison runs after the window has closed, through ``reference.gf``.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from reference import gf as ref_gf

FOLDS = 4096
WINDOW_WORDS = 2048
RANDOM_WINDOWS = 14


def _lane_xor(x: np.ndarray, l: int) -> np.ndarray:
    """uint64 lanes holding 64/l words each -> the XOR of those words."""
    bits = 32
    while bits >= l:
        x = x ^ (x >> bits)
        bits //= 2
    return x & ((1 << l) - 1)


def folds(words: np.ndarray, l: int) -> tuple[np.ndarray, np.ndarray]:
    """(rows, W) words of l bits -> (seg, res), each (rows, FOLDS) int64."""
    rows, W = words.shape
    per = 64 // l                       # words in one uint64 lane
    f = min(FOLDS, W)
    if W % f or (W // f) % per or f % per:
        raise ValueError(f"row of {W} words does not fold into {f} segments "
                         f"of whole 64-bit lanes")
    lanes = words.view(np.uint64)
    seg = _lane_xor(np.bitwise_xor.reduce(
        lanes.reshape(rows, f, W // f // per), axis=2), l)
    res = np.bitwise_xor.reduce(lanes.reshape(rows, W // f, f // per), axis=1)
    res = np.ascontiguousarray(res).view(words.dtype).reshape(rows, f)
    return seg.astype(np.int64), res.astype(np.int64)


def windows(rng: np.random.Generator, W: int) -> list[int]:
    """Start words of the sampled windows of a row of ``W`` words."""
    w = min(WINDOW_WORDS, W)
    starts = [0, W - w]
    starts += [int(s) for s in rng.integers(0, W - w + 1, RANDOM_WINDOWS)]
    return starts


def take(words: np.ndarray, starts: list[int]) -> np.ndarray:
    w = min(WINDOW_WORDS, words.shape[1])
    return np.concatenate([words[:, s:s + w] for s in starts], axis=1)


@dataclasses.dataclass
class DataRef:
    """The reference's view of one object: folds and windows of its data."""

    l: int
    words: int                 # words in each row
    seg: np.ndarray
    res: np.ndarray
    win: np.ndarray
    starts: list[int]

    @classmethod
    def of(cls, blocks: np.ndarray, l: int,
           rng: np.random.Generator) -> "DataRef":
        """From the object's (k, B) uint8 blocks."""
        words = blocks.view(ref_gf.WORD[l])
        seg, res = folds(words, l)
        starts = windows(rng, words.shape[1])
        return cls(l, words.shape[1], seg, res,
                   take(words, starts).astype(np.int64), starts)


@dataclasses.dataclass
class RowAnswer:
    """Folds and windows of one stored coded row (``None``: not found)."""

    obj: int
    row: int
    seg: np.ndarray | None = None
    res: np.ndarray | None = None
    win: np.ndarray | None = None


def read_row(path: str, obj: int, row: int, ref: DataRef) -> RowAnswer:
    """Fold the row stored at ``path``, read from the file itself; a file
    that is absent or of another length answers nothing."""
    dt = ref_gf.WORD[ref.l]
    if (not os.path.exists(path)
            or os.path.getsize(path) != ref.words * np.dtype(dt).itemsize):
        return RowAnswer(obj, row)
    words = np.fromfile(path, dtype=dt)[None]
    seg, res = folds(words, ref.l)
    return RowAnswer(obj, row, seg[0], res[0], take(words, ref.starts)[0])


def compare(G: np.ndarray, refs: dict[int, DataRef],
            answers: list[RowAnswer]) -> dict[str, int]:
    """Counts of stored rows absent, and of folds and words that differ
    from the reference."""
    out = {"rows_absent": 0, "fold_mismatch": 0, "window_mismatch": 0}
    by_obj: dict[int, list[RowAnswer]] = {}
    for a in answers:
        if a.seg is None:
            out["rows_absent"] += 1
        else:
            by_obj.setdefault(a.obj, []).append(a)
    for obj, got in by_obj.items():
        ref = refs[obj]
        g = G[[a.row for a in got]]
        seg = np.stack([a.seg for a in got])
        res = np.stack([a.res for a in got])
        win = np.stack([a.win for a in got])
        out["fold_mismatch"] += int(
            np.count_nonzero(ref_gf.apply(g, ref.seg, ref.l) != seg)
            + np.count_nonzero(ref_gf.apply(g, ref.res, ref.l) != res))
        out["window_mismatch"] += int(
            np.count_nonzero(ref_gf.apply(g, ref.win, ref.l) != win))
    return out
