"""Plain GF(2^l) arithmetic for the benchmark's reference.

Written from the field's definition and nothing else: an element is a
polynomial over GF(2) of degree < l held in an integer, addition is XOR,
and multiplication is the carry-less product reduced by the field's
primitive polynomial. Multiplying a vector by a constant is done the long
way, one bit of the constant at a time (shift and add), so no table or
packed layout of the system under test is shared.
"""
from __future__ import annotations

import numpy as np

#: primitive polynomials of GF(2^8) and GF(2^16), as used by Jerasure
#: (Plank et al.) and by the RapidRAID paper's implementation
POLY = {8: 0x11D, 16: 0x1100B}
WORD = {8: np.uint8, 16: np.uint16}


def mul(a: int, b: int, l: int) -> int:
    """Product of two field elements."""
    top, poly = 1 << l, POLY[l]
    out = 0
    while b:
        if b & 1:
            out ^= a
        b >>= 1
        a <<= 1
        if a & top:
            a ^= poly
    return out


def power(a: int, e: int, l: int) -> int:
    out = 1
    for _ in range(e):
        out = mul(out, a, l)
    return out


def _times_x(v: np.ndarray, l: int) -> np.ndarray:
    """Every element of ``v`` (int64) multiplied by x."""
    v = v << 1
    return np.where(v >> l, v ^ POLY[l], v)


def apply(G: np.ndarray, X: np.ndarray, l: int) -> np.ndarray:
    """GF(2^l) matrix product: G (r, k) coefficients times X (k, m) words
    -> (r, m) words, one bit of each coefficient at a time."""
    G = np.asarray(G, dtype=np.int64)
    r, k = G.shape
    out = np.zeros((r, X.shape[1]), dtype=np.int64)
    for j in range(k):
        x = X[j].astype(np.int64)[None, :]
        for b in range(l):
            bit = ((G[:, j] >> b) & 1)[:, None]
            out ^= x * bit
            x = _times_x(x, l)
    return out.astype(WORD[l])
