"""Finite-field GF(2^l) arithmetic for erasure coding, l in {8, 16}.

Three execution styles, all bit-exact against each other:

1. Host (numpy) table arithmetic — used to build generator/decode matrices,
   run Gaussian elimination, and search coefficients. Mirrors Jerasure's
   log/antilog approach from the paper.
2. ``jnp`` log/exp table arithmetic — the straightforward JAX port
   (data-dependent gathers; fine on CPU, slow on TPU VPU).
3. Packed **bit-plane** arithmetic — the TPU-native path: a multiply by a
   *static* coefficient ``c`` is ``xor_j bit_j(x) * (c * alpha^j)``, with 4
   bytes (or 2 halfwords) packed per 32-bit lane (``pack_u32``). No gathers; pure
   shift/mask/mul/xor, which vectorizes on the TPU VPU. The Pallas kernels in
   ``repro.kernels.gf_encode`` are built on this formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Primitive polynomials (same ones Jerasure uses).
PRIM_POLY = {8: 0x11D, 16: 0x1100B}
WORD_DTYPE = {8: np.uint8, 16: np.uint16}
# Packed-lane constants: bytes-per-u32 lane and the "every word's LSB" mask.
LANES = {8: 4, 16: 2}
LSB_MASK = {8: 0x01010101, 16: 0x00010001}


@functools.lru_cache(maxsize=None)
def gf_tables(l: int) -> tuple[np.ndarray, np.ndarray]:
    """(exp, log) tables. ``exp`` is doubled so exp[log a + log b] needs no mod."""
    if l not in PRIM_POLY:
        raise ValueError(f"unsupported field GF(2^{l})")
    q = 1 << l
    exp = np.zeros(2 * (q - 1), dtype=np.int64)
    log = np.zeros(q, dtype=np.int64)
    x = 1
    for i in range(q - 1):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & q:  # PRIM_POLY includes the x^l bit, so this clears it too
            x ^= PRIM_POLY[l]
    exp[q - 1:] = exp[: q - 1]
    return exp, log


# ---------------------------------------------------------------------------
# Host (numpy) arithmetic
# ---------------------------------------------------------------------------

def gf_mul_np(a, b, l: int):
    """Elementwise GF(2^l) product of numpy arrays (any int dtype)."""
    exp, log = gf_tables(l)
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    out = exp[log[a] + log[b]]
    out = np.where((a == 0) | (b == 0), 0, out)
    return out.astype(WORD_DTYPE[l])


def gf_inv_scalar(a: int, l: int) -> int:
    if a == 0:
        raise ZeroDivisionError("no inverse of 0")
    exp, log = gf_tables(l)
    q = 1 << l
    return int(exp[(q - 1 - log[a]) % (q - 1)])


def gf_mul_scalar(a: int, b: int, l: int) -> int:
    return int(gf_mul_np(np.int64(a), np.int64(b), l))


def gf_pow_scalar(a: int, e: int, l: int) -> int:
    if e == 0:
        return 1
    if a == 0:
        return 0
    exp, log = gf_tables(l)
    q = 1 << l
    return int(exp[(int(log[a]) * e) % (q - 1)])


def gf_matmul_np(A: np.ndarray, B: np.ndarray, l: int) -> np.ndarray:
    """GF matrix product: A (n,k) x B (k,...) -> (n,...), xor-accumulated."""
    A = np.asarray(A)
    B = np.asarray(B)
    n, k = A.shape
    out = np.zeros((n,) + B.shape[1:], dtype=WORD_DTYPE[l])
    for j in range(k):
        out ^= gf_mul_np(A[:, j].reshape((n,) + (1,) * (B.ndim - 1)), B[j][None], l)
    return out


def gf_rank_np(M: np.ndarray, l: int) -> int:
    """Rank over GF(2^l) via Gaussian elimination, vectorized per pivot step."""
    exp, log = gf_tables(l)
    M = np.array(M, dtype=np.int64, copy=True)
    rows, cols = M.shape
    rank = 0
    for c in range(cols):
        col = M[rank:, c]
        nz = np.nonzero(col)[0]
        if nz.size == 0:
            continue
        piv = rank + int(nz[0])
        if piv != rank:
            M[[rank, piv]] = M[[piv, rank]]
        # normalize pivot row, then eliminate column c from ALL other rows at once
        inv = gf_inv_scalar(int(M[rank, c]), l)
        pivrow = gf_mul_np(M[rank], np.int64(inv), l).astype(np.int64)
        M[rank] = pivrow
        factors = M[:, c].copy()
        factors[rank] = 0
        nzr = np.nonzero(factors)[0]
        if nzr.size:
            upd = exp[log[factors[nzr]][:, None] + log[pivrow][None, :]]
            upd = np.where(pivrow[None, :] == 0, 0, upd)
            M[nzr] ^= upd
        rank += 1
        if rank == rows:
            break
    return rank


def gf_inv_matrix_np(M: np.ndarray, l: int) -> np.ndarray:
    """Inverse of a square GF(2^l) matrix (host Gaussian elimination)."""
    M = np.array(M, dtype=np.int64, copy=True)
    k = M.shape[0]
    assert M.shape == (k, k)
    aug = np.concatenate([M, np.eye(k, dtype=np.int64)], axis=1)
    for c in range(k):
        piv = None
        for r in range(c, k):
            if aug[r, c] != 0:
                piv = r
                break
        if piv is None:
            raise np.linalg.LinAlgError("singular GF matrix")
        aug[[c, piv]] = aug[[piv, c]]
        inv = gf_inv_scalar(int(aug[c, c]), l)
        aug[c] = gf_mul_np(aug[c], np.int64(inv), l)
        for r in range(k):
            if r != c and aug[r, c] != 0:
                aug[r] ^= gf_mul_np(aug[c], aug[r, c], l).astype(np.int64)
    return aug[:, k:].astype(WORD_DTYPE[l])


# ---------------------------------------------------------------------------
# jnp table arithmetic (reference device path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jnp_tables(l: int):
    exp, log = gf_tables(l)
    return jnp.asarray(exp, dtype=jnp.int32), jnp.asarray(log, dtype=jnp.int32)


def gf_mul(a: jax.Array, b: jax.Array, l: int) -> jax.Array:
    """Elementwise GF(2^l) product (broadcasts); inputs any unsigned dtype."""
    exp, log = _jnp_tables(l)
    ai = a.astype(jnp.int32)
    bi = b.astype(jnp.int32)
    prod = exp[log[ai] + log[bi]]
    prod = jnp.where((ai == 0) | (bi == 0), 0, prod)
    return prod.astype(WORD_DTYPE[l])


def gf_matmul(A, B: jax.Array, l: int) -> jax.Array:
    """A (n,k) static-or-traced coeffs x B (k, ...) -> (n, ...)."""
    A = jnp.asarray(A)
    n, k = A.shape
    out = None
    for j in range(k):
        term = gf_mul(A[:, j].reshape((n,) + (1,) * (B.ndim - 1)), B[j][None], l)
        out = term if out is None else out ^ term
    return out


# ---------------------------------------------------------------------------
# Packed bit-plane arithmetic (TPU-native formulation)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="l")
def pack_u32(x: jax.Array, l: int) -> jax.Array:
    """Pack words of GF(2^l) (uint8/uint16) along the last dim into uint32 lanes.

    Last dim must be a multiple of LANES[l]. The layout is planar: the last
    axis splits into LANES[l] contiguous parts of Q words, and lane j holds
    word i*Q + j in bits [i*l, (i+1)*l). Every packed GF operation is
    positionwise, so any bijective layout codes identically; this one needs
    only contiguous slices, where packing adjacent words into a lane makes
    XLA materialize a (..., Q, LANES) array whose minor axis the TPU pads to
    128 lanes (64x the bytes at GF(2^16)).
    """
    lanes = LANES[l]
    assert x.shape[-1] % lanes == 0, (x.shape, lanes)
    q = x.shape[-1] // lanes
    out = x[..., :q].astype(jnp.uint32)
    for i in range(1, lanes):
        out = out | (x[..., i * q:(i + 1) * q].astype(jnp.uint32) << (i * l))
    return out


@functools.partial(jax.jit, static_argnames="l")
def unpack_u32(xp: jax.Array, l: int) -> jax.Array:
    """Inverse of ``pack_u32``: (..., Q) uint32 lanes -> (..., LANES[l]*Q)."""
    mask = jnp.uint32((1 << l) - 1)
    return jnp.concatenate(
        [((xp >> (i * l)) & mask).astype(WORD_DTYPE[l])
         for i in range(LANES[l])], axis=-1)


def bitplane_consts(c: int, l: int) -> list[int]:
    """Per-bit constants for multiply-by-c: const_j = c * alpha^j (alpha = x)."""
    return [gf_mul_scalar(c, 1 << j, l) for j in range(l)]


def bitplane_table(M, l: int) -> np.ndarray:
    """Vectorized ``bitplane_consts`` over a whole coefficient array.

    (...,) GF(2^l) coefficients -> (..., l) uint32 with
    ``out[..., j] = M[...] * alpha^j`` — one table-lookup broadcast instead
    of a Python loop per (coefficient, bit) pair.
    """
    M = np.asarray(M, dtype=np.int64)
    pows = np.asarray([1 << j for j in range(l)], dtype=np.int64)
    return gf_mul_np(M[..., None], pows, l).astype(np.uint32)


def gf_mul_const_packed(xp: jax.Array, c: int, l: int) -> jax.Array:
    """Multiply packed words by static coefficient c; pure shift/mask/mul/xor.

    Each lane byte/halfword b satisfies ``c*b = xor_j bit_j(b) * (c*alpha^j)``;
    since mask lanes are in {0,1} and const_j < 2^l, the integer product never
    carries across packed lanes.
    """
    if c == 0:
        return jnp.zeros_like(xp)
    lsb = jnp.uint32(LSB_MASK[l])
    acc = jnp.zeros_like(xp)
    for j, const_j in enumerate(bitplane_consts(c, l)):
        if const_j == 0:
            continue
        mask = (xp >> j) & lsb
        acc = acc ^ (mask * jnp.uint32(const_j))
    return acc


def gf_matvec_packed(coeffs: np.ndarray, Xp: jax.Array, l: int) -> jax.Array:
    """coeffs (n,k) STATIC numpy x packed blocks Xp (k, B_packed) -> (n, B_packed)."""
    coeffs = np.asarray(coeffs)
    n, k = coeffs.shape
    rows = []
    for i in range(n):
        acc = jnp.zeros_like(Xp[0])
        for j in range(k):
            c = int(coeffs[i, j])
            if c:
                acc = acc ^ gf_mul_const_packed(Xp[j], c, l)
        rows.append(acc)
    return jnp.stack(rows)
