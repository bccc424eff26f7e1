"""Pallas TPU kernels for GF(2^l) erasure encoding.

Three kernels, all operating on VMEM tiles with explicit BlockSpecs:

* ``gf_encode_kernel``   — static-coefficient matrix encode on the VPU using
  packed bit-plane arithmetic (4 bytes / 2 halfwords per 32-bit lane; zero
  gathers). The masks ``(x_j >> b) & lsb`` are hoisted and reused across all
  output rows, so the op count is k*l masks + rows*k*l mul/xor per tile.
* ``chain_step_kernel``  — the fused per-node RapidRAID step (Eqs. 3-4):
  consumes the incoming wire chunk, produces BOTH the local codeword chunk
  (xi path) and the forwarded wire (psi path) in one pass over the data —
  the paper's "both phases executed simultaneously" observation (§IV-A).
  Coefficients arrive as a (max_b, l) uint32 plane array (traced, per node).
* ``repair_step_kernel`` — the repair dual of ``chain_step_kernel``: one
  helper node's fused GF inner-product contribution to the partial
  reconstructions of up to n-k lost shards streaming down the helper chain
  (repair pipelining; ``repro.storage.repair``).
* ``gf_encode_mxu_kernel`` — beyond-paper variant: lift GF(2^8) to F_2 bit
  matrices; encoding becomes an int8 matmul mod 2 that runs on the MXU
  (the systolic array) instead of the VPU. Trades 64x nominal MACs for the
  MXU's much higher int8 throughput; see EXPERIMENTS.md §Perf for the
  roofline comparison.

``gf_encode_kernel`` and ``chain_step_kernel`` accept an optional leading
OBJECT axis (multi-object archival, paper §VI): a (O, ...) input makes the
object index the leading pallas grid dimension, so ONE fused launch encodes
O objects and the launch + coefficient-plane overhead is amortized across
the batch.

Every kernel takes ``interpret`` as a required keyword: the one place that
decides it is ``ops._interpret_default`` (the Pallas interpreter off-TPU,
the compiled Mosaic kernel on a TPU). The BlockSpecs below are the
real TPU tiling (last dim a multiple of 128 lanes, working set sized for
~16 MB VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import gf

DEFAULT_BLOCK = 512  # uint32 lanes per tile: 2 KiB/row — k=16 rows fit easily
DEFAULT_MXU_BLOCK = 1024  # words per MXU tile: bit-lift multiplies rows by l


def _encode_body(x_ref, o_ref, *, M: np.ndarray, l: int):
    rows, k = M.shape
    lsb = jnp.uint32(gf.LSB_MASK[l])
    x = x_ref[0]  # (k, TB) uint32 — this grid cell's object
    acc = [jnp.zeros_like(x[0]) for _ in range(rows)]
    # hoist bit masks: one (x_j >> b) & lsb per (input row, bit-plane)
    for j in range(k):
        consts = [gf.bitplane_consts(int(M[r, j]), l) for r in range(rows)]
        for b in range(l):
            if not any(consts[r][b] for r in range(rows)):
                continue
            m = (x[j] >> b) & lsb
            for r in range(rows):
                cst = consts[r][b]
                if cst:
                    acc[r] = acc[r] ^ (m * jnp.uint32(cst))
    o_ref[...] = jnp.stack(acc)[None]


def gf_encode_kernel(M: np.ndarray, data_packed: jax.Array, l: int,
                     block: int = DEFAULT_BLOCK, *, interpret: bool):
    """Static-coeff encode, single object or a batch of objects in ONE launch.

    (k, Bp) packed -> (rows, Bp), or (O, k, Bp) -> (O, rows, Bp) with the
    object axis as the leading pallas grid dimension — the coefficient
    constants are baked into the (unrolled) kernel body once and reused for
    every object, so launch + plane-hoisting overhead is amortized over O.
    """
    M = np.asarray(M)
    rows, k = M.shape
    single = data_packed.ndim == 2
    if single:
        data_packed = data_packed[None]
    O, kk, Bp = data_packed.shape
    if kk != k or Bp % block:
        raise ValueError(
            f"gf_encode_kernel: data {data_packed.shape} needs k={k} rows and "
            f"a packed length divisible by block={block} (pad via "
            f"repro.kernels.gf_encode.ops.encode_packed for ragged lengths)")
    out = pl.pallas_call(
        functools.partial(_encode_body, M=M, l=l),
        grid=(O, Bp // block),
        in_specs=[pl.BlockSpec((1, k, block), lambda o, i: (o, 0, i))],
        out_specs=pl.BlockSpec((1, rows, block), lambda o, i: (o, 0, i)),
        out_shape=jax.ShapeDtypeStruct((O, rows, Bp), jnp.uint32),
        interpret=interpret,
        name="gf_encode",
    )(data_packed)
    return out[0] if single else out


def _chain_step_body(x_ref, local_ref, bpsi_ref, bxi_ref, c_ref, xout_ref,
                     *, l: int, max_b: int):
    lsb = jnp.uint32(gf.LSB_MASK[l])
    x_in = x_ref[0]            # (1, TB) — this grid cell's object
    c = x_in
    xo = x_in
    for s in range(max_b):
        blk = local_ref[0, s, :][None]  # (1, TB)
        for b in range(l):
            m = (blk >> b) & lsb     # shared between psi and xi paths
            c = c ^ (m * bxi_ref[s, b])
            xo = xo ^ (m * bpsi_ref[s, b])
    c_ref[...] = c[None]
    xout_ref[...] = xo[None]


def chain_step_kernel(x_in: jax.Array, local: jax.Array, bp_psi: jax.Array,
                      bp_xi: jax.Array, l: int, block: int = DEFAULT_BLOCK,
                      *, interpret: bool):
    """Fused RapidRAID node step on one chunk, for 1 object or a batch.

    Single object: x_in (1, C) uint32 wire, local (max_b, C) packed replica
    blocks -> (c, x_out) each (1, C). Batched: x_in (O, 1, C), local
    (O, max_b, C) -> each output (O, 1, C), one fused launch with the object
    axis on the pallas grid. bp_psi/bp_xi (max_b, l) uint32 bit-plane
    coefficient constants are shared across objects (same code).
    """
    single = local.ndim == 2
    if single:
        x_in, local = x_in[None], local[None]
    O, max_b, C = local.shape
    assert x_in.shape == (O, 1, C) and C % block == 0
    body = functools.partial(_chain_step_body, l=l, max_b=max_b)
    c, xo = pl.pallas_call(
        body,
        grid=(O, C // block),
        in_specs=[
            pl.BlockSpec((1, 1, block), lambda o, i: (o, 0, i)),
            pl.BlockSpec((1, max_b, block), lambda o, i: (o, 0, i)),
            pl.BlockSpec((max_b, l), lambda o, i: (0, 0)),  # planes: whole
            pl.BlockSpec((max_b, l), lambda o, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block), lambda o, i: (o, 0, i)),
            pl.BlockSpec((1, 1, block), lambda o, i: (o, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((O, 1, C), jnp.uint32),
            jax.ShapeDtypeStruct((O, 1, C), jnp.uint32),
        ],
        interpret=interpret,
        name="chain_step",
    )(x_in, local, bp_psi, bp_xi)
    return (c[0], xo[0]) if single else (c, xo)


def _repair_step_body(x_ref, local_ref, bp_ref, o_ref, *, l: int):
    lsb = jnp.uint32(gf.LSB_MASK[l])
    acc = x_ref[0]             # (rows, TB) incoming partial reconstructions
    blk = local_ref[0, 0, :]   # (TB,) this helper's shard chunk
    for b in range(l):
        m = (blk >> b) & lsb   # one mask per bit, shared across all rows
        acc = acc ^ (m[None, :] * bp_ref[:, b][:, None])
    o_ref[...] = acc[None]


def repair_step_kernel(x_in: jax.Array, local: jax.Array, bp: jax.Array,
                       l: int, block: int = DEFAULT_BLOCK,
                       *, interpret: bool):
    """Fused GF inner-product repair step (repair pipelining, one helper).

    The helper adds its term of ``c_lost = xor_h R[:, h] * c_h`` to the
    partial reconstructions streaming down the chain: ``x_in`` (rows, C)
    uint32 packed partial sums for the ``rows`` lost shards, ``local``
    (1, C) the helper's own shard chunk, ``bp`` (rows, l) the bit-plane
    constants of the helper's repair-coefficient column
    (``bp[r, b] = R[r, h] * alpha^b``). Returns x_in ^ contribution.

    Batched: x_in (O, rows, C), local (O, 1, C) -> (O, rows, C), one fused
    launch with the object axis on the pallas grid (``bp`` shared — after a
    node failure every object archived on the node set lost the same rows).
    """
    single = x_in.ndim == 2
    if single:
        x_in, local = x_in[None], local[None]
    O, rows, C = x_in.shape
    assert local.shape == (O, 1, C) and C % block == 0, (x_in.shape,
                                                         local.shape, block)
    out = pl.pallas_call(
        functools.partial(_repair_step_body, l=l),
        grid=(O, C // block),
        in_specs=[
            pl.BlockSpec((1, rows, block), lambda o, i: (o, 0, i)),
            pl.BlockSpec((1, 1, block), lambda o, i: (o, 0, i)),
            pl.BlockSpec((rows, l), lambda o, i: (0, 0)),  # planes: whole
        ],
        out_specs=pl.BlockSpec((1, rows, block), lambda o, i: (o, 0, i)),
        out_shape=jax.ShapeDtypeStruct((O, rows, C), jnp.uint32),
        interpret=interpret,
        name="repair_step",
    )(x_in, local, bp)
    return out[0] if single else out


# ---------------------------------------------------------------------------
# MXU bit-lift variant (beyond paper)
# ---------------------------------------------------------------------------

def bitlift_matrix(M: np.ndarray, l: int) -> np.ndarray:
    """Lift (rows,k) GF(2^l) coeffs to an (rows*l, k*l) F2 matrix (int8).

    bit_i(c*x) = xor_b bit_b(x) * bit_i(c * alpha^b), so
    Mbits[r*l + i, j*l + b] = bit_i(M[r,j] * alpha^b).
    """
    rows, k = M.shape
    out = np.zeros((rows * l, k * l), dtype=np.int8)
    for r in range(rows):
        for j in range(k):
            c = int(M[r, j])
            if not c:
                continue
            for b in range(l):
                prod = gf.gf_mul_scalar(c, 1 << b, l)
                for i in range(l):
                    out[r * l + i, j * l + b] = (prod >> i) & 1
    return out


def _mxu_body(x_ref, mb_ref, o_ref, *, l: int, rows: int, k: int):
    x = x_ref[...]  # (k, TB) words as int32 (uint8/16 widened on host)
    # unpack to bit planes: col order j*l + b  ->  (k*l, TB) int8
    bits = jnp.stack([(x >> b) & 1 for b in range(l)], axis=1)  # (k, l, TB)
    bits = bits.reshape(k * l, -1).astype(jnp.int8)
    Mb = mb_ref[...]  # (rows*l, k*l) int8 bit-lifted generator
    y = jax.lax.dot_general(Mb, bits, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    y = y & 1                                            # mod-2: xor via MXU
    y = y.reshape(rows, l, -1)
    word = jnp.zeros_like(y[:, 0])
    for i in range(l):
        word = word | (y[:, i] << i)
    o_ref[...] = word


def gf_encode_mxu_kernel(M: np.ndarray, data_words: jax.Array, l: int,
                         block: int = DEFAULT_MXU_BLOCK,
                         *, interpret: bool):
    """Bit-lifted MXU encode: (k, B) words (int32) -> (rows, B) words (int32)."""
    M = np.asarray(M)
    rows, k = M.shape
    Mbits = bitlift_matrix(M, l)
    kk, B = data_words.shape
    if kk != k or B % block:
        raise ValueError(
            f"gf_encode_mxu_kernel: data {data_words.shape} needs k={k} rows "
            f"and a word count divisible by block={block} (pad via "
            f"repro.kernels.gf_encode.ops.encode_mxu for ragged lengths)")
    body = functools.partial(_mxu_body, l=l, rows=rows, k=k)
    return pl.pallas_call(
        body,
        grid=(B // block,),
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (0, i)),
            pl.BlockSpec((rows * l, k * l), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, B), jnp.int32),
        interpret=interpret,
        name="gf_encode_mxu",
    )(data_words, jnp.asarray(Mbits))
