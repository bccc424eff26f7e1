"""Pallas GF-encode kernels vs pure-jnp oracle: shape/dtype/code-param sweep."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import classical, gf, rapidraid as rr
from repro.kernels.gf_encode import kernel, ops, ref


def rand_words(rng, k, B, l):
    return rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("n,k", [(8, 4), (16, 11), (6, 4)])
@pytest.mark.parametrize("cols", [512, 1024])
def test_encode_kernel_sweep_rapidraid(l, n, k, cols):
    code = rr.RapidRAIDCode.make(n, k, l=l, seed=1)
    rng = np.random.default_rng(0)
    B = cols * gf.LANES[l]
    data = rand_words(rng, k, B, l)
    dp = gf.pack_u32(jnp.asarray(data), l)
    got = ops.encode_packed(code.G, dp, l, block=512)
    want = ref.encode_packed_ref(code.G, dp, l)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and against the word-level table oracle
    np.testing.assert_array_equal(
        np.asarray(gf.unpack_u32(got, l)), code.encode_np(data))


@pytest.mark.parametrize("l", [8, 16])
def test_encode_kernel_classical_parity(l):
    code = classical.make_code(16, 11, l=l)
    rng = np.random.default_rng(1)
    B = 512 * gf.LANES[l]
    data = rand_words(rng, 11, B, l)
    got = ops.encode_words(code.parity_matrix, jnp.asarray(data), l)
    np.testing.assert_array_equal(np.asarray(got), classical.encode_np(code, data))


@pytest.mark.parametrize("block", [256, 512])
def test_encode_kernel_multi_tile_grid(block):
    """Grid > 1: tiling must not leak across block boundaries."""
    l, n, k = 8, 8, 4
    code = rr.RapidRAIDCode.make(n, k, l=l, seed=3)
    rng = np.random.default_rng(2)
    B = block * 4 * gf.LANES[l]  # 4 grid steps
    data = rand_words(rng, k, B, l)
    dp = gf.pack_u32(jnp.asarray(data), l)
    got = ops.encode_packed(code.G, dp, l, block=block)
    np.testing.assert_array_equal(
        np.asarray(gf.unpack_u32(got, l)), code.encode_np(data))


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("max_b", [1, 2])
def test_chain_step_kernel(l, max_b):
    rng = np.random.default_rng(3)
    C = 512
    x_in = rng.integers(0, 2 ** 32, size=(1, C), dtype=np.uint32)
    local_words = rand_words(rng, max_b, C * gf.LANES[l], l)
    local = np.asarray(gf.pack_u32(jnp.asarray(local_words), l))
    psi = rng.integers(1, 1 << l, size=(max_b,))
    xi = rng.integers(1, 1 << l, size=(max_b,))
    bp_psi = np.array([[gf.gf_mul_scalar(int(p), 1 << j, l) for j in range(l)]
                       for p in psi], dtype=np.uint32)
    bp_xi = np.array([[gf.gf_mul_scalar(int(x), 1 << j, l) for j in range(l)]
                      for x in xi], dtype=np.uint32)
    c, xo = ops.chain_step(jnp.asarray(x_in), jnp.asarray(local),
                           jnp.asarray(bp_psi), jnp.asarray(bp_xi), l)
    c_ref, xo_ref = ref.chain_step_ref(jnp.asarray(x_in), jnp.asarray(local),
                                       psi, xi, l)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))
    np.testing.assert_array_equal(np.asarray(xo), np.asarray(xo_ref))


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("n,k", [(8, 4), (16, 11)])
def test_mxu_bitlift_kernel(l, n, k):
    code = rr.RapidRAIDCode.make(n, k, l=l, seed=5)
    rng = np.random.default_rng(4)
    B = 1024
    data = rand_words(rng, k, B, l)
    got = ops.encode_mxu(code.G, jnp.asarray(data), l, block=1024)
    np.testing.assert_array_equal(np.asarray(got), code.encode_np(data))


@pytest.mark.parametrize("l,B", [(8, 1000), (16, 998), (16, 1002)])
def test_mxu_vpu_numpy_parity_ragged_lengths(l, B):
    """Word counts NOT divisible by the kernel block (and odd packed
    lengths): MXU bit-lift, VPU bit-plane, and the numpy oracle must agree.
    Regression for the bare-assert crash (MXU) and the block=1 per-word
    grid degeneration (pick_block on odd packed lengths)."""
    code = rr.RapidRAIDCode.make(8, 4, l=l, seed=7)
    rng = np.random.default_rng(6)
    data = rand_words(rng, 4, B, l)
    want = code.encode_np(data)
    got_mxu = ops.encode_mxu(code.G, jnp.asarray(data), l, block=1024)
    assert got_mxu.dtype == gf.WORD_DTYPE[l]  # l=16 output dtype round-trips
    np.testing.assert_array_equal(np.asarray(got_mxu), want)
    got_vpu = ops.encode_words(code.G, jnp.asarray(data), l, block=512)
    np.testing.assert_array_equal(np.asarray(got_vpu), want)


def test_encode_packed_ragged_odd_packed_length():
    """Odd packed length straight through encode_packed (pad-and-slice)."""
    l = 16
    code = rr.RapidRAIDCode.make(6, 4, l=l, seed=9)
    rng = np.random.default_rng(8)
    data = rand_words(rng, 4, 998, l)            # Bp = 499, odd
    dp = gf.pack_u32(jnp.asarray(data), l)
    assert dp.shape[-1] == 499
    got = ops.encode_packed(code.G, dp, l)
    assert got.shape == (6, 499)
    np.testing.assert_array_equal(
        np.asarray(gf.unpack_u32(got, l)), code.encode_np(data))


def test_pick_block_never_degenerates():
    assert ops.pick_block(499) == 512
    assert ops.pick_block(250) == 256
    assert ops.pick_block(4096) == kernel.DEFAULT_BLOCK
    assert ops.pick_block(1) == 1
    assert all(ops.pick_block(bp) >= min(bp, 256) for bp in range(1, 2000))


def test_kernel_raises_not_asserts_on_bad_shapes():
    """Direct kernel calls get a real ValueError (asserts vanish under -O)."""
    M = np.ones((2, 2), dtype=np.uint8)
    with pytest.raises(ValueError):
        kernel.gf_encode_kernel(M, jnp.zeros((2, 3), jnp.uint32), 8,
                                block=2, interpret=True)
    with pytest.raises(ValueError):
        kernel.gf_encode_mxu_kernel(M, jnp.zeros((2, 3), jnp.int32), 8,
                                    block=2, interpret=True)


def test_bitlift_matrix_rank():
    """F2 lift of an invertible GF matrix must have full F2 rank (k*l)."""
    l = 8
    code = classical.make_code(8, 4, l=l)
    sub = code.G[[1, 3, 5, 7]]
    Mb = kernel.bitlift_matrix(sub, l)
    # F2 rank via numpy mod-2 elimination
    A = Mb.astype(np.int64) % 2
    rank = 0
    for c in range(A.shape[1]):
        piv = None
        for r in range(rank, A.shape[0]):
            if A[r, c]:
                piv = r
                break
        if piv is None:
            continue
        A[[rank, piv]] = A[[piv, rank]]
        for r in range(A.shape[0]):
            if r != rank and A[r, c]:
                A[r] = (A[r] + A[rank]) % 2
        rank += 1
    assert rank == 4 * l
