"""The plain GF(2^l) reference and the code constructions it checks by."""
import numpy as np
import pytest

from harness import answers
from reference import gf, lrc, rapidraid


def test_known_products():
    # x * x^7 = x^8 = x^4 + x^3 + x^2 + 1 under 0x11D
    assert gf.mul(0x02, 0x80, 8) == 0x1D
    assert gf.mul(0x8000, 0x02, 16) == 0x100B
    assert gf.mul(0x53, 0x01, 8) == 0x53 and gf.mul(0x53, 0, 8) == 0
    # x is primitive: its order is 2^l - 1 and no proper divisor of it
    for l, divisors in ((8, (1, 3, 5, 15, 17, 51, 85)),
                        (16, (1, 3, 5, 17, 257, 4369, 21845))):
        assert gf.power(2, (1 << l) - 1, l) == 1
        assert all(gf.power(2, d, l) != 1 for d in divisors)


@pytest.mark.parametrize("l", [8, 16])
def test_apply_matches_scalar_products(l):
    rng = np.random.default_rng(5)
    G = rng.integers(0, 1 << l, size=(3, 4))
    X = rng.integers(0, 1 << l, size=(4, 50)).astype(gf.WORD[l])
    want = np.zeros((3, 50), dtype=np.int64)
    for r in range(3):
        for j in range(4):
            want[r] ^= [gf.mul(int(G[r, j]), int(x), l) for x in X[j]]
    assert np.array_equal(gf.apply(G, X, l), want)


def test_rapidraid_generator_unrolls_the_chain():
    # (4,3): node 0 holds o0, node 1 holds o1 and o0, node 2 o2 and o1,
    # node 3 o2; psi slots 0..4 (nodes 0-2), xi slots 0..5
    cfg = {"n": 4, "k": 3, "l": 16, "code_seed": 3}
    rng = np.random.default_rng(3)
    psi = [int(v) for v in rng.integers(1, 1 << 16, size=5)]
    xi = [int(v) for v in rng.integers(1, 1 << 16, size=6)]
    want = np.array([
        [xi[0], 0, 0],
        [psi[0] ^ xi[2], xi[1], 0],
        [psi[0] ^ psi[2], psi[1] ^ xi[4], xi[3]],
        [psi[0] ^ psi[2], psi[1] ^ psi[4], psi[3] ^ xi[5]],
    ])
    assert np.array_equal(rapidraid.generator(cfg), want)


def test_lrc_layout():
    cfg = {"n": 16, "k": 12, "l": 16, "code_seed": 0, "local_groups": 2}
    G = lrc.generator(cfg)
    assert np.array_equal(G[:12], np.eye(12))
    assert G[12].tolist() == [1] * 6 + [0] * 6
    assert G[13].tolist() == [0] * 6 + [1] * 6
    assert (G[14:] != 0).all()
    assert lrc.repair_reads(cfg, [3]) == 6 and lrc.repair_reads(cfg, [15]) == 12


@pytest.mark.parametrize("l", [8, 16])
def test_folds_commute_with_the_code(l):
    rng = np.random.default_rng(l)
    cfg = {"n": 6, "k": 4, "l": l, "code_seed": 1}
    G = rapidraid.generator(cfg)
    blocks = rng.integers(0, 256, size=(4, 32768), dtype=np.uint8)
    coded = gf.apply(G, blocks.view(gf.WORD[l]), l)
    ref = answers.DataRef.of(blocks, l, rng)
    seg, res = answers.folds(coded, l)
    assert np.array_equal(gf.apply(G, ref.seg, l), seg)
    assert np.array_equal(gf.apply(G, ref.res, l), res)
    assert np.array_equal(gf.apply(G, ref.win, l),
                          answers.take(coded, ref.starts))
    # a word moved inside its segment changes the residue folds only
    moved = coded.copy()
    moved[0, [1, 2]] = moved[0, [2, 1]]
    s2, r2 = answers.folds(moved, l)
    assert np.array_equal(s2, seg) and not np.array_equal(r2, res)
