"""HDFS-Xorbas LRC(10,6,5), the ``xorbas`` family (Sathiamoorthy et al.,
VLDB 2013): its generator against the benchmark's plain reference, the
paper's distance 5, a 5-helper XOR repair of every single lost row, the
implied-parity rule, and an archive -> loss -> repair -> read round trip
through ``StorageClient``.
"""
import importlib.util
import itertools
import os

import numpy as np
import pytest

from repro.core import codes, gf
from repro.storage import archive as arc
from repro.storage import object_store as obj
from repro.storage.client import StorageClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, K, L = 16, 10, 16
CFG = {"n": N, "k": K, "l": L, "code_seed": 0, "local_groups": 2}
GROUPS = (list(range(5)), list(range(5, 10)))
S, P = (10, 11), (12, 13, 14, 15)


def _reference(name):
    path = os.path.join(REPO, "bench", "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF, REF_GF = _reference("xorbas"), _reference("gf")


@pytest.fixture(scope="module")
def code():
    return codes.make("xorbas", N, K, l=L, seed=0)


def _expected_helpers(row):
    for g, members in enumerate(GROUPS):
        if row in members:
            return [r for r in members if r != row] + [S[g]]
        if row == S[g]:
            return members
    return [r for r in P if r != row] + list(S)


def test_generator_matches_the_reference(code):
    G = REF.generator(CFG)
    assert np.array_equal(code.G.astype(np.int64), G)
    assert code.spec.family == "xorbas" and code.storage_overhead == 1.6
    # the implied parity: P1 + ... + P4 = S1 + S2 = every data block
    assert (np.bitwise_xor.reduce(G[list(P)], axis=0) == 1).all()
    assert (G[list(P)] != 0).all()


def test_every_four_loss_pattern_decodes(code):
    alive = set(range(N))
    bad = [lost for lost in itertools.combinations(range(N), 4)
           if not code.decodable(alive - set(lost))]
    assert bad == []
    assert sum(1 for _ in itertools.combinations(range(N), 4)) == 1820


@pytest.mark.parametrize("row", range(N))
def test_single_loss_repairs_from_five_by_xor(code, row):
    alive = [r for r in range(N) if r != row]
    helpers, R = code.repair_plan([row], alive)
    assert sorted(helpers) == sorted(_expected_helpers(row))
    assert R.shape == (1, 5) and (R == 1).all()
    assert REF.repair_reads(CFG, [row]) == 5
    rng = np.random.default_rng(row)
    X = rng.integers(0, 1 << L, size=(K, 96)).astype(gf.WORD_DTYPE[L])
    cw = REF_GF.apply(REF.generator(CFG), X, L)
    got = code.repair_np([row], alive, cw[alive])
    assert np.array_equal(got[0], cw[row])


def test_two_losses_fall_back_to_the_matrix_plan(code):
    helpers, R = code.repair_plan([0, 12], [r for r in range(N)
                                            if r not in (0, 12)])
    assert len(helpers) == K and R.shape == (2, K)
    assert REF.repair_reads(CFG, [0, 12]) == K


def test_a_seed_whose_implied_row_has_a_zero_is_refused():
    """Over GF(2^8) some seeds make P4 = 1 + P1 + P2 + P3 hit 0; those and
    only those are refused, and no other seed is tried in their place."""
    refused = []
    for seed in range(120):
        G = REF.generator({**CFG, "l": 8, "code_seed": seed})
        try:
            got = codes.make("xorbas", N, K, l=8, seed=seed).G
        except ValueError as e:
            assert "zero coefficient" in str(e)
            refused.append(seed)
            assert not G[-1].all()
            continue
        assert G[-1].all() and np.array_equal(got.astype(np.int64), G)
    assert refused


class _CountingStore(obj.NodeStore):
    """Counts the coded rows read whole."""

    def __post_init__(self):
        super().__post_init__()
        self.coded_reads = 0

    def get(self, i, rel):
        self.coded_reads += rel.startswith("archive/")
        return super().get(i, rel)


@pytest.mark.parametrize("row", [3, 10, 14], ids=["data", "S1", "P3"])
def test_client_heals_each_kind_of_loss_from_five(tmp_path, row):
    acfg = arc.ArchiveConfig(n=N, k=K, l=L, family="xorbas")
    store = _CountingStore(str(tmp_path), N)
    client = StorageClient(store, acfg)
    blocks = np.random.default_rng(7).integers(0, 256, size=(K, 4096),
                                               dtype=np.uint8)
    client.put_hot(1, blocks)
    client.archive(1)
    manifest = client.manifest(1)
    assert manifest["family"] == "xorbas"
    store.delete(manifest["perm"][row], arc.ARC.format(step=1, i=row))
    store.coded_reads = 0
    assert client.repair(1) == [row]
    assert store.coded_reads == 5
    got = client.read_range(1, 0, K * 4096).data
    assert got == blocks.tobytes()
    want = REF_GF.apply(REF.generator(CFG), blocks.view(np.uint16), L)
    raw = store.get(manifest["perm"][row], arc.ARC.format(step=1, i=row))
    assert raw == want[row].tobytes()
