"""Pipelined repair & degraded reads: bit-exactness, clean failure, healing.

Acceptance pins (ISSUE 2):
  * losing any 1..(n-k) shards repairs bit-exactly against ``encode_np``;
  * degraded reads match plain reads byte-for-byte;
  * losing more than n-k shards fails CLEANLY — raises before touching any
    stored byte, never installs a corrupt block;
  * the reverse (repair-direction) pipeline schedule is the encode schedule
    mirrored, with identical tick accounting.
"""
import itertools
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from tests.hypothesis_compat import given, settings, st
from tests.subproc import run_with_devices

from repro.core import fault_tolerance as ft
from repro.core import gf, pipeline, rapidraid as rr
from repro.kernels.gf_encode import ops, ref
from repro.storage import archive as arc
from repro.storage import object_store as obj
from repro.storage import repair as rep


# ---------------------------------------------------------------------------
# reverse (repair-direction) schedule
# ---------------------------------------------------------------------------


def test_chain_perm_directions():
    assert pipeline.chain_perm(4) == [(0, 1), (1, 2), (2, 3)]
    assert pipeline.chain_perm(4, reverse=True) == [(1, 0), (2, 1), (3, 2)]


def test_chain_pos_mirror():
    n = 6
    fwd = [pipeline.chain_pos(i, n) for i in range(n)]
    rev = [pipeline.chain_pos(i, n, reverse=True) for i in range(n)]
    assert fwd == list(range(n))
    assert rev == list(reversed(range(n)))
    # tick accounting is direction-independent
    assert pipeline.num_ticks(8, n) == 8 + n - 1
    assert pipeline.num_ticks_many(8, n, 4, 2) == 8 + n - 1 + 3 * 2


# ---------------------------------------------------------------------------
# repair plan + host repair vs encode_np, every loss count 1..n-k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,l", [(8, 4, 8), (8, 4, 16), (6, 4, 16)])
def test_repair_np_every_loss_count(n, k, l):
    code = rr.RapidRAIDCode.make(n, k, l=l, seed=3)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 1 << l, size=(k, 64)).astype(gf.WORD_DTYPE[l])
    cw = code.encode_np(data)
    for r in range(1, n - k + 1):
        missing = sorted(rng.choice(n, size=r, replace=False).tolist())
        ids = [i for i in range(n) if i not in missing]
        got = rep.repair_np(code, missing, ids, cw[ids])
        np.testing.assert_array_equal(got, cw[missing])


def test_repair_plan_coefficients_identity():
    """R @ c_helpers = c_missing for EVERY (n-k)-subset of a small code."""
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=1)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 1 << 16, size=(4, 16)).astype(np.uint16)
    cw = code.encode_np(data)
    for missing in itertools.combinations(range(6), 2):
        alive = [i for i in range(6) if i not in missing]
        try:
            helpers, R = ft.repair_plan(code, list(missing), alive)
        except ValueError:
            continue  # a dependent survivor set of a non-MDS draw
        got = gf.gf_matmul_np(R, cw[helpers], 16)
        np.testing.assert_array_equal(got, cw[list(missing)])


def test_repair_plan_rejects_overlap_and_undecodable():
    code = rr.RapidRAIDCode.make(8, 4, l=16, seed=0)
    with pytest.raises(ValueError):
        ft.repair_plan(code, [1], [1, 2, 3, 4])      # row both missing+alive
    with pytest.raises(ValueError):
        ft.repair_plan(code, [0, 1, 2, 3, 4], [5, 6, 7])   # > n-k lost


# ---------------------------------------------------------------------------
# fused repair kernel == oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("rows", [1, 3])
def test_repair_step_kernel_matches_ref(l, rows):
    rng = np.random.default_rng(5)
    C = 256
    x_in = rng.integers(0, 2 ** 32, size=(rows, C), dtype=np.uint32)
    lw = rng.integers(0, 1 << l, size=(C * gf.LANES[l],)) \
        .astype(gf.WORD_DTYPE[l])
    local = np.asarray(gf.pack_u32(jnp.asarray(lw), l))
    coeffs = rng.integers(0, 1 << l, size=(rows,))
    bp = np.array([gf.bitplane_consts(int(c), l) for c in coeffs],
                  dtype=np.uint32)
    got = ops.repair_step(jnp.asarray(x_in), jnp.asarray(local[None]),
                          jnp.asarray(bp), l, block=128)
    want = ref.repair_step_ref(jnp.asarray(x_in), jnp.asarray(local),
                               coeffs, l)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # batched: object axis on the pallas grid
    xb = np.stack([x_in, x_in ^ np.uint32(7)])
    lb = np.broadcast_to(local[None, None], (2, 1, C))
    gb = ops.repair_step(jnp.asarray(xb), jnp.asarray(lb), jnp.asarray(bp),
                         l, block=128)
    np.testing.assert_array_equal(np.asarray(gb[0]), np.asarray(got))


# ---------------------------------------------------------------------------
# store-level: targeted repair, batched heal, degraded reads
# ---------------------------------------------------------------------------


ACFG = arc.ArchiveConfig(n=8, k=4, l=16, num_chunks=4)


def _archived_store(tmp, steps=(1,), nbytes_per_block=512, seed=0):
    store = obj.NodeStore(str(tmp), ACFG.n)
    rng = np.random.default_rng(seed)
    blocks = {}
    for s in steps:
        blocks[s] = rng.integers(0, 256, size=(ACFG.k, nbytes_per_block),
                                 dtype=np.uint8)
        m = arc.hot_save(store, s, blocks[s], ACFG)
        m["blob_len"] = blocks[s].size
        arc._put_manifest(store, s, m)
        arc.archive_step(store, s, ACFG)
    return store, blocks


def test_store_repair_every_loss_count(tmp_path):
    for r in range(1, ACFG.n - ACFG.k + 1):
        with tempfile.TemporaryDirectory(dir=tmp_path) as tmp:
            store, blocks = _archived_store(tmp, seed=r)
            for i in range(r):
                store.fail_node(i)
            assert arc.repair(store, 1, ACFG) == list(range(r))
            # digests were verified during placement; restore is bit-exact
            np.testing.assert_array_equal(
                arc.restore_blocks(store, 1, ACFG), blocks[1])
            # every shard is back on disk
            m = arc.get_manifest(store, 1)
            assert len(arc._alive_coded(store, 1, m)) == ACFG.n


def test_store_repair_over_limit_raises_not_corrupts(tmp_path):
    store, _ = _archived_store(tmp_path)
    m = arc.get_manifest(store, 1)
    for i in range(ACFG.n - ACFG.k + 1):       # one more than tolerable
        store.fail_node(i)
    survivors_before = {pos: raw
                        for pos, raw in arc._alive_coded(store, 1, m)}
    with pytest.raises(ValueError):
        arc.repair(store, 1, ACFG)
    # the failed repair wrote NOTHING: survivors byte-identical, manifest
    # perm unchanged, no resurrected shards
    after = dict(arc._alive_coded(store, 1, arc.get_manifest(store, 1)))
    assert after.keys() == survivors_before.keys()
    for pos, raw in survivors_before.items():
        assert after[pos] == raw
    assert arc.get_manifest(store, 1)["perm"] == m["perm"]


def test_repair_heals_corrupt_helper(tmp_path):
    """A corrupt-but-present shard is demoted to missing and repaired."""
    store, blocks = _archived_store(tmp_path)
    store.fail_node(1)                                  # one lost...
    store.put(3, arc.ARC.format(step=1, i=3), b"\x00" * 1024)  # ...one corrupt
    repaired = arc.repair(store, 1, ACFG)
    assert set(repaired) == {1, 3}
    np.testing.assert_array_equal(arc.restore_blocks(store, 1, ACFG),
                                  blocks[1])


def test_repair_many_one_batched_launch(tmp_path):
    store, blocks = _archived_store(tmp_path, steps=(1, 2, 3))
    for i in (0, 5):
        store.fail_node(i)
    out = arc.repair_many(store, [1, 2, 3], ACFG)
    assert out == [[0, 5]] * 3
    for s in (1, 2, 3):
        np.testing.assert_array_equal(
            arc.restore_blocks(store, s, ACFG), blocks[s])


def test_restore_blocks_heal_on_read(tmp_path):
    store, blocks = _archived_store(tmp_path)
    store.fail_node(2)
    got = arc.restore_blocks(store, 1, ACFG, heal=True)
    np.testing.assert_array_equal(got, blocks[1])
    m = arc.get_manifest(store, 1)
    assert len(arc._alive_coded(store, 1, m)) == ACFG.n  # healed


def test_degraded_read_matches_plain_read(tmp_path):
    store, blocks = _archived_store(tmp_path)
    blob = blocks[1].reshape(-1).tobytes()
    plain = [arc.read_range(store, 1, ACFG, off, ln)
             for off, ln in ((0, 64), (100, 1000), (510, 4), (2000, 48))]
    for i in (1, 3, 6, 7):                     # lose n-k = 4 shards
        store.fail_node(i)
    for (off, ln), want in zip(((0, 64), (100, 1000), (510, 4), (2000, 48)),
                               plain):
        assert want == blob[off:off + ln]
        assert arc.read_range(store, 1, ACFG, off, ln) == want


def test_degraded_read_boundary_span_stays_slice_sized(tmp_path):
    """A read spanning a block boundary costs k SMALL reads, not k blocks."""
    reads = []

    class TracingStore(obj.NodeStore):
        def get_range(self, i, rel, offset, nbytes):
            reads.append(nbytes)
            return super().get_range(i, rel, offset, nbytes)

    store = TracingStore(str(tmp_path), ACFG.n)
    rng = np.random.default_rng(4)
    blocks = rng.integers(0, 256, size=(ACFG.k, 512), dtype=np.uint8)
    m = arc.hot_save(store, 1, blocks, ACFG)
    m["blob_len"] = blocks.size
    arc._put_manifest(store, 1, m)
    arc.archive_step(store, 1, ACFG)
    store.fail_node(0)
    blob = blocks.reshape(-1).tobytes()
    reads.clear()
    assert arc.read_range(store, 1, ACFG, 508, 8) == blob[508:516]
    assert max(reads) <= 8, reads


def test_manager_read_range_eof_probe(tmp_path):
    """Past-end / zero-length manager reads return b'' (no assert crash)."""
    from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path), hot_keep=0,
                                             archive_old=False))
    mgr.save(1, {"w": np.arange(64, dtype=np.float32)})
    mgr.archive(1)
    assert mgr.read_range(1, 10 ** 9, 10) == b""
    assert mgr.read_range(1, 0, 0) == b""


def test_repair_many_does_not_mix_codes(tmp_path):
    """Steps archived under different seeds repair in separate groups."""
    store = obj.NodeStore(str(tmp_path), ACFG.n)
    rng = np.random.default_rng(5)
    other = arc.ArchiveConfig(n=8, k=4, l=16, seed=99, num_chunks=4)
    bl = {}
    for s, cfg in ((1, ACFG), (2, other)):
        bl[s] = rng.integers(0, 256, size=(4, 512), dtype=np.uint8)
        m = arc.hot_save(store, s, bl[s], cfg)
        m["blob_len"] = bl[s].size
        arc._put_manifest(store, s, m)
        arc.archive_step(store, s, cfg)
    store.fail_node(3)
    assert arc.repair_many(store, [1, 2], ACFG) == [[3], [3]]
    for s, cfg in ((1, ACFG), (2, other)):
        np.testing.assert_array_equal(arc.restore_blocks(store, s, cfg),
                                      bl[s])


def test_degraded_read_hot_tier(tmp_path):
    store = obj.NodeStore(str(tmp_path), ACFG.n)
    rng = np.random.default_rng(7)
    blocks = rng.integers(0, 256, size=(ACFG.k, 512), dtype=np.uint8)
    arc.hot_save(store, 9, blocks, ACFG)
    blob = blocks.reshape(-1).tobytes()
    assert arc.read_range(store, 9, ACFG, 500, 40) == blob[500:540]
    store.fail_node(0)                          # other replica still serves
    assert arc.read_range(store, 9, ACFG, 0, 16) == blob[:16]


@settings(max_examples=25, deadline=None)
@given(off=st.integers(min_value=0, max_value=4 * 512),
       ln=st.integers(min_value=0, max_value=600),
       lost=st.sets(st.integers(min_value=0, max_value=7), max_size=4))
def test_degraded_read_property(off, ln, lost):
    """Any byte range, any loss of at most n-k shards: degraded == plain
    read when the survivors decode, a clear FileNotFoundError when they do
    not (RapidRAID is not MDS, so a few n-k loss sets are fatal)."""
    with tempfile.TemporaryDirectory() as tmp:
        store, blocks = _archived_store(tmp)
        blob = blocks[1].reshape(-1).tobytes()
        for i in lost:
            store.fail_node(i)
        ln_c = min(ln, 4 * 512 - off)
        survivors = [p for p in range(ACFG.n) if p not in lost]
        if ln_c and not ACFG.code().decodable(survivors):
            with pytest.raises(FileNotFoundError):
                arc.read_range(store, 1, ACFG, off, ln_c)
            return
        assert arc.read_range(store, 1, ACFG, off, ln_c) == \
            blob[off:off + ln_c]


@settings(max_examples=15, deadline=None)
@given(extra=st.integers(min_value=1, max_value=3),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_repair_over_limit_property(extra, seed):
    """Losing n-k+extra shards always raises, never fabricates data."""
    rng = np.random.default_rng(seed)
    code = rr.RapidRAIDCode.make(8, 4, l=16, seed=11)
    data = rng.integers(0, 1 << 16, size=(4, 32)).astype(np.uint16)
    cw = code.encode_np(data)
    missing = sorted(rng.choice(8, size=4 + extra, replace=False).tolist())
    ids = [i for i in range(8) if i not in missing]
    with pytest.raises(ValueError):
        rep.repair_np(code, missing, ids, cw[ids])
    with pytest.raises(ValueError):
        ft.repair_plan(code, missing, ids)


def test_degraded_read_kernel_matches_np():
    code = rr.RapidRAIDCode.make(8, 4, l=16, seed=2)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 1 << 16, size=(4, 128)).astype(np.uint16)
    cw = code.encode_np(data)
    ids = [0, 2, 4, 5, 7]
    sl = cw[ids][:, 32:96]
    want = rep.degraded_read_np(code, ids, sl, [1, 3])
    np.testing.assert_array_equal(data[[1, 3], 32:96], want)
    got = rep.degraded_read(code, ids, sl, [1, 3])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# distributed reverse-chain repair (subprocess with forced host devices)
# ---------------------------------------------------------------------------


PIPELINED_REPAIR_SNIPPET = """
import numpy as np, jax
from repro.core import gf, rapidraid as rr
from repro.storage import repair as rep

n, k, l, chunks, n_lost = {n}, {k}, {l}, {chunks}, {n_lost}
assert len(jax.devices()) == k, jax.devices()
code = rr.RapidRAIDCode.make(n, k, l=l, seed=13)
rng = np.random.default_rng(0)
B = chunks * gf.LANES[l] * 8
data = rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])
cw = code.encode_np(data)
missing = list(range(n_lost))
ids = [i for i in range(n) if i not in missing]
got = np.asarray(rep.pipelined_repair(code, ids, cw[ids], missing,
                                      num_chunks=chunks))
np.testing.assert_array_equal(got, cw[missing])
star = np.asarray(rep.star_repair(code, ids, cw[ids], missing))
np.testing.assert_array_equal(star, cw[missing])
print("OK", got.shape)
"""


@pytest.mark.multidevice
@pytest.mark.parametrize("n,k,l,chunks,n_lost", [
    (8, 4, 8, 4, 1),     # single failure, GF(2^8)
    (8, 4, 16, 4, 4),    # maximum tolerable loss, GF(2^16)
    (16, 11, 16, 8, 2),  # the paper's production code
])
def test_pipelined_repair_reverse_chain(n, k, l, chunks, n_lost):
    out = run_with_devices(
        PIPELINED_REPAIR_SNIPPET.format(n=n, k=k, l=l, chunks=chunks,
                                        n_lost=n_lost), ndev=k)
    assert "OK" in out


REPAIR_MANY_SNIPPET = """
import numpy as np, jax
from repro.core import gf, rapidraid as rr
from repro.storage import repair as rep

code = rr.RapidRAIDCode.make(8, 4, l=16, seed=13)
rng = np.random.default_rng(3)
B = gf.LANES[16] * 4 * 8
objs = rng.integers(0, 1 << 16, size=(3, 4, B)).astype(np.uint16)
cws = np.stack([code.encode_np(o) for o in objs])
missing = [2, 6]
ids = [i for i in range(8) if i not in missing]
for stagger in (1, 4):
    got = np.asarray(rep.pipelined_repair_many(
        code, ids, cws[:, ids], missing, num_chunks=4, stagger=stagger))
    np.testing.assert_array_equal(got, cws[:, missing])
print("OK")
"""


@pytest.mark.multidevice
def test_pipelined_repair_many_staggered():
    """B concurrent repairs through one staggered reverse-chain launch."""
    out = run_with_devices(REPAIR_MANY_SNIPPET, ndev=4)
    assert "OK" in out
