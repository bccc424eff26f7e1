"""Abstract erasure-code API: the family grid every code must pass.

One parametrized surface for all registered families (RapidRAID, LRC, MBR):
encode -> lose 1..f_max shards -> repair -> decode bit-exact, through the
same archive data plane. Family-specific guarantees are asserted where they
differ — LRC single-shard repair reads ONLY its local group (instrumented at
the store layer, not just the plan), MBR repair moves less than k shards of
bytes — plus registry behavior (clear error for unknown families, manifest
back-compat) and the deprecation shims.
"""
import dataclasses
import itertools

import numpy as np
import pytest

from repro.core import codes, gf
from repro.core import rapidraid as rr
from repro.storage import archive as arc
from repro.storage import object_store as obj
from tests.subproc import run_with_devices

FAMILIES = ("rapidraid", "lrc", "mbr")
N, K, L = 8, 4, 16


@pytest.fixture(params=FAMILIES)
def code(request):
    return codes.make(request.param, N, K, l=L)


def _payload(code, B=256, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << code.l, size=(code.k, B)).astype(
        gf.WORD_DTYPE[code.l])


# ---------------------------------------------------------------------------
# the shared grid
# ---------------------------------------------------------------------------


def test_roundtrip_lose_repair_decode(code):
    """encode -> every loss pattern up to f_max -> repair bit-exact ->
    decode bit-exact from the survivors."""
    data = _payload(code)
    cw = code.encode_np(data)
    assert cw.shape == (code.n, code.shard_words(data.shape[1]))
    f_max = code.max_tolerated_losses()
    assert f_max >= 1
    for n_lost in range(1, f_max + 1):
        for missing in itertools.islice(
                itertools.combinations(range(code.n), n_lost), 12):
            missing = list(missing)
            alive = [i for i in range(code.n) if i not in missing]
            rebuilt = code.repair_np(missing, alive, cw[alive])
            np.testing.assert_array_equal(rebuilt, cw[missing])
            got = code.decode_np(alive, cw[alive],
                                 block_words=data.shape[1])
            np.testing.assert_array_equal(got, data)


def test_decodable_matches_decode(code):
    """``decodable`` is the oracle: True subsets decode, False ones raise."""
    rng = np.random.default_rng(1)
    data = _payload(code, seed=1)
    cw = code.encode_np(data)
    for _ in range(8):
        m = rng.integers(1, code.n + 1)
        ids = sorted(rng.choice(code.n, size=m, replace=False).tolist())
        if code.decodable(ids):
            np.testing.assert_array_equal(
                code.decode_np(ids, cw[ids], block_words=data.shape[1]),
                data)
        else:
            with pytest.raises(ValueError):
                code.decode_np(ids, cw[ids], block_words=data.shape[1])


def test_archive_roundtrip_and_heal(code, tmp_path):
    """The real data plane per family: hot_save -> batched fused-kernel
    archive -> shard losses -> repair -> restore + ranged degraded read."""
    fam = code.family
    store = obj.NodeStore(str(tmp_path), N)
    acfg = arc.ArchiveConfig(n=N, k=K, l=L, family=fam, num_chunks=4)
    rng = np.random.default_rng(2)
    blocks = {s: rng.integers(0, 256, size=(K, 256), dtype=np.uint8)
              for s in (1, 2)}
    for s, b in blocks.items():
        arc.hot_save(store, s, b, acfg)
    manifests = arc.archive_many(store, [1, 2], acfg, use_devices=False)
    for (s, b), manifest in zip(blocks.items(), manifests):
        assert manifest["family"] == fam
        np.testing.assert_array_equal(arc.restore_blocks(store, s, acfg), b)
    # knock out two shards of step 1, heal through arc.repair
    m = arc.get_manifest(store, 1)
    for pos in (0, 3):
        store.delete(m["perm"][pos], arc.ARC.format(step=1, i=pos))
    assert arc.repair(store, 1, acfg, use_devices=False) == [0, 3]
    np.testing.assert_array_equal(arc.restore_blocks(store, 1, acfg),
                                  blocks[1])
    want = b"".join(blocks[2][j].tobytes() for j in range(K))
    assert arc.read_range(store, 2, acfg, 100, 500) == want[100:600]


def test_archive_step_fused_encode_matches_oracle(code, tmp_path,
                                                  monkeypatch):
    """``archive_step`` with fewer devices than nodes codes in the fused
    kernel, not host numpy, and stores exactly ``encode_np``'s shards."""
    store = obj.NodeStore(str(tmp_path), N)
    acfg = arc.ArchiveConfig(n=N, k=K, l=L, family=code.family,
                             num_chunks=4)
    data = _payload(code, B=512, seed=3)
    arc.hot_save(store, 1, data.view(np.uint8), acfg)

    def no_host_coding(*args):
        raise AssertionError("archive_step coded on the host")

    with monkeypatch.context() as mp:
        mp.setattr(gf, "gf_matmul_np", no_host_coding)
        manifest = arc.archive_step(store, 1, acfg)
    want = code.encode_np(data)
    for pos in range(N):
        got = store.get(manifest["perm"][pos], arc.ARC.format(step=1, i=pos))
        assert got == want[pos].tobytes(), pos


def test_repair_transfer_model_is_honest(code):
    """``repair_transfer_words`` equals what a single-shard repair reads."""
    B = 256
    helpers = code.repair_helpers([0], list(range(1, code.n)))
    if code.positionwise:
        assert (code.repair_transfer_words(B)
                == len(helpers) * code.shard_words(B))
    else:
        # MBR: beta=1 sub-block per helper, NOT the whole shard
        assert code.repair_transfer_words(B) < len(helpers) * code.shard_words(B)


# ---------------------------------------------------------------------------
# family-specific guarantees
# ---------------------------------------------------------------------------


def test_lrc_repair_touches_only_local_group(tmp_path):
    """Single-shard LRC repair reads <= group-size shards, all from the
    lost shard's OWN group — instrumented at the store layer."""
    code = codes.make("lrc", N, K, l=L)
    store = obj.NodeStore(str(tmp_path), N)
    acfg = arc.ArchiveConfig(n=N, k=K, l=L, family="lrc", num_chunks=4)
    rng = np.random.default_rng(3)
    blocks = rng.integers(0, 256, size=(K, 256), dtype=np.uint8)
    arc.hot_save(store, 1, blocks, acfg)
    arc.archive_step(store, 1, acfg, use_devices=False)
    manifest = arc.get_manifest(store, 1)
    for lost in range(code.n):
        gi = code.row_group(lost)
        # global parity rows have no locality; they repair via the generic
        # k-helper plan, which this test does not constrain
        group = (set(code.group_rows(gi)) if gi is not None
                 else set(range(code.n)))
        store.delete(manifest["perm"][lost], arc.ARC.format(step=1, i=lost))
        shard_reads = []
        orig_get = store.get

        def spy(i, rel, _orig=orig_get, _reads=shard_reads):
            if rel.startswith("archive/"):
                _reads.append(rel)
            return _orig(i, rel)

        store.get = spy
        try:
            assert arc.repair(store, 1, acfg, use_devices=False) == [lost]
        finally:
            del store.get
        read_rows = {int(rel.split("c_")[1].split(".")[0])
                     for rel in shard_reads}
        if gi is not None:
            assert len(read_rows) <= code.locality, (lost, read_rows)
        assert read_rows <= group - {lost}, (lost, group, read_rows)
    # and the plan agrees with the instrumentation
    helpers, R = code.repair_plan([0], list(range(1, code.n)))
    assert set(helpers) <= set(code.group_rows(code.row_group(0)))
    assert np.all(R == 1)  # XOR-only local reconstruction


def test_lrc_is_not_mds_but_tolerates_structured_losses():
    """The locality price: some n-k loss pattern is fatal, but every single
    loss (and every loss the policy repairs tick-by-tick) is fine."""
    code = codes.make("lrc", N, K, l=L)
    f_max = code.max_tolerated_losses()
    assert 1 <= f_max < code.n - code.k or f_max == code.n - code.k
    # two global parities + both members of one group is undecodable for
    # this geometry: fewer than sub_k independent rows remain
    assert any(
        not code.decodable([i for i in range(code.n) if i not in lost])
        for lost in itertools.combinations(range(code.n), code.n - code.k))


def test_mbr_repair_bandwidth_below_k_shards():
    """MBR single-node repair: d summands of one sub-block each — strictly
    less traffic than the k full shards a positionwise repair reads."""
    code = codes.make("mbr", N, K, l=L)
    B = 256
    data = _payload(code, B=B, seed=4)
    cw = code.encode_np(data)
    W = code.sub_block_words(B)
    failed = 2
    helpers = [i for i in range(code.n) if i != failed][:code.d]
    mus = np.stack([code.helper_summand(failed, h, cw[h]) for h in helpers])
    assert mus.shape == (code.d, W)   # beta = 1 sub-block per helper
    transferred = mus.size
    assert transferred == code.repair_transfer_words(B)
    assert transferred < code.k * B   # < one logical object
    assert transferred < code.k * code.shard_words(B)
    rebuilt = code.combine_summands(failed, helpers, mus)
    np.testing.assert_array_equal(rebuilt, cw[[failed]])


def test_mbr_tolerates_any_n_minus_k_losses():
    code = codes.make("mbr", N, K, l=L)
    assert code.max_tolerated_losses() == code.n - code.k


# ---------------------------------------------------------------------------
# registry + manifests + shims
# ---------------------------------------------------------------------------


def test_unknown_family_is_a_clear_error():
    with pytest.raises(ValueError, match="unknown code family 'zfec'"):
        codes.make("zfec", N, K)
    with pytest.raises(ValueError, match="registered families"):
        codes.make("zfec", N, K)


def test_unknown_family_in_manifest_raises(tmp_path):
    store = obj.NodeStore(str(tmp_path), N)
    acfg = arc.ArchiveConfig(n=N, k=K, l=L)
    rng = np.random.default_rng(5)
    blocks = rng.integers(0, 256, size=(K, 256), dtype=np.uint8)
    arc.hot_save(store, 1, blocks, acfg)
    arc.archive_step(store, 1, acfg, use_devices=False)
    manifest = arc.get_manifest(store, 1)
    import json
    bad = {**manifest, "family": "zfec"}
    for i in range(N):
        store.put(i, arc.MANIFEST.format(step=1), json.dumps(bad).encode())
    with pytest.raises(ValueError, match="unknown code family 'zfec'"):
        arc.get_manifest(store, 1)


def test_pre_family_manifest_defaults_to_rapidraid():
    """Manifests written before the family field decode as RapidRAID."""
    spec = codes.CodeSpec.from_manifest({"n": N, "k": K, "l": L, "seed": 3})
    assert spec.family == "rapidraid"
    code = codes.from_spec(spec)
    assert isinstance(code, rr.RapidRAIDCode)
    assert code == rr.RapidRAIDCode.make(N, K, l=L, seed=3)


def test_registry_memoizes_and_spec_roundtrips(code):
    again = codes.make(code.family, N, K, l=L)
    assert again is code                      # warm per-code lru caches
    assert codes.from_spec(code.spec) is code
    spec2 = codes.CodeSpec.from_manifest(code.spec.to_manifest())
    assert spec2 == code.spec


def test_cache_key_separates_handbuilt_rapidraid():
    """A hand-built coefficient set must NOT collide with the canonical
    seeded draw in the jit cache."""
    canonical = rr.RapidRAIDCode.make(N, K, l=L, seed=0)
    assert canonical.cache_key == canonical.spec
    psi = tuple(1 for _ in canonical.psi)
    xi = tuple(1 for _ in canonical.xi)
    hand = rr.RapidRAIDCode(n=N, k=K, l=L, psi=psi, xi=xi, seed=0)
    assert hand.spec == canonical.spec        # same spec...
    assert hand.cache_key != canonical.cache_key   # ...different cache key


def test_deprecated_shims_are_gone():
    """The PR-7 deprecation shims were removed once all callers migrated:
    ``codes.make`` / the ``ErasureCode`` methods are the only API."""
    import repro.core as core
    for name in ("make_code", "encode_np", "decode_np"):
        assert not hasattr(rr, name), f"rapidraid.{name} shim resurrected"
        assert not hasattr(core, name), f"repro.core.{name} leaked"


# ---------------------------------------------------------------------------
# jit-cache independence (device data plane)
# ---------------------------------------------------------------------------

FAMILY_TRACE_SNIPPET = """
import numpy as np
import pytest
from repro.core import codes, gf, jitcache
from repro.storage import chain, multi, repair as rep

n, k, l, nc = 8, 4, 16, 4
rng = np.random.default_rng(0)
B = gf.LANES[l] * nc * 6

def warm(fn):
    first = np.asarray(fn())
    before = jitcache.stats()
    second = np.asarray(fn())
    after = jitcache.stats()
    assert after["misses"] == before["misses"], (before, after)
    assert after["hits"] > before["hits"], (before, after)
    np.testing.assert_array_equal(first, second)

for fam in ("rapidraid", "lrc"):
    code = codes.make(fam, n, k, l=l)
    data = rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])
    cw = code.encode_np(data)
    ids = list(range(k + 1))
    assert code.decodable(ids)
    missing = [0]
    alive = [i for i in range(n) if i not in missing]
    warm(lambda: chain.pipelined_decode(code, ids, cw[ids], num_chunks=nc))
    warm(lambda: rep.pipelined_repair(code, alive, cw[alive], missing,
                                      num_chunks=nc))
    if code.supports_chain_encode:
        warm(lambda: chain.pipelined_encode(code, data, num_chunks=nc))
    else:
        try:
            chain.pipelined_encode(code, data, num_chunks=nc)
        except ValueError as e:
            assert "chain" in str(e)
        else:
            raise AssertionError("lrc must refuse the chain encode")

# MBR is sub-packetized: the positionwise device plane refuses it cleanly
mbr = codes.make("mbr", n, k, l=l)
mcw = mbr.encode_np(data)
try:
    chain.pipelined_decode(mbr, list(range(k + 1)), mcw[:k + 1],
                           num_chunks=nc)
except ValueError as e:
    assert "sub-packetized" in str(e) or "positionwise" in str(e), e
else:
    raise AssertionError("mbr must refuse the positionwise decode plane")

# one compiled program per (entry, family): the families did NOT share or
# evict each other's programs, and none traced twice
for entry in ("decode", "repair"):
    counts = jitcache.entry_counts(entry)
    assert len(counts) == 2, (entry, counts)
    assert all(v in (1, -1) for v in counts.values()), (entry, counts)
    fams = {"rapidraid": 0, "lrc": 0}
    for key in counts:
        for fam in fams:
            if f"family='{fam}'" in key:
                fams[fam] += 1
    assert all(c == 1 for c in fams.values()), (entry, counts)
print("OK", jitcache.stats())
"""


@pytest.mark.multidevice
def test_per_family_programs_cached_independently():
    """Each family compiles its decode/repair program exactly once; the
    cache keys (CodeSpec) keep families from colliding."""
    out = run_with_devices(FAMILY_TRACE_SNIPPET, ndev=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# temperature-aware selection plumbing (host-side unit level; the full soak
# lives in tests/test_lifecycle.py and benchmarks/fig_codes.py)
# ---------------------------------------------------------------------------


def test_code_policy_selects_by_age():
    from repro.core import scheduler
    policy = scheduler.CodePolicy(hot_family="lrc", cold_family="rapidraid",
                                  cold_age=5)
    assert policy.family_for(0) == "lrc"
    assert policy.family_for(4) == "lrc"
    assert policy.family_for(5) == "rapidraid"
    with pytest.raises(ValueError, match="unknown code family"):
        scheduler.CodePolicy(hot_family="zfec")


def test_archive_config_family_routes_registry(code):
    acfg = arc.ArchiveConfig(n=N, k=K, l=L, family=code.family)
    assert acfg.code() is code
    assert dataclasses.replace(acfg, family="rapidraid").code().family == \
        "rapidraid"
