"""Streaming super-chunk data plane: equivalence, footprint, framing.

Acceptance pins for the streaming executor (``repro.core.streaming``) and
its storage/checkpoint integration:

* **Bit-exact equivalence** — streaming with one super-chunk IS the
  monolithic path (same program, same bytes), and an object archived as S
  stripes stores BYTE-IDENTICAL coded files (positionwise codes apply the
  generator per word), so every pre-streaming reader works unchanged.
  Property-tested over random object sizes / superchunk sizes / loss sets:
  streaming encode -> lose 1..n-k -> repair -> decode round-trips.
* **Bounded footprint** — an object >= 8x the per-device streaming budget
  archives and restores digest-verified through
  ``archive_step(..., superchunk_bytes=...)`` with the compiled stripe
  program's ``streaming.measure_footprint`` under the budget and ONE compile
  across all super-chunks (multi-device subprocess).
* **Framing** — ``StreamWriter`` publishes atomically (abort leaves
  nothing), its incremental digest matches the whole-object digest, and
  down-node streaming writes are dropped exactly like ``put``.
* **Fail-clear ranges** — ``read_range`` raises ValueError (with the
  range and object size) on out-of-bounds / inverted ranges, on hot,
  archived, degraded, and streamed steps alike.

The streaming budget env knob (``RAPIDRAID_STREAM_BUDGET_BYTES``) is
honored by the acceptance test, so CI's small-budget tier-1 leg exercises
genuinely multi-stripe plans end to end.
"""
import tempfile

import numpy as np
import pytest

from repro.core import streaming
from repro.core import codes as codes_lib
from repro.storage import archive as arc
from repro.storage import object_store as obj
from tests.hypothesis_compat import given, settings, st
from tests.subproc import run_with_devices

N, K, L = 8, 4, 8
ACFG = arc.ArchiveConfig(n=N, k=K, l=L, seed=5, num_chunks=4)


def _store_with(tmp, blocks, acfg=ACFG, step=1):
    store = obj.NodeStore(str(tmp), acfg.n)
    arc.hot_save(store, step, blocks, acfg)
    return store


# ---------------------------------------------------------------------------
# plan geometry
# ---------------------------------------------------------------------------


def test_plan_identity_when_unset_or_covering():
    for sc in (None, 640, 10 ** 9):
        plan = streaming.plan_stream(640, sc, l=8, num_chunks=4)
        assert (plan.sc_words, plan.num_superchunks, plan.tail_words) == \
            (640, 1, 640)
        assert not plan.streaming
        assert plan.stripe_span(0) == (0, 640)


def test_plan_rounds_to_granule_and_covers():
    # granule = LANES[8] * nc = 16 words
    plan = streaming.plan_stream(640, 100, l=8, num_chunks=4)
    assert plan.sc_words == 96 and plan.sc_words % 16 == 0
    assert plan.num_superchunks == 7
    assert plan.tail_words == 640 - 6 * 96
    spans = [plan.stripe_span(s) for s in range(plan.num_superchunks)]
    assert spans[0][0] == 0 and spans[-1][1] == 640
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    # never below one granule, even for absurdly small requests
    tiny = streaming.plan_stream(640, 1, l=8, num_chunks=4)
    assert tiny.sc_words == 16


def test_plan_rejects_bad_inputs():
    with pytest.raises(ValueError, match="superchunk_words"):
        streaming.plan_stream(640, 0, l=8, num_chunks=4)
    with pytest.raises(ValueError, match="at least 1 word"):
        streaming.plan_stream(0, None, l=8, num_chunks=4)


def test_superchunk_words_fits_budget_and_grows():
    code = ACFG.code()
    small = streaming.superchunk_words_for(1 << 14, code, 4)
    large = streaming.superchunk_words_for(1 << 20, code, 4)
    assert streaming.estimate_stripe_bytes(code, small) <= 1 << 14
    assert streaming.estimate_stripe_bytes(code, large) <= 1 << 20
    assert large > small
    # granule-aligned so the stripe always chunks cleanly
    from repro.core import gf
    assert small % (gf.LANES[code.l] * 4) == 0


def test_budget_env_round_trip(monkeypatch):
    monkeypatch.delenv(streaming.BUDGET_ENV, raising=False)
    assert streaming.budget_from_env() is None
    assert streaming.budget_from_env(123) == 123
    monkeypatch.setenv(streaming.BUDGET_ENV, "65536")
    assert streaming.budget_from_env(123) == 65536


# ---------------------------------------------------------------------------
# stream framing (object_store)
# ---------------------------------------------------------------------------


def test_stream_writer_atomic_publish_and_digest(tmp_path):
    store = obj.NodeStore(str(tmp_path), 2)
    frames = [b"alpha", b"beta", b"gamma-" * 100]
    w = store.put_stream(0, "archive/obj.bin")
    for f in frames:
        w.write(f)
        assert not store.has(0, "archive/obj.bin")   # nothing until close
    w.close()
    whole = b"".join(frames)
    assert store.get(0, "archive/obj.bin") == whole
    assert w.digest() == obj.digest(whole)
    assert w.nbytes == len(whole)


def test_stream_writer_abort_leaves_nothing(tmp_path):
    import os
    store = obj.NodeStore(str(tmp_path), 1)
    w = store.put_stream(0, "archive/x.bin")
    w.write(b"partial")
    w.abort()
    assert not store.has(0, "archive/x.bin")
    assert not os.path.exists(store.path(0, "archive/x.bin") + ".tmp")
    # context manager: exception inside aborts, clean exit publishes
    with pytest.raises(RuntimeError):
        with store.put_stream(0, "archive/y.bin") as w2:
            w2.write(b"doomed")
            raise RuntimeError("boom")
    assert not store.has(0, "archive/y.bin")
    with store.put_stream(0, "archive/z.bin") as w3:
        w3.write(b"kept")
    assert store.get(0, "archive/z.bin") == b"kept"


def test_stream_get_frames(tmp_path):
    store = obj.NodeStore(str(tmp_path), 1)
    payload = bytes(range(256)) * 5
    store.put(0, "a/b.bin", payload)
    frames = list(store.get_stream(0, "a/b.bin", 300))
    assert b"".join(frames) == payload
    assert all(len(f) == 300 for f in frames[:-1])
    with pytest.raises(ValueError, match="frame_bytes"):
        list(store.get_stream(0, "a/b.bin", 0))


def test_churn_store_drops_streamed_writes_to_down_nodes(tmp_path):
    store = obj.ChurnNodeStore(str(tmp_path), 2)
    store.fail(1)
    w = store.put_stream(1, "archive/lost.bin")
    w.write(b"into the void")
    w.close()
    assert not super(obj.ChurnNodeStore, store).has(1, "archive/lost.bin")
    # digest still reflects what WOULD have been written (manifest parity)
    assert w.digest() == obj.digest(b"into the void")
    with pytest.raises(FileNotFoundError):
        list(store.get_stream(1, "archive/lost.bin", 4))
    store.rejoin(1)
    w2 = store.put_stream(1, "archive/ok.bin")
    w2.write(b"landed")
    w2.close()
    assert store.get(1, "archive/ok.bin") == b"landed"


# ---------------------------------------------------------------------------
# streamed archival == monolithic archival (host path, inline)
# ---------------------------------------------------------------------------


def _rand_blocks(rng, B):
    return rng.integers(0, 256, size=(K, B), dtype=np.uint8)


def test_streamed_archive_bytes_identical_to_monolithic(tmp_path):
    rng = np.random.default_rng(0)
    blocks = _rand_blocks(rng, 8 * 41)          # tail stripe exercised
    s1 = _store_with(tmp_path / "mono", blocks)
    m1 = arc.archive_step(s1, 1, ACFG, use_devices=False)
    s2 = _store_with(tmp_path / "strm", blocks)
    m2 = arc.archive_step(s2, 1, ACFG, use_devices=False,
                          superchunk_bytes=64)
    assert m2["coded_digests"] == m1["coded_digests"]
    assert m2["streaming"]["num_superchunks"] > 1
    assert len(m2["streaming"]["stripes"]) == m2["streaming"]["num_superchunks"]
    for pos in range(N):
        a = s1.get(m1["perm"][pos], arc.ARC.format(step=1, i=pos))
        b = s2.get(m2["perm"][pos], arc.ARC.format(step=1, i=pos))
        assert a == b, f"coded block {pos} differs between paths"
    np.testing.assert_array_equal(arc.restore_blocks(s2, 1, ACFG), blocks)


def test_one_superchunk_is_the_monolithic_path(tmp_path):
    """superchunk >= object: the plan degenerates and NO streaming manifest
    is written — byte-for-byte today's behavior."""
    rng = np.random.default_rng(1)
    blocks = _rand_blocks(rng, 8 * 16)
    s1 = _store_with(tmp_path / "mono", blocks)
    m1 = arc.archive_step(s1, 1, ACFG, use_devices=False)
    s2 = _store_with(tmp_path / "one", blocks)
    m2 = arc.archive_step(s2, 1, ACFG, use_devices=False,
                          superchunk_bytes=10 ** 9)
    assert "streaming" not in m2
    assert m2["coded_digests"] == m1["coded_digests"]


def test_streaming_rejects_subpacketized_families(tmp_path):
    if "mbr" not in codes_lib.families():
        pytest.skip("no sub-packetized family registered")
    acfg = arc.ArchiveConfig(n=5, k=3, l=8, seed=2, family="mbr")
    rng = np.random.default_rng(3)
    blocks = rng.integers(0, 256, size=(3, 24 * 8), dtype=np.uint8)
    store = _store_with(tmp_path, blocks, acfg=acfg)
    with pytest.raises(ValueError, match="sub-packetized"):
        arc.archive_step(store, 1, acfg, use_devices=False,
                         superchunk_bytes=16)


def test_streamed_archive_aborts_on_corrupt_hot_block(tmp_path):
    """Hot digest mismatch detected mid-stream: nothing publishes."""
    rng = np.random.default_rng(4)
    blocks = _rand_blocks(rng, 8 * 32)
    store = _store_with(tmp_path, blocks)
    manifest = arc.get_manifest(store, 1)
    # corrupt block 2 on EVERY replica that holds it
    rel = arc.HOT.format(step=1, j=2)
    for node, held in enumerate(manifest["placement"]):
        if 2 in held:
            raw = bytearray(store.get(node, rel))
            raw[17] ^= 0xFF
            store.put(node, rel, bytes(raw))
    with pytest.raises(ValueError, match="hot block 2"):
        arc.archive_step(store, 1, ACFG, use_devices=False,
                         superchunk_bytes=64)
    for pos in range(N):
        assert not store.has(pos, arc.ARC.format(step=1, i=pos))
    assert arc.get_manifest(store, 1)["tier"] == "hot"   # untouched


def test_streamed_restore_routes_around_corruption(tmp_path):
    rng = np.random.default_rng(5)
    blocks = _rand_blocks(rng, 8 * 32)
    store = _store_with(tmp_path, blocks)
    m = arc.archive_step(store, 1, ACFG, use_devices=False,
                         superchunk_bytes=64)
    p = store.path(m["perm"][0], arc.ARC.format(step=1, i=0))
    raw = bytearray(open(p, "rb").read())
    raw[5] ^= 0x01
    open(p, "wb").write(bytes(raw))
    np.testing.assert_array_equal(arc.restore_blocks(store, 1, ACFG), blocks)


def test_streamed_repair_and_scrub(tmp_path):
    rng = np.random.default_rng(6)
    blocks = _rand_blocks(rng, 8 * 48)
    store = _store_with(tmp_path, blocks)
    m = arc.archive_step(store, 1, ACFG, use_devices=False,
                         superchunk_bytes=96)
    for pos in (1, 6):
        store.fail_node(m["perm"][pos])
    assert sorted(arc.repair(store, 1, ACFG, use_devices=False)) == [1, 6]
    m2 = arc.get_manifest(store, 1)
    for pos in (1, 6):   # repaired bytes match the streamed digests
        raw = store.get(m2["perm"][pos], arc.ARC.format(step=1, i=pos))
        assert obj.digest(raw) == m2["coded_digests"][pos]
    np.testing.assert_array_equal(arc.restore_blocks(store, 1, ACFG), blocks)


# ---------------------------------------------------------------------------
# read_range: fail-clear bounds + streamed/degraded ranges
# ---------------------------------------------------------------------------


def _archived(tmp, streaming_sc=None, rng_seed=7):
    rng = np.random.default_rng(rng_seed)
    blocks = _rand_blocks(rng, 8 * 64)
    store = _store_with(tmp, blocks)
    arc.archive_step(store, 1, ACFG, use_devices=False,
                     superchunk_bytes=streaming_sc)
    return store, blocks


@pytest.mark.parametrize("streaming_sc", [None, 128])
def test_read_range_rejects_bad_ranges(tmp_path, streaming_sc):
    store, blocks = _archived(tmp_path, streaming_sc)
    size = K * blocks.shape[1]
    for off, nb, what in [(-1, 4, "out of bounds"), (size, 1, "out of bounds"),
                          (size - 1, 2, "out of bounds"),
                          (10, -5, "inverted")]:
        with pytest.raises(ValueError, match=what) as ei:
            arc.read_range(store, 1, ACFG, off, nb)
        assert str(size) in str(ei.value)       # object size in the message
    assert arc.read_range(store, 1, ACFG, 5, 0) == b""
    assert arc.read_range(store, 1, ACFG, size - 4, 4) == \
        blocks.reshape(-1)[-4:].tobytes()


def test_read_range_hot_tier_rejects_bad_ranges(tmp_path):
    rng = np.random.default_rng(8)
    blocks = _rand_blocks(rng, 8 * 8)
    store = _store_with(tmp_path, blocks)
    with pytest.raises(ValueError, match="out of bounds"):
        arc.read_range(store, 1, ACFG, K * blocks.shape[1], 1)


@pytest.mark.parametrize("streaming_sc", [None, 128])
def test_read_range_degraded_on_streamed_archive(tmp_path, streaming_sc):
    store, blocks = _archived(tmp_path, streaming_sc)
    blob = blocks.reshape(-1).tobytes()
    m = arc.get_manifest(store, 1)
    for pos in (0, 3, 5, 7):                   # n-k = 4 lost
        store.fail_node(m["perm"][pos])
    B = blocks.shape[1]
    for off, nb in [(0, 16), (B - 3, 7), (2 * B + 5, 300), (4 * B - 9, 9)]:
        assert arc.read_range(store, 1, ACFG, off, nb) == blob[off:off + nb]


# ---------------------------------------------------------------------------
# equivalence property: random sizes / stripes / losses
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(nblk=st.integers(min_value=2, max_value=40),
       sc_bytes=st.integers(min_value=1, max_value=512),
       nlose=st.integers(min_value=1, max_value=N - K),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_stream_lose_repair_decode_property(nblk, sc_bytes, nlose, seed):
    """streaming encode -> lose 1..n-k -> repair -> decode is bit-exact
    against the non-streaming path for random object/stripe geometry; a
    loss set the code cannot decode (RapidRAID is not MDS) makes repair
    raise instead."""
    rng = np.random.default_rng(seed)
    blocks = _rand_blocks(rng, 8 * nblk)
    with tempfile.TemporaryDirectory() as t1, \
            tempfile.TemporaryDirectory() as t2:
        s1 = _store_with(t1, blocks)
        m1 = arc.archive_step(s1, 1, ACFG, use_devices=False)
        s2 = _store_with(t2, blocks)
        m2 = arc.archive_step(s2, 1, ACFG, use_devices=False,
                              superchunk_bytes=sc_bytes)
        assert m2["coded_digests"] == m1["coded_digests"]
        lost = rng.choice(N, size=nlose, replace=False)
        for pos in lost:
            s2.fail_node(m2["perm"][pos])
        if not ACFG.code().decodable([p for p in range(N) if p not in lost]):
            with pytest.raises(ValueError):
                arc.repair(s2, 1, ACFG, use_devices=False)
            return
        repaired = arc.repair(s2, 1, ACFG, use_devices=False)
        assert sorted(repaired) == sorted(int(p) for p in lost)
        np.testing.assert_array_equal(arc.restore_blocks(s2, 1, ACFG),
                                      blocks)


# ---------------------------------------------------------------------------
# device acceptance: footprint bound + single compile (subprocess)
# ---------------------------------------------------------------------------

ACCEPTANCE_SNIPPET = """
import os, tempfile
import numpy as np
from repro.core import jitcache, streaming
from repro.storage import archive, chain
from repro.storage.object_store import NodeStore

n, k, l, nc = 8, 4, 8, 4
acfg = archive.ArchiveConfig(n=n, k=k, l=l, seed=5, num_chunks=nc)
code = acfg.code()
budget = streaming.budget_from_env(1 << 16)
sc_words = streaming.superchunk_words_for(budget, code, nc)
wb = l // 8

# object >= 8x the per-device streaming footprint budget
B = -(-2 * budget // 8) * 8
assert k * B >= 8 * budget
rng = np.random.default_rng(0)
blocks = rng.integers(0, 256, size=(k, B), dtype=np.uint8)

with tempfile.TemporaryDirectory() as d:
    store = NodeStore(d, n)
    archive.hot_save(store, 1, blocks, acfg)
    m = archive.archive_step(store, 1, acfg, use_devices=True,
                             superchunk_bytes=sc_words * wb)
    S = m["streaming"]["num_superchunks"]
    assert S >= 8, S

    # ONE compiled program across all S super-chunks
    counts = jitcache.entry_counts("encode")
    assert len(counts) == 1 and all(v == 1 for v in counts.values()), counts

    # peak live device bytes of the stripe program bounded by the budget
    fn = chain.encode_program(code, sc_words, nc)
    mem = streaming.measure_footprint(
        fn, np.zeros((k, sc_words), dtype=np.uint8))
    assert mem <= budget, (mem, budget)

    # restores digest-verified
    got = archive.restore_blocks(store, 1, acfg)
    np.testing.assert_array_equal(got, blocks)

    # streaming with ONE super-chunk is bit-identical to non-streaming
    small = rng.integers(0, 256, size=(k, sc_words * wb), dtype=np.uint8)
    mono = np.asarray(chain.pipelined_encode(code, small.view(np.uint8),
                                             num_chunks=nc))
    one = chain.pipelined_encode(code, small.view(np.uint8), num_chunks=nc,
                                 superchunk_words=sc_words)
    np.testing.assert_array_equal(mono, np.asarray(one))
print("acceptance ok: S=%d budget=%d" % (S, budget))
"""


@pytest.mark.multidevice
def test_streaming_acceptance_device_budget():
    out = run_with_devices(ACCEPTANCE_SNIPPET, ndev=N)
    assert "acceptance ok" in out


TRACE_SNIPPET = """
import numpy as np
from repro.core import gf, jitcache, streaming
from repro.core import codes
from repro.storage import chain, repair as rep

n, k, l, nc = 8, 4, 8, 4
code = codes.make("rapidraid", n, k, l=l, seed=5)
rng = np.random.default_rng(0)
granule = gf.LANES[l] * nc
B = granule * 21 + granule // 2 * 0            # 21 granules
data = rng.integers(0, 256, size=(k, B), dtype=np.uint8)

# S stripes reuse one program; a second streamed call stays warm
for _ in range(2):
    out = chain.pipelined_encode(code, data, num_chunks=nc,
                                 superchunk_words=granule * 4)
counts = jitcache.entry_counts("encode")
assert len(counts) == 1 and all(v == 1 for v in counts.values()), counts
stats = jitcache.stats()
assert stats["misses"] == 1 and stats["hits"] > 0, stats

# repair streams through one program too
cw = np.asarray(out)
alive = [0, 2, 3, 4, 6, 7]
rep_out = rep.pipelined_repair(code, alive, cw[alive], [1, 5],
                               num_chunks=nc,
                               superchunk_words=granule * 4)
rcounts = jitcache.entry_counts("repair")
assert len(rcounts) == 1 and all(v == 1 for v in rcounts.values()), rcounts
ref = rep.repair_np(code, [1, 5], alive, cw[alive])
np.testing.assert_array_equal(np.asarray(rep_out), ref)
print("trace ok")
"""


@pytest.mark.multidevice
def test_stream_trace_counts_single_program():
    out = run_with_devices(TRACE_SNIPPET, ndev=N)
    assert "trace ok" in out


# ---------------------------------------------------------------------------
# checkpoint routing
# ---------------------------------------------------------------------------


def test_devio_routes_large_states_through_streaming(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from repro.checkpoint import devio
    acfg = arc.ArchiveConfig(n=8, k=4, l=16, seed=0, num_chunks=4)
    state = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
             "step": np.int64(7)}
    store = obj.NodeStore(str(tmp_path), 8)
    m = devio.save_state(store, 1, state, acfg, footprint_bytes=6000)
    assert m["streaming"]["num_superchunks"] > 1
    got = devio.restore_state(store, 1, state, acfg)
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(state["w"]))
    assert got["step"] == state["step"] and got["step"].dtype == np.int64
    # under the env knob the routing engages without an explicit threshold
    monkeypatch.setenv(streaming.BUDGET_ENV, "6000")
    m2 = devio.save_state(store, 2, state, acfg)
    assert m2.get("streaming")
    # roomy budget: the device-direct single-program path is kept
    m3 = devio.save_state(store, 3, state, acfg, footprint_bytes=1 << 30)
    assert m3.get("device_direct") and not m3.get("streaming")
