"""Every archive write path codes through one body and publishes through
``archive._publish``.

A step archived from the hot tier (``archive_step``, ``archive_many``, in
device stripes or whole) and a checkpoint written straight to the coded
tier (``publish_device_archive``, ``publish_streaming_archive``) store
``encode_np``'s bytes, and each entry writes exactly its own manifest
keys. Off the chain, the streamed checkpoint codes on the device like
``archive_step``, never through the host ``encode_np``.
"""
import numpy as np
import pytest

from repro.core import codes, gf
from repro.storage import archive as arc
from repro.storage import object_store as obj

FAMILIES = {"rapidraid": dict(n=8, k=5, l=8),
            "lrc": dict(n=8, k=4, l=16),
            "xorbas": dict(n=8, k=4, l=16)}
WORDS = 1024
BASE_KEYS = {"step", "tier", "n", "k", "l", "seed", "family", "block_bytes",
             "digests", "placement", "perm", "coded_digests", "orig_digests"}
# entry -> (its own manifest keys, superchunk words: a divisor of WORDS
# puts every stripe boundary on it, 384 leaves a 256-word tail)
ENTRIES = {
    "archive_step": (set(), None),
    "archive_many_of_one": ({"batched_with"}, None),
    "archive_step_striped": ({"streaming"}, 384),
    "publish_device_archive": ({"blob_len", "device_direct", "state_key"},
                               None),
    "publish_streaming_on_boundary": ({"blob_len", "streaming", "state_key"},
                                      256),
    "publish_streaming_off_boundary": ({"blob_len", "streaming",
                                        "state_key"}, 384),
}


def _setup(tmp_path, family):
    geo = FAMILIES[family]
    acfg = arc.ArchiveConfig(seed=1, num_chunks=4, family=family, **geo)
    code = codes.make(family, geo["n"], geo["k"], l=geo["l"], seed=1)
    data = np.random.default_rng(geo["k"]).integers(
        0, 1 << geo["l"], size=(geo["k"], WORDS)).astype(
            gf.WORD_DTYPE[geo["l"]])
    return acfg, code, data, obj.NodeStore(str(tmp_path), geo["n"])


def _write(store, acfg, entry, blocks, coded, sc_words):
    """Run ``entry`` on step 1; -> its manifest."""
    wb = acfg.l // 8
    if entry.startswith("archive"):
        arc.hot_save(store, 1, blocks, acfg)
        if entry == "archive_many_of_one":
            return arc.archive_many(store, [1], acfg, use_devices=False)[0]
        sc = None if sc_words is None else sc_words * wb
        return arc.archive_step(store, 1, acfg, use_devices=False,
                                superchunk_bytes=sc)
    if entry == "publish_device_archive":
        return arc.publish_device_archive(store, 1, acfg, blocks, coded,
                                          blocks.size - 3, state_key="s")
    return arc.publish_streaming_archive(
        store, 1, acfg, blocks, blocks.size - 3,
        superchunk_bytes=sc_words * wb, state_key="s", use_devices=False)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_write_path_stores_encode_np_and_its_own_manifest(tmp_path, family,
                                                          entry):
    acfg, code, data, store = _setup(tmp_path, family)
    want = np.asarray(code.encode_np(data))
    extra, sc_words = ENTRIES[entry]
    m = _write(store, acfg, entry, data.view(np.uint8),
               np.ascontiguousarray(want).view(np.uint8), sc_words)
    for pos in range(acfg.n):
        got = store.get(m["perm"][pos], arc.ARC.format(step=1, i=pos))
        assert got == want[pos].tobytes(), pos
        assert m["coded_digests"][pos] == obj.digest(want[pos].tobytes())
    assert m["orig_digests"] == m["digests"] == [
        obj.digest(row.tobytes()) for row in data]
    assert set(m) == BASE_KEYS | extra
    assert m["tier"] == "archive" and m["family"] == family
    assert arc.get_manifest(store, 1) == m
    if "streaming" in m:
        stream = m["streaming"]
        assert stream["num_superchunks"] == -(-WORDS // sc_words)
        assert sum(s["words"] for s in stream["stripes"]) == WORDS
    if entry.startswith("archive"):    # the hot replicas were reclaimed
        assert not any(store.has(node, arc.HOT.format(step=1, j=j))
                       for node, held in enumerate(m["placement"])
                       for j in held)
    np.testing.assert_array_equal(arc.restore_blocks(store, 1, acfg),
                                  data.view(np.uint8))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_streamed_checkpoint_off_the_chain_never_calls_encode_np(
        tmp_path, family, monkeypatch):
    acfg, code, data, store = _setup(tmp_path, family)
    want = np.asarray(code.encode_np(data))

    def refuse(self, *args, **kwargs):
        raise AssertionError("encode_np called on the write path")

    monkeypatch.setattr(type(acfg.code()), "encode_np", refuse)
    m = arc.publish_streaming_archive(store, 1, acfg, data.view(np.uint8),
                                      data.nbytes, superchunk_bytes=384 *
                                      (acfg.l // 8), use_devices=False)
    assert m["streaming"]["num_superchunks"] == 3
    for pos in range(acfg.n):
        assert store.get(pos, arc.ARC.format(step=1, i=pos)) == \
            want[pos].tobytes()
