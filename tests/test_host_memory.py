"""Payload bytes between the store and the device are not copied on the host.

The fused archive and repair paths send the store's digest-verified
buffers to the device as they are and write coded rows as views of the
array the device returned. Per family: positionwise codes never gather
payload on the host, sub-packetized ones (MBR) keep their host message
path, and both store exactly ``encode_np``'s shards, with every digest
check and replica fallback in place.
"""
import contextlib

import numpy as np
import pytest

from repro.core import codes, gf
from repro.storage import archive as arc
from repro.storage import object_store as obj

FAMILIES = ("rapidraid", "lrc", "xorbas", "mbr")
N, K, L = 8, 4, 16
B = 512                                     # words per block


@pytest.fixture(params=FAMILIES)
def code(request):
    return codes.make(request.param, N, K, l=L)


def _acfg(code):
    return arc.ArchiveConfig(n=N, k=K, l=L, family=code.family, num_chunks=4)


def _payload(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << L, size=(K, B)).astype(gf.WORD_DTYPE[L])


@pytest.fixture
def spans(code, monkeypatch):
    """Records every span the archive module opens, as (name, args); the
    path a code must not take raises."""
    seen = []

    class Recorded(contextlib.nullcontext):
        def __init__(self, args):
            super().__init__(self)
            self.args = args

        def set_metadata(self, **more):
            self.args.update(more)

    def record(name, **args):
        seen.append((name, args))
        return Recorded(args)

    def refuse(*args):
        raise AssertionError(f"{code.family} took the wrong path")

    monkeypatch.setattr(arc, "span", record)
    monkeypatch.setattr(arc, "_gather" if code.positionwise else "_to_device",
                        refuse)
    return seen


def _assert_files(store, code, step, data):
    manifest = arc.get_manifest(store, step)
    want = code.encode_np(data)
    for pos in range(N):
        got = store.get(manifest["perm"][pos], arc.ARC.format(step=step, i=pos))
        assert got == want[pos].tobytes(), (step, pos)
        assert obj.digest(got) == manifest["coded_digests"][pos]


def test_fused_archive_falls_back_past_a_corrupt_replica(code, spans, tmp_path):
    """A hot replica whose bytes fail their digest is skipped for the other
    holder before anything goes to the device; the coded files match the
    oracle, a systematic code's data row 0 (written from the hot buffer)
    among them."""
    store = obj.NodeStore(str(tmp_path), N)
    data = _payload(1)
    manifest = arc.hot_save(store, 1, data.view(np.uint8), _acfg(code))
    first = [i for i, held in enumerate(manifest["placement"]) if 0 in held][0]
    rel = arc.HOT.format(step=1, j=0)
    store.put(first, rel, bytes(len(store.get(first, rel))))
    arc.archive_step(store, 1, _acfg(code), use_devices=False)
    _assert_files(store, code, 1, data)
    assert not store.has(first, rel)                # reclaimed after placing


def test_repair_many_heals_three_objects_and_a_corrupt_helper(
        code, spans, tmp_path):
    """Three objects lose the same row; one of them also holds a corrupt
    helper, which is demoted to missing and healed too. Every file ends
    byte-identical to ``encode_np``'s rows."""
    store = obj.NodeStore(str(tmp_path), N)
    acfg = _acfg(code)
    data = {s: _payload(10 + s) for s in (1, 2, 3)}
    for s, d in data.items():
        arc.hot_save(store, s, d.view(np.uint8), acfg)
        arc.archive_step(store, s, acfg, use_devices=False)
    lost = 2
    for s in data:
        m = arc.get_manifest(store, s)
        store.delete(m["perm"][lost], arc.ARC.format(step=s, i=lost))
    helper = code.repair_helpers([lost], [p for p in range(N) if p != lost])[0]
    m = arc.get_manifest(store, 2)
    rel = arc.ARC.format(step=2, i=helper)
    store.put(m["perm"][helper], rel,
              bytes(len(store.get(m["perm"][helper], rel))))
    got = arc.repair_many(store, [1, 2, 3], acfg, use_devices=False)
    assert got == [[lost], sorted([lost, helper]), [lost]]
    for s, d in data.items():
        _assert_files(store, code, s, d)


def test_host_copies_only_for_sub_packetized_codes(code, spans, tmp_path):
    """Positionwise codes send every store buffer straight to the device
    (``h2d`` ``direct`` = the rows sent) and copy no payload on the host;
    MBR builds its message and helper arrays on the host."""
    store = obj.NodeStore(str(tmp_path), N)
    acfg = _acfg(code)
    arc.hot_save(store, 1, _payload(4).view(np.uint8), acfg)
    arc.archive_step(store, 1, acfg, use_devices=False)
    archived = len(spans)
    m = arc.get_manifest(store, 1)
    store.delete(m["perm"][0], arc.ARC.format(step=1, i=0))
    assert arc.repair(store, 1, acfg, use_devices=False) == [0]
    names = [n for n, _ in spans]
    copies = ("host_copy" in names[:archived], "host_copy" in names[archived:])
    h2d = [a for n, a in spans if n == "h2d"]
    plans = [a["helpers"] for n, a in spans if n == "repair_plan"]
    if code.positionwise:
        helpers = len(code.repair_helpers([0], list(range(1, N))))
        assert copies == (False, False)
        assert plans == [helpers, helpers]
        assert [a["direct"] for a in h2d] == [K, helpers]
        assert [a["bytes"] for a in h2d] == [K * B * 2, helpers * B * 2]
    else:
        assert copies == (True, True)          # MBR repairs on the host
        assert [a["direct"] for a in h2d] == [0]


@pytest.mark.parametrize("store_cls", [obj.NodeStore, obj.ChurnNodeStore])
def test_put_and_digest_take_a_row_view(store_cls, tmp_path):
    """A memoryview of a read-only uint8 row (what the device returned) is
    written and hashed as exactly ``row.tobytes()``."""
    store = store_cls(str(tmp_path), 2)
    rows = np.random.default_rng(5).integers(0, 256, (3, 4096), np.uint8)
    rows.setflags(write=False)
    view = memoryview(rows[1])
    store.put(1, "archive/row.bin", view)
    assert store.get(1, "archive/row.bin") == rows[1].tobytes()
    assert obj.digest(view) == obj.digest(rows[1].tobytes())
