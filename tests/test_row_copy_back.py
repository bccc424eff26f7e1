"""Each coded row's copy back starts at the launch and lands as it is written.

The fused archive leaves every coded row on the device with its copy back
started, and writes and hashes each row in row order as it reaches the
host. What is stored does not change: the files, coded digests and
manifests are those of ``encode_np`` and the manifest fields; a failed
coded write publishes nothing and drops no replica; and the result of
``_fused_encode`` still copies to one writable (O, R, W) host array, so a
wrapper that alters that array alters what is stored.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import gf
from repro.storage import archive as arc
from repro.storage import object_store as obj

L = 16
WORDS = 4096
FAMILIES = {"rapidraid": arc.ArchiveConfig(n=16, k=11, l=L),
            "lrc": arc.ArchiveConfig(n=16, k=12, l=L, family="lrc")}


def _hot(tmp_path, acfg, steps):
    """A store holding ``steps`` as hot objects; -> (store, {step: words})."""
    store = obj.NodeStore(str(tmp_path), acfg.n)
    data = {}
    for s in steps:
        data[s] = np.random.default_rng(100 * acfg.k + s).integers(
            0, 1 << L, size=(acfg.k, WORDS)).astype(gf.WORD_DTYPE[L])
        arc.hot_save(store, s, data[s].view(np.uint8), acfg)
    return store, data


def _manifest_bytes(store, step):
    return [store.get(i, arc.MANIFEST.format(step=step))
            for i in range(store.n_nodes)]


@pytest.mark.parametrize("path", ["archive_step", "archive_many"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_files_digests_and_manifests_are_the_oracles(tmp_path, family, path):
    """Every stored row is ``encode_np``'s, every manifest replica is the
    hot manifest with the archive fields added in their order."""
    acfg = FAMILIES[family]
    code = acfg.code()
    steps = (1, 2) if path == "archive_many" else (1,)
    store, data = _hot(tmp_path, acfg, steps)
    hot = {s: arc.get_manifest(store, s) for s in steps}
    if path == "archive_many":
        arc.archive_many(store, list(steps), acfg, use_devices=False)
    else:
        arc.archive_step(store, 1, acfg, use_devices=False)
    for s in steps:
        want = code.encode_np(data[s])
        for pos in range(acfg.n):
            got = store.get(pos, arc.ARC.format(step=s, i=pos))
            assert got == want[pos].tobytes(), (s, pos)
        manifest = {**hot[s], "tier": "archive", "family": family,
                    "perm": list(range(acfg.n)),
                    "coded_digests": [obj.digest(want[p].tobytes())
                                      for p in range(acfg.n)],
                    "orig_digests": hot[s]["digests"]}
        if path == "archive_many":
            manifest["batched_with"] = list(steps)
        assert _manifest_bytes(store, s) == \
            [json.dumps(manifest).encode()] * acfg.n
        assert not any(store.has(node, arc.HOT.format(step=s, j=j))
                       for node, held in enumerate(manifest["placement"])
                       for j in held)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_failed_coded_write_keeps_every_replica(tmp_path, family,
                                                  monkeypatch):
    """A ``store.put`` that raises on the 5th coded row: every hot replica
    is still there and no manifest says the step is archived."""
    acfg = FAMILIES[family]
    store, _ = _hot(tmp_path, acfg, (1,))
    before = _manifest_bytes(store, 1)
    put, written = store.put, []

    def failing(node, rel, data):
        if rel.startswith("archive/"):
            written.append(rel)
            if len(written) == 5:
                raise OSError("disk full")
        return put(node, rel, data)

    monkeypatch.setattr(store, "put", failing)
    with pytest.raises(OSError, match="disk full"):
        arc.archive_step(store, 1, acfg, use_devices=False)
    monkeypatch.undo()
    assert written[-1] == arc.ARC.format(step=1, i=4)
    placement = arc.get_manifest(store, 1)["placement"]
    assert all(store.has(node, arc.HOT.format(step=1, j=j))
               for node, held in enumerate(placement) for j in held)
    assert _manifest_bytes(store, 1) == before
    assert arc.get_manifest(store, 1)["tier"] == "hot"


def _coded_rows(code):
    return [i for i in range(code.n) if i not in arc._passthrough(code)]


@pytest.mark.parametrize("kind", ["half", "altered"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_wrapper_that_alters_the_copy_alters_the_stored_bytes(
        tmp_path, family, kind, monkeypatch):
    """``_fused_encode(...).copy()`` is a writable (O, R, W) host array of
    the oracle's coded rows; what a wrapper writes into it is stored."""
    acfg = FAMILIES[family]
    code = acfg.code()
    store, data = _hot(tmp_path, acfg, (1,))
    coded = _coded_rows(code)
    want = code.encode_np(data[1])

    rows = arc._fused_encode(code, [list(data[1])], L)
    assert all(isinstance(r, jax.Array) for obj_rows in rows
               for r in obj_rows)
    out = rows.copy()
    assert isinstance(out, np.ndarray) and out.flags.writeable
    assert out.shape == (1, len(coded), WORDS)
    np.testing.assert_array_equal(out[0], want[coded])

    full = arc._fused_encode

    def faulty(code, objs_w, l):
        out = full(code, objs_w, l).copy()
        if kind == "half":
            out[..., out.shape[-1] // 2:] = 0
        else:
            out[0, 3, 17] ^= 1
        return out

    monkeypatch.setattr(arc, "_fused_encode", faulty)
    manifest = arc.archive_step(store, 1, acfg, use_devices=False)
    altered = want.copy()
    if kind == "half":
        altered[coded, WORDS // 2:] = 0
    else:
        altered[coded[3], 17] ^= 1
    for pos in range(acfg.n):
        got = store.get(pos, arc.ARC.format(step=1, i=pos))
        assert got == altered[pos].tobytes(), pos
        assert manifest["coded_digests"][pos] == obj.digest(got)
    assert any(store.get(p, arc.ARC.format(step=1, i=p)) != want[p].tobytes()
               for p in coded)
