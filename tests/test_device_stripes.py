"""Archives that one fused launch cannot hold are coded in device stripes.

``archive_step`` without ``superchunk_bytes`` streams a positionwise code
by itself when its fused encode would need more device memory than the
budget (``streaming.device_budget``): each stripe's hot range reads go to
the device as they are, code in the fused kernel through
``streaming.execute``, and frame into the same files, byte for byte, as
one launch writes. The budget here is forced through
``RAPIDRAID_STREAM_BUDGET_BYTES``; a fake device stands in for the chip's
``bytes_limit``.
"""
import contextlib
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import codes, streaming
from repro.storage import archive as arc
from repro.storage import object_store as obj
from repro.storage.client import StorageClient

GEOMETRY = {"rapidraid": 11, "lrc": 12, "xorbas": 10}
N, L = 16, 16
WORDS = 9216                # a tail stripe of 1024 words under 2048-word ones
GiB = 1 << 30


def _budget(k):
    """Two stripes of 2048 words in flight."""
    return 2 * streaming.fused_encode_bytes(k, N, 2048, L)


def _archive(tmp, family, budget, monkeypatch):
    k = GEOMETRY[family]
    acfg = arc.ArchiveConfig(n=N, k=k, l=L, family=family)
    if budget is None:
        monkeypatch.delenv(streaming.BUDGET_ENV, raising=False)
    else:
        monkeypatch.setenv(streaming.BUDGET_ENV, str(budget))
    store = obj.NodeStore(str(tmp), N)
    client = StorageClient(store, acfg)
    blocks = np.random.default_rng(k).integers(
        0, 256, size=(k, WORDS * 2), dtype=np.uint8)
    client.put_hot(1, blocks)
    manifest = client.archive(1)
    files = [store.get(manifest["perm"][p], arc.ARC.format(step=1, i=p))
             for p in range(N)]
    return client, blocks, manifest, files


@pytest.mark.parametrize("family", sorted(GEOMETRY))
def test_striped_archive_writes_the_monolithic_files(tmp_path, family,
                                                     monkeypatch):
    _, _, mono, mono_files = _archive(tmp_path / "mono", family, None,
                                      monkeypatch)
    client, blocks, striped, files = _archive(
        tmp_path / "striped", family, _budget(GEOMETRY[family]), monkeypatch)
    assert "streaming" not in mono
    assert striped["streaming"]["num_superchunks"] == 5
    assert [s["words"] for s in striped["streaming"]["stripes"]] == \
        [2048] * 4 + [1024]
    assert striped["coded_digests"] == mono["coded_digests"]
    assert files == mono_files
    assert client.read_range(1, 0, blocks.size).data == blocks.tobytes()


def test_striped_archive_codes_every_stripe_on_the_device(tmp_path,
                                                          monkeypatch):
    """No host oracle: each stripe goes through the fused kernel, its k
    range reads sent as they are; one ``stripe_read`` and one
    ``stripe_write`` per stripe, the hot digests under ``sha256``, and no
    payload copy on the host."""
    seen = []

    def record(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    def refuse(*args, **kwargs):
        raise AssertionError("coded on the host")

    monkeypatch.setattr(arc, "span", record)
    monkeypatch.setattr(streaming, "span", record)
    monkeypatch.setattr(obj, "span", record)
    monkeypatch.setattr(codes.ErasureCode, "encode_np", refuse)
    _archive(tmp_path, "xorbas", _budget(10), monkeypatch)
    names = [n for n, _ in seen]
    assert names.count("stripe_read") == names.count("stripe_write") == 5
    assert names.count("kernel_launch") == names.count("d2h") == 5
    assert "host_copy" not in names and "hot_load" not in names
    h2d = [a for n, a in seen if n == "h2d"]
    assert [a["direct"] for a in h2d] == [10] * 5
    assert [a["bytes"] for a in h2d] == [10 * 2048 * 2] * 4 + [10 * 1024 * 2]
    reads = [a for n, a in seen if n == "stripe_read"]
    assert [a["stripe"] for a in reads] == list(range(5))
    # put_hot's 10 digests; then a stripe's 10 incremental hot digests
    # and its 16 per-stripe coded ones
    assert names.count("sha256") == 10 + 5 * (10 + 16)


@pytest.mark.parametrize("name, k, block_mib, stripes", [
    ("rr16", 11, 64, None), ("lrc12", 12, 85, None), ("xorbas", 10, 256, 5)])
def test_budget_rule_at_a_16_GiB_limit(monkeypatch, name, k, block_mib,
                                       stripes):
    """Half of a 16 GiB device: the (16,11) 64 MiB and LRC(12,2,2) 85 MiB
    archives stay one launch; the Xorbas 256 MiB stripe codes in 5 equal
    device stripes of whole kernel tiles, two of which fit the budget."""
    device = SimpleNamespace(memory_stats=lambda: {"bytes_limit": 16 * GiB})
    monkeypatch.setattr(arc.jax, "devices", lambda: [device])
    monkeypatch.delenv(streaming.BUDGET_ENV, raising=False)
    assert streaming.device_budget() == 8 * GiB
    family = {"rr16": "rapidraid", "lrc12": "lrc"}.get(name, name)
    code = codes.make(family, N, k, l=L)
    words = block_mib * (1 << 20) // 2
    width = arc._fused_stripe_words(code, words)
    if stripes is None:
        assert width is None
        assert streaming.fused_encode_bytes(k, N, words, L) <= 8 * GiB
        return
    assert -(-words // width) == stripes
    assert width % 1024 == 0
    assert 2 * streaming.fused_encode_bytes(k, N, width, L) <= 8 * GiB
    assert streaming.fused_encode_bytes(k, N, words, L) > 16 * GiB


def test_forced_budget_wins_and_the_cpu_has_none(monkeypatch):
    monkeypatch.setenv(streaming.BUDGET_ENV, "12345")
    assert streaming.device_budget() == 12345
    monkeypatch.delenv(streaming.BUDGET_ENV)
    assert streaming.device_budget() is None     # the CPU reports no limit
