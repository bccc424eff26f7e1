"""Generator rows that are unit rows are not coded and not copied back.

A systematic code's data rows are the hot blocks themselves. The fused
archive codes only the other rows of the generator and writes each data
row from the digest-verified hot buffer it equals: on the monolithic
path, in device stripes and in ``archive_many``. The stored files and
digests are ``encode_np``'s either way; RapidRAID, which has no unit rows,
still codes all n rows.
"""
import contextlib
import dataclasses
import functools

import numpy as np
import pytest

from repro.core import codes, gf, streaming
from repro.kernels.gf_encode import ops as kernel_ops
from repro.storage import archive as arc
from repro.storage import object_store as obj

GEOMETRY = {"rapidraid": 11, "lrc": 12, "xorbas": 10}
N, L = 16, 16
WORDS = 9216                # 4 stripes of 2048 words and a tail of 1024
STRIPE = 2048
PATHS = ("monolithic", "striped", "archive_many")


@dataclasses.dataclass(frozen=True)
class _Stub:
    """A positionwise code with a hand-written generator."""
    rows: tuple
    positionwise = True

    @functools.cached_property
    def G(self):
        return np.asarray(self.rows, dtype=np.uint16)


@pytest.mark.parametrize("code, want", [
    (codes.make("lrc", 8, 4, l=L), {j: j for j in range(4)}),
    (codes.make("lrc", 16, 12, l=L), {j: j for j in range(12)}),
    (codes.make("xorbas", 16, 10, l=L), {j: j for j in range(10)}),
    (codes.make("rapidraid", 16, 11, l=L), {}),
    (codes.make("mbr", 8, 4, l=L), {}),
    (_Stub(((2, 0), (0, 3), (1, 1))), {}),           # scaled unit rows
    (_Stub(((0, 1), (5, 0), (1, 1), (1, 0))), {0: 1, 3: 0}),
], ids=["lrc8", "lrc16", "xorbas", "rapidraid", "mbr", "scaled", "mixed"])
def test_passthrough_holds_exact_unit_rows_only(code, want):
    assert dict(arc._passthrough(code)) == want


def _archive(tmp_path, family, path, monkeypatch):
    """Archive one object (two for ``archive_many``) of ``family`` along
    ``path``; -> (code, {step: data}, manifests, spans, kernel row counts)."""
    k = GEOMETRY[family]
    code = codes.make(family, N, k, l=L)
    acfg = arc.ArchiveConfig(n=N, k=k, l=L, family=family)
    if path == "striped":
        monkeypatch.setenv(streaming.BUDGET_ENV, str(
            2 * streaming.fused_encode_bytes(k, N, STRIPE, L)))
    else:
        monkeypatch.delenv(streaming.BUDGET_ENV, raising=False)
    seen, rows = [], []

    def record(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    encode = kernel_ops.encode_auto

    def counted(M, data, l, **kw):
        rows.append(np.asarray(M).shape[0])
        return encode(M, data, l, **kw)

    monkeypatch.setattr(arc, "span", record)
    monkeypatch.setattr(streaming, "span", record)
    monkeypatch.setattr(kernel_ops, "encode_auto", counted)
    store = obj.NodeStore(str(tmp_path), N)
    steps = (1, 2) if path == "archive_many" else (1,)
    data = {}
    for s in steps:
        data[s] = np.random.default_rng(10 * k + s).integers(
            0, 1 << L, size=(k, WORDS)).astype(gf.WORD_DTYPE[L])
        arc.hot_save(store, s, data[s].view(np.uint8), acfg)
    if path == "archive_many":
        manifests = arc.archive_many(store, list(steps), acfg,
                                     use_devices=False)
    else:
        manifests = [arc.archive_step(store, 1, acfg, use_devices=False)]
    for s, m in zip(steps, manifests):
        want = code.encode_np(data[s])
        for pos in range(N):
            got = store.get(m["perm"][pos], arc.ARC.format(step=s, i=pos))
            assert got == want[pos].tobytes(), (s, pos)
            assert m["coded_digests"][pos] == obj.digest(want[pos].tobytes())
    return code, manifests, seen, rows


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("family", sorted(GEOMETRY))
def test_only_rows_that_are_not_unit_rows_are_coded(tmp_path, family, path,
                                                    monkeypatch):
    """The kernel gets n - k generator rows for a systematic code and all
    n for RapidRAID, and the files are ``encode_np``'s."""
    code, manifests, _, rows = _archive(tmp_path, family, path, monkeypatch)
    coded = N if family == "rapidraid" else N - code.k
    launches = 5 if path == "striped" else 1
    assert rows == [coded] * launches
    assert ("streaming" in manifests[0]) == (path == "striped")


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("family", sorted(GEOMETRY))
def test_copy_back_counts_only_the_coded_rows(tmp_path, family, path,
                                              monkeypatch):
    """``passthrough`` counts the rows written from hot buffers (k per
    object or stripe, 0 for RapidRAID) and ``d2h``'s ``bytes`` only the
    rows copied back: per stripe when striped, else one ``d2h`` per coded
    row, ``passthrough`` on the launch's first."""
    code, manifests, seen, _ = _archive(tmp_path, family, path, monkeypatch)
    through = 0 if family == "rapidraid" else code.k
    d2h = [a for n, a in seen if n == "d2h"]
    if path == "striped":
        writes = [a for n, a in seen if n == "stripe_write"]
        assert [a["passthrough"] for a in writes] == [through] * 5
        assert [a["bytes"] for a in writes] == \
            [N * STRIPE * 2] * 4 + [N * 1024 * 2]
        # the tail stripe is padded to the stripe width on the device
        assert [a["bytes"] for a in d2h] == [(N - through) * STRIPE * 2] * 5
    else:
        objects = len(manifests)
        assert len(d2h) == objects * (N - through)
        assert [a.get("passthrough") for a in d2h] == \
            [objects * through] + [None] * (len(d2h) - 1)
        assert [a["row"] for a in d2h] == list(range(through, N)) * objects
        assert [a["bytes"] for a in d2h] == [WORDS * 2] * len(d2h)
    assert "host_copy" not in {n for n, _ in seen}
