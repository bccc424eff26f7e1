"""The storage verbs' own spans, read back from a real profiler trace.

One ``StorageClient`` archive, repair and ``read_range`` of the paper's
(16,11) GF(2^16) code at 32 KiB blocks run under ``jax.profiler``, each
verb marked with its name as a caller timing it would mark it; the
``.xplane.pb`` is read back with ``jax.profiler.ProfileData``. The same
calls without the profiler store the same bytes.
"""
from __future__ import annotations

import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro.storage import archive as arc
from repro.storage import object_store as obj
from repro.storage.client import StorageClient

ACFG = arc.ArchiveConfig(n=16, k=11, l=16)
#: Azure's LRC(12,2,2): rows 0..11 of its generator are unit rows
LRC = arc.ArchiveConfig(n=16, k=12, l=16, family="lrc")
BLOCK = 32 << 10
LOST = 5
RANGE = (BLOCK - 4096, 8192)        # crosses from block 0 into block 1
VERBS = ("archive", "repair", "read_range")

#: the spans each verb must hold (``repro.spans``)
EXPECTED = {
    "archive": {"manifest", "hot_load", "sha256", "h2d", "kernel_launch",
                "d2h", "reclaim", "store.has", "store.get", "store.put",
                "store.delete"},
    "repair": {"manifest", "repair_plan", "sha256", "h2d", "kernel_launch",
               "d2h", "place_repaired", "store.has", "store.get",
               "store.put"},
    "read_range": {"manifest", "read_plan", "read_decode", "host_copy",
                   "store.has", "store.get", "store.get_range"},
}


def _session(root: str, label=None, acfg=ACFG) -> obj.NodeStore:
    """Archive, lose a row, repair, read a range; ``label`` marks each verb
    (a ``jax.profiler.TraceAnnotation`` of the verb's name) when given."""
    store = obj.NodeStore(root, acfg.n)
    client = StorageClient(store, acfg)
    rng = np.random.default_rng(13)
    blocks = rng.integers(0, 256, size=(acfg.k, BLOCK), dtype=np.uint8)
    client.put_hot(1, blocks)
    mark = label or (lambda name: contextlib.nullcontext())
    with mark("archive"):
        client.archive(1)
    node = client.manifest(1)["perm"][LOST]
    store.delete(node, arc.ARC.format(step=1, i=LOST))
    with mark("repair"):
        repaired = client.repair(1)
    with mark("read_range"):
        got = client.read_range(1, *RANGE).data
    assert repaired == [LOST]
    assert got == blocks.reshape(-1)[RANGE[0]:RANGE[0] + RANGE[1]].tobytes()
    return store


def _files(root: str) -> dict[str, bytes]:
    out = {}
    for dirpath, _, names in os.walk(root):
        for name in names:
            p = os.path.join(dirpath, name)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def _traced_session(base, acfg):
    """(host events as (name, start, end, stats), plain files, traced files);
    the plain run goes first, so the traced one holds no compile."""
    plain = _files(_session(str(base / "plain"), acfg=acfg).root)
    prof = str(base / "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(prof, profiler_options=opts)
    try:
        store = _session(str(base / "traced"), jax.profiler.TraceAnnotation,
                         acfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events]
    return events, plain, _files(store.root)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _traced_session(tmp_path_factory.mktemp("spans"), ACFG)


@pytest.fixture(scope="module")
def traced_lrc(tmp_path_factory):
    return _traced_session(tmp_path_factory.mktemp("spans_lrc"), LRC)


def _inside(events, verb):
    ((lo, hi),) = [(a, b) for n, a, b, _ in events if n == verb]
    return [e for e in events if lo <= e[1] and e[2] <= hi and e[0] != verb]


@pytest.mark.parametrize("verb", VERBS)
def test_each_span_appears_inside_its_verb(traced, verb):
    events, _, _ = traced
    names = {n for n, *_ in _inside(events, verb)}
    assert EXPECTED[verb] <= names, EXPECTED[verb] - names


def test_no_program_span_takes_a_verb_label(traced):
    events, _, _ = traced
    for verb in VERBS:
        assert sum(n == verb for n, *_ in events) == 1


def test_sha256_once_per_hot_verify_and_coded_row(traced):
    events, _, _ = traced
    hashes = [e for e in _inside(events, "archive") if e[0] == "sha256"]
    assert len(hashes) == ACFG.k + ACFG.n
    assert all(stats["bytes"] == BLOCK for *_, stats in hashes)


def test_byte_counts_on_the_spans(traced):
    events, _, _ = traced
    archive = _inside(events, "archive")
    (hot,) = [s for n, *_, s in archive if n == "hot_load"]
    assert hot["bytes"] == ACFG.k * BLOCK
    (h2d,) = [s for n, *_, s in archive if n == "h2d"]
    assert h2d["bytes"] == ACFG.k * BLOCK
    d2h = [s for n, *_, s in archive if n == "d2h"]
    assert [s["bytes"] for s in d2h] == [BLOCK] * ACFG.n   # one per row
    decode = [s for n, *_, s in _inside(events, "read_range")
              if n == "read_decode"]
    assert len(decode) == 2                    # one per touched block


@pytest.mark.parametrize("verb", VERBS)
def test_host_copies_only_where_payload_is_copied(traced, verb):
    """The coding verbs of a positionwise code send the store's buffers to
    the device and write the coded rows as views: no ``host_copy`` lies
    inside them. A range read still slices and joins on the host."""
    events, _, _ = traced
    copies = [e for e in _inside(events, verb) if e[0] == "host_copy"]
    assert bool(copies) == (verb == "read_range"), copies


def test_h2d_sends_each_store_buffer_direct(traced):
    """One ``h2d`` per launch; ``direct`` counts the store buffers sent
    without a host copy: the k hot blocks, then the repair's helpers."""
    events, _, _ = traced
    helpers = ACFG.code().repair_helpers(
        [LOST], [p for p in range(ACFG.n) if p != LOST])
    for verb, rows in (("archive", ACFG.k), ("repair", len(helpers))):
        (h2d,) = [s for n, *_, s in _inside(events, verb) if n == "h2d"]
        assert h2d["direct"] == rows, verb
        assert h2d["bytes"] == rows * BLOCK, verb


def test_repair_plan_counts_its_helpers(traced):
    """Both plan spans of a fused repair (the helper choice and the repair
    matrix) carry ``helpers``, the rows the plan reads."""
    events, _, _ = traced
    helpers = ACFG.code().repair_helpers(
        [LOST], [p for p in range(ACFG.n) if p != LOST])
    plans = [s for n, *_, s in _inside(events, "repair")
             if n == "repair_plan"]
    assert [s["helpers"] for s in plans] == [len(helpers)] * 2


def test_no_span_nests_in_its_own_name(traced):
    events, _, _ = traced
    ours = {n for names in EXPECTED.values() for n in names}
    for name in ours - {n for n in ours if n.startswith("store.")}:
        got = sorted((a, b) for n, a, b, _ in events if n == name)
        for (a0, b0), (a1, b1) in zip(got, got[1:]):
            assert a1 >= b0, (name, (a0, b0), (a1, b1))


def test_stored_bytes_unchanged_by_the_profiler(traced):
    _, plain, traced_files = traced
    assert plain.keys() == traced_files.keys()
    assert all(plain[p] == traced_files[p] for p in plain)


@pytest.mark.parametrize("session, acfg, through", [
    ("traced", ACFG, 0), ("traced_lrc", LRC, LRC.k)], ids=["rapidraid", "lrc"])
def test_d2h_copies_back_only_rows_that_are_not_data(request, session, acfg,
                                                     through):
    """A systematic code's data rows are written from the hot buffers
    (``passthrough``, on the first row's span), so ``d2h`` copies back only
    the other rows, one span each, every copy started at the launch
    (``kernel_launch``'s ``streamed``); the profiler leaves the stored
    bytes as they are."""
    events, plain, traced_files = request.getfixturevalue(session)
    archive = _inside(events, "archive")
    d2h = [s for n, *_, s in archive if n == "d2h"]
    assert [s["row"] for s in d2h] == list(range(through, acfg.n))
    assert d2h[0]["passthrough"] == through
    assert sum(s["bytes"] for s in d2h) == (acfg.n - through) * BLOCK
    (launch,) = [s for n, *_, s in archive if n == "kernel_launch"]
    assert launch["streamed"] == acfg.n - through
    assert plain == traced_files
