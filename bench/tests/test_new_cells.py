"""The cells ``xorbas.archive`` and ``lrc12.repair`` end to end on the CPU
at the tiny size, their control and faults, the data-row draw of
``repair_data``, and the ``stripe_overlap`` reader on synthetic traces."""
import json
import os
import tempfile
import time
from types import SimpleNamespace

import pytest

from conftest import ROOT
from harness import session, spec
from harness.timed_store import TimedStore
from harness.trace_reduce import Trace
from test_control_faults import _encode_fault, _repair_fault
from repro.core import streaming
from repro.kernels.gf_encode import ops as kernel_ops
from repro.storage import archive as arc
from repro.storage.client import StorageClient

SEED = 2**33 + 117
CELLS = ["xorbas.archive", "lrc12.repair"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_runs_correct(tiny_root, cell, trace):
    out = session.run_cell(tiny_root, cell, SEED, 0.3, trace,
                           time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"}
    assert want and want <= set(out["metrics"])


def test_xorbas_archive_in_device_stripes_is_correct(tiny_root, monkeypatch):
    """Under a forced budget the tiny stripe is coded in device stripes;
    the answers still match the reference."""
    monkeypatch.setenv(streaming.BUDGET_ENV, str(1 << 20))
    out = session.run_cell(tiny_root, "xorbas.archive", SEED, 0.3, False,
                           time.perf_counter())
    assert out["correct"], out["checks"]


def test_xorbas_control_is_not_correct(tiny_root):
    """The client over GF(2^8), its answers held to the GF(2^16)
    reference: the comparison fails it. (``lrc12.repair`` has no such
    control: a lost data row is the XOR of its group in either field.)"""
    out = session.run_cell(tiny_root, "xorbas.archive", SEED, 0.2, False,
                           time.perf_counter(), control=True)
    assert not out["correct"] and out["failed"] == 0


FAULTS = {"xorbas.archive": ("_fused_encode", _encode_fault),
          "lrc12.repair": ("_place_repaired", _repair_fault)}


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_new_cell_fault_is_not_correct(tiny_root, monkeypatch, cell, kind):
    """The faults of ``test_control_faults``, in place during the timed
    calls only."""
    name, make = FAULTS[cell]
    bench = spec.load(tiny_root)
    op = spec.op(bench, spec.cell(tiny_root, bench, cell)[2]["op"])
    call = op.call

    def faulty(self, i):
        with monkeypatch.context() as mp:
            mp.setattr(arc, name, make(kind))
            return call(self, i)

    monkeypatch.setattr(op, "call", faulty)
    out = session.run_cell(tiny_root, cell, SEED, 0.2, False,
                           time.perf_counter())
    assert not out["correct"], out["checks"]


def test_a_wrong_word_in_a_device_stripe_is_not_correct(tiny_root,
                                                        monkeypatch):
    """One word altered in each device stripe's encode fails the run."""
    monkeypatch.setenv(streaming.BUDGET_ENV, str(1 << 20))
    streamed = []
    stream, encode = arc._archive_step_streaming, kernel_ops.encode_auto

    def counted(*args):
        streamed.append(1)
        return stream(*args)

    def altered(M, data, l, interpret=None):
        out = encode(M, data, l, interpret=interpret)
        return out.at[0, 17].set(out[0, 17] ^ 1)

    monkeypatch.setattr(arc, "_archive_step_streaming", counted)
    monkeypatch.setattr(kernel_ops, "encode_auto", altered)
    out = session.run_cell(tiny_root, "xorbas.archive", SEED, 0.2, False,
                           time.perf_counter())
    assert streamed and not out["correct"] and out["failed"] == 0


@pytest.mark.parametrize("seed", [SEED, SEED + 1, 2**31 + 5])
def test_repair_data_loses_a_data_row(tiny_root, seed):
    bench = spec.load(tiny_root)
    _, cfg, mix = spec.cell(tiny_root, bench, "lrc12.repair")
    ref = spec.reference(bench, cfg)
    with tempfile.TemporaryDirectory() as tmp:
        store = TimedStore(tmp, cfg["n"])
        client = StorageClient(store, session._acfg(cfg, cfg["l"]))
        op = spec.op(bench, mix["op"])(mix, cfg, client, seed, ref)
        op.setup()
    assert len(op.lost) == 1 and op.lost[0] < cfg["k"]
    assert op.kernel_bytes == (6 + 1) * cfg["block_bytes"]


def run_of(spans, ops=()):
    return SimpleNamespace(trace=Trace(list(ops), list(spans)),
                           op=SimpleNamespace(label="archive"))


def overlap(run):
    return spec.reader(spec.load(ROOT), "stripe_overlap.archive")(run)


def test_stripe_overlap_counts_device_time_under_stripe_io():
    """Of 2 s of device work in the call, 0.5 s runs under a stripe read
    and 1 s under a stripe write; the 0.5 s under ``d2h`` and the work
    outside the call are left out."""
    run = run_of([("archive", 0.0, 10.0), ("stripe_read", 1.0, 3.0),
                  ("sha256", 1.5, 2.5), ("d2h", 3.0, 5.0),
                  ("stripe_write", 5.0, 7.0), ("stripe_read", 11.0, 12.0)],
                 ops=[(0, "gf_encode", 2.0, 2.5), (0, "gf_encode", 4.0, 4.5),
                      (0, "gf_encode", 6.0, 7.0),
                      (0, "gf_encode", 11.0, 12.0)])
    assert overlap(run) == pytest.approx(75.0)


@pytest.mark.parametrize("spans, ops", [
    ([("archive", 0.0, 10.0), ("d2h", 3.0, 5.0)],
     [(0, "gf_encode", 4.0, 4.5)]),                  # one launch: no stripes
    ([("archive", 0.0, 10.0), ("stripe_read", 1.0, 3.0)], []),  # no device
])
def test_stripe_overlap_reads_nothing_without_stripes_or_device(spans, ops):
    assert overlap(run_of(spans, ops)) is None
    assert overlap(SimpleNamespace(trace=None, op=None)) is None
