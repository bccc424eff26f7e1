"""The readers of the program's spans, on synthetic traces and on one
recorded chip trace.

``data/trace_rr16_archive_spans.json`` is one traced archive of
``rr16.archive`` on a TPU v5e with the program's own spans, reduced and
kept as ``Trace.from_profile(<profile dir>, ["archive"]).to_json()``.
"""
import os
from types import SimpleNamespace

import pytest

from conftest import ROOT
from harness import spec
from harness.trace_reduce import Trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_rr16_archive_spans.json")


def run_of(spans, ops=(), label="archive"):
    return SimpleNamespace(trace=Trace(list(ops), list(spans)),
                           op=SimpleNamespace(label=label))


def metric(name, run):
    return spec.reader(spec.load(ROOT), name)(run)


def test_nested_and_overlapping_spans_count_once():
    run = run_of([("archive", 0.0, 10.0), ("hot_load", 0.5, 4.0),
                  ("sha256", 1.0, 2.0), ("sha256", 1.5, 3.0),
                  ("host_copy", 3.0, 3.5), ("host_copy", 3.2, 3.4)])
    assert metric("sha256_share.archive", run) == pytest.approx(20.0)
    assert metric("host_copy_share.archive", run) == pytest.approx(5.0)


def test_shares_are_clipped_to_the_calls():
    """Spans outside every call (a traced set-up) and the parts of spans
    that stick out of a call are left out; the base is the calls' summed
    length, not the extent from first to last."""
    run = run_of([("sha256", -5.0, -1.0),             # set-up
                  ("read_range", 0.0, 10.0), ("read_range", 20.0, 30.0),
                  ("read_plan", 8.0, 12.0), ("read_decode", 25.0, 27.0),
                  ("read_decode", 35.0, 36.0)], label="read_range")
    assert metric("read_decode_share.read", run) == pytest.approx(20.0)
    assert metric("unattributed_share.read", run) == pytest.approx(80.0)


def test_transfer_share_leaves_out_device_busy_time():
    """The kernel runs inside ``d2h``'s wait: only the idle part of the
    transfers counts."""
    run = run_of([("repair", 0.0, 10.0), ("h2d", 1.0, 2.0),
                  ("kernel_launch", 2.0, 2.5), ("d2h", 3.0, 6.0)],
                 ops=[(0, "tpu_custom_call", 4.0, 5.0),
                      (0, "copy", 1.5, 1.75)],
                 label="repair")
    assert metric("transfer_share.repair", run) == pytest.approx(27.5)


def test_unattributed_counts_time_under_no_other_span():
    run = run_of([("archive", 0.0, 10.0), ("store.get", 1.0, 3.0),
                  ("sha256", 2.0, 4.0), ("PjitFunction(f)", 6.0, 7.0),
                  ("store.put", 9.5, 11.0)])
    assert metric("unattributed_share.archive", run) == pytest.approx(55.0)


@pytest.mark.parametrize("name", ["sha256_share.archive",
                                  "host_copy_share.archive",
                                  "transfer_share.archive",
                                  "read_decode_share.read"])
def test_silent_where_the_program_emits_no_such_span(name):
    """A program that emits none of these spans reads nothing;
    the unattributed share still reads, all of the call."""
    label = name.split(".")[1].replace("read", "read_range")
    run = run_of([(label, 0.0, 10.0), ("store.get", 1.0, 2.0)], label=label)
    assert metric(name, run) is None
    assert metric("unattributed_share.archive", run) == pytest.approx(90.0)
    assert metric(name, SimpleNamespace(trace=None, op=run.op)) is None


def test_recorded_chip_trace_with_program_spans():
    """One archive of ``rr16.archive`` on a TPU v5e: the program's spans
    leave at most 10 % of the call unattributed, and busy plus idle still
    add up to the window."""
    with open(RECORDED) as f:
        t = Trace.from_json(f.read())
    run = SimpleNamespace(trace=t, op=SimpleNamespace(label="archive"))
    lo, hi = t.span_extent("archive")
    busy = t.busy(lo, hi)
    idle = t.idle_by_host(lo, hi)
    assert sum(idle.values()) == pytest.approx(hi - lo - busy, rel=1e-6)
    assert metric("unattributed_share.archive", run) <= 10.0
    shares = {f: metric(f"{f}.archive", run) for f in
              ("sha256_share", "host_copy_share", "transfer_share")}
    assert all(0 < v < 100 for v in shares.values()), shares
