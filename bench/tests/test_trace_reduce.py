"""The reduction from a profiler trace to busy, idle and kernel time.

``data/trace_rr16_archive.json`` is one traced archive of ``rr16.archive``
on a TPU v5e, reduced and kept as ``Trace.from_profile(<profile dir>,
["archive"]).to_json()``, where ``<profile dir>`` is the directory that
``jax.profiler`` wrote in that run.
"""
import os

import pytest

from harness.trace_reduce import OTHER, Trace, top

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_rr16_archive.json")


def synthetic() -> Trace:
    ops = [(0, "kernel", 1.0, 2.0), (0, "kernel", 1.5, 3.0),
           (0, "copy", 5.0, 6.0)]
    spans = [("archive", 0.0, 10.0), ("store.get", 0.5, 1.2),
             ("store.put", 6.0, 8.0)]
    return Trace(ops, spans)


def test_busy_is_the_union_of_op_intervals():
    t = synthetic()
    assert t.busy(0.0, 10.0) == pytest.approx(3.0)
    assert t.busy(2.5, 5.5) == pytest.approx(1.0)
    assert t.busy(3.0, 5.0) == 0.0


def test_kernel_time_and_top_ops():
    t = synthetic()
    assert t.op_seconds(lambda n: n == "kernel", 0.0, 10.0) == \
        pytest.approx(2.5)
    assert t.top_ops(0.0, 10.0) == [["kernel", pytest.approx(2.5)],
                                    ["copy", pytest.approx(1.0)]]


def test_idle_gaps_go_to_the_innermost_host_span():
    t = synthetic()
    idle = t.idle_by_host(0.0, 10.0)
    assert idle == {"archive": pytest.approx(4.5),
                    "store.get": pytest.approx(0.5),
                    "store.put": pytest.approx(2.0)}
    assert sum(idle.values()) == pytest.approx(10.0 - t.busy(0.0, 10.0))
    assert t.idle_by_host(-1.0, 0.0) == {OTHER: pytest.approx(1.0)}
    assert top(idle, 2) == [["archive", pytest.approx(4.5)],
                            ["store.put", pytest.approx(2.0)]]


def test_round_trip_through_json():
    t = synthetic()
    assert Trace.from_json(t.to_json()) == t


def test_recorded_chip_trace():
    """One archive of ``rr16.archive`` traced on a TPU v5e: busy and idle
    add up to the window, the coding kernel ran inside the archive's span,
    and every idle second is set against a span."""
    with open(RECORDED) as f:
        t = Trace.from_json(f.read())
    lo, hi = t.span_extent("archive")
    busy = t.busy(lo, hi)
    idle = t.idle_by_host(lo, hi)
    assert 0 < busy < 0.05 * (hi - lo)
    assert sum(idle.values()) == pytest.approx(hi - lo - busy, rel=1e-6)
    assert set(idle) <= {OTHER} | {n for n, _, _ in t.spans}
    assert max(idle, key=idle.get) == "archive"
    kernel = t.op_seconds(lambda n: "tpu_custom_call" in n, lo, hi)
    assert 0.5 * busy < kernel <= busy
