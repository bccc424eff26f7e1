"""Shares of the traced calls that the program's own spans cover.

The program marks its host work with spans on the profiler's clock
(``repro.spans``: ``sha256``, ``host_copy``, ``h2d``, ``d2h``, ...), and
``Trace.from_profile`` keeps them with the benchmark's own annotations, as
(name, start, end); their args are not kept. Every share here is taken
over the cell's calls only, the spans named ``run.op.label``: the set-up
that a traced run may also hold (``rr16.read`` traces its set-up archive)
is left out.
"""
from __future__ import annotations

from harness.trace_reduce import _merge


def _clip(spans: list[list[float]], windows: list[list[float]]
          ) -> list[tuple[float, float]]:
    """The parts of merged ``spans`` that lie inside merged ``windows``."""
    out, i = [], 0
    for a, b in spans:
        while i < len(windows) and windows[i][1] <= a:
            i += 1
        j = i
        while j < len(windows) and windows[j][0] < b:
            lo, hi = max(a, windows[j][0]), min(b, windows[j][1])
            if hi > lo:
                out.append((lo, hi))
            j += 1
    return out


def _calls(run) -> list[list[float]] | None:
    if run.trace is None:
        return None
    calls = _merge([(a, b) for n, a, b in run.trace.spans
                    if n == run.op.label])
    return calls or None


def _inside(run, calls, keep) -> list[tuple[float, float]]:
    """The union of the spans whose name ``keep`` accepts, clipped to
    ``calls``."""
    return _clip(_merge([(a, b) for n, a, b in run.trace.spans if keep(n)]),
                 calls)


def share(run, names, *, idle_only: bool = False) -> float | None:
    """Length of the union of the spans named in ``names``, clipped to the
    calls, over the calls' summed length, in %. ``idle_only`` counts only
    the time inside those spans in which no op ran on the device
    (``Trace.busy``). None where no such span lies inside a call (a program
    that does not emit them)."""
    calls = _calls(run)
    if calls is None:
        return None
    names = set(names)
    got = _inside(run, calls, lambda n: n in names)
    if not got:
        return None
    covered = sum(b - a for a, b in got)
    if idle_only:
        covered -= sum(run.trace.busy(a, b) for a, b in got)
    return 100.0 * covered / sum(b - a for a, b in calls)


def unattributed(run) -> float | None:
    """Share of the calls in which no other host span is open: no span of
    the program, of the store or of JAX itself, in %."""
    calls = _calls(run)
    if calls is None:
        return None
    covered = _inside(run, calls, lambda n: n != run.op.label)
    total = sum(b - a for a, b in calls)
    return 100.0 * (total - sum(b - a for a, b in covered)) / total
