"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to two lists on one clock, in seconds:

* ``ops``: every operation that ran on a device, as (device, name, start,
  end), from the device planes' ``XLA Ops`` lines (every line of a device
  plane that has no such line);
* ``spans``: the host events, as (name, start, end), of each host thread
  that holds one of the benchmark's own annotations (the operation being
  timed, and each storage call, ``store.<call>``): those annotations and
  JAX's own events on that thread (``np.asarray(jax.Array)``, a
  device-to-host copy; ``PjitFunction(...)``, a dispatch; ...).

Busy time is the union of a device's op intervals; the idle time inside a
window is set against the innermost host span open at each instant, or
``other host`` where none is. ``Trace.to_json`` keeps that reduced form, so
the reduction can be checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"/device:[A-Z]+:(\d+)$")
OTHER = "other host"


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class _Union:
    """Busy intervals of one device, with the covered length of any
    window in O(log n)."""

    def __init__(self, intervals):
        self.iv = _merge(intervals)
        self.starts = [a for a, _ in self.iv]
        self.cum = [0.0]
        for a, b in self.iv:
            self.cum.append(self.cum[-1] + b - a)

    def covered(self, lo: float, hi: float) -> float:
        if hi <= lo or not self.iv:
            return 0.0
        return max(0.0, self._upto(hi) - self._upto(lo))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        a, b = self.iv[i - 1]
        return self.cum[i - 1] + min(t, b) - a


@dataclasses.dataclass
class Trace:
    ops: list[tuple[int, str, float, float]]
    spans: list[tuple[str, float, float]]
    _unions: dict = dataclasses.field(default_factory=dict, repr=False,
                                      compare=False)

    # -- loading --------------------------------------------------------------

    @classmethod
    def from_profile(cls, log_dir: str, span_names) -> "Trace":
        """Read the ``.xplane.pb`` that ``jax.profiler`` wrote under
        ``log_dir``; keep the host threads that hold a span whose name is
        in ``span_names`` or starts with ``store.``."""
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"want one trace under {log_dir}, "
                               f"found {len(paths)}")
        data = ProfileData.from_file(paths[0])
        ops, spans = [], []
        names = set(span_names)

        def events(d, line):
            return [(d, e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]

        for plane in data.planes:
            dev = DEVICE_PLANE.match(plane.name)
            if dev:
                d = int(dev.group(1))
                lines = list(plane.lines)
                has_ops = any(line.name == OPS_LINE for line in lines)
                for line in lines:
                    if line.name == OPS_LINE or not has_ops:
                        ops += events(d, line)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    got = [(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                    if any(ours(n, names) for n, _, _ in got):
                        spans += got
        return cls(ops, spans)

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([tuple(o) for o in d["ops"]],
                   [tuple(s) for s in d["spans"]])

    # -- reductions -----------------------------------------------------------

    def devices(self) -> list[int]:
        return sorted({d for d, *_ in self.ops})

    def span_extent(self, name: str) -> tuple[float, float] | None:
        """From the first start to the last end of the spans ``name``."""
        return self.extent(lambda n: n == name)

    def extent(self, match) -> tuple[float, float] | None:
        """From the first start to the last end of the spans ``match``
        accepts."""
        got = [(a, b) for n, a, b in self.spans if match(n)]
        if not got:
            return None
        return min(a for a, _ in got), max(b for _, b in got)

    def busy(self, lo: float, hi: float) -> float:
        """Seconds in [lo, hi) in which an op ran, averaged over the
        devices that ran any op in the trace."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(self._union(d).covered(lo, hi) for d in devs) / len(devs)

    def _union(self, dev: int) -> _Union:
        if dev not in self._unions:
            self._unions[dev] = _Union(
                [(a, b) for d, _, a, b in self.ops if d == dev])
        return self._unions[dev]

    def op_seconds(self, match, lo: float, hi: float) -> float:
        """Summed device time of the ops in [lo, hi) whose name ``match``
        accepts, averaged over devices."""
        devs = self.devices()
        t = sum(min(b, hi) - max(a, lo) for _, n, a, b in self.ops
                if a < hi and b > lo and match(n))
        return t / len(devs) if devs else 0.0

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list[list]:
        """The ``n`` op names that took the most device time in [lo, hi)."""
        per: dict[str, float] = defaultdict(float)
        for _, name, a, b in self.ops:
            if a < hi and b > lo:
                per[name] += min(b, hi) - max(a, lo)
        devs = max(1, len(self.devices()))
        return [[name, t / devs] for name, t in
                sorted(per.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, lo: float, hi: float) -> dict[str, float]:
        """Idle device seconds in [lo, hi), by the innermost host span
        open at the time (``other host`` where none is)."""
        cuts = {lo, hi}
        for _, a, b in self.spans:
            cuts.update(t for t in (a, b) if lo < t < hi)
        cuts = sorted(cuts)
        open_at = sorted(self.spans, key=lambda s: s[1])
        out: dict[str, float] = defaultdict(float)
        active: list[tuple[str, float, float]] = []
        i = 0
        for a, b in zip(cuts, cuts[1:]):
            while i < len(open_at) and open_at[i][1] <= a:
                active.append(open_at[i])
                i += 1
            active = [s for s in active if s[2] > a]
            label = max(active, key=lambda s: s[1])[0] if active else OTHER
            idle = (b - a) - self.busy(a, b)
            if idle > 0:
                out[label] += idle
        return dict(out)


def ours(name: str, labels) -> bool:
    """A benchmark annotation: an operation's label or a store call."""
    return name in labels or name.startswith("store.")


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
