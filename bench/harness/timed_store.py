"""The system's node store, with each call timed and marked in the trace.

``TimedStore`` is the program's ``NodeStore`` with every storage call
wrapped: the host clock adds its duration to ``seconds``, and a
``jax.profiler.TraceAnnotation`` named ``store.<call>`` puts it on the
profiler's timeline, so the device's idle gaps can be set against what the
host was doing. Outside a profiler session an annotation costs a few
hundred nanoseconds.
"""
from __future__ import annotations

import time
from collections import Counter

import jax

from repro.storage.object_store import NodeStore, StreamWriter


class TimedStore(NodeStore):

    def __post_init__(self):
        super().__post_init__()
        self.seconds = 0.0
        self.calls: Counter = Counter()

    def _timed(self, call: str, fn, *args):
        with jax.profiler.TraceAnnotation(call):
            t0 = time.perf_counter()
            try:
                return fn(*args)
            finally:
                self.seconds += time.perf_counter() - t0
                self.calls[call] += 1

    def put(self, i, rel, data):
        return self._timed("store.put", super().put, i, rel, data)

    def get(self, i, rel):
        return self._timed("store.get", super().get, i, rel)

    def get_range(self, i, rel, offset, nbytes):
        return self._timed("store.get_range", super().get_range, i, rel,
                           offset, nbytes)

    def delete(self, i, rel):
        return self._timed("store.delete", super().delete, i, rel)

    def has(self, i, rel):
        return self._timed("store.has", super().has, i, rel)

    def put_stream(self, i, rel):
        return _TimedWriter(self, self.path(i, rel))

    def get_stream(self, i, rel, frame_bytes):
        frames = super().get_stream(i, rel, frame_bytes)
        while True:
            try:
                frame = self._timed("store.get_stream", next, frames)
            except StopIteration:
                return
            yield frame


class _TimedWriter(StreamWriter):
    """``put_stream``'s writer, its writes and publish timed."""

    def __init__(self, store: TimedStore, path: str):
        store._timed("store.put_stream", super().__init__, path)
        self._store = store

    def write(self, frame):
        self._store._timed("store.put_stream", super().write, frame)

    def close(self):
        self._store._timed("store.put_stream", super().close)
