"""One run of one cell: set-up, the measured window, the answers checked.

``run_cell`` takes the cell's configuration and mix from ``BENCHMARK.json``
and returns the result object that ``bench/run.py`` prints last. It does
not look for a chip; ``run.py`` does that before calling it, and the tests
call it directly on the CPU.

The window: the mix's operation is called back to back, each call timed
by the host clock from before the client verb to its return (every verb
returns only once its bytes are stored or read). Work the mix does with
the clock stopped (ingesting the next object, deleting the shards a repair
heals, taking the answers) is not in the window. The window closes once
the timed calls add up to ``seconds``; the call in flight then completes
and counts.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

import jax

from harness import spec
from harness.timed_store import TimedStore
from harness.trace_reduce import Trace, ours, top
from repro.storage.client import ArchiveConfig, StorageClient

#: each stated field's control: the nearest field below it that the
#: program codes in
LOWER = {16: 8}

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(**rec) -> None:
    """One JSON line on standard output, before the result."""
    print(json.dumps(rec), flush=True)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    op: object                 # the cell's operation (``harness.op``)
    setup_s: float
    durations: list[float]
    store_s: list[float]
    device_kind: str
    trace: Trace | None = None


class _Compiles:
    """Counts JAX lowerings and backend compiles as they happen."""

    def __init__(self):
        self.counts = dict.fromkeys(COMPILE_EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if event in self.counts:
            self.counts[event] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def _disk_io() -> dict[str, int]:
    """This process's bytes sent toward storage and cancelled again."""
    out = {}
    try:
        with open("/proc/self/io") as f:
            for line in f:
                key, val = line.split(":")
                out[key] = int(val)
    except OSError:
        return {}
    return {"write_bytes": out.get("write_bytes", 0),
            "cancelled_write_bytes": out.get("cancelled_write_bytes", 0)}


def _acfg(cfg: dict, l: int) -> ArchiveConfig:
    return ArchiveConfig(n=cfg["n"], k=cfg["k"], l=l, seed=cfg["code_seed"],
                         family=cfg["family"])


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, control: bool = False) -> dict:
    """Run the cell; -> the result object (``correct`` ... ``checks``).

    ``t_start`` is the host clock's reading at process start, from which
    ``setup_s`` counts. ``control`` builds the client over the program's
    own field below the one the configuration states (``LOWER``), while
    the comparison still holds every answer to the stated field's
    reference: a sound comparison fails it.
    """
    bench = spec.load(root)
    _, cfg, mix = spec.cell(root, bench, cell_name)
    ref = spec.reference(bench, cfg)
    compiles = _Compiles()
    io0 = _disk_io()
    with tempfile.TemporaryDirectory(prefix="rrbench_") as tmp:
        store = TimedStore(os.path.join(tmp, "nodes"), cfg["n"])
        client = StorageClient(
            store, _acfg(cfg, LOWER[cfg["l"]] if control else cfg["l"]))
        m = spec.op(bench, mix["op"])(mix, cfg, client, seed, ref)
        prof = os.path.join(tmp, "profile")
        tracing = False

        def start_trace():
            nonlocal tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(prof, profiler_options=opts)
            tracing = True

        if trace and mix.get("trace_setup"):
            start_trace()
        m.setup()
        setup_s = time.perf_counter() - t_start
        log(phase="setup", setup_s=setup_s, compiles=compiles.total(),
            store_calls=dict(store.calls))
        c0 = compiles.total()
        durations, store_s = [], []
        attempted = failed = 0
        traced_s = 0.0
        i = 0
        while sum(durations) < seconds:
            m.prepare(i)
            if trace and not tracing and i == 0:
                start_trace()
            s0 = store.seconds
            attempted += 1
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(m.label):
                    m.call(i)
            except Exception:
                failed += 1
                traceback.print_exc(file=sys.stderr)
                break
            dt = time.perf_counter() - t0
            durations.append(dt)
            store_s.append(store.seconds - s0)
            if tracing:
                traced_s += dt
                if (i + 1 >= mix.get("trace_ops", 1 << 30)
                        or traced_s >= mix.get("trace_seconds", 1e30)):
                    jax.profiler.stop_trace()
                    tracing = False
            m.after(i)
            i += 1
        if tracing:
            jax.profiler.stop_trace()
        in_window = compiles.total() - c0
        dev = jax.devices()[0]
        peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        io1 = _disk_io()
        log(phase="window", ops=len(durations), timed_s=sum(durations),
            durations=durations,
            compiles_in_window=in_window, store_calls=dict(store.calls),
            disk={k: io1[k] - io0.get(k, 0) for k in io1})
        if in_window:
            print(f"warning: {in_window} compiles inside the window",
                  file=sys.stderr)
        checks = m.checks() if attempted else {}
        checks["ops_failed"] = failed
        got = Trace.from_profile(prof, [m.label]) if trace else None

    run = Run(m, setup_s, durations, store_s, dev.device_kind, got)
    metrics = {}
    for entry in spec.metrics(bench, cell_name, trace):
        value = spec.reader(bench, entry["name"])(run)
        if value is None:
            print(f"metric {entry['name']}: nothing to read in this run",
                  file=sys.stderr)
        else:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": attempted > 0 and not any(checks.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if got is not None:
        lo, hi = got.extent(lambda n: ours(n, [m.label]))
        device["busy_s"] = got.busy(lo, hi)
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": got.top_ops(lo, hi),
                            "idle_gaps": top(got.idle_by_host(lo, hi))}
    out["checks"] = {name: {"value": v, "limit": 0}
                     for name, v in checks.items()}
    return out

