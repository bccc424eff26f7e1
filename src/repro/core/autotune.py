"""Cost-model-driven autotuner for GF kernels and pipeline plans.

Every perf-critical constant in the stack used to be hand-calibrated on one
CPU container: the Pallas tile widths (``ops.pick_block`` /
``pick_tick_block``), the MXU-vs-VPU dispatch, the pipeline chunk count
(``num_chunks=8`` everywhere), the stagger, and the makespan model's
``compute_rate``/``tick_overhead``. Repair Pipelining (Li et al., PAPERS.md)
shows pipelined-EC throughput is dominated by exactly these per-tick
slice/dispatch parameters — and none of them transfer to a backend the
constants were never tuned on. This module replaces them with SEARCHED,
MEASURED, per-backend configurations:

* **search** — short timed probes of the REAL jitted kernels and chain
  programs sweep candidate configs (tile widths, dispatch, chunk counts,
  stagger) and keep the fastest;
* **cross-check** — each plan probe is compared against a prediction
  derived from the compiled program's ``cost_analysis`` HLO properties
  (the same numbers ``repro.launch.cost_model`` / ``roofline`` parse) and
  the calibrated makespan model, so a probe that disagrees wildly with the
  model is visible in the cache entry (``fig_autotune`` plots the scatter);
* **calibrate** — a measured chunk sweep least-squares-fits the topology
  model's ``compute_rate``/``tick_overhead``
  (``topology.fit_chain_constants``), replacing the hand-tuned constants
  the scheduler plans with;
* **cache** — results persist in a JSON tuning cache keyed like
  ``repro.core.jitcache`` (backend, entry point, code spec, shapes), so a
  warm process performs ZERO search probes (``stats()`` proves it) and a
  warm tuning cache adds zero recompiles (every consumer resolves to the
  same config, hence the same jitcache key).

Knobs:

* ``RAPIDRAID_TUNE`` — ``off`` (hand-tuned defaults, never read or write
  the cache), ``cached`` (default: consult the cache, fall back to the
  defaults, never probe), ``search`` (probe-and-persist on cache miss);
* ``RAPIDRAID_TUNE_CACHE`` — cache file path (default
  ``~/.cache/rapidraid/autotune.json``).

``python -m repro.autotune`` pre-warms the cache for a geometry. Lookups
are trace-safe: call sites inside ``jax.jit`` traces (the per-tick tile
width, the checkpoint data plane) only ever do cache lookups — probes run
exclusively on concrete host-side values, and never recurse (probes always
pass explicit configs).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Sequence

import numpy as np

from repro.core import gf
from repro.core import topology as topo_lib

TUNE_ENV = "RAPIDRAID_TUNE"
CACHE_ENV = "RAPIDRAID_TUNE_CACHE"
MODES = ("off", "cached", "search")
CACHE_VERSION = 1

#: hand-tuned default the pipeline entry points fall back to — the value
#: every PR before the autotuner hard-coded.
DEFAULT_NUM_CHUNKS = 8
#: candidate chunk counts for plan tuning (model search + probes filter to
#: counts that divide the geometry).
CHUNK_CANDIDATES = (1, 2, 4, 8, 16, 32, 64)
#: candidate staggers are derived per num_chunks: (1, nc//2, nc).

_PROBE_ITERS = 3            # timed repetitions per candidate (median wins)


def mode() -> str:
    """The tuning mode from ``RAPIDRAID_TUNE`` (validated)."""
    m = os.environ.get(TUNE_ENV, "cached").strip().lower() or "cached"
    if m not in MODES:
        raise ValueError(
            f"{TUNE_ENV}={m!r}: must be one of {', '.join(MODES)}")
    return m


def cache_path() -> str:
    """The tuning-cache file path from ``RAPIDRAID_TUNE_CACHE``."""
    p = os.environ.get(CACHE_ENV)
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache", "rapidraid",
                        "autotune.json")


def _backend() -> str:
    import jax
    return jax.default_backend()


def is_concrete(x) -> bool:
    """False for jax tracers: probing under a trace would time the trace,
    not the kernel, so traced call sites get cache-only lookups."""
    import jax
    return not isinstance(x, jax.core.Tracer)


# ---------------------------------------------------------------------------
# the persisted tuning cache
# ---------------------------------------------------------------------------


class TuningCache:
    """JSON-backed map from canonical key strings to tuned-config entries.

    Each entry is a dict with at least ``value`` (the tuned config) plus
    probe evidence (``measured_s``, ``predicted_s``, per-candidate
    timings). Keys mirror ``repro.core.jitcache``:
    ``entry|backend|code-spec|shape parts``.
    """

    def __init__(self, path: str):
        self.path = path
        self.entries: dict[str, dict] = {}
        self.load()

    def load(self) -> None:
        """(Re)read the cache file; a missing file is an empty cache, a
        mangled one is a ``ValueError`` naming the path and the defect."""
        self.entries = {}
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            raise ValueError(
                f"tuning cache {self.path} is not valid JSON ({e}); delete "
                f"it or point {CACHE_ENV} elsewhere") from e
        if not isinstance(raw, dict) or "entries" not in raw:
            raise ValueError(
                f"tuning cache {self.path} has no 'entries' map — not a "
                f"RapidRAID tuning cache")
        if raw.get("version") != CACHE_VERSION:
            raise ValueError(
                f"tuning cache {self.path} has version {raw.get('version')!r},"
                f" expected {CACHE_VERSION} — delete it to re-tune")
        if not isinstance(raw["entries"], dict) or not all(
                isinstance(v, dict) for v in raw["entries"].values()):
            raise ValueError(
                f"tuning cache {self.path}: 'entries' must map keys to "
                f"config dicts")
        self.entries = raw["entries"]

    def save(self) -> None:
        """Atomic write-through (tmp + rename), creating parent dirs."""
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": self.entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def get(self, key: str) -> dict | None:
        return self.entries.get(key)

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = entry


_cache: TuningCache | None = None
_cache_for_path: str | None = None
_stats = {"hits": 0, "misses": 0, "probes": 0}


def reset() -> None:
    """Drop the in-process cache handle and zero the counters (tests; also
    how a process picks up an externally rewritten cache file)."""
    global _cache, _cache_for_path
    _cache = None
    _cache_for_path = None
    for k in _stats:
        _stats[k] = 0


def stats() -> dict[str, int]:
    """Lookup hit/miss and probe counters — a warm cache must show
    ``probes == 0`` (the benchmark and tests assert it)."""
    return dict(_stats)


def cache() -> TuningCache:
    """The process-wide cache for the current ``RAPIDRAID_TUNE_CACHE``."""
    global _cache, _cache_for_path
    path = cache_path()
    if _cache is None or _cache_for_path != path:
        _cache = TuningCache(path)
        _cache_for_path = path
    return _cache


def _key(entry: str, *parts) -> str:
    """Canonical cache key: entry point + backend + ordered key parts.

    Code identities pass their ``CodeSpec`` (hashable AND serializable —
    the same object that keys ``repro.core.jitcache`` programs and archive
    manifests); everything else is scalars.
    """
    def _fmt(p):
        if dataclasses.is_dataclass(p) and not isinstance(p, type):
            d = dataclasses.asdict(p)
            return ",".join(f"{k}={d[k]}" for k in sorted(d))
        return str(p)
    return "|".join([entry, _backend()] + [_fmt(p) for p in parts])


def _lookup(key: str) -> dict | None:
    """Cache-only lookup honoring the mode (never probes, never writes)."""
    if mode() == "off":
        return None
    hit = cache().get(key)
    if hit is None:
        _stats["misses"] += 1
        return None
    _stats["hits"] += 1
    return hit


def _persist(key: str, entry: dict) -> None:
    c = cache()
    c.put(key, entry)
    c.save()


# ---------------------------------------------------------------------------
# probe harness + HLO cost cross-check
# ---------------------------------------------------------------------------


def _median_time(fn: Callable[[], object], iters: int = _PROBE_ITERS) -> float:
    """Median wall seconds of ``fn`` after one warm-up call (compile)."""
    import jax

    def run():
        out = fn()
        if out is not None:
            jax.block_until_ready(out)

    run()                                   # warm: compile + first dispatch
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _sweep(candidates: Sequence, probe: Callable[[object], object],
           iters: int = _PROBE_ITERS) -> tuple[object, dict]:
    """Time ``probe(candidate)`` for every candidate; return the fastest.

    One probe = one swept candidate list (``stats()['probes']`` counts
    sweeps, the unit the warm-cache zero-probe assertions gate on).
    Candidates whose probe raises are skipped; if every candidate fails the
    caller falls back to its heuristic.
    """
    _stats["probes"] += 1
    timings: dict = {}
    for cand in candidates:
        try:
            timings[cand] = _median_time(lambda: probe(cand), iters)
        except Exception:  # noqa: BLE001 — a candidate that can't run loses
            continue
    if not timings:
        return None, {}
    best = min(timings, key=timings.get)
    return best, {str(c): round(t, 6) for c, t in timings.items()}


def program_cost(jitted, *args) -> dict[str, float]:
    """FLOPs / bytes-accessed of a jitted callable from ``cost_analysis``.

    The same HLO properties ``repro.launch.cost_model`` composes per-step
    costs from (including the older-jaxlib list-form quirk). Returns zeros
    when the backend exposes no cost analysis.
    """
    try:
        ca = jitted.lower(*args).compile().cost_analysis() or {}
    except Exception:  # noqa: BLE001 — backends without AOT cost analysis
        return {"flops": 0.0, "bytes": 0.0}
    if isinstance(ca, (list, tuple)):   # older jaxlibs: one dict per program
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def predict_seconds(cost: dict[str, float], n_ticks: int,
                    topo: topo_lib.Topology) -> float:
    """Roofline-style runtime prediction from HLO properties.

    GF coding is pure mask/shift/xor streaming — memory-bound — so the
    byte term dominates: bytes at the calibrated ``compute_rate`` plus the
    calibrated per-tick overhead for each of the program's ``n_ticks``
    pipeline ticks. The scatter of this prediction against the measured
    probe is the cross-check ``fig_autotune`` reports.
    """
    rate = min(topo.compute_rate)
    return cost.get("bytes", 0.0) / rate + n_ticks * topo.tick_overhead


# ---------------------------------------------------------------------------
# kernel configs: tile widths + MXU/VPU dispatch
# ---------------------------------------------------------------------------


def block_candidates(Bp: int, preferred: int,
                     lo: int = 128, hi: int = 2048) -> tuple[int, ...]:
    """Power-of-two tile-width candidates for a padded-tile kernel.

    The encode wrappers pad ragged buffers to a whole number of tiles, so
    any width is legal; sweeping past the buffer only adds padding waste.
    """
    cover = 1
    while cover < max(Bp, 1):
        cover *= 2
    cands = {preferred}
    b = lo
    while b <= min(hi, cover):
        cands.add(b)
        b *= 2
    cands.add(min(cover, hi))
    return tuple(sorted(cands))


def kernel_block(entry: str, l: int, Bp: int, *, heuristic: int,
                 candidates: Sequence[int] = (),
                 probe: Callable[[int], object] | None = None) -> int:
    """Tuned tile width for a pad-and-slice kernel entry point.

    ``entry`` is the kernel name (``encode_packed`` / ``encode_mxu``), the
    key carries (backend, l, Bp). Cache hit wins; on a miss, ``search``
    mode with a concrete ``probe`` sweeps the candidates on the REAL jitted
    kernel and persists the fastest; otherwise the hand-tuned heuristic.
    """
    key = _key(entry, f"l={l}", f"Bp={Bp}")
    hit = _lookup(key)
    if hit is not None:
        blk = int(hit.get("value", 0))
        if blk > 0:
            return blk
    if mode() == "search" and probe is not None and candidates:
        best, timings = _sweep(candidates, probe)
        if best is not None:
            _persist(key, {"value": int(best), "heuristic": int(heuristic),
                           "timings_s": timings})
            return int(best)
    return heuristic


def tick_block(l: int, S: int, *, heuristic: int) -> int:
    """Tuned tile width for the per-tick pipeline kernels (cache-only).

    Consulted from INSIDE jit traces (``storage.chain._tick_kernel_args``),
    so it never probes — ``tune_tick_block`` (prewarm/CLI) fills the cache.
    A cached width that no longer divides ``S`` (stale geometry) falls back
    to the heuristic: the tick kernels cannot pad.
    """
    hit = _lookup(_key("tick_block", f"l={l}", f"S={S}"))
    if hit is not None:
        blk = int(hit.get("value", 0))
        if blk > 0 and S % blk == 0:
            return blk
    return heuristic


def _tick_divisor_candidates(S: int, preferred: int,
                             max_cands: int = 6) -> list[int]:
    divs = [d for d in range(1, min(S, preferred) + 1) if S % d == 0]
    divs = [d for d in divs if d >= 8 or d == S]
    cands = sorted(divs)[-max_cands:]
    if S <= 4 * preferred and S not in cands:
        cands.append(S)                    # whole-chunk tile: the old default
    return cands


def tune_tick_block(l: int, S: int, max_b: int = 2) -> int:
    """Probe ``chain_step`` over divisor tile widths of chunk length ``S``.

    Runs the real fused Pallas tick kernel on synthetic packed data for the
    largest few divisors of ``S`` (the only legal widths — tick kernels
    slice, never pad) and persists the fastest. Returns the tuned width.
    """
    import jax.numpy as jnp

    from repro.kernels.gf_encode import ops as kernel_ops

    heuristic = kernel_ops.pick_tick_block(S)
    key = _key("tick_block", f"l={l}", f"S={S}")
    hit = _lookup(key)
    if hit is not None and int(hit.get("value", 0)) > 0 \
            and S % int(hit["value"]) == 0:
        return int(hit["value"])
    if mode() != "search":
        return heuristic
    import functools

    import jax

    rng = np.random.default_rng(0)
    x_in = jnp.asarray(rng.integers(0, 1 << 32, size=(1, S), dtype=np.uint64)
                       .astype(np.uint32))
    local = jnp.asarray(rng.integers(0, 1 << 32, size=(max_b, S),
                                     dtype=np.uint64).astype(np.uint32))
    bp = jnp.asarray(rng.integers(0, 1 << l, size=(max_b, l),
                                  dtype=np.uint64).astype(np.uint32))
    # one jitted closure per candidate, built ONCE: the timed calls hit the
    # compiled program, not the eager pallas trace (which is block-blind)
    fns = {b: jax.jit(functools.partial(kernel_ops.chain_step, l=l, block=b))
           for b in _tick_divisor_candidates(S, kernel_ops.kernel.DEFAULT_BLOCK)}
    best, timings = _sweep(sorted(fns),
                           lambda b: fns[b](x_in, local, bp, bp))
    if best is None:
        return heuristic
    _persist(key, {"value": int(best), "heuristic": int(heuristic),
                   "timings_s": timings})
    return int(best)


def dispatch_for(l: int, rows: int, k: int, B: int, *,
                 probes: dict[str, Callable[[], object]] | None = None
                 ) -> str:
    """MXU-vs-VPU dispatch for a static-matrix encode of shape (rows,k)xB.

    Returns ``"vpu"`` (packed bit-plane kernel — the hand-tuned default) or
    ``"mxu"`` (bit-lifted int8 matmul). On a ``search`` miss with concrete
    inputs, times BOTH real kernels and persists the winner per
    (backend, l, rows, k, B).
    """
    key = _key("dispatch", f"l={l}", f"rows={rows}", f"k={k}", f"B={B}")
    hit = _lookup(key)
    if hit is not None and hit.get("value") in ("vpu", "mxu"):
        return hit["value"]
    if mode() == "search" and probes:
        best, timings = _sweep(sorted(probes), lambda d: probes[d]())
        if best is not None:
            _persist(key, {"value": str(best), "heuristic": "vpu",
                           "timings_s": timings})
            return str(best)
    return "vpu"


# ---------------------------------------------------------------------------
# pipeline plan parameters: num_chunks + stagger
# ---------------------------------------------------------------------------


def calibrated_topology(n: int, l: int = 16,
                        fallback: bool = True) -> topo_lib.Topology | None:
    """Uniform n-node topology with MEASURED compute_rate/tick_overhead.

    Reads the persisted chain calibration (``calibrate_chain``); without
    one, returns the hand-tuned ``Topology.uniform`` defaults when
    ``fallback`` else None. The scheduler consults this for ``topo=None``
    plans, and ``num_chunks_for`` uses it to pick chunk counts by model
    when probing is off or impossible.
    """
    hit = _lookup(_key("chain_calib", f"l={l}"))
    if hit is not None and "compute_rate" in hit and "tick_overhead" in hit:
        return topo_lib.Topology.uniform(
            n, compute_rate=float(hit["compute_rate"]),
            nic_bw=topo_lib.CALIBRATION_NIC_BW, hop_latency=0.0,
            tick_overhead=float(hit["tick_overhead"]),
            tick_quad=float(hit.get("tick_quad", 0.0)))
    return topo_lib.Topology.uniform(n) if fallback else None


def calibrate_chain(code, nwords: int = 1 << 15,
                    chunk_counts: Sequence[int] = (1, 2, 4, 8, 16),
                    iters: int = _PROBE_ITERS) -> dict:
    """Measure a real chunk sweep and fit the makespan-model constants.

    Times ``storage.chain.pipelined_encode`` (warm) at each chunk count on
    a synthetic (k, nwords) object, least-squares-fits
    ``topology.fit_chain_constants``, cross-checks every sample against the
    fitted model AND an HLO-derived prediction (``program_cost`` of the
    compiled chain program), and persists the calibration per
    (backend, l). Needs ``code.n`` local devices; raises otherwise (the
    CLI forces host devices).
    """
    import jax

    from repro.storage import chain as chain_lib

    lanes = gf.LANES[code.l]
    chunk_counts = sorted({int(c) for c in chunk_counts
                           if c >= 1 and nwords % (lanes * c) == 0})
    if len(chunk_counts) < 2:
        raise ValueError(
            f"calibrate_chain: nwords={nwords} admits chunk counts "
            f"{chunk_counts}; need >= 2 (whole uint32 lanes per chunk)")
    rng = np.random.default_rng(0)
    data = rng.integers(0, 1 << code.l,
                        size=(code.k, nwords)).astype(gf.WORD_DTYPE[code.l])
    block_bytes = data[0].nbytes
    _stats["probes"] += 1
    def run(c: int) -> None:
        jax.block_until_ready(
            chain_lib.pipelined_encode(code, data, num_chunks=c))

    # compile (and tick-probe) every chunk count first, then time them in
    # interleaved rounds: a slow stretch of the process (start-up, a busy
    # neighbour) then costs each count one round, which the median drops
    for c in chunk_counts:
        run(c)
    times: dict[int, list[float]] = {c: [] for c in chunk_counts}
    for _ in range(iters):
        for c in chunk_counts:
            t0 = time.perf_counter()
            run(c)
            times[c].append(time.perf_counter() - t0)
    samples = [(c, sorted(ts)[len(ts) // 2]) for c, ts in times.items()]
    hlo = {str(c): program_cost(chain_lib.encode_program(code, nwords, c),
                                data) for c in chunk_counts}
    topo, pred = topo_lib.fit_chain_constants(samples, code.n, code.k,
                                              block_bytes)
    rel_err = [abs(p - t) / t for (_, t), p in zip(samples, pred)]
    entry = {
        "compute_rate": topo.compute_rate[0],
        "tick_overhead": topo.tick_overhead,
        "tick_quad": topo.tick_quad,
        "n": code.n, "k": code.k, "block_bytes": block_bytes,
        "samples": [{"num_chunks": c, "measured_s": round(t, 6),
                     "model_s": round(float(p), 6),
                     "hlo_bytes": hlo[str(c)]["bytes"],
                     "hlo_pred_s": round(predict_seconds(
                         hlo[str(c)], c + code.n - 1, topo), 6)}
                    for (c, t), p in zip(samples, pred)],
        "max_rel_err": round(float(max(rel_err)), 4),
    }
    if mode() != "off":
        _persist(_key("chain_calib", f"l={code.l}"), entry)
    return entry


def chunk_candidates_for(l: int, total_words: int,
                         valid: Callable[[int], bool] | None = None
                         ) -> list[int]:
    """The chunk counts a geometry admits, smallest first."""
    lanes = gf.LANES[l]
    if valid is None:
        def valid(c):
            return total_words % (lanes * c) == 0
    return [c for c in CHUNK_CANDIDATES
            if c * lanes <= total_words and valid(c)]


def num_chunks_for(entry: str, code, total_words: int, *,
                   default: int = DEFAULT_NUM_CHUNKS,
                   chain_len: int | None = None,
                   valid: Callable[[int], bool] | None = None,
                   probe: Callable[[int], object] | None = None,
                   extra_key: tuple = ()) -> int:
    """Tuned pipeline chunk count for one entry point + geometry.

    Resolution order: ``off`` → hand-tuned default; cache hit (validated
    against the geometry) → tuned value; ``search`` + a concrete ``probe``
    → timed sweep of the real entry point over the admissible candidates,
    persisted; otherwise → the calibrated makespan model's best candidate
    when a chain calibration exists, else the default. Probes always pass
    explicit chunk counts, so they never recurse into this resolver.
    """
    if mode() == "off":
        return default
    n = code.n if chain_len is None else chain_len
    key = _key(entry, code.spec, f"B={total_words}", f"chain={n}",
               *[f"x{i}={v}" for i, v in enumerate(extra_key)], "num_chunks")
    cands = chunk_candidates_for(code.l, total_words, valid)
    hit = _lookup(key)
    if hit is not None:
        c = int(hit.get("value", 0))
        if c in cands or (valid is not None and c >= 1 and valid(c)):
            return c
    if not cands:
        return default
    if mode() == "search" and probe is not None:
        best, timings = _sweep(cands, probe)
        if best is not None:
            _persist(key, {"value": int(best), "heuristic": default,
                           "timings_s": timings})
            return int(best)
    # model fallback: only when a MEASURED calibration exists — the
    # hand-tuned Topology defaults (tick_overhead=0) would always pick the
    # finest candidate, a silent behavior change the default must not make
    topo = calibrated_topology(n, l=code.l, fallback=False)
    if topo is not None:
        block_bytes = total_words * (code.l // 8)
        best = min(cands, key=lambda c: topo_lib.chain_makespan(
            topo, range(n), min(code.k, n), block_bytes, c))
        if mode() == "search":
            _persist(key, {"value": int(best), "heuristic": default,
                           "from": "model"})
        return int(best)
    return default


def stagger_for(code, b_obj: int, num_chunks: int, *, default: int = 1,
                probe: Callable[[int], object] | None = None) -> int:
    """Tuned stagger for the staggered multi-object pipeline.

    ``stagger=1`` (maximal overlap) is the hand-tuned default;
    ``stagger=num_chunks`` degenerates to back-to-back chains — the right
    choice when per-tick compute, not the wire, is the bottleneck (exactly
    the CPU-interpret case), so the probe sweeps between the two.
    """
    if mode() == "off":
        return default
    key = _key("stagger", code.spec, f"b={b_obj}", f"nc={num_chunks}")
    cands = sorted({1, max(1, num_chunks // 2), num_chunks})
    hit = _lookup(key)
    if hit is not None:
        s = int(hit.get("value", 0))
        if 1 <= s <= num_chunks:
            return s
    if mode() == "search" and probe is not None and b_obj > 1:
        best, timings = _sweep(cands, probe)
        if best is not None:
            _persist(key, {"value": int(best), "heuristic": default,
                           "timings_s": timings})
            return int(best)
    return default


# ---------------------------------------------------------------------------
# prewarm: fill every cache family for one geometry (the CLI entry)
# ---------------------------------------------------------------------------


def prewarm(code, nwords: int = 1 << 14, b_obj: int = 4,
            chunk_counts: Sequence[int] = (1, 2, 4, 8, 16)) -> dict:
    """Populate the tuning cache for one code geometry (search mode only).

    Runs, in order: the chain calibration sweep (fits
    compute_rate/tick_overhead), kernel tile-width sweeps (VPU + MXU),
    MXU-vs-VPU dispatch, per-tick tile widths for every admissible chunk
    count, and the plan parameters (num_chunks for encode / encode_many,
    stagger). Returns a report of every tuned value. Requires
    ``RAPIDRAID_TUNE=search`` and ``code.n`` local devices for the chain
    probes (kernel probes run on any device count).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.gf_encode import ops as kernel_ops

    if mode() != "search":
        raise ValueError(
            f"prewarm needs {TUNE_ENV}=search, got {TUNE_ENV}={mode()!r}")
    l = code.l
    lanes = gf.LANES[l]
    rng = np.random.default_rng(0)
    data = rng.integers(0, 1 << l, size=(code.k, nwords)) \
        .astype(gf.WORD_DTYPE[l])
    report: dict = {"backend": _backend(), "cache": cache_path(),
                    "spec": dataclasses.asdict(code.spec),
                    "nwords": nwords}

    # kernel tile widths + dispatch (device-count independent)
    dj = jnp.asarray(data)
    Bp = nwords // lanes
    report["encode_packed_block"] = kernel_ops.encode_block_for(code.G, dj, l)
    report["encode_mxu_block"] = kernel_ops.mxu_block_for(code.G, dj, l)
    report["dispatch"] = kernel_ops.dispatch_for_data(code.G, dj, l)
    report["tick_blocks"] = {
        c: tune_tick_block(l, Bp // c)
        for c in chunk_candidates_for(l, nwords) if (Bp % c) == 0}

    # chain calibration + plan parameters (need code.n devices)
    if len(jax.devices()) >= code.n:
        from repro.storage import chain as chain_lib
        from repro.storage import multi as multi_lib
        report["calibration"] = calibrate_chain(code, nwords, chunk_counts)
        report["num_chunks_encode"] = num_chunks_for(
            "encode", code, nwords,
            probe=lambda c: chain_lib.pipelined_encode(code, data,
                                                       num_chunks=c))
        objs = rng.integers(0, 1 << l, size=(b_obj, code.k, nwords)) \
            .astype(gf.WORD_DTYPE[l])
        nc_many = num_chunks_for(
            "encode_many", code, nwords, extra_key=(b_obj,),
            probe=lambda c: multi_lib.pipelined_encode_many(
                code, objs, num_chunks=c))
        report["num_chunks_encode_many"] = nc_many
        report["stagger"] = stagger_for(
            code, b_obj, nc_many,
            probe=lambda s: multi_lib.pipelined_encode_many(
                code, objs, num_chunks=nc_many, stagger=s))
    else:
        report["calibration"] = None
        report["skipped"] = (f"chain probes need {code.n} devices, have "
                             f"{len(jax.devices())}")
    report["stats"] = stats()
    return report
