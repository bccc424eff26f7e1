"""Shared compiled-program cache for the warm archival fast path.

Every distributed entry point in ``repro.storage`` (pipelined encode /
decode / repair, their staggered multi-object variants, and the classical
baseline) runs as one jitted ``shard_map`` program. Before this cache each
call rebuilt ``jax.jit(compat.shard_map(closure))`` from a FRESH closure, so
jax's own jit cache — which keys on function identity — missed every time
and the whole program was retraced and recompiled per invocation. Archival
is a high-volume background workload (XORing Elephants, PAPERS.md): the
per-object constant tax dominates fleet cost long before the modeled
pipeline wins show up.

The fix is structural, not a bigger jit cache: builders construct the jitted
program ONCE per logical key

    (entry point, code, mesh, shapes, num_chunks, direction, ...)

and this module memoizes the resulting callable. Since the streaming
super-chunk refactor (``repro.core.streaming``) the shape element of every
chain key is the SUPER-CHUNK width (``plan.sc_words``), not the object
length: a non-streaming call's plan has ``sc_words == total_words`` so its
key is unchanged, while an object split into S stripes maps every stripe
onto one key — S super-chunks compile exactly one program, and the
trace-count tests assert that too. Because the SAME callable
object is returned on every warm call, jax's jit cache then guarantees no
retrace for identical input shapes — ``compile_counts`` exposes the per-key
trace counts so tests can assert exactly that.

The cache is unbounded by design: an archival fleet runs a handful of code
geometries and bucketed block lengths (``storage.archive`` already groups
batches by ``block_bytes``), so the key population is small and every entry
is a warm path worth keeping. Callers feeding genuinely unbounded shape
diversity should bucket/pad shapes upstream — one program per bucket — or
call ``clear()`` at their own epoch boundaries.
"""
from __future__ import annotations

import os
from typing import Any, Callable

#: JAX reads this variable itself; where it is set the cache stays there
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the fixed fallback inside the checkout: the cache directory is part of
#: each entry's key, so it must not move from run to run
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_programs: dict[Any, Callable] = {}
_stats = {"hits": 0, "misses": 0}


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; -> its directory.

    Compiled programs then survive the process, so a second run of an
    entry point skips the compiles the first one paid for. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and nothing
    is set here; otherwise the cache goes to ``CHECKOUT_CACHE_DIR``.
    Called by the entry points (``chip_smoke.py``, ``python -m repro.*``),
    never on import.
    """
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def get(key: Any, builder: Callable[[], Callable]) -> Callable:
    """Return the compiled program for ``key``, building it on first use.

    ``key`` must be hashable and must capture everything the built program
    closes over statically (code, mesh, static shapes, chunk count,
    direction); ``builder`` is invoked only on a miss.
    """
    try:
        fn = _programs[key]
    except KeyError:
        _stats["misses"] += 1
        fn = _programs[key] = builder()
        return fn
    _stats["hits"] += 1
    return fn


def stats() -> dict[str, int]:
    """Cache hit/miss/size counters (process-wide)."""
    return {**_stats, "size": len(_programs)}


def compile_counts() -> dict[str, int]:
    """Per-program jit-cache sizes: {key: number of traced signatures}.

    A warm entry point called twice with identical shapes must show 1 here —
    the trace-count regression tests assert it. Programs without jax's
    ``_cache_size`` introspection (plain callables) report -1.
    """
    out = {}
    for key, fn in _programs.items():
        size = getattr(fn, "_cache_size", None)
        out[repr(key)] = int(size()) if callable(size) else -1
    return out


def programs(entry: str) -> list[Callable]:
    """The cached programs of one entry point (``key[0] == entry``), in
    build order — for inspecting what a call compiled (``.lower(...)``)."""
    return [fn for key, fn in _programs.items()
            if isinstance(key, tuple) and key and key[0] == entry]


def entry_counts(entry: str) -> dict[str, int]:
    """``compile_counts`` filtered to one entry point (``key[0] == entry``).

    The checkpoint trace-count tests assert e.g. every ``"ckpt_save"``
    program traced exactly once across repeated same-shaped saves, without
    caring what other entry points the process compiled.
    """
    out = {}
    for key, fn in _programs.items():
        if isinstance(key, tuple) and key and key[0] == entry:
            size = getattr(fn, "_cache_size", None)
            out[repr(key)] = int(size()) if callable(size) else -1
    return out


def clear() -> None:
    """Drop every cached program and reset the counters (tests only)."""
    _programs.clear()
    _stats["hits"] = 0
    _stats["misses"] = 0
