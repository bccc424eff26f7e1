"""Generic chunked chain-pipeline scheduler (paper §III, Fig. 2).

The paper's insight: a chain of n nodes streaming a block at network-buffer
granularity costs ``T = tau_block + (n-1) * tau_buf`` instead of the classical
``tau_block * max(k, m-1)``. The same software-pipeline schedule shows up in
GPipe-style pipeline parallelism; this module is the shared scheduler used by

  * ``repro.storage.chain``   — RapidRAID pipelined archival over devices
  * ``repro.train.pipeline``  — optional pipeline-parallel stage axis

Semantics (SPMD over a 1-D ``axis_name`` of size n):
  tick t in [0, S + n - 1):  stage i processes chunk ch = t - i when valid,
  receives its predecessor's wire from the previous tick (stage 0 receives
  zeros), and forwards a wire to stage i+1 via ``lax.ppermute``.

The schedule is DIRECTION-AGNOSTIC: with ``reverse=True`` device idx plays
chain *position* n-1-idx and the wire flows toward device 0 — the repair
path (``repro.storage.repair``), where the replacement node sits at the
receiving end of the helper chain, is the encode pipeline run backwards.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def num_ticks(num_chunks: int, n_stages: int) -> int:
    return num_chunks + n_stages - 1


def chain_perm(n: int, reverse: bool = False) -> list[tuple[int, int]]:
    """Source→dest pairs for a non-wrapping chain.

    Forward: device i -> i+1 (encode; the last device finishes the stream).
    Reverse: device i+1 -> i (repair; device 0 finishes the stream).
    """
    if reverse:
        return [(i + 1, i) for i in range(n - 1)]
    return [(i, i + 1) for i in range(n - 1)]


def chain_pos(idx, n: int, reverse: bool = False):
    """Chain position played by device ``idx`` (traced or static)."""
    return (n - 1 - idx) if reverse else idx


def software_pipeline(
    step_fn: Callable,
    wire_init: jax.Array,
    out_init,
    num_chunks: int,
    axis_name: str,
    *,
    reverse: bool = False,
):
    """Run the chain pipeline inside a ``shard_map``-ed function.

    ``step_fn(wire_in, out, ch, active) -> (wire_out, out)`` computes one
    chunk: consumes the predecessor's wire (zeros at the head position and at
    inactive ticks' boundary), updates the output accumulator, and produces
    the wire to forward. ``out`` may be any pytree.

    ``reverse=False``: device idx is chain position idx, wire flows toward
    device n-1.  ``reverse=True``: device idx is position n-1-idx, wire flows
    toward device 0 (the repair direction).  Tick accounting is identical in
    both directions: ``num_chunks + n - 1`` ticks.

    Returns the final ``out``.
    """
    n = lax.axis_size(axis_name)
    pos = chain_pos(lax.axis_index(axis_name), n, reverse)
    perm = chain_perm(n, reverse)

    def tick(carry, t):
        wire, out = carry
        ch = t - pos
        active = (ch >= 0) & (ch < num_chunks)
        ch_safe = jnp.clip(ch, 0, num_chunks - 1)
        wire_in = jnp.where(pos == 0, jnp.zeros_like(wire), wire)
        wire_out, out = step_fn(wire_in, out, ch_safe, active)
        wire_next = lax.ppermute(wire_out, axis_name, perm)
        return (wire_next, out), None

    (_, out), _ = lax.scan(tick, (wire_init, out_init),
                           jnp.arange(num_ticks(num_chunks, n)))
    return out


# ---------------------------------------------------------------------------
# Staggered multi-chain pipeline (multi-object archival, paper §VI / Fig. 4)
# ---------------------------------------------------------------------------


def window_size(num_chunks: int, num_objects: int, stagger: int) -> int:
    """Max objects simultaneously active on one stage.

    Object b's chunk ch is processed by stage i at tick t = i + b*stagger + ch,
    so the active objects at (i, t) satisfy 0 <= t - i - b*stagger < num_chunks
    — at most (num_chunks-1)//stagger + 1 values of b.
    """
    return min(num_objects, (num_chunks - 1) // stagger + 1)


def num_ticks_many(num_chunks: int, n_stages: int, num_objects: int,
                   stagger: int) -> int:
    return num_chunks + n_stages - 1 + (num_objects - 1) * stagger


def staggered_pipeline(
    step_fn: Callable,
    wire_init: jax.Array,
    out_init: jax.Array,
    num_chunks: int,
    axis_name: str,
    *,
    num_objects: int,
    stagger: int = 1,
    reverse: bool = False,
):
    """Interleave ``num_objects`` chain pipelines over one stage axis.

    Object b runs the ordinary chunk pipeline shifted by ``b * stagger``
    ticks, so stage i streams object b's chunks while object b+1's are still
    in flight — ONE SPMD program instead of ``num_objects`` sequential
    launches. Total ticks: ``num_chunks + n - 1 + (num_objects-1)*stagger``
    versus ``num_objects * (num_chunks + n - 1)`` for the sequential loop.

    Per-tick work stays constant: at most ``W = window_size(...)`` objects
    are active on a stage at once, and the wire carries only that W-slot
    sliding window. The windows align across the chain — stage i+1's window
    start at tick t+1 equals stage i's at tick t — so a forwarded window
    lands exactly where the receiver expects it. Slots holding inactive
    objects carry don't-care values; correctness needs a slot only while its
    object is active, and then it holds exactly the single-chain wire.

    ``step_fn(wire_b, out_b, b, ch, active) -> (wire_out_b, out_b)`` computes
    one object's chunk: ``wire_b``/``out_b`` are one object's wire slot and
    output accumulator, ``b`` the (traced) object index for slicing
    per-object operands from closed-over arrays. It is vmapped over the
    window. ``wire_init`` is ONE object's wire (tiled to the window);
    ``out_init`` has a leading ``num_objects`` axis.

    ``stagger=1`` minimizes total latency (the paper's concurrent-archival
    win); ``stagger=num_chunks`` degenerates to W=1 — back-to-back chaining
    with single-object per-tick work.

    ``reverse=True`` runs every chain in the repair direction (device idx
    plays position n-1-idx, wire flows toward device 0); the stagger/window
    algebra is position-based, so it is untouched by the direction.
    """
    assert stagger >= 1 and num_objects >= 1
    n = lax.axis_size(axis_name)
    pos = chain_pos(lax.axis_index(axis_name), n, reverse)
    perm = chain_perm(n, reverse)
    W = window_size(num_chunks, num_objects, stagger)
    total = num_ticks_many(num_chunks, n, num_objects, stagger)

    def tick(carry, t):
        wire, out = carry                      # wire (W, ...); out (B, ...)
        # first object that can still be active: ceil((t-p-(nc-1))/stagger)
        w0 = jnp.clip(-(-(t - pos - (num_chunks - 1)) // stagger),
                      0, num_objects - W)
        out_win = lax.dynamic_slice_in_dim(out, w0, W, axis=0)
        bs = w0 + jnp.arange(W)
        ch = t - pos - bs * stagger
        active = (ch >= 0) & (ch < num_chunks)
        ch_safe = jnp.clip(ch, 0, num_chunks - 1)
        wire_in = jnp.where(pos == 0, jnp.zeros_like(wire), wire)
        wire_out, out_win = jax.vmap(step_fn)(wire_in, out_win, bs, ch_safe,
                                              active)
        out = lax.dynamic_update_slice_in_dim(out, out_win, w0, axis=0)
        wire_next = lax.ppermute(wire_out, axis_name, perm)
        return (wire_next, out), None

    wire0 = jnp.broadcast_to(wire_init[None], (W,) + wire_init.shape)
    (_, out), _ = lax.scan(tick, (wire0, out_init), jnp.arange(total))
    return out
