"""Streaming super-chunk executor: archive objects larger than device memory.

The paper's pipelined coding assumes the whole object rides the encoding
chain at once; every distributed entry point inherited that assumption —
``pipelined_encode`` / ``archive_step`` / ``save_state`` all materialized
the full object on-device, so a 10 GB object could not archive through a
100 MB device footprint even though the pipeline is chunk-granular by
construction. This module removes the assumption at one place:

* an object's blocks are split along the word axis into fixed-size
  **super-chunks** — each an INDEPENDENT stripe run through the existing
  ``software_pipeline`` / ``staggered_pipeline`` schedule (Repair
  Pipelining, Li et al., PAPERS.md, is the cross-stripe scheduling model:
  stripes are coded independently, so the chain stays at line rate as long
  as the next stripe is always in flight);
* ``execute`` drives the stripes through a DOUBLE-BUFFERED loop: stripe
  s+1's host->device transfer and stripe s-1's store I/O (shard ``put``
  frames / digests) overlap stripe s's compiled pipeline ticks, riding
  jax's async dispatch — the host thread never blocks on a result until
  ``depth`` stripes are in flight behind it;
* every stripe reuses ONE cached program (``repro.core.jitcache`` keys
  carry the super-chunk width, not the object length), so S super-chunks
  compile exactly once and peak live device bytes are bounded by the
  stripe footprint, not the object.

Positionwise codes (RapidRAID, LRC) apply their generator per word, so the
stripe-wise codeword concatenation is BIT-IDENTICAL to the monolithic
encode — streaming with one super-chunk IS today's behavior, and streaming
with S super-chunks stores exactly the same bytes. Sub-packetized families
(MBR) mix words across the block; their stripes are independently coded
units with their own manifests entries, decodable stripe-by-stripe.

``storage.chain`` / ``storage.multi`` / ``storage.repair`` re-express their
monolithic entry points as thin wrappers over this executor;
``storage.archive`` adds the stripe-aware manifests and store framing.
"""
from __future__ import annotations

import collections
import dataclasses
import os
from typing import Callable

import numpy as np

from repro.core import gf

#: env knob used by CI to force a small per-device streaming budget; the
#: tier-1 streaming leg runs the whole test module under a few MB.
BUDGET_ENV = "RAPIDRAID_STREAM_BUDGET_BYTES"


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """How one object's word axis splits into equal-width super-chunks.

    All stripes share ``sc_words`` (the compiled program's static shape);
    the last stripe holds only ``tail_words`` valid words and is
    zero-padded up to ``sc_words`` on the way in, trimmed on the way out.
    """

    total_words: int          # words per block across the whole object
    sc_words: int             # words per block per super-chunk (stripe)
    num_superchunks: int
    tail_words: int           # valid words in the final stripe

    @property
    def streaming(self) -> bool:
        """False when the plan is the degenerate single-stripe identity."""
        return self.num_superchunks > 1 or self.tail_words != self.sc_words

    def stripe_words(self, s: int) -> int:
        """Valid (un-padded) words of stripe ``s``."""
        return (self.tail_words if s == self.num_superchunks - 1
                else self.sc_words)

    def stripe_span(self, s: int) -> tuple[int, int]:
        """[start, stop) valid word range of stripe ``s`` in the object."""
        start = s * self.sc_words
        return start, start + self.stripe_words(s)


def plan_stream(total_words: int, superchunk_words: int | None, *,
                l: int, num_chunks: int) -> StreamPlan:
    """Split ``total_words`` into stripes of at most ``superchunk_words``.

    The stripe width is rounded DOWN to whole pipeline granules
    (``LANES[l] * num_chunks`` words — every stripe must split into
    ``num_chunks`` chunks of whole uint32 lanes, exactly the monolithic
    entry points' precondition) and never below one granule.
    ``superchunk_words=None`` (or >= the object) is the single-stripe
    identity plan: no padding, no trimming, today's behavior bit-exactly.
    """
    if total_words < 1:
        raise ValueError(f"plan_stream: need at least 1 word, got {total_words}")
    granule = gf.LANES[l] * num_chunks
    if superchunk_words is None or superchunk_words >= total_words:
        return StreamPlan(total_words, total_words, 1, total_words)
    if superchunk_words < 1:
        raise ValueError(
            f"plan_stream: superchunk_words must be >= 1, got "
            f"{superchunk_words}")
    sc = max(granule, (superchunk_words // granule) * granule)
    sc = min(sc, total_words)
    num = -(-total_words // sc)
    tail = total_words - (num - 1) * sc
    return StreamPlan(total_words, sc, num, tail)


def estimate_stripe_bytes(code, sc_words: int, *, rows_in: int | None = None,
                          rows_out: int | None = None) -> int:
    """Modeled peak live device bytes for one stripe of the chain encode.

    Counts every materialized per-stripe buffer of the compiled program:
    the (rows_in, W) input words, the placed-and-packed
    (n, max_blocks, W) uint32 local view, the packed wire/output, and the
    unpacked (rows_out, W) result — times 2 for the double buffer (two
    stripes in flight). A deliberate over-count: the streaming budget is a
    guarantee, so the model errs high and ``measure_footprint`` verifies
    the real number in tests/benchmarks.
    """
    wb = code.l // 8
    rows_in = code.k if rows_in is None else rows_in
    rows_out = code.n if rows_out is None else rows_out
    max_b = max((len(b) for b in getattr(code, "place", [(0,)])), default=1)
    packed = 4 * (sc_words // gf.LANES[code.l] + 1)
    per_stripe = (rows_in * sc_words * wb            # input words
                  + code.n * max_b * packed          # placed + packed local
                  + code.n * packed                  # packed codeword
                  + rows_out * sc_words * wb)        # unpacked output
    return 2 * per_stripe


def superchunk_words_for(footprint_bytes: int, code, num_chunks: int) -> int:
    """Largest stripe width whose modeled device footprint fits the budget.

    Inverts ``estimate_stripe_bytes`` and floors to one pipeline granule —
    callers that need a hard guarantee assert the compiled program's
    ``measure_footprint`` against the budget (the streaming tests do).
    """
    granule = gf.LANES[code.l] * num_chunks
    lo, hi = granule, granule
    while estimate_stripe_bytes(code, hi * 2) <= footprint_bytes:
        hi *= 2
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if estimate_stripe_bytes(code, mid) <= footprint_bytes:
            lo = mid
        else:
            hi = mid - 1
    return max(granule, (lo // granule) * granule)


def budget_from_env(default: int | None = None) -> int | None:
    """CI's forced streaming budget (``RAPIDRAID_STREAM_BUDGET_BYTES``)."""
    raw = os.environ.get(BUDGET_ENV)
    return int(raw) if raw else default


# ---------------------------------------------------------------------------
# the double-buffered executor
# ---------------------------------------------------------------------------


def execute(plan: StreamPlan, program: Callable,
            get_stripe: Callable[[int], np.ndarray],
            put_stripe: Callable[[int, np.ndarray], None],
            *, depth: int = 1) -> None:
    """Drive every stripe of ``plan`` through ``program``, double-buffered.

    ``get_stripe(s)`` produces stripe s's host input (already padded to the
    plan's static width); ``program`` is the ONE cached executable shared by
    all stripes; ``put_stripe(s, out)`` consumes the materialized result
    (store I/O, digests, assembly). With ``depth`` >= 1 the loop keeps that
    many dispatched-but-unread results in flight, so while stripe s's ticks
    run on the devices the host is simultaneously reading stripe s+1's
    input (get) and writing stripe s-1's output (put) — the host never
    blocks on a device result until the window is full. Results are
    retired strictly in stripe order.
    """
    if depth < 1:
        raise ValueError(f"execute: depth must be >= 1, got {depth}")
    import jax
    pending: collections.deque = collections.deque()
    for s in range(plan.num_superchunks):
        x = get_stripe(s)
        try:  # async h2d so the transfer overlaps the in-flight compute
            x = jax.device_put(x)
        except (TypeError, ValueError):  # non-array inputs: let program cope
            pass
        pending.append((s, program(x)))   # async dispatch
        while len(pending) > depth:
            s0, y0 = pending.popleft()
            put_stripe(s0, np.asarray(y0))
    while pending:
        s0, y0 = pending.popleft()
        put_stripe(s0, np.asarray(y0))


def run_words(program: Callable, data: np.ndarray, plan: StreamPlan, *,
              sink: Callable[[int, np.ndarray], None] | None = None,
              depth: int = 1):
    """Stream an in-memory word array through ``program`` stripe by stripe.

    ``data`` (..., total_words) is sliced along its last axis; ``program``
    must preserve that axis width ((..., sc_words) -> (rows, ..., sc_words)).
    With the identity plan this is exactly ``program(data)`` — same program
    object, same output, bit-identical to the pre-streaming entry points
    (callers keep receiving a ``jax.Array``). Otherwise the stripes run
    through ``execute`` and the trimmed results are either assembled into
    one (..., total_words) host array (returned) or handed to ``sink``
    per stripe (returns None) — the bounded-memory path, where no
    full-object output buffer ever exists.
    """
    if not plan.streaming:
        out = program(data)
        if sink is None:
            return out
        sink(0, np.asarray(out))
        return None

    pad = plan.num_superchunks * plan.sc_words - plan.total_words
    out_full: np.ndarray | None = None

    def get_stripe(s: int) -> np.ndarray:
        lo = s * plan.sc_words
        stripe = data[..., lo:lo + plan.sc_words]
        if s == plan.num_superchunks - 1 and pad:
            stripe = np.concatenate(
                [stripe, np.zeros(stripe.shape[:-1] + (pad,),
                                  dtype=data.dtype)], axis=-1)
        return np.ascontiguousarray(stripe)

    def put_stripe(s: int, out: np.ndarray) -> None:
        nonlocal out_full
        out = out[..., :plan.stripe_words(s)]
        if sink is not None:
            sink(s, out)
            return
        if out_full is None:
            out_full = np.zeros(out.shape[:-1] + (plan.total_words,),
                                dtype=out.dtype)
        lo, hi = plan.stripe_span(s)
        out_full[..., lo:hi] = out

    execute(plan, program, get_stripe, put_stripe, depth=depth)
    return out_full


def measure_footprint(fn: Callable, *sample_args) -> int:
    """Peak live device bytes of ``fn`` compiled for ``sample_args``.

    AOT-compiles the jitted callable and reads its memory analysis — the
    number the streaming acceptance tests bound against the footprint
    budget.
    """
    compiled = fn.lower(*sample_args).compile()
    return int(compiled.memory_analysis().peak_memory_in_bytes)
