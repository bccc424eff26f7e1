"""Jit'd public wrappers around the GF encode kernels.

``interpret=None`` resolves through ``_interpret_default``, the one place
that decides it: the Pallas interpreter off-TPU (the kernel bodies then
execute exactly as written, validating logic + tiling) and the compiled
kernel on a TPU, never the interpreter there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune, gf
from repro.kernels.gf_encode import kernel


def _interpret_default() -> bool:
    """The Pallas interpreter off-TPU; the compiled kernel on a TPU."""
    return jax.default_backend() != "tpu"


def pick_block(Bp: int, preferred: int = kernel.DEFAULT_BLOCK) -> int:
    """Pallas tile width for a packed length of ``Bp`` uint32 lanes.

    Returns ``preferred`` for long buffers, or the smallest power of two
    covering ``Bp`` for short ones. The tile no longer has to divide ``Bp``:
    the encode wrappers pad ragged buffers to a whole number of tiles and
    slice the result, so an odd/ragged length never degenerates to
    ``block=1`` (a per-word pallas grid) the way the old
    largest-dividing-power-of-two rule did.
    """
    if Bp >= preferred:
        return preferred
    b = 1
    while b < Bp:
        b *= 2
    return b


def pick_tick_block(S: int, preferred: int = kernel.DEFAULT_BLOCK) -> int:
    """Tile width for the per-tick pipeline kernels (chunk length ``S``).

    The tick kernels (``chain_step``/``repair_step``) run inside a scanned
    pipeline, so padding per tick is off the table: the tile must DIVIDE
    the chunk. Long aligned chunks tile at ``preferred``; a ragged chunk
    gets the largest divisor of ``S`` that still fits ``preferred`` (e.g.
    ``S=1536`` tiles at 384, where the old rule ran one whole-chunk tile
    blowing the VMEM working set). Only when no useful divisor exists —
    ``S`` prime, or every fitting divisor under 8 lanes (a near-per-word
    pallas grid, e.g. ``S=2*997`` whose only fitting divisor is 2) — does
    it fall back to the single whole-chunk tile.
    """
    if S % preferred == 0:
        return preferred
    if S <= preferred:
        return S
    best = 1
    d = 1
    while d * d <= S:
        if S % d == 0:
            if d <= preferred:
                best = max(best, d)
            if S // d <= preferred:
                best = max(best, S // d)
        d += 1
    return best if best >= 8 else S


def _pad_tail(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    """Zero-pad the last axis up to a tile multiple (GF-safe: 0 encodes to 0)."""
    pad = -x.shape[-1] % multiple
    if pad:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, widths)
    return x, pad


@functools.partial(jax.jit, static_argnames=("M_key", "l", "block", "interpret"))
def _encode_packed_jit(data_packed, M_key, l, block, interpret):
    M = np.asarray(M_key)
    Bp = data_packed.shape[-1]
    data_packed, pad = _pad_tail(data_packed, block)
    out = kernel.gf_encode_kernel(M, data_packed, l, block=block,
                                  interpret=interpret)
    return out[..., :Bp] if pad else out


def _tuned_encode_block(M_key, dp, l, interpret) -> int:
    """Tile width for ``encode_packed`` when none was requested: the tuning
    cache (probing the real jitted kernel on a search-mode miss with
    concrete data), falling back to the ``pick_block`` heuristic."""
    Bp = dp.shape[-1]
    probe = None
    if autotune.is_concrete(dp):
        def probe(b):
            return _encode_packed_jit(dp, M_key, l, pick_block(Bp, b),
                                      interpret)
    blk = autotune.kernel_block(
        "encode_packed", l, Bp, heuristic=pick_block(Bp),
        candidates=autotune.block_candidates(Bp, kernel.DEFAULT_BLOCK),
        probe=probe)
    return pick_block(Bp, blk)


def encode_packed(M: np.ndarray, data_packed: jax.Array, l: int,
                  block: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Packed bit-plane VPU encode. (k, Bp) uint32 -> (rows, Bp) uint32, or
    batched (O, k, Bp) -> (O, rows, Bp) as one fused launch. Ragged lengths
    are padded to a whole number of tiles and sliced back. ``block=None``
    (the default) resolves the tile width through the tuning cache."""
    if interpret is None:
        interpret = _interpret_default()
    M_key = tuple(tuple(int(v) for v in row) for row in np.asarray(M))
    if block is None:
        block = _tuned_encode_block(M_key, data_packed, l, interpret)
    else:
        block = pick_block(data_packed.shape[-1], block)
    return _encode_packed_jit(data_packed, M_key, l, block, interpret)


def encode_words(M: np.ndarray, data: jax.Array, l: int,
                 block: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Word-level convenience wrapper: packs, encodes, unpacks.

    Accepts (k, B) words or a batch (O, k, B) — packing operates on the
    last axis either way.
    """
    dp = gf.pack_u32(data, l)
    out = encode_packed(M, dp, l, block=block, interpret=interpret)
    return gf.unpack_u32(out, l)


@functools.partial(jax.jit, static_argnames=("M_key", "l", "block", "interpret"))
def _encode_mxu_jit(data_words, M_key, l, block, interpret):
    M = np.asarray(M_key)
    B = data_words.shape[-1]
    data_words, pad = _pad_tail(data_words, block)
    out = kernel.gf_encode_mxu_kernel(M, data_words, l, block=block,
                                      interpret=interpret)
    return out[..., :B] if pad else out


def _tuned_mxu_block(M_key, dw, l, interpret) -> int:
    """``encode_mxu`` tile width from the tuning cache, heuristic
    ``pick_block(B, DEFAULT_MXU_BLOCK)`` — the old hard-coded 1024 now
    routed through the same picker as the VPU path."""
    B = dw.shape[-1]
    probe = None
    if autotune.is_concrete(dw):
        def probe(b):
            return _encode_mxu_jit(dw, M_key, l, pick_block(B, b), interpret)
    blk = autotune.kernel_block(
        "encode_mxu", l, B,
        heuristic=pick_block(B, kernel.DEFAULT_MXU_BLOCK),
        candidates=autotune.block_candidates(B, kernel.DEFAULT_MXU_BLOCK),
        probe=probe)
    return pick_block(B, blk)


def encode_mxu(M: np.ndarray, data: jax.Array, l: int,
               block: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """Bit-lifted MXU encode. (k, B) words -> (rows, B) words.

    Word counts that do not divide ``block`` are zero-padded to a whole
    number of tiles and sliced back (same pad-and-slice as the VPU path).
    ``block=None`` resolves through the tuning cache with the
    ``DEFAULT_MXU_BLOCK`` heuristic.
    """
    if interpret is None:
        interpret = _interpret_default()
    M_key = tuple(tuple(int(v) for v in row) for row in np.asarray(M))
    dw = data.astype(jnp.int32)
    if block is None:
        block = _tuned_mxu_block(M_key, dw, l, interpret)
    else:
        block = pick_block(data.shape[-1], block)
    out = _encode_mxu_jit(dw, M_key, l, block, interpret)
    return out.astype(gf.WORD_DTYPE[l])


def _encode_mxu_any(M: np.ndarray, data: jax.Array, l: int,
                    interpret: bool | None = None) -> jax.Array:
    """MXU encode for 2-D or batched input: the MXU kernel is strictly
    (k, B), so a batch rides as a word-axis concat (one launch, same
    padding rules) and is split back after."""
    if data.ndim == 2:
        return encode_mxu(M, data, l, interpret=interpret)
    O, k, B = data.shape
    flat = data.transpose(1, 0, 2).reshape(k, O * B)
    out = encode_mxu(M, flat, l, interpret=interpret)
    return out.reshape(-1, O, B).transpose(1, 0, 2)


def dispatch_for_data(M: np.ndarray, data: jax.Array, l: int,
                      interpret: bool | None = None) -> str:
    """Tuned MXU-vs-VPU dispatch (``"vpu"``/``"mxu"``) for this encode.

    On a search-mode cache miss with concrete data, times BOTH real
    kernels on the actual input and persists the winner per
    (backend, l, rows, k, B); otherwise cached value or the hand-tuned
    ``"vpu"`` default.
    """
    M = np.asarray(M)
    probes = None
    if autotune.is_concrete(data) and autotune.mode() == "search":
        probes = {
            "vpu": lambda: encode_words(M, data, l, interpret=interpret),
            "mxu": lambda: _encode_mxu_any(M, data, l, interpret=interpret),
        }
    return autotune.dispatch_for(l, int(M.shape[0]), int(data.shape[-2]),
                                 int(data.shape[-1]), probes=probes)


def encode_auto(M: np.ndarray, data: jax.Array, l: int,
                interpret: bool | None = None) -> jax.Array:
    """Dispatch-tuned word-level encode: VPU packed bit-plane or MXU
    bit-lifted matmul, whichever the tuner measured faster for this
    (l, shape, backend). Accepts (k, B) or batched (O, k, B) words."""
    if dispatch_for_data(M, data, l, interpret=interpret) == "mxu":
        return _encode_mxu_any(M, data, l, interpret=interpret)
    return encode_words(M, data, l, interpret=interpret)


def encode_block_for(M: np.ndarray, data: jax.Array, l: int,
                     interpret: bool | None = None) -> int:
    """Resolve (probing in search mode) the tuned ``encode_packed`` tile
    width for this (k, B) word geometry; used by ``autotune.prewarm``."""
    if interpret is None:
        interpret = _interpret_default()
    M_key = tuple(tuple(int(v) for v in row) for row in np.asarray(M))
    dp = gf.pack_u32(jnp.asarray(data), l)
    return _tuned_encode_block(M_key, dp, l, interpret)


def mxu_block_for(M: np.ndarray, data: jax.Array, l: int,
                  interpret: bool | None = None) -> int:
    """Resolve the tuned ``encode_mxu`` tile width for this geometry."""
    if interpret is None:
        interpret = _interpret_default()
    M_key = tuple(tuple(int(v) for v in row) for row in np.asarray(M))
    dw = jnp.asarray(data).astype(jnp.int32)
    return _tuned_mxu_block(M_key, dw, l, interpret)


def repair_step(x_in: jax.Array, local: jax.Array, bp: jax.Array, l: int,
                block: int = kernel.DEFAULT_BLOCK,
                interpret: bool | None = None) -> jax.Array:
    """Fused GF inner-product repair step (one helper's contribution).

    Single object (x_in (rows, C), local (1, C)) or a batch
    (x_in (O, rows, C), local (O, 1, C)) in one launch; ``bp`` (rows, l)
    bit-plane constants of the helper's repair-coefficient column.
    """
    if interpret is None:
        interpret = _interpret_default()
    return kernel.repair_step_kernel(x_in, local, bp, l, block=block,
                                     interpret=interpret)


def chain_step(x_in: jax.Array, local: jax.Array, bp_psi: jax.Array,
               bp_xi: jax.Array, l: int, block: int = kernel.DEFAULT_BLOCK,
               interpret: bool | None = None):
    """Fused per-node RapidRAID chunk step (traced coefficients).

    Single object (x_in (1, C), local (max_b, C)) or a batch of objects
    (x_in (O, 1, C), local (O, max_b, C)) in one launch.
    """
    if interpret is None:
        interpret = _interpret_default()
    return kernel.chain_step_kernel(x_in, local, bp_psi, bp_xi, l,
                                    block=block, interpret=interpret)
