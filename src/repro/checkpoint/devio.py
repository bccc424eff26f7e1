"""Device-direct erasure-coded checkpoint I/O: pytree <-> coded shards.

The host path (``manager.save``) serializes the train state with
``tree_to_bytes`` — every device leaf crosses to host numpy, is copied into
one contiguous blob, split into blocks, and only then coded. For a model-zoo
train state that host round trip is the whole save cost. Here the
``tree_to_bytes``-EQUIVALENT flatten/packing happens in-program, from the
mesh-sharded arrays:

  save:    leaves --bitcast/concat--> blob --split--> (k, B) blocks
           --chain encode--> (n, B) coded words          [ONE cached program]
  restore: (k, B) survivor words --decode--> blob --static slices/bitcast-->
           leaves                                        [ONE cached program]

so optimizer state is erasure-coded across the mesh instead of replicated,
and the only host transfers are the program outputs headed for the node
disks. Blob layout is BYTE-IDENTICAL to ``tree_to_bytes`` (shared
``object_store.leaf_metas`` / ``tree_header``), so ``manager.restore`` reads
device-saved checkpoints and ``restore_state`` reads host-archived ones.

Two execution paths mirror ``storage.archive``: with >= n devices the encode
embeds the pipelined chain (``chain._encode_core`` under ``shard_map``,
chain node p = device p of the training mesh via ``sharding.chain_order``);
otherwise one fused batched GF kernel launch. Either way the program is
built once per ``(entry, code, device order, state layout, block bytes,
chunks)`` key through ``repro.core.jitcache`` — repeated saves of
same-shaped states reuse one executable (trace-count tested).

Host-only leaves (e.g. the ``np.int64`` step counter, which cannot live on
device without x64) are pre-bitcast to uint8 on host and ride along as
program inputs; their bytes land at the exact ``tree_to_bytes`` offsets.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jitcache, rapidraid, streaming
from repro.storage import archive as arc
from repro.storage import chain as chain_lib
from repro.storage import object_store as obj

LANE_BYTES = 64   # whole uint32 packing lanes AND chunk-divisible blocks


# ---------------------------------------------------------------------------
# state layout: the tree_to_bytes-compatible byte plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """Byte plan for one train-state shape: where every leaf's bytes live in
    the blob, which leaves are device-resident, and a hashable cache key."""

    treedef: Any
    metas: tuple
    prefix: bytes               # MAGIC + header length + header JSON
    blob_len: int
    device_leaf: tuple          # per-leaf: packed in-program (vs host u8)
    key: tuple                  # (prefix digest, device classification)


def state_layout(state) -> StateLayout:
    """Layout for ``state`` (arrays or ``jax.ShapeDtypeStruct`` templates).

    The prefix (and therefore the whole blob) is byte-identical to what
    ``tree_to_bytes`` would write for the same pytree — both build their
    header from ``object_store.leaf_metas``.
    """
    leaves, treedef = jax.tree.flatten(state)
    metas = obj.leaf_metas(leaves)
    prefix = obj.tree_header(treedef, metas)
    body_len = (metas[-1]["offset"] + metas[-1]["nbytes"]) if metas else 0
    device_leaf = tuple(
        isinstance(x, (jax.Array, jax.ShapeDtypeStruct)) for x in leaves)
    return StateLayout(
        treedef=treedef, metas=tuple(metas), prefix=prefix,
        blob_len=len(prefix) + body_len, device_leaf=device_leaf,
        key=(obj.digest(prefix), device_leaf))


def _host_u8(leaf) -> np.ndarray:
    """Host-side bitcast of a non-device leaf to its blob bytes."""
    arr = np.ascontiguousarray(np.asarray(leaf))
    return arr.view(np.uint8).reshape(-1)


# ---------------------------------------------------------------------------
# in-program byte plumbing
# ---------------------------------------------------------------------------


def _leaf_to_u8(x):
    """Traced leaf -> its little-endian blob bytes (1-D uint8)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    if x.dtype == jnp.uint8:
        return x.reshape(-1)
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)


def _u8_to_leaf(raw, dtype, shape):
    """Blob bytes (1-D uint8) -> traced leaf of the stored dtype/shape."""
    dt = jnp.dtype(dtype)
    shape = tuple(shape)
    if dt == jnp.bool_:
        return raw.astype(jnp.bool_).reshape(shape)
    if dt == jnp.uint8:
        return raw.reshape(shape)
    return jax.lax.bitcast_convert_type(
        raw.reshape(shape + (dt.itemsize,)), dt)


def _u8_to_words(blocks, l: int):
    """(..., B) uint8 -> (..., B words) GF words, little-endian like numpy's
    ``.view(WORD_DTYPE)`` on the host."""
    if l == 8:
        return blocks
    pairs = blocks.reshape(blocks.shape[:-1] + (-1, 2)).astype(jnp.uint16)
    return pairs[..., 0] | (pairs[..., 1] << 8)


def _words_to_u8(words, l: int):
    """Inverse of ``_u8_to_words`` (matches host ``.view(np.uint8)``)."""
    if l == 8:
        return words
    lo = (words & 0xFF).astype(jnp.uint8)
    hi = (words >> 8).astype(jnp.uint8)
    return jnp.stack([lo, hi], axis=-1).reshape(words.shape[:-1] + (-1,))


# ---------------------------------------------------------------------------
# cached programs
# ---------------------------------------------------------------------------


def _build_save(code, layout: StateLayout, order, num_chunks: int,
                use_chain: bool, block_bytes: int):
    """One jitted program: state leaves -> ((k, B) blocks, (n, Bw) coded).

    The original blocks come back alongside the codeword so the caller can
    record ``orig_digests`` (what host restore verifies decode against)
    without re-deriving them.
    """
    l, k = code.l, code.k
    prefix_c = jnp.asarray(np.frombuffer(layout.prefix, dtype=np.uint8))
    pad = k * block_bytes - layout.blob_len
    if use_chain:
        mesh = chain_lib.make_chain_mesh(code.n, order)
        encode = chain_lib._encode_core(code, mesh, num_chunks)
    else:
        from repro.kernels.gf_encode import ops as kernel_ops

        def encode(words):
            return kernel_ops.encode_words(code.G, words, l)

    @jax.jit
    def program(*leaves):
        parts = [prefix_c] + [_leaf_to_u8(x) for x in leaves]
        if pad:
            parts.append(jnp.zeros((pad,), jnp.uint8))
        blob = jnp.concatenate(parts) if len(parts) > 1 else prefix_c
        blocks = blob.reshape(k, block_bytes)
        return blocks, encode(_u8_to_words(blocks, l))
    return program


def _build_restore(code, ids: tuple, layout: StateLayout, order,
                   num_chunks: int, use_chain: bool):
    """One jitted program: (k, Bw) survivor words -> tuple of leaves.

    Device-classified leaves come out in their stored dtype (static-offset
    slices + bitcast, all in-program); host-classified leaves come out as
    raw uint8 for the caller to view into numpy dtypes jax can't hold.
    """
    l = code.l
    if use_chain:
        mesh = chain_lib.make_chain_mesh(len(ids), order)
        decode = chain_lib._decode_core(code, ids, mesh, num_chunks)
    else:
        from repro.kernels.gf_encode import ops as kernel_ops
        D = rapidraid.decode_matrix(code, list(ids))

        def decode(shards_w):
            return kernel_ops.encode_words(D, shards_w, l)

    plen = len(layout.prefix)

    @jax.jit
    def program(shards_w):
        blob = _words_to_u8(decode(shards_w), l).reshape(-1)
        out = []
        for meta, is_dev in zip(layout.metas, layout.device_leaf):
            a = plen + meta["offset"]
            raw = jax.lax.slice(blob, (a,), (a + meta["nbytes"],))
            out.append(_u8_to_leaf(raw, meta["dtype"], meta["shape"])
                       if is_dev else raw)
        return tuple(out)
    return program


def _mesh_order(mesh, n: int):
    from repro.train import sharding
    return None if mesh is None else sharding.chain_order(mesh, n)


# ---------------------------------------------------------------------------
# save / restore entry points
# ---------------------------------------------------------------------------


def save_state(store, step: int, state, acfg: arc.ArchiveConfig,
               mesh=None, num_chunks: int | None = None,
               use_devices: bool | None = None,
               footprint_bytes: int | None = None) -> dict:
    """Erasure-code ``state`` straight from its device buffers into the
    coded tier (no hot replicas, no host blob). Returns the manifest.

    ``mesh``: the training mesh; chain node p is its p-th device
    (``sharding.chain_order``), so each node encodes from the shard walk the
    state already lives on. Without it (or with fewer devices than n) the
    encode runs as one fused kernel launch — the same program shape, still
    compiled once per state layout.

    ``footprint_bytes`` (default: the ``RAPIDRAID_STREAM_BUDGET_BYTES``
    env knob) bounds the encode's per-device bytes: a state whose modeled
    device-direct footprint exceeds it routes through the STREAMING path
    instead — host serialization, then super-chunk stripes through one
    cached chain program into atomic framed writes
    (``archive.publish_streaming_archive``) — so grok-scale states
    checkpoint under a fixed device budget. States that fit keep the
    zero-host-blob device-direct program.
    """
    code = acfg.code()
    if not code.positionwise:
        raise ValueError(
            f"device-direct checkpointing needs a positionwise code; "
            f"{code.family!r} is sub-packetized — archive via the host "
            f"path (manager.save) or pick family='rapidraid'/'lrc'")
    layout = state_layout(state)
    B = obj.block_bytes_for(layout.blob_len, acfg.k, lane_bytes=LANE_BYTES)
    if footprint_bytes is None:
        footprint_bytes = streaming.budget_from_env()
    if (footprint_bytes is not None
            and streaming.estimate_stripe_bytes(code, B * 8 // acfg.l)
            > footprint_bytes):
        blob = obj.tree_to_bytes(state)
        blocks = obj.split_blocks(blob, acfg.k, lane_bytes=LANE_BYTES)
        sc_words = streaming.superchunk_words_for(
            footprint_bytes, code, num_chunks or acfg.num_chunks)
        return arc.publish_streaming_archive(
            store, step, acfg, blocks, len(blob),
            superchunk_bytes=sc_words * (acfg.l // 8),
            state_key=layout.key[0], use_devices=use_devices)
    nc = streaming.chunk_count(B * 8 // acfg.l, acfg.l,
                               num_chunks or acfg.num_chunks)
    order = _mesh_order(mesh, acfg.n)
    if use_devices is None and mesh is not None:
        use_devices = order is not None
    use_chain = (arc._use_chain(code, use_devices, acfg.n)
                 and len(jax.devices()) >= acfg.n)
    okey = tuple(order) if order is not None else None
    fn = jitcache.get(
        ("ckpt_save", code.cache_key, okey, use_chain, layout.key, B, nc),
        lambda: _build_save(code, layout, order, nc, use_chain, B))

    leaves = jax.tree.flatten(state)[0]
    inputs = [x if is_dev else _host_u8(x)
              for x, is_dev in zip(leaves, layout.device_leaf)]
    blocks, coded_w = fn(*inputs)
    return arc.publish_device_archive(
        store, step, acfg, np.asarray(blocks), arc._u8(np.asarray(coded_w)),
        layout.blob_len, state_key=layout.key[0])


def restore_state(store, step: int, like, acfg: arc.ArchiveConfig,
                  mesh=None, shardings=None,
                  num_chunks: int | None = None,
                  use_devices: bool | None = None):
    """Decode step's shards and rebuild the train state in one cached
    program; tolerates up to n-k lost shards (digest-verified survivors).

    ``like`` supplies the tree structure and the device/host classification
    (``jax.Array`` / ``ShapeDtypeStruct`` leaves come back as device arrays,
    numpy leaves as host arrays). ``shardings`` (a matching pytree) places
    each restored leaf — the elastic path onto a smaller/reshaped mesh.
    Hot-tier steps fall back to the replica read (nothing to decode).
    """
    manifest = arc.get_manifest(store, step)
    layout = state_layout(like)
    blob_len = manifest.get("blob_len")
    if blob_len is not None and blob_len != layout.blob_len:
        raise ValueError(
            f"step {step}: template does not match the archived state "
            f"(blob {blob_len} bytes, template describes "
            f"{layout.blob_len})")
    if (manifest.get("state_key") is not None
            and manifest["state_key"] != layout.key[0]):
        raise ValueError(
            f"step {step}: template layout {layout.key[0]} does not match "
            f"the archived state layout {manifest['state_key']} "
            f"(different treedef, dtypes, or shapes)")

    coded = (arc._manifest_code(manifest)
             if manifest["tier"] == "archive" else None)
    if (manifest["tier"] != "archive" or manifest.get("hot_retained")
            or manifest.get("streaming") or not coded.positionwise):
        # sub-packetized families and STREAMED archives restore through the
        # host decode path (restore_blocks reads streamed steps stripe-by-
        # stripe against the manifest's per-stripe digests)
        blocks = arc.restore_blocks(store, step, acfg)
        blob = obj.join_blocks(blocks, blob_len or layout.blob_len)
        tree = obj.bytes_to_leaves(blob, like)
    else:
        code = coded
        alive = arc._alive_coded(store, step, manifest)
        if len(alive) < manifest["k"]:
            raise FileNotFoundError(
                f"step {step}: only {len(alive)} of n={manifest['n']} coded "
                f"blocks alive, need k={manifest['k']}")
        alive_ids = [pos for pos, _ in alive]
        try:
            chosen = rapidraid.independent_rows(
                code.G[alive_ids], manifest["k"], manifest["l"])
        except ValueError as e:
            raise FileNotFoundError(
                f"step {step}: survivors not decodable ({e})") from None
        helpers = tuple(alive_ids[p] for p in chosen)
        raws = dict(alive)
        shards_w = arc._words(
            np.stack([np.frombuffer(raws[h], dtype=np.uint8)
                      for h in helpers]), manifest["l"])
        nc = streaming.chunk_count(shards_w.shape[1], manifest["l"],
                                   num_chunks or acfg.num_chunks)
        order = _mesh_order(mesh, len(helpers))
        if use_devices is None and mesh is not None:
            use_devices = order is not None
        use_chain = (arc._use_chain(code, use_devices, len(helpers),
                                    repair=True)
                     and len(jax.devices()) >= len(helpers))
        okey = tuple(order) if order is not None else None
        fn = jitcache.get(
            ("ckpt_restore", code.cache_key, helpers, okey, use_chain,
             layout.key,
             manifest["block_bytes"], nc),
            lambda: _build_restore(code, helpers, layout, order, nc,
                                   use_chain))
        out_leaves = fn(shards_w)
        leaves = []
        for leaf, meta, is_dev in zip(out_leaves, layout.metas,
                                      layout.device_leaf):
            if is_dev:
                leaves.append(leaf)
            else:
                raw = np.asarray(leaf)
                dt = jnp.dtype(meta["dtype"])
                leaves.append(raw.view(dt).reshape(meta["shape"]))
        tree = jax.tree.unflatten(layout.treedef, leaves)

    if shardings is not None:
        tree = jax.tree.map(
            lambda a, s: jax.device_put(np.asarray(a), s), tree, shardings)
    return tree
