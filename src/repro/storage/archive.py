"""Checkpoint archival: replicated hot tier -> RapidRAID coded tier -> repair.

This is the paper's lifecycle applied to training checkpoints:

1. **hot_save** — the freshly written checkpoint object (k blocks) is stored
   with two replicas overlapped over n nodes exactly per RapidRAID's
   placement (replica 1 on nodes 0..k-1, replica 2 on nodes n-k..n-1), the
   layout pipelined insertion produces and the precondition for chain
   encoding (paper §V).
2. **archive_step** — the migration: the n nodes run the pipelined encode
   (``repro.storage.chain`` over a device chain, or one fused kernel launch
   on the default device when there are fewer devices than nodes), each
   node keeps its coded block c_i, replicas are dropped: storage falls from
   2x to n/k. **archive_many** encodes B steps concurrently (the paper's
   multi-object archival, §VI). Every write path codes through
   ``_archive_group`` or the stripe body ``_archive_step_streaming``, and
   publishes through ``_publish``.
3. **restore** — any k live coded blocks reconstruct the object (GF
   Gaussian elimination on the host builds the decode matrix; the matmul
   runs through the same GF path). ``read_range`` serves byte ranges
   WITHOUT materializing the object: hot-tier slice reads, or a degraded
   read that decodes only the covering word range of k surviving shards.
4. **repair** — after node loss, only the missing c_i are recomputed:
   ``repro.core.fault_tolerance.repair_plan`` picks k helpers and the
   repair coefficients R with R @ c_helpers = c_missing, and the fused GF
   kernel (or the reverse pipelined helper chain on a device mesh) applies
   them — no decode-to-o-and-re-encode. ``repair_many`` heals B objects
   through ONE staggered launch; ``restore_blocks(heal=True)`` and
   ``read_range(heal=True)`` heal missing shards detected on the read path.

Straggler mitigation: ``order_chain`` permutes slow nodes to chain ends
(the paper's Fig. 5 insight); the manifest records the node->codeword-row
mapping so decode is permutation-aware.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import classical, codes, fault_tolerance, gf, rapidraid, streaming
from repro.spans import span
from repro.storage import chain as chain_lib
from repro.storage import multi as multi_lib
from repro.storage import repair as repair_lib
from repro.storage.object_store import NodeStore, digest

MANIFEST = "manifests/{step:08d}.json"
HOT = "hot/{step:08d}/block_{j:02d}.bin"
ARC = "archive/{step:08d}/c_{i:02d}.bin"


@dataclasses.dataclass(frozen=True)
class ArchiveConfig:
    n: int = 16
    k: int = 11
    l: int = 16               # GF(2^16): random coefficients suffice (§V-A)
    seed: int = 0
    num_chunks: int = 8       # pipeline chunks per block
    family: str = "rapidraid"    # registered code family (repro.core.codes)

    def code(self) -> codes.ErasureCode:
        return codes.make(self.family, self.n, self.k, l=self.l,
                          seed=self.seed)


@dataclasses.dataclass(frozen=True)
class ReadResult:
    """What a read returned AND how it was served.

    ``data``: the payload — ``(k, B)`` uint8 blocks from
    :func:`restore_blocks_ex`, raw ``bytes`` from :func:`read_range_ex`.
    ``served_from``: which path produced the bytes —

    * ``"hot"`` — replica-tier read (including the retained-replica
      fallback of a two-phase migration);
    * ``"coded"`` — archive-tier decode with the FULL shard set alive
      (RapidRAID is non-systematic, so even the healthy path is a k-fanin
      decode — "coded" means nothing had to be routed around);
    * ``"degraded"`` — archive-tier decode that routed around missing or
      corrupt shards.

    ``nodes``: the physical nodes that served payload bytes for this
    read (replica holders, decode helpers); liveness probes of nodes that
    contributed nothing are not counted. ``healed``: True when
    ``heal=True`` actually re-materialized shards on this read (reads
    doubling as scrubs). Serving metrics and tests consume these fields
    instead of inferring the path from side effects.
    """

    data: "np.ndarray | bytes"
    served_from: str
    nodes: tuple[int, ...]
    healed: bool
    step: int

    def __post_init__(self):
        if self.served_from not in ("hot", "coded", "degraded"):
            raise ValueError(
                f"served_from must be 'hot', 'coded' or 'degraded', "
                f"got {self.served_from!r}")


def _result(data, served_from: str, nodes, healed: bool,
            step: int) -> ReadResult:
    return ReadResult(data=data, served_from=served_from,
                      nodes=tuple(sorted({int(x) for x in nodes})),
                      healed=bool(healed), step=int(step))


def _words(blocks_u8: np.ndarray, l: int) -> np.ndarray:
    dt = gf.WORD_DTYPE[l]
    return blocks_u8.view(dt)


def _u8(blocks_w: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(blocks_w).view(np.uint8)


def _rows(rows) -> list[memoryview]:
    """Each of a sequence of C-contiguous 1-D rows (any word dtype) as a
    uint8 memoryview, which ``store.put`` and ``digest`` take in place,
    with no copy."""
    return [memoryview(_u8(row)) for row in rows]


# ---------------------------------------------------------------------------
# hot tier (replicated per RapidRAID placement)
# ---------------------------------------------------------------------------


def hot_save(store: NodeStore, step: int, blocks: np.ndarray,
             acfg: ArchiveConfig) -> dict:
    """blocks (k, B) uint8 -> two overlapped replicas over n nodes."""
    k, B = blocks.shape
    assert k == acfg.k
    # serialize each block ONCE: every replica put and the digest reuse it
    blobs = [blocks[j].tobytes() for j in range(k)]
    for node, held in enumerate(rapidraid.placement(acfg.n, acfg.k)):
        for j in held:
            store.put(node, HOT.format(step=step, j=j), blobs[j])
    manifest = _base_manifest(step, acfg, B, [digest(b) for b in blobs])
    _put_manifest(store, step, manifest)
    return manifest


def _base_manifest(step: int, acfg: ArchiveConfig, block_bytes: int,
                   digests: list[str] | None, tier: str = "hot") -> dict:
    """The fields every manifest starts with; a step written straight to
    the coded tier keeps the nominal hot placement (one schema)."""
    return {
        "step": step, "tier": tier, "n": acfg.n, "k": acfg.k, "l": acfg.l,
        "seed": acfg.seed, "family": acfg.family,
        "block_bytes": int(block_bytes), "digests": digests,
        "placement": [list(h) for h in rapidraid.placement(acfg.n, acfg.k)],
    }


def hot_load(store: NodeStore, step: int, manifest: dict) -> np.ndarray:
    """Read each block from any node still holding a replica."""
    return _hot_load_ex(store, step, manifest)[0]


def _verified_hot(store: NodeStore, step: int, manifest: dict):
    """Yield (raw, node) for each hot block in order: the bytes of the first
    replica holder whose copy matches the manifest digest."""
    for j in range(manifest["k"]):
        holders = [i for i, held in enumerate(manifest["placement"])
                   if j in held]
        for node in holders:
            rel = HOT.format(step=step, j=j)
            if store.has(node, rel):
                raw = store.get(node, rel)
                if digest(raw) == manifest["digests"][j]:
                    yield raw, node
                    break
        else:
            raise FileNotFoundError(
                f"hot block {j} of step {step} lost on all replicas")


def _hot_load_ex(store: NodeStore, step: int,
                 manifest: dict) -> tuple[np.ndarray, list[int]]:
    """(blocks, replica nodes actually read) — the node-tracking core of
    ``hot_load`` that ``restore_blocks_ex`` builds its ReadResult from."""
    k, B = manifest["k"], manifest["block_bytes"]
    with span("hot_load", bytes=k * B):
        out = np.zeros((k, B), dtype=np.uint8)
        touched: list[int] = []
        for j, (raw, node) in enumerate(_verified_hot(store, step, manifest)):
            with span("host_copy", bytes=B):
                out[j] = np.frombuffer(raw, dtype=np.uint8)
            touched.append(node)
    return out, touched


def _hot_rows(store: NodeStore, step: int, manifest: dict,
              l: int) -> list[np.ndarray]:
    """The k digest-verified hot blocks as GF(2^l) word views of the
    store's own buffers, with no copy: what the fused encode sends to the
    device."""
    k, B = manifest["k"], manifest["block_bytes"]
    with span("hot_load", bytes=k * B):
        return [np.frombuffer(raw, dtype=gf.WORD_DTYPE[l])
                for raw, _ in _verified_hot(store, step, manifest)]


# ---------------------------------------------------------------------------
# archival migration (the paper's pipelined encode)
# ---------------------------------------------------------------------------


def _plan_placement(acfg: ArchiveConfig, block_bytes: int, topology,
                    node_speeds) -> tuple[np.ndarray, int, dict | None]:
    """(perm, num_chunks, sched-manifest-entry) for one archival chain.

    ``topology`` (a ``repro.core.topology.Topology``) engages the
    heterogeneity-aware scheduler: chain ordering + chunk count minimizing
    the modeled makespan, with the plan recorded in the manifest so decode
    and repair replay the same placement. ``node_speeds`` keeps the older
    slow-nodes-to-the-ends heuristic. Neither -> in-order placement.
    """
    if topology is not None:
        from repro.core import scheduler, topology as topo_lib
        if topology.n_nodes < acfg.n:
            raise ValueError(
                f"chain needs {acfg.n} nodes, topology has {topology.n_nodes}")
        nodes = None
        if topology.n_nodes > acfg.n:  # pick the n cheapest nodes
            nodes = sorted(range(topology.n_nodes),
                           key=lambda i: topo_lib.node_cost(topology, i)
                           )[: acfg.n]
        plan = scheduler.plan_chain(topology, acfg.k, float(block_bytes),
                                    nodes=nodes)
        return (np.asarray(plan.order), plan.num_chunks,
                {**plan.to_manifest(), "topology": topology.to_dict()})
    if node_speeds is not None:
        perm = chain_lib.order_chain(np.asarray(node_speeds), acfg.n, acfg.k)
        return perm, acfg.num_chunks, None
    return np.arange(acfg.n), acfg.num_chunks, None


def _device_order(perm: np.ndarray, scheduled: bool) -> list[int] | None:
    """Scheduler placement for the device chain, when the devices can play
    it (entries must name distinct local devices)."""
    order = [int(p) for p in perm]
    if (scheduled and len(set(order)) == len(order)
            and max(order) < len(jax.devices())):
        return order
    return None


def _use_chain(code, use_devices: bool | None, rows: int, *,
               repair: bool = False) -> bool:
    """Code on a chain of ``rows`` devices, not in one fused launch:
    ``use_devices`` (None: at least ``rows`` local devices) and a family
    with the chain (``repair``: any positionwise code's helper chain)."""
    if use_devices is None:
        use_devices = len(jax.devices()) >= rows
    return bool(use_devices) and (code.positionwise if repair
                                  else code.supports_chain_encode)


def _drop_hot(store: NodeStore, step: int, manifest: dict) -> None:
    """Delete every hot replica the manifest's placement holds."""
    for node, held in enumerate(manifest["placement"]):
        for j in held:
            store.delete(node, HOT.format(step=step, j=j))


def _put_coded(store: NodeStore, step: int, perm, rows) -> list[memoryview]:
    """Write codeword row p on node ``perm[p]``; -> the rows as written."""
    blobs = _rows(rows)
    for pos, blob in enumerate(blobs):
        store.put(int(perm[pos]), ARC.format(step=step, i=pos), blob)
    return blobs


def _publish(store: NodeStore, step: int, base: dict, acfg: ArchiveConfig,
             perm, coded_digests, *, reclaim_hot: bool = True,
             sched: dict | None = None, **extra) -> dict:
    """Publish a step whose n coded rows are stored. A step coded from the
    hot tier first drops its replicas (2x -> n/k) or, with ``reclaim_hot``
    false, records ``hot_retained``; ``coded_digests`` is read after that.
    ``extra`` entries that are not None join the manifest."""
    hot = base["tier"] == "hot"
    if hot and reclaim_hot:
        with span("reclaim"):
            _drop_hot(store, step, base)
    manifest = {
        **base, "tier": "archive", "family": acfg.family,
        "perm": [int(p) for p in perm],
        "coded_digests": list(coded_digests),
        "orig_digests": base["digests"],
        **{key: v for key, v in extra.items() if v is not None},
    }
    if hot and not reclaim_hot:
        manifest["hot_retained"] = True
    if sched is not None:
        manifest["sched"] = sched
    _put_manifest(store, step, manifest)
    return manifest


def archive_step(store: NodeStore, step: int, acfg: ArchiveConfig,
                 node_speeds: np.ndarray | None = None,
                 use_devices: bool | None = None,
                 topology=None, reclaim_hot: bool = True,
                 superchunk_bytes: int | None = None) -> dict:
    """Migrate step's hot replicas to RapidRAID coded blocks; drop hot.

    ``topology`` engages the heterogeneity-aware scheduler
    (``repro.core.scheduler``): chain placement + chunk count chosen against
    the topology's makespan model and recorded in the manifest
    (``perm`` / ``sched``), so repair and decode reuse the placement.

    ``superchunk_bytes`` streams the migration through the stripe body
    (``_archive_step_streaming``): neither peak device nor peak host bytes
    hold the object, positionwise codes write coded blocks BYTE-IDENTICAL
    to the monolithic path, and the manifest adds the stripe geometry and
    per-stripe digests (``streaming``). A hot digest mismatch aborts the
    coded writes before anything is published. Sub-packetized families
    cannot stream (raises ValueError). Without ``superchunk_bytes``, a
    positionwise code off the chain streams by itself when one fused launch
    would hold more than ``streaming.device_budget`` allows, in the fewest
    equal stripes that fit (``streaming.fused_stripe_words``). Otherwise
    the step codes as ``archive_many``'s group of one (``_archive_group``).

    ``reclaim_hot=False`` defers the replica deletion: the step is coded
    and readable from the archive tier, but the hot replicas stay on disk
    (manifest ``hot_retained``) until ``reclaim_replicas`` has digest-
    verified every placed coded block — the lifecycle engine's
    never-drop-the-last-copy-unverified invariant.
    """
    manifest = get_manifest(store, step)
    if manifest["tier"] != "hot":
        raise ValueError(f"step {step} already archived")
    code = acfg.code()

    # chain position p stores codeword row p on physical node perm[p]
    perm, nc, sched = _plan_placement(acfg, manifest["block_bytes"],
                                      topology, node_speeds)

    wb = acfg.l // 8
    words = manifest["block_bytes"] // wb
    on_chain = _use_chain(code, use_devices, acfg.n)
    sc_words = None
    if superchunk_bytes is not None:
        sc_words = max(1, superchunk_bytes // wb)
    elif code.positionwise and not on_chain:
        sc_words = _fused_stripe_words(code, words)
    if sc_words is not None:
        plan = streaming.plan_stream(words, sc_words, l=acfg.l,
                                     num_chunks=nc)
        if plan.streaming:
            return _archive_step_streaming(
                store, step, acfg, manifest, code, perm, nc, sched, plan,
                on_chain, reclaim_hot, _hot_reader(store, step, manifest))
        # plan degenerated to one stripe: the monolithic path IS the stream
    return _archive_group(store, [step], acfg, code, perm, nc, 1, on_chain,
                          {step: manifest}, sched, reclaim_hot)[step]


def _hot_reader(store: NodeStore, step: int, manifest: dict):
    """``read(j, offset, nbytes)`` of hot block j, from one replica
    holder per block (existence probe only)."""
    holders = []
    for j in range(manifest["k"]):
        rel = HOT.format(step=step, j=j)
        cands = [i for i, held in enumerate(manifest["placement"])
                 if j in held and store.has(i, rel)]
        if not cands:
            raise FileNotFoundError(
                f"hot block {j} of step {step} lost on all replicas")
        holders.append(cands[0])
    return lambda j, offset, nbytes: store.get_range(
        holders[j], HOT.format(step=step, j=j), offset, nbytes)


def _fused_stripe_words(code, words: int) -> int | None:
    """Stripe width of a fused archive encode that would not fit the
    device budget (``streaming.device_budget``); None where it fits."""
    from repro.kernels.gf_encode import kernel
    budget = streaming.device_budget()
    if budget is None:
        return None
    return streaming.fused_stripe_words(
        code.k, code.n, words, code.l, budget,
        granule=gf.LANES[code.l] * kernel.DEFAULT_BLOCK)


@functools.partial(jax.jit, static_argnums=1)
def _stack_padded(rows: list[jax.Array], width: int) -> jax.Array:
    """k device rows of at most ``width`` words -> one (k, width) array,
    zero-padded on the device (a tail stripe is shorter)."""
    x = jnp.stack(rows)
    return jnp.pad(x, ((0, 0), (0, width - x.shape[-1])))


def _archive_step_streaming(store: NodeStore, step: int, acfg: ArchiveConfig,
                            base: dict, code, perm, nc: int,
                            sched: dict | None, plan: streaming.StreamPlan,
                            on_chain: bool, reclaim_hot: bool,
                            read, **extra) -> dict:
    """The one stripe body: stripe reads -> stripe encodes on the device ->
    framed coded writes, never holding the object, then ``_publish``.

    ``read(j, offset, nbytes)`` gives those bytes of block j: hot range
    reads (``_hot_reader``) or slices of in-memory blocks. Their digests
    accumulate as the stripes pass and must equal ``base["digests"]``
    (None: taken from the stream) before anything publishes. Each stripe's
    k rows are stacked on the device and coded on the chain or in one
    fused launch of the rows that are not pass-through rows, which are
    written from the stripe's own reads; ``streaming.execute`` overlaps
    the next stripe's reads and the last one's writes with the device."""
    from repro.kernels.gf_encode import ops as kernel_ops
    if not code.positionwise:
        raise ValueError(
            f"{code.family} is sub-packetized — stripe concatenation is not "
            f"a codeword, so it cannot stream (archive it whole)")
    k, n, l = acfg.k, acfg.n, acfg.l
    wb = l // 8
    dt = gf.WORD_DTYPE[l]
    nc = streaming.chunk_count(plan.sc_words, l, nc)
    if sched is not None:
        sched = {**sched, "num_chunks": int(nc)}  # record what actually ran
    sha = [hashlib.sha256() for _ in range(k)]
    # pass-through rows are written from the stripe's own reads, held
    # from get_stripe until put_stripe; the device codes the rest
    passthrough = {} if on_chain else _passthrough(code)
    coded = [i for i in range(n) if i not in passthrough]
    held: dict[int, list[np.ndarray]] = {}

    def get_stripe(s: int) -> list[np.ndarray]:
        lo, hi = plan.stripe_span(s)
        nb = (hi - lo) * wb
        rows = []
        with span("stripe_read", stripe=s, bytes=k * nb):
            for j in range(k):
                raw = read(j, lo * wb, nb)
                if len(raw) != nb:
                    raise ValueError(
                        f"step {step}: block {j} short read (stripe "
                        f"{s}: got {len(raw)} of {nb} bytes)")
                with span("sha256", bytes=nb):
                    sha[j].update(raw)
                rows.append(np.frombuffer(raw, dtype=dt))
        held[s] = rows
        return rows

    writers = [store.put_stream(int(perm[pos]), ARC.format(step=step, i=pos))
               for pos in range(n)]
    stripes: list[dict] = []

    def put_stripe(s: int, out_w: np.ndarray) -> None:
        words = plan.stripe_words(s)
        (rows,) = _codewords(passthrough, [held.pop(s)], [out_w])
        with span("stripe_write", stripe=s, bytes=n * words * wb,
                  passthrough=len(passthrough)):
            recs = []
            for pos, row in enumerate(rows):
                blob = memoryview(row[:words].view(np.uint8))
                writers[pos].write(blob)
                recs.append(digest(blob))
        stripes.append({"words": int(words), "coded_digests": recs})

    encode = (chain_lib.encode_program(
        code, plan.sc_words, nc, order=_device_order(perm, sched is not None))
        if on_chain else functools.partial(kernel_ops.encode_auto,
                                           code.G[coded], l=l))

    def program(rows):
        return encode(_stack_padded(rows, plan.sc_words))
    program.__name__ = "chain_encode" if on_chain else "encode_auto"

    try:
        streaming.execute(plan, program, get_stripe, put_stripe)
        hashed = [h.hexdigest()[:16] for h in sha]
        if base["digests"] is None:     # in-memory blocks: their own record
            base = {**base, "digests": hashed}
        for j in range(k):
            if hashed[j] != base["digests"][j]:
                raise ValueError(
                    f"step {step}: hot block {j} does not match its manifest "
                    f"digest — streamed archive aborted, nothing published")
    except BaseException:
        for w in writers:
            w.abort()
        raise
    for w in writers:
        w.close()
    # incremental frame hashes == whole-file digests, identical to the
    # monolithic path's (the files are byte-identical)
    return _publish(
        store, step, base, acfg, perm, [w.digest() for w in writers],
        reclaim_hot=reclaim_hot, sched=sched, **extra,
        streaming={"num_superchunks": int(plan.num_superchunks),
                   "superchunk_bytes": int(plan.sc_words * wb),
                   "num_chunks": int(nc), "stripes": stripes})


@functools.partial(jax.jit, static_argnums=1)
def _stack_rows(rows: list[jax.Array], objects: int) -> jax.Array:
    """O * R device rows of W words -> one (O, R, W) array, on the device."""
    return jnp.stack(rows).reshape(objects, len(rows) // objects, -1)


def _to_device(objs) -> jax.Array:
    """O sequences of R word rows -> (O, R, W) on the default device.

    Each row (a view of a store buffer) is sent as it is and the rows are
    stacked on the device, so no payload byte is copied on the host; the
    ``h2d`` span's ``direct`` counts the rows sent so.
    """
    rows = [row for obj in objs for row in obj]
    with span("h2d", bytes=sum(r.nbytes for r in rows), direct=len(rows)):
        return _stack_rows(jax.device_put(rows), len(objs))


def _gather(objs) -> np.ndarray:
    """O sequences of R word rows -> one (O, R, W) host array, filled once
    (the paths that need the payload on the host)."""
    first = objs[0][0]
    out = np.empty((len(objs), len(objs[0]), len(first)), first.dtype)
    with span("host_copy", bytes=out.nbytes):
        for o, obj in enumerate(objs):
            for r, row in enumerate(obj):
                out[o, r] = row
    return out


@functools.lru_cache(maxsize=64)
def _passthrough(code) -> types.MappingProxyType:
    """{codeword row i: data row j} for every row of a positionwise code's
    generator that is exactly the unit row e_j (a systematic code's data
    rows); empty for a sub-packetized code, whose rows index message
    symbols. A scaled unit row is not in it: it is coded like any other."""
    if not code.positionwise:
        return types.MappingProxyType({})
    out = {}
    for i, row in enumerate(np.asarray(code.G)):
        (nz,) = np.nonzero(row)
        if len(nz) == 1 and row[nz[0]] == 1:
            out[i] = int(nz[0])
    return types.MappingProxyType(out)


def _fused_encode(code, objs_w, l: int) -> np.ndarray:
    """O objects (sequences of k 1-D word rows) -> (O, R, B) words of the
    R codeword rows that are not pass-through rows (``_passthrough``), in
    row order, in ONE fused kernel launch, the object axis on the kernel
    grid; ``_codewords`` puts the n rows together. Positionwise rows go to
    the device as they are (``_to_device``); a regenerating code's
    sub-packetized message is built on the host, so EVERY family encodes
    through the same fused GF kernel.
    """
    from repro.kernels.gf_encode import ops as kernel_ops
    O = len(objs_w)
    if code.positionwise:
        msgs_dev = _to_device(objs_w)
    else:
        objs_w = _gather(objs_w)
        with span("host_copy", bytes=objs_w.nbytes):
            msgs = np.stack([np.asarray(code.to_message(o)) for o in objs_w])
        with span("h2d", bytes=msgs.nbytes, direct=0):
            msgs_dev = jnp.asarray(msgs)
    passthrough = _passthrough(code)
    coded = [i for i in range(len(code.G)) if i not in passthrough]
    with span("kernel_launch", kernel="encode_auto"):
        rows_dev = kernel_ops.encode_auto(code.G[coded], msgs_dev, l)
    with span("d2h", bytes=rows_dev.nbytes,
              passthrough=O * len(passthrough)):
        rows = np.asarray(rows_dev)
    return rows.reshape(O, code.n - len(passthrough), -1)


def _codewords(passthrough, objs_w, coded_w) -> list[list[np.ndarray]]:
    """Per object, its n codeword rows as 1-D word rows, stacked nowhere:
    a pass-through row (``passthrough``) is the verified data row it
    equals (``objs_w``), any other the next row of the coded ``coded_w``."""
    out = []
    for obj, coded in zip(objs_w, coded_w):
        rest = iter(coded)
        out.append([obj[passthrough[i]] if i in passthrough else next(rest)
                    for i in range(len(passthrough) + len(coded))])
    return out


def _archive_group(store: NodeStore, grp: list[int], acfg: ArchiveConfig,
                   code, perm: np.ndarray, num_chunks: int, stagger: int,
                   on_chain: bool, manifests: dict[int, dict],
                   sched: dict | None, reclaim_hot: bool = True,
                   **extra) -> dict[int, dict]:
    """Encode one rectangular (same block length, same placement) batch of
    hot steps, place the coded blocks and ``_publish`` each step."""
    # blocks are loaded one group at a time (and released once the group
    # is written) so peak host memory is one group, not the batch
    rows = [_hot_rows(store, s, manifests[s], acfg.l) for s in grp]
    nc = streaming.chunk_count(
        manifests[grp[0]]["block_bytes"] // (acfg.l // 8), acfg.l,
        num_chunks)
    if sched is not None:
        sched = {**sched, "num_chunks": int(nc)}  # record what actually ran
    if on_chain:
        order = _device_order(perm, sched is not None)
        if len(grp) == 1:
            coded_w = np.asarray(chain_lib.pipelined_encode(
                code, _gather(rows)[0], num_chunks=nc, order=order))[None]
        else:
            coded_w = np.asarray(multi_lib.pipelined_encode_many(
                code, _gather(rows), num_chunks=nc, stagger=stagger,
                order=order))
    else:
        coded_w = _codewords(_passthrough(code), rows,
                             _fused_encode(code, rows, acfg.l))
    out: dict[int, dict] = {}
    for b, step in enumerate(grp):
        blobs = _put_coded(store, step, perm, coded_w[b])
        out[step] = _publish(
            store, step, manifests[step], acfg, perm,
            (digest(blob) for blob in blobs), reclaim_hot=reclaim_hot,
            sched=sched, **extra)
    return out


def archive_many(store: NodeStore, steps: list[int], acfg: ArchiveConfig,
                 node_speeds: np.ndarray | None = None,
                 use_devices: bool | None = None,
                 stagger: int = 1, topology=None,
                 reclaim_hot: bool = True) -> list[dict]:
    """Batched migration: archive B hot steps CONCURRENTLY (paper §VI).

    All steps' objects are encoded together — on an n-device mesh via the
    staggered multi-chain, off-device via ONE fused batched pallas launch
    (the object axis rides the kernel grid). Steps whose block lengths
    differ are grouped so each encode sees a rectangular (B, k, block_len)
    batch. Returns the updated manifests in step order; each records its
    group (``batched_with``).

    ``topology`` engages the multi-chain scheduler
    (``repro.core.scheduler.plan_many``): when the cluster holds at least
    two chains' worth of nodes, concurrent chains are bin-packed onto
    DISJOINT node sets (no shared NICs); otherwise every chain runs
    staggered on the one scheduler-ordered node set. Each step's manifest
    records its placement (``perm`` / ``sched``) so repair reuses it.
    """
    code = acfg.code()
    on_chain = _use_chain(code, use_devices, acfg.n)

    manifests: dict[int, dict] = {}
    groups: dict[int, list[int]] = {}
    for step in steps:
        manifest = get_manifest(store, step)
        if manifest["tier"] != "hot":
            raise ValueError(f"step {step} already archived")
        manifests[step] = manifest
        groups.setdefault(manifest["block_bytes"], []).append(step)

    out: dict[int, dict] = {}
    for block_bytes, grp in groups.items():
        # (steps, perm, num_chunks, sched) of each chain
        if topology is None:
            chains = [(grp, *_plan_placement(acfg, block_bytes, None,
                                             node_speeds))]
        else:
            from repro.core import scheduler
            mplan = scheduler.plan_many(topology, len(grp), acfg.n, acfg.k,
                                        float(block_bytes), stagger=stagger)
            by_chain: dict[int, list[int]] = {}
            for b, s in enumerate(grp):
                by_chain.setdefault(mplan.assignment[b], []).append(s)
            chains = []
            for g, sub in sorted(by_chain.items()):
                p = mplan.plans[g]
                chains.append((sub, np.asarray(p.order), p.num_chunks, {
                    **p.to_manifest(), "topology": topology.to_dict(),
                    "chain_group": int(g)}))
        for sub, perm, nc, sched in chains:
            out.update(_archive_group(
                store, sub, acfg, code, perm, nc, stagger, on_chain,
                manifests, sched, reclaim_hot,
                batched_with=[int(s) for s in sub]))
    return [out[s] for s in steps]


def reclaim_replicas(store: NodeStore, step: int) -> dict | None:
    """Drop a retained hot tier AFTER digest-verifying the archived copy.

    The second phase of a ``reclaim_hot=False`` migration: the replicas
    are deleted only once ALL n coded blocks are on their recorded nodes
    and match their digests. A missing or corrupt shard defers the reclaim
    (returns None) until the scrubber has healed it; a digest-MISMATCHED
    shard is deleted on the spot, demoting it to the missing-shard state
    the repair path heals. Returns the updated manifest, the manifest
    unchanged if no replicas are retained (idempotent); raises ValueError
    for a step that was never archived.
    """
    manifest = get_manifest(store, step)
    if manifest["tier"] == "hot":
        raise ValueError(
            f"step {step} is not archived — refusing to reclaim replicas")
    if not manifest.get("hot_retained"):
        return manifest
    alive = {pos for pos, _ in _alive_coded(store, step, manifest)}
    if len(alive) < manifest["n"]:
        for pos in range(manifest["n"]):   # corrupt copies -> missing
            rel = ARC.format(step=step, i=pos)
            if pos not in alive and store.has(manifest["perm"][pos], rel):
                store.delete(manifest["perm"][pos], rel)
        return None                      # unverified shards: keep the replicas
    _drop_hot(store, step, manifest)
    manifest = {**manifest, "hot_retained": False}
    _put_manifest(store, step, manifest)
    return manifest


def archive_classical(store: NodeStore, step: int, acfg: ArchiveConfig) -> dict:
    """CEC baseline (paper Fig. 1): single node gathers k blocks, computes
    m parities, scatters them. Used by benchmarks for comparison."""
    manifest = get_manifest(store, step)
    blocks = hot_load(store, step, manifest)
    code = classical.make_code(acfg.n, acfg.k, l=acfg.l)
    parity_w = classical.encode_np(code, _words(blocks, acfg.l))
    coded = np.concatenate([blocks, _u8(parity_w)], axis=0)
    coded_blobs = [coded[i].tobytes() for i in range(acfg.n)]
    for i in range(acfg.n):
        store.put(i, ARC.format(step=step, i=i), coded_blobs[i])
    for node, held in enumerate(manifest["placement"]):
        for j in held:
            store.delete(node, HOT.format(step=step, j=j))
    manifest = {**manifest, "tier": "archive_classical",
                "perm": list(range(acfg.n)),
                "coded_digests": [digest(b) for b in coded_blobs],
                "orig_digests": manifest["digests"]}
    _put_manifest(store, step, manifest)
    return manifest


# ---------------------------------------------------------------------------
# restore & repair
# ---------------------------------------------------------------------------


def _alive_coded(store: NodeStore, step: int, manifest: dict):
    """[(codeword_row, bytes)] for every surviving coded block."""
    perm = manifest["perm"]
    out = []
    for pos in range(manifest["n"]):
        node = perm[pos]
        rel = ARC.format(step=step, i=pos)
        if store.has(node, rel):
            raw = store.get(node, rel)
            if digest(raw) == manifest["coded_digests"][pos]:
                out.append((pos, raw))
    return out

def restore_blocks(store: NodeStore, step: int, acfg: ArchiveConfig,
                   heal: bool = False) -> np.ndarray:
    """(k, B) uint8 original blocks from whichever tier survives.

    ``heal=True``: when the read detects missing coded shards (and the step
    is still recoverable), re-materialize them via ``repair`` before
    returning — reads double as scrubs. Raw-array shim over
    :func:`restore_blocks_ex` (which additionally reports how the read
    was served).
    """
    return restore_blocks_ex(store, step, acfg, heal=heal).data


def restore_blocks_ex(store: NodeStore, step: int, acfg: ArchiveConfig,
                      heal: bool = False) -> ReadResult:
    """:class:`ReadResult` with ``data`` = (k, B) uint8 original blocks.

    The full-information form of ``restore_blocks``: same bytes, plus the
    serve path (hot / coded / degraded), the nodes that funded the read,
    and whether ``heal=True`` actually repaired shards along the way.
    """
    manifest = get_manifest(store, step)
    if manifest["tier"] == "hot":
        blocks, nodes = _hot_load_ex(store, step, manifest)
        return _result(blocks, "hot", nodes, False, step)
    if manifest["tier"] == "archive" and manifest.get("streaming"):
        return _restore_streaming(store, step, acfg, manifest, heal=heal)
    alive = _alive_coded(store, step, manifest)
    healed = False
    if heal and manifest["tier"] == "archive" and len(alive) < manifest["n"]:
        try:
            healed = bool(repair(store, step, acfg))
        except ValueError:
            # undecodable survivors: with retained replicas the hot tier
            # below still serves the read; without them, fall through to
            # the clear too-few-blocks error instead of dying mid-heal
            if not manifest.get("hot_retained"):
                raise
        manifest = get_manifest(store, step)   # perm may have changed
        alive = _alive_coded(store, step, manifest)
    if len(alive) < manifest["k"]:
        if manifest.get("hot_retained"):
            # two-phase migration: the replicas were never reclaimed, so
            # the hot tier still backs the object
            blocks, nodes = _hot_load_ex(store, step, manifest)
            return _result(blocks, "hot", nodes, healed, step)
        raise FileNotFoundError(
            f"step {step}: only {len(alive)} of n={manifest['n']} coded "
            f"blocks alive, need k={manifest['k']}")
    k, l = manifest["k"], manifest["l"]
    ids = [pos for pos, _ in alive[: manifest["n"]]]
    shards = np.stack([np.frombuffer(raw, dtype=np.uint8)
                       for _, raw in alive])
    shards_w = _words(shards, l)
    # use the first decodable subset (greedy rank selection inside)
    if manifest["tier"] == "archive_classical":
        code = classical.make_code(manifest["n"], k, l=l)
        data_w = classical.decode_np(code, ids, shards_w)
    else:
        code = _manifest_code(manifest)
        data_w = code.decode_np(
            ids, shards_w, block_words=manifest["block_bytes"] // (l // 8))
    blocks = _u8(data_w)
    for j in range(k):
        # a real exception (asserts vanish under python -O): a decode that
        # does not match the archived digest must never be returned
        if digest(blocks[j].tobytes()) != manifest["orig_digests"][j]:
            raise ValueError(
                f"step {step}: decoded block {j} does not match the archived "
                f"digest — corrupt shard set or code mismatch")
    served = "coded" if len(alive) == manifest["n"] else "degraded"
    return _result(blocks, served,
                   [manifest["perm"][pos] for pos in ids], healed, step)


def _manifest_code(manifest: dict) -> codes.ErasureCode:
    """Reconstruct the exact code a manifest describes (any family)."""
    return codes.from_spec(codes.CodeSpec.from_manifest(manifest))


def _restore_streaming(store: NodeStore, step: int, acfg: ArchiveConfig,
                       manifest: dict, heal: bool = False) -> ReadResult:
    """Stripe-at-a-time restore of a streamed archive, as a ReadResult.

    Reads only each stripe's word range of k helper shards
    (``NodeStore.get_range``) and verifies it against the manifest's
    per-stripe digests as it goes — a corrupt slice demotes that shard to
    missing and the helper set is re-planned, so corruption is routed
    around exactly as ``_alive_coded`` does for whole files, without ever
    reading (or holding) more than k stripes at once.
    """
    code = _manifest_code(manifest)
    k, B, l = manifest["k"], manifest["block_bytes"], manifest["l"]
    wb = l // 8
    stream = manifest["streaming"]
    plan = streaming.plan_stream(B // wb, stream["superchunk_bytes"] // wb,
                                 l=l, num_chunks=stream["num_chunks"])
    perm = manifest["perm"]
    healed = False
    if heal and any(not store.has(perm[pos], ARC.format(step=step, i=pos))
                    for pos in range(manifest["n"])):
        try:
            healed = bool(repair(store, step, acfg))
        except ValueError:
            if not manifest.get("hot_retained"):
                raise
        manifest = get_manifest(store, step)   # perm may have changed
        perm = manifest["perm"]
    dead = {pos for pos in range(manifest["n"])
            if not store.has(perm[pos], ARC.format(step=step, i=pos))}
    out = np.zeros((k, B), dtype=np.uint8)
    while True:
        alive_ids = [p for p in range(manifest["n"]) if p not in dead]
        helpers = None
        if len(alive_ids) >= k:
            try:
                chosen = codes.independent_rows(code.G[alive_ids], k, l)
                helpers = [alive_ids[p] for p in chosen]
            except ValueError:
                helpers = None
        if helpers is None:
            if manifest.get("hot_retained"):
                # two-phase migration: the replicas still back the object
                blocks, nodes = _hot_load_ex(store, step, manifest)
                return _result(blocks, "hot", nodes, healed, step)
            raise FileNotFoundError(
                f"step {step}: only {len(alive_ids)} decodable of "
                f"n={manifest['n']} coded blocks, need k={k}")
        D = code.decode_matrix(helpers)
        corrupt = None
        for s in range(plan.num_superchunks):
            lo, hi = plan.stripe_span(s)
            rec = stream["stripes"][s]
            slices = []
            for h in helpers:
                raw = store.get_range(perm[h], ARC.format(step=step, i=h),
                                      lo * wb, (hi - lo) * wb)
                if digest(raw) != rec["coded_digests"][h]:
                    corrupt = h
                    break
                slices.append(np.frombuffer(raw, dtype=np.uint8)
                              .view(gf.WORD_DTYPE[l]))
            if corrupt is not None:
                break
            out[:, lo * wb:hi * wb] = _u8(
                gf.gf_matmul_np(D, np.stack(slices), l))
        if corrupt is None:
            break
        dead.add(corrupt)
    for j in range(k):
        if digest(out[j].tobytes()) != manifest["orig_digests"][j]:
            raise ValueError(
                f"step {step}: decoded block {j} does not match the archived "
                f"digest — corrupt shard set or code mismatch")
    served = "coded" if not dead else "degraded"
    return _result(out, served, [perm[h] for h in helpers], healed, step)


def _place_repaired(store: NodeStore, step: int, manifest: dict,
                    missing: list[int], repaired: np.ndarray,
                    replacement_nodes: dict[int, int] | None) -> None:
    """Digest-verify ALL repaired rows against the manifest, then place.

    Verification precedes every write, so a miscomputed repair raises
    ValueError without installing a single block or touching the manifest.
    """
    with span("place_repaired"):
        blobs = _rows(repaired)
        for blob, pos in zip(blobs, missing):
            if digest(blob) != manifest["coded_digests"][pos]:
                raise ValueError(
                    f"repair of codeword row {pos} does not match the "
                    f"archived digest — refusing to install")
        perm = list(manifest["perm"])
        for pos, blob in zip(missing, blobs):
            node = perm[pos]
            if replacement_nodes and pos in replacement_nodes:
                node = replacement_nodes[pos]
                perm[pos] = node
            store.put(node, ARC.format(step=step, i=pos), blob)
        manifest["perm"] = perm
        _put_manifest(store, step, manifest)


def _repair_state(store: NodeStore, step: int,
                  manifest: dict) -> tuple[list[int], list[int], list[bytes]]:
    """(missing, helpers, helper_shards) for one step's repair.

    Liveness is probed by existence (no full-archive hashing); only the k
    helper shards that fund the reconstruction are read, and each is
    digest-verified — a corrupt-but-present helper is demoted to missing
    and the plan recomputed, so corruption is healed, not propagated.
    Raises ValueError when the survivors are not decodable.
    """
    code = _manifest_code(manifest)
    perm = manifest["perm"]
    dead = {pos for pos in range(manifest["n"])
            if not store.has(perm[pos], ARC.format(step=step, i=pos))}
    raws: dict[int, bytes] = {}
    while True:
        missing = sorted(dead)
        if not missing:
            return [], [], []
        alive = [p for p in range(manifest["n"]) if p not in dead]
        with span("repair_plan") as plan_span:
            helpers = code.repair_helpers(missing, alive)
            plan_span.set_metadata(helpers=len(helpers))
        for h in helpers:
            if h not in raws:
                raws[h] = store.get(perm[h], ARC.format(step=step, i=h))
        bad = [h for h in helpers
               if digest(raws[h]) != manifest["coded_digests"][h]]
        if not bad:
            return missing, helpers, [raws[h] for h in helpers]
        dead |= set(bad)


def repair(store: NodeStore, step: int, acfg: ArchiveConfig,
           replacement_nodes: dict[int, int] | None = None,
           use_devices: bool | None = None,
           superchunk_bytes: int | None = None) -> list[int]:
    """Recompute lost coded blocks and place them (on replacements if given).

    Targeted repair: only the missing rows are reconstructed — one GF inner
    product over k digest-verified helper shards
    (``fault_tolerance.repair_plan``), run through the reverse pipelined
    helper chain on a device mesh or the fused repair kernel off-device. No
    decode-to-object-and-re-encode, and no reads beyond the k helpers.
    Every repaired row is digest-verified against the manifest BEFORE any
    placement (a failed repair raises; it never installs a corrupt block).

    Returns the list of repaired codeword rows; raises ValueError when more
    than n-k rows are lost.
    """
    return repair_many(store, [step], acfg,
                       replacement_nodes=replacement_nodes,
                       use_devices=use_devices,
                       superchunk_bytes=superchunk_bytes)[0]


def repair_many(store: NodeStore, steps: list[int], acfg: ArchiveConfig,
                replacement_nodes: dict[int, int] | None = None,
                use_devices: bool | None = None,
                stagger: int = 1,
                superchunk_bytes: int | None = None) -> list[list[int]]:
    """Heal several archived steps CONCURRENTLY (batched repair).

    After a node failure every object archived on the node set lost the
    same codeword rows, so the repairs share helpers and coefficients:
    steps are grouped by (code geometry + seed, block length, missing rows,
    helper set) and each group runs as ONE staggered reverse-chain launch
    on a device mesh (B repairs share one ``shard_map`` program) or one
    fused batched kernel launch off-device. Per step, only the k helper
    shards are read (digest-verified; corrupt helpers are demoted to
    missing and repaired too — see ``_repair_state``). Returns the repaired
    rows per step, in step order.

    Streamed archives heal stripe-by-stripe: ``superchunk_bytes`` (or,
    when unset, the geometry recorded in the step's ``streaming`` manifest)
    runs the device reverse chains through the streaming executor — per-
    stripe launches of one cached program, cross-stripe scheduled per Li
    et al. — so a lost node on a many-stripe object repairs under the same
    bounded device footprint it archived with. The repaired bytes are
    identical either way (positionwise codes).
    """
    from repro.kernels.gf_encode import ops as kernel_ops
    manifests: dict[int, dict] = {}
    layout: dict[tuple, list[int]] = {}
    state: dict[int, tuple[list[int], list[int], list[bytes]]] = {}
    for step in steps:
        manifest = get_manifest(store, step)
        if manifest["tier"] != "archive":
            raise ValueError(f"step {step} not archived")
        manifests[step] = manifest
        missing, helpers, raws = _repair_state(store, step, manifest)
        state[step] = (missing, helpers, raws)
        # steps only batch when they share the CODE as well as the loss
        # pattern — a seed/geometry mismatch must not borrow coefficients
        key = (manifest["block_bytes"], manifest["n"], manifest["k"],
               manifest["l"], manifest["seed"],
               manifest.get("family", "rapidraid"), tuple(missing),
               tuple(helpers))
        layout.setdefault(key, []).append(step)

    out: dict[int, list[int]] = {}
    for (*_, missing_t, helpers_t), grp in layout.items():
        missing = list(missing_t)
        helpers = list(helpers_t)
        if not missing:
            for step in grp:
                out[step] = []
            continue
        l = manifests[grp[0]]["l"]
        code = _manifest_code(manifests[grp[0]])
        # per object, its helpers' verified buffers as word views (no copy)
        rows = [[np.frombuffer(raw, dtype=gf.WORD_DTYPE[l])
                 for raw in state[s][2]] for s in grp]
        if not code.positionwise:
            # sub-packetized repair (regenerating codes): per-object host
            # combine of the beta-sub-block helper summands
            shards_w = _gather(rows)
            repaired_w = np.stack([
                code.repair_np(missing, helpers, shards_w[b])
                for b in range(len(grp))])
        elif _use_chain(code, use_devices, len(helpers), repair=True):
            shards_w = _gather(rows)            # (B_obj, |helpers|, B)
            stream = manifests[grp[0]].get("streaming") or {}
            sc_bytes = (stream.get("superchunk_bytes")  # archive's geometry
                        if superchunk_bytes is None else superchunk_bytes)
            sc_words = (None if sc_bytes is None
                        else max(1, sc_bytes // (l // 8)))
            nc = acfg.num_chunks
            if sc_words is None or sc_words >= shards_w.shape[-1]:
                # identity plan: the monolithic chunking rules apply
                sc_words = None
                nc = streaming.chunk_count(shards_w.shape[-1], l, nc)
            repaired_w = np.asarray(repair_lib.pipelined_repair_many(
                code, helpers, shards_w, missing, num_chunks=nc,
                stagger=stagger, superchunk_words=sc_words))
        else:
            # helpers is already the plan's decodable helper set, so the
            # plan over it returns the same set and an aligned R
            with span("repair_plan", helpers=len(helpers)):
                _, R = fault_tolerance.repair_plan(code, missing, helpers)
            shards_dev = _to_device(rows)       # (B_obj, |helpers|, B)
            with span("kernel_launch", kernel="encode_packed"):
                repaired_dev = gf.unpack_u32(kernel_ops.encode_packed(
                    R, gf.pack_u32(shards_dev, l), l), l)
            with span("d2h", bytes=repaired_dev.nbytes):
                repaired_w = np.asarray(repaired_dev)
        for b, step in enumerate(grp):
            _place_repaired(store, step, manifests[step], missing,
                            _u8(repaired_w[b]), replacement_nodes)
            out[step] = missing
    return [out[s] for s in steps]


# ---------------------------------------------------------------------------
# degraded reads: byte ranges without materializing the object
# ---------------------------------------------------------------------------


def read_range(store: NodeStore, step: int, acfg: ArchiveConfig,
               offset: int, nbytes: int, heal: bool = False) -> bytes:
    """Serve object bytes [offset, offset+nbytes) without full-object decode.

    Raw-bytes shim over :func:`read_range_ex`; see there for the serve-path
    semantics the full-information form additionally reports.
    """
    return read_range_ex(store, step, acfg, offset, nbytes, heal=heal).data


def _hot_range(store: NodeStore, step: int, manifest: dict,
               offset: int, end: int) -> tuple[bytes, list[int]]:
    """Serve [offset, end) from surviving replicas; -> (bytes, holder nodes).

    Used for the hot tier proper AND as the ``hot_retained`` fallback when
    an archived object's survivors are not decodable mid two-phase reclaim.
    """
    B = manifest["block_bytes"]
    out = bytearray()
    nodes = []
    for j in range(offset // B, (end - 1) // B + 1):
        a = max(offset, j * B) - j * B
        b = min(end, (j + 1) * B) - j * B
        rel = HOT.format(step=step, j=j)
        holders = [i for i, held in enumerate(manifest["placement"])
                   if j in held and store.has(i, rel)]
        if not holders:
            raise FileNotFoundError(
                f"hot block {j} of step {step} lost on all replicas")
        out += store.get_range(holders[0], rel, a, b - a)
        nodes.append(holders[0])
    return bytes(out), nodes


def read_range_ex(store: NodeStore, step: int, acfg: ArchiveConfig,
                  offset: int, nbytes: int, heal: bool = False) -> ReadResult:
    """:class:`ReadResult` with ``data`` = object bytes [offset, offset+nbytes).

    Hot tier: slice reads straight from a surviving replica. Archive tier:
    a DEGRADED READ — only the covering word range of k surviving shards is
    read from disk (``NodeStore.get_range``) and only the touched blocks'
    rows of the decode matrix are applied, so a small read costs k small
    reads regardless of how many shards were lost. Slice reads cannot be
    digest-checked (the manifest pins whole-block digests); ``heal=True``
    first re-materializes any missing shards (full repair, digest-verified)
    so subsequent reads run non-degraded.

    Offsets address the padded k*block_bytes object; out-of-bounds or
    inverted ranges raise ValueError (no silent clamping — a caller that
    wants clamp-to-EOF semantics owns the clamp, as
    ``CheckpointManager.read_range`` does against its ``blob_len``).
    Streamed archives (manifest ``streaming``) serve ranges identically:
    positionwise stripes concatenate to the same coded bytes, so the
    range read touches exactly the stripes that cover it.
    """
    manifest = get_manifest(store, step)
    k, B, l = manifest["k"], manifest["block_bytes"], manifest["l"]
    end = offset + nbytes
    if offset < 0 or nbytes < 0 or end > k * B:
        raise ValueError(
            f"read_range: range [{offset}, {end}) is "
            f"{'inverted' if nbytes < 0 else 'out of bounds'} for step "
            f"{step}'s {k * B}-byte object (offset={offset}, "
            f"nbytes={nbytes})")
    if nbytes == 0:
        served = "hot" if manifest["tier"] == "hot" else "coded"
        return _result(b"", served, [], False, step)
    j0, j1 = offset // B, (end - 1) // B

    if manifest["tier"] == "hot":
        out, nodes = _hot_range(store, step, manifest, offset, end)
        return _result(out, "hot", nodes, False, step)

    if manifest["tier"] != "archive":
        # classical tier: fall back to full restore (no RapidRAID decode)
        res = restore_blocks_ex(store, step, acfg)
        return _result(res.data.reshape(-1)[offset:end].tobytes(),
                       res.served_from, res.nodes, res.healed, step)

    code = _manifest_code(manifest)
    if not code.positionwise:
        # sub-packetized shards have no positionwise word ranges — serve
        # the range from a full (digest-verified) restore
        res = restore_blocks_ex(store, step, acfg, heal=heal)
        return _result(res.data.reshape(-1)[offset:end].tobytes(),
                       res.served_from, res.nodes, res.healed, step)

    perm = manifest["perm"]
    healed = False
    if heal and any(not store.has(perm[pos], ARC.format(step=step, i=pos))
                    for pos in range(manifest["n"])):
        # existence probe only — slice reads cannot digest-check, so heal
        # here targets lost shards; a full scrub is repair()/repair_many()
        try:
            healed = bool(repair(store, step, acfg))
        except ValueError:
            # undecodable survivors: retained replicas (below) still serve
            # the range; without them the decodability check raises clearly
            if not manifest.get("hot_retained"):
                raise
        manifest = get_manifest(store, step)
        perm = manifest["perm"]
    with span("read_plan"):
        alive_ids = [pos for pos in range(manifest["n"])
                     if store.has(perm[pos], ARC.format(step=step, i=pos))]
        try:
            chosen = codes.independent_rows(code.G[alive_ids], k, l)
        except ValueError as e:
            if manifest.get("hot_retained"):
                # two-phase migration window: survivors are not decodable
                # but the replicas were never reclaimed — the hot tier
                # still backs the object (same fallback as
                # restore_blocks_ex)
                out, nodes = _hot_range(store, step, manifest, offset, end)
                return _result(out, "hot", nodes, healed, step)
            raise FileNotFoundError(
                f"step {step}: survivors not decodable ({e})") from None
        helpers = [alive_ids[p] for p in chosen]

        # per touched block: read ONLY its word-aligned slice of each helper
        # shard and apply that block's row of the decode matrix
        # (degraded_read_np's math with D hoisted out of the loop)
        D = code.decode_matrix(helpers)
    wb = l // 8
    dt = gf.WORD_DTYPE[l]
    out = bytearray()
    for j in range(j0, j1 + 1):
        a = max(offset, j * B) - j * B
        b = min(end, (j + 1) * B) - j * B
        lo = (a // wb) * wb
        hi = -(-b // wb) * wb
        raws = [store.get_range(perm[h], ARC.format(step=step, i=h),
                                lo, hi - lo) for h in helpers]
        with span("host_copy", bytes=len(helpers) * (hi - lo)):
            slices_w = np.stack([np.frombuffer(raw, dtype=np.uint8).view(dt)
                                 for raw in raws])
        with span("read_decode", bytes=slices_w.nbytes):
            row = _u8(gf.gf_matmul_np(D[[j]], slices_w, l))[0]
        with span("host_copy", bytes=b - a):
            out += row[a - lo:b - lo].tobytes()
    served = "coded" if len(alive_ids) == manifest["n"] else "degraded"
    with span("host_copy", bytes=len(out)):
        data = bytes(out)
    return _result(data, served, [perm[h] for h in helpers], healed, step)


def publish_device_archive(store: NodeStore, step: int, acfg: ArchiveConfig,
                           blocks: np.ndarray, coded: np.ndarray,
                           blob_len: int, state_key: str | None = None
                           ) -> dict:
    """Place an already-encoded checkpoint (device-direct write path) into
    the coded tier and publish its manifest.

    ``repro.checkpoint.devio`` computes ``blocks`` (k, B) and ``coded``
    (n, B) in ONE on-device program; this is the storage-side half:
    codeword row i on node i, and a manifest every reader consumes
    unchanged. No hot replicas ever hit disk on this path.
    """
    if blocks.shape != (acfg.k, blocks.shape[1]) or blocks.dtype != np.uint8:
        raise ValueError(f"blocks must be (k={acfg.k}, B) uint8, "
                         f"got {blocks.shape} {blocks.dtype}")
    if coded.shape != (acfg.n, blocks.shape[1]):
        raise ValueError(f"coded must be (n={acfg.n}, B={blocks.shape[1]}), "
                         f"got {coded.shape}")
    base = _base_manifest(step, acfg, blocks.shape[1],
                          [digest(b) for b in _rows(blocks)], tier="archive")
    blobs = _put_coded(store, step, range(acfg.n), coded)
    return _publish(store, step, base, acfg, range(acfg.n),
                    (digest(b) for b in blobs), blob_len=int(blob_len),
                    device_direct=True, state_key=state_key)


def publish_streaming_archive(store: NodeStore, step: int,
                              acfg: ArchiveConfig, blocks: np.ndarray,
                              blob_len: int, superchunk_bytes: int,
                              state_key: str | None = None,
                              use_devices: bool | None = None) -> dict:
    """Stream an in-memory (k, B) block set into the coded tier under a
    bounded device footprint.

    The checkpoint streaming route (``repro.checkpoint.devio.save_state``
    above its ``footprint_bytes`` threshold): the train state's blocks are
    already on the host, but the ENCODE must not materialize the object on
    the devices: its stripes are slices of the blocks, coded through
    ``archive_step``'s stripe body. Same manifest contract as
    ``publish_device_archive`` plus the ``streaming`` stripe records.
    """
    code = acfg.code()
    if blocks.ndim != 2 or blocks.shape[0] != acfg.k \
            or blocks.dtype != np.uint8:
        raise ValueError(f"blocks must be (k={acfg.k}, B) uint8, "
                         f"got {blocks.shape} {blocks.dtype}")
    wb = acfg.l // 8
    blocks = np.ascontiguousarray(blocks)
    plan = streaming.plan_stream(blocks.shape[1] // wb,
                                 max(1, superchunk_bytes // wb), l=acfg.l,
                                 num_chunks=acfg.num_chunks)
    base = _base_manifest(step, acfg, blocks.shape[1], None, tier="archive")
    return _archive_step_streaming(
        store, step, acfg, base, code, range(acfg.n), acfg.num_chunks, None,
        plan, _use_chain(code, use_devices, acfg.n), False,
        lambda j, offset, nbytes: blocks[j, offset:offset + nbytes],
        blob_len=int(blob_len), state_key=state_key)


# ---------------------------------------------------------------------------
# manifests (replicated on every node)
# ---------------------------------------------------------------------------


def _put_manifest(store: NodeStore, step: int, manifest: dict) -> None:
    with span("manifest"):
        data = json.dumps(manifest).encode()
        for i in range(store.n_nodes):
            store.put(i, MANIFEST.format(step=step), data)


_REQUIRED_KEYS = ("step", "tier", "n", "k", "l", "seed", "block_bytes")
_TIER_KEYS = {
    "hot": ("placement", "digests"),
    "archive": ("placement", "perm", "coded_digests", "orig_digests"),
    "archive_classical": ("placement", "perm", "coded_digests",
                          "orig_digests"),
}


def _validate_manifest(manifest, step: int) -> dict:
    """Clear ValueError (never a downstream KeyError) for damaged manifests."""
    if not isinstance(manifest, dict):
        raise ValueError(f"step {step}: manifest is {type(manifest).__name__},"
                         f" not an object")
    tier = manifest.get("tier")
    if tier not in _TIER_KEYS:
        raise ValueError(f"step {step}: manifest tier {tier!r} unknown "
                         f"(want one of {sorted(_TIER_KEYS)})")
    missing = [key for key in _REQUIRED_KEYS + _TIER_KEYS[tier]
               if key not in manifest]
    if missing:
        raise ValueError(f"step {step}: manifest ({tier}) is missing "
                         f"required keys {missing} — corrupt or "
                         f"partially written")
    stream = manifest.get("streaming")
    if stream is not None:
        want = ("num_superchunks", "superchunk_bytes", "num_chunks",
                "stripes")
        absent = [key for key in want if key not in stream]
        if absent:
            raise ValueError(f"step {step}: streaming manifest record is "
                             f"missing keys {absent}")
        if len(stream["stripes"]) != stream["num_superchunks"]:
            raise ValueError(
                f"step {step}: streaming record claims "
                f"{stream['num_superchunks']} super-chunks but carries "
                f"{len(stream['stripes'])} stripe records")
    family = manifest.get("family", "rapidraid")
    if family not in codes.families():
        raise ValueError(
            f"step {step}: manifest names unknown code family {family!r} "
            f"— registered families: {', '.join(codes.families())}")
    return manifest


def get_manifest(store: NodeStore, step: int) -> dict:
    """First VALID manifest replica; a corrupt replica falls through to the
    next node's copy, and only-corrupt-copies raises a clear ValueError
    (so a scrubber can report the step instead of dying on JSON internals).
    """
    rel = MANIFEST.format(step=step)
    errors: list[str] = []
    found = False
    with span("manifest"):
        for i in range(store.n_nodes):
            if not store.has(i, rel):
                continue
            found = True
            try:
                return _validate_manifest(json.loads(store.get(i, rel)), step)
            except ValueError as e:       # JSONDecodeError is a ValueError
                errors.append(f"node {i}: {e}")
    if found:
        raise ValueError(
            f"step {step}: every manifest replica is corrupt — "
            + "; ".join(errors))
    raise FileNotFoundError(f"no manifest for step {step}")


def list_steps(store: NodeStore) -> list[int]:
    """Steps with a published manifest on any node.

    Unparseable names in a ``manifests/`` directory raise a clear
    ValueError naming the file; a ``.json.tmp`` is an interrupted
    ``NodeStore.put`` — ignored when the published manifest exists
    somewhere, reported when the step has nothing but partial writes.
    """
    import os
    import re
    pat = re.compile(r"^(\d{8})\.json(\.tmp)?$")
    steps: set[int] = set()
    partial: set[int] = set()
    for i in range(store.n_nodes):
        d = store.path(i, "manifests")
        if not os.path.isdir(d):
            continue
        for f in os.listdir(d):
            m = pat.match(f)
            if m is None:
                raise ValueError(
                    f"node {i}: unrecognized file {f!r} in manifests/ — "
                    f"want NNNNNNNN.json")
            (partial if m.group(2) else steps).add(int(m.group(1)))
    orphans = partial - steps
    if orphans:
        raise ValueError(
            f"steps {sorted(orphans)} have only partially-written manifests "
            f"(interrupted put left .json.tmp and no published copy)")
    return sorted(steps)
