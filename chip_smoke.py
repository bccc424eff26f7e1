"""Chip smoke: the paper's (16,11) archival path once, end to end, on a TPU.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4     # the multi-chip chain path only

One process drives the default ``ArchiveConfig`` -- RapidRAID (16,11) over
GF(2^16) with 64 MiB blocks, so one object is 704 MiB as in paper §VI --
through the ``StorageClient`` entry points:

  write hot -> archive -> oracle -> lose n-k shards -> repair -> read back
  -> write hot x2 -> archive_many (one batched launch) -> oracle

Data comes from ``--seed``; the node store lives in a temporary directory
that is removed at the end (about 4 GB at its peak). Coded shards are
checked against ``encode_np`` on seeded word windows (the first, the last
and a random 1 MiB of every block: the code is positionwise, so a window's
encode is exact), repaired rows against the manifest digests, reads
against the original bytes.

Each phase prints one JSON line: its wall seconds (smoke timings with
compilation included, not benchmark numbers), where the coding ran
(``device``: fused Pallas kernel launches and no host GF coding; ``host``:
numpy GF arithmetic), the kernel launches and distinct kernel programs,
whether the compiled kernel program holds ``tpu_custom_call`` (the kernel
was compiled, not interpreted) and the jitcache compile count. The last
line is ``{"ok": true, "device": {...}}``. A failed check raises and the
script exits non-zero; without a TPU it exits non-zero before any phase.

``--chips 4`` runs only what exists across chips: a shard_map chain
``pipelined_encode``, a staggered ``pipelined_encode_many`` and a
``pipelined_repair`` of a (4,3) RapidRAID code over GF(2^16) at 64 MiB
blocks, each compared byte for byte with the one-chip fused encode and
with ``encode_np`` windows, and each checked to span its own device per
chain position.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import gf, jitcache  # noqa: E402
from repro.kernels.gf_encode import ops as kernel_ops  # noqa: E402
from repro.storage import archive as arc  # noqa: E402
from repro.storage.client import ArchiveConfig, StorageClient  # noqa: E402
from repro.storage.object_store import NodeStore  # noqa: E402

MIB = 1 << 20
BLOCK_BYTES = 64 * MIB          # paper §VI: 64 MB blocks, 704 MB objects
#: the jitted kernel programs behind every fused encode/repair launch
KERNEL_PROGRAMS = ("_encode_packed_jit", "_encode_mxu_jit")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


# ---------------------------------------------------------------------------
# what a phase ran: kernel launches and host GF coding
# ---------------------------------------------------------------------------


def _abstract(x):
    """The argument as the jit cache keyed it (an uncommitted array has
    no sharding in the key), so lowering it again finds the same program."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return x


class PhaseProbe:
    """Records, while active, every launch of the jitted kernel programs
    (with abstract arguments, so the program can be lowered again) and the
    words that host numpy GF matmuls coded. Coefficient algebra on
    matrices no wider than ``n`` columns is set-up, not coding."""

    def __init__(self, n: int):
        self.n = n
        self.calls: list[tuple] = []
        self.host_words = 0

    def __enter__(self):
        self._saved = {name: getattr(kernel_ops, name)
                       for name in KERNEL_PROGRAMS}
        for name, fn in self._saved.items():
            setattr(kernel_ops, name, self._recording(fn))
        self._matmul = gf.gf_matmul_np
        gf.gf_matmul_np = self._counting(self._matmul)
        return self

    def __exit__(self, *exc) -> None:
        for name, fn in self._saved.items():
            setattr(kernel_ops, name, fn)
        gf.gf_matmul_np = self._matmul

    def _recording(self, fn):
        def launch(*args):
            self.calls.append((fn, tuple(_abstract(a) for a in args)))
            return fn(*args)
        return launch

    def _counting(self, fn):
        def matmul(A, B, l):
            if np.shape(B)[-1] > self.n:
                self.host_words += int(np.size(B))
            return fn(A, B, l)
        return matmul

    def programs(self) -> list[tuple]:
        """Distinct (program, abstract args) pairs launched."""
        seen, out = set(), []
        for fn, args in self.calls:
            key = (id(fn), repr(args))
            if key not in seen:
                seen.add(key)
                out.append((fn, args))
        return out


def _has_tpu_kernel(fn, args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def run_phase(name: str, n: int, body, *args) -> dict:
    """Run one phase under a probe; -> its record (printed as JSON)."""
    t0 = time.perf_counter()
    with PhaseProbe(n) as probe:
        extra = body(*args) or {}
    wall = time.perf_counter() - t0
    progs = probe.programs()
    launches, host = len(probe.calls), probe.host_words
    coding = ("device" if launches and not host else
              "host" if host and not launches else
              "none" if not (launches or host) else "mixed")
    rec = {
        "phase": name, "wall_s": wall, "coding": coding,
        "kernel_launches": launches, "kernel_programs": len(progs),
        "interpret": sorted({bool(a[-1]) for _, a in progs}),
        "tpu_custom_call": bool(progs) and all(_has_tpu_kernel(fn, a)
                                              for fn, a in progs),
        "host_coded_words": host,
        "jitcache_compiles": sum(jitcache.compile_counts().values()),
        **extra,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------------------------------
# the one-chip archival path
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Smoke:
    """Everything the phases share: the client, its code and the objects."""

    client: StorageClient
    acfg: ArchiveConfig
    rng: np.random.Generator
    block_bytes: int
    window_bytes: int
    objects: dict = dataclasses.field(default_factory=dict)

    @property
    def code(self):
        return self.acfg.code()

    def new_object(self, step: int) -> np.ndarray:
        k, B = self.acfg.k, self.block_bytes
        obj = np.frombuffer(self.rng.bytes(k * B), np.uint8).reshape(k, B)
        self.objects[step] = obj
        return obj


def word_windows(rng: np.random.Generator, words: int,
                 win: int) -> list[tuple[int, int]]:
    """The first, the last and one random window of ``win`` words."""
    win = min(win, words)
    r = int(rng.integers(0, words - win + 1))
    return [(0, win), (words - win, words), (r, r + win)]


def oracle_check(s: Smoke, step: int) -> int:
    """Coded shards of ``step`` == ``encode_np`` on word windows; -> bytes."""
    code, l = s.code, s.acfg.l
    wb = l // 8
    manifest = s.client.manifest(step)
    check(manifest["tier"] == "archive", f"step {step} not archived")
    data_w = s.objects[step].view(gf.WORD_DTYPE[l])
    checked = 0
    for a, b in word_windows(s.rng, data_w.shape[1], s.window_bytes // wb):
        want = code.encode_np(np.ascontiguousarray(data_w[:, a:b]))
        for pos in range(code.n):
            got = s.client.store.get_range(
                manifest["perm"][pos], arc.ARC.format(step=step, i=pos),
                a * wb, (b - a) * wb)
            check(got == want[pos].tobytes(),
                  f"step {step} shard {pos} words [{a}, {b}) differ from "
                  f"encode_np")
            checked += len(got)
    return checked


def phase_write_hot(s: Smoke, steps: list[int]) -> dict:
    for step in steps:
        m = s.client.put_hot(step, s.new_object(step))
        check(m["tier"] == "hot", f"step {step} not hot after put_hot")
    return {"steps": steps}


def phase_archive(s: Smoke, step: int) -> dict:
    m = s.client.archive(step, reclaim_hot=True)
    check(m["tier"] == "archive" and not m.get("hot_retained"),
          f"step {step} not archived with its replicas reclaimed")
    return {"step": step}


def phase_oracle(s: Smoke, steps: list[int]) -> dict:
    return {"steps": steps,
            "bytes_checked": sum(oracle_check(s, st) for st in steps)}


def phase_lose(s: Smoke, step: int) -> dict:
    """Delete n-k coded shards, drawn from the seed among the loss
    patterns the code can repair."""
    code = s.code
    manifest = s.client.manifest(step)
    while True:
        lost = sorted(int(p) for p in s.rng.choice(
            code.n, code.n - code.k, replace=False))
        if code.decodable([p for p in range(code.n) if p not in lost]):
            break
    for pos in lost:
        s.client.store.delete(manifest["perm"][pos],
                              arc.ARC.format(step=step, i=pos))
    return {"step": step, "lost": lost}


def phase_repair(s: Smoke, step: int, lost: list[int]) -> dict:
    rows = s.client.repair(step)     # digest-verified before placement
    check(sorted(rows) == lost, f"repaired rows {rows}, lost {lost}")
    manifest = s.client.manifest(step)
    for pos in lost:
        check(s.client.store.has(manifest["perm"][pos],
                                 arc.ARC.format(step=step, i=pos)),
              f"repaired row {pos} not placed")
    return {"step": step, "repaired": rows}


def phase_read(s: Smoke, step: int) -> dict:
    """Byte ranges: the first window, one across a block boundary, a
    random one and the object's tail."""
    k, B, w = s.acfg.k, s.block_bytes, s.window_bytes
    total = k * B
    flat = s.objects[step].reshape(-1)
    j = int(s.rng.integers(1, k))
    r = int(s.rng.integers(0, total - w + 1))
    ranges = [(0, w), (j * B - w // 2, w), (r, w), (total - w, w)]
    served = set()
    for off, nbytes in ranges:
        res = s.client.read_range(step, off, nbytes)
        check(res.data == flat[off:off + nbytes].tobytes(),
              f"read_range({off}, {nbytes}) of step {step} differs")
        served.add(res.served_from)
    return {"step": step, "ranges": ranges, "served_from": sorted(served),
            "note": "read path decodes on the host (ROADMAP B2)"}


def phase_archive_many(s: Smoke, steps: list[int]) -> dict:
    ms = s.client.archive_many(steps, reclaim_hot=True)
    check(all(m["tier"] == "archive" for m in ms),
          f"steps {steps} not all archived")
    return {"steps": steps}


def run_one_chip(seed: int, block_bytes: int, window_bytes: int,
                 workdir: str) -> list[dict]:
    """The whole one-chip sequence; -> the phase records."""
    acfg = ArchiveConfig()
    n = acfg.n
    rng = np.random.default_rng(seed)

    def smoke_in(name: str) -> Smoke:
        store = NodeStore(os.path.join(workdir, name), n)
        return Smoke(StorageClient(store, acfg), acfg, rng, block_bytes,
                     window_bytes)

    s = smoke_in("store_one")
    recs = [run_phase("write_hot", n, phase_write_hot, s, [1]),
            run_phase("archive", n, phase_archive, s, 1),
            run_phase("oracle", n, phase_oracle, s, [1])]
    lose = run_phase("lose", n, phase_lose, s, 1)
    recs += [lose,
             run_phase("repair", n, phase_repair, s, 1, lose["lost"]),
             run_phase("read", n, phase_read, s, 1)]
    shutil.rmtree(s.client.store.root)   # bound the store's peak on disk
    many = smoke_in("store_many")
    recs += [run_phase("write_hot_many", n, phase_write_hot, many, [2, 3]),
             run_phase("archive_many", n, phase_archive_many, many, [2, 3]),
             run_phase("oracle_many", n, phase_oracle, many, [2, 3])]
    by = {r["phase"]: r for r in recs}
    for name in ("archive", "repair", "archive_many"):
        check(by[name]["coding"] == "device",
              f"{name} coded on {by[name]['coding']}, not the device")
    check(by["archive_many"]["kernel_launches"] == 1,
          "archive_many did not encode its batch in one launch")
    for name in ("oracle", "oracle_many"):
        check(by[name]["bytes_checked"] > 0, f"{name} checked nothing")
    return recs


# ---------------------------------------------------------------------------
# four chips: the shard_map chain programs
# ---------------------------------------------------------------------------


def _spans_devices(fn, args, ndev: int) -> None:
    """The program is partitioned over ``ndev`` devices and hops between
    them (collective-permute), not a program run on device 0 alone."""
    text = fn.lower(*args).compile().as_text()
    check(f"num_partitions={ndev}" in text,
          f"program is not partitioned over {ndev} devices")
    check("collective-permute" in text, "program has no chain hops")


def _one_row_per_device(out: jax.Array, axis: int, n: int) -> None:
    devs = {sh.device for sh in out.addressable_shards}
    check(len(devs) == n, f"output spans {len(devs)} devices, want {n}")
    for sh in out.addressable_shards:
        check(sh.data.shape[axis] == 1,
              f"device {sh.device} holds {sh.data.shape[axis]} rows")


def run_four_chips(seed: int, block_bytes: int,
                   window_bytes: int) -> list[dict]:
    """Chain encode, staggered encode_many and pipelined repair of a (4,3)
    code on a 4-device mesh, each against the one-chip fused encode."""
    from repro.core import fault_tolerance
    from repro.storage import chain, multi, repair

    acfg = ArchiveConfig(n=4, k=3, seed=seed)
    code, l = acfg.code(), acfg.l
    n, k = code.n, code.k
    rng = np.random.default_rng(seed)
    words = block_bytes // (l // 8)
    win = window_bytes // (l // 8)
    dt = gf.WORD_DTYPE[l]
    objs = np.frombuffer(rng.bytes(2 * k * words * (l // 8)), dt) \
        .reshape(2, k, words)

    def fused(x):          # one-chip reference: one launch on device 0
        return np.asarray(kernel_ops.encode_auto(code.G, jnp.asarray(x), l))

    def windows_match(data_w, coded_w) -> int:
        checked = 0
        for a, b in word_windows(rng, words, win):
            want = code.encode_np(np.ascontiguousarray(data_w[:, a:b]))
            check(np.array_equal(coded_w[:, a:b], want),
                  f"words [{a}, {b}) differ from encode_np")
            checked += want.nbytes
        return checked

    recs = []

    def encode():
        out = chain.pipelined_encode(code, objs[0], num_chunks=8)
        _one_row_per_device(out, 0, n)
        _spans_devices(jitcache.programs("encode")[-1],
                       (jax.ShapeDtypeStruct((k, words), dt),), n)
        got = np.asarray(out)
        check(np.array_equal(got, fused(objs[0])),
              "chain encode differs from the fused one-chip encode")
        return {"bytes_checked": windows_match(objs[0], got)}

    def encode_many():
        out = multi.pipelined_encode_many(code, objs, num_chunks=8,
                                          stagger=1)
        _one_row_per_device(out, 1, n)
        _spans_devices(jitcache.programs("encode_many")[-1],
                       (jax.ShapeDtypeStruct(objs.shape, dt),), n)
        got = np.asarray(out)
        check(np.array_equal(got, fused(objs)),
              "staggered encode_many differs from the fused batched encode")
        return {"bytes_checked": sum(windows_match(objs[b], got[b])
                                     for b in range(2))}

    def repair_lost():
        coded = fused(objs[0])
        lost = [int(rng.integers(0, n))]
        ids = [p for p in range(n) if p not in lost]
        helpers, R = fault_tolerance.repair_plan(code, lost, ids)
        out = repair.pipelined_repair(code, ids, coded[ids], lost,
                                      num_chunks=8)
        _spans_devices(jitcache.programs("repair")[-1],
                       (jax.ShapeDtypeStruct((len(helpers), words), dt),),
                       len(helpers))
        got = np.asarray(out)
        check(np.array_equal(got, coded[lost]),
              "pipelined repair differs from the fused encode's rows")
        packed = gf.pack_u32(jnp.asarray(coded[helpers]), l)
        ref = np.asarray(gf.unpack_u32(kernel_ops.encode_packed(R, packed, l),
                                       l))
        check(np.array_equal(got, ref),
              "pipelined repair differs from the fused one-chip repair")
        return {"lost": lost, "helpers": [int(h) for h in helpers],
                "bytes_checked": int(got.nbytes)}

    for name, body in (("chain_encode", encode),
                       ("chain_encode_many", encode_many),
                       ("chain_repair", repair_lost)):
        t0 = time.perf_counter()
        extra = body()
        rec = {"phase": name, "wall_s": time.perf_counter() - t0,
               "code": [n, k, l], "block_bytes": block_bytes,
               "jitcache_compiles": sum(jitcache.compile_counts().values()),
               **extra}
        print(json.dumps(rec), flush=True)
        recs.append(rec)
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({len(devices)} device(s))")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    print(json.dumps({"compile_cache": jitcache.enable_persistent_cache(),
                      "jax": jax.__version__, "kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)
    if args.chips == 4:
        run_four_chips(args.seed, BLOCK_BYTES, MIB)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            recs = run_one_chip(args.seed, BLOCK_BYTES, MIB, work)
        for r in recs:
            if r["kernel_launches"]:
                check(r["tpu_custom_call"] and r["interpret"] == [False],
                      f"{r['phase']}: the kernel program was interpreted")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
