"""Streaming archival: per-device footprint vs archival throughput.

(beyond paper) RapidRAID's chain assumes the whole object rides the
pipeline at once; ``repro.core.streaming`` splits it into super-chunk
stripes so archival runs under a FIXED per-device byte budget. The knob
trades footprint for overlap: smaller stripes bound memory tighter but
spend a larger fraction of ticks filling/draining the chain.

Two measurements:

* **model** (deterministic, blocking in CI) — a 1 GiB object at the
  paper's (16, 11) geometry, archived under per-device budgets from 4 MB
  to 256 MB. Per budget: the planned stripe geometry
  (``superchunk_words_for`` / ``plan_stream``), the modeled peak device
  bytes (``estimate_stripe_bytes``, the number the acceptance tests bound
  with ``streaming.measure_footprint``), the footprint reduction vs the
  monolithic encode, and the cross-stripe overlap speedup: S double-
  buffered stripes cost ``S*C + n - 1`` chain ticks where sequential
  stripe launches cost ``S*(C + n - 1)`` (Repair Pipelining's cross-
  stripe schedule, Li et al.).
* **real** (advisory) — wall-clock of ``archive_step`` on this machine at
  a smoke-scale object, monolithic vs streamed under a small budget, with
  the streamed output digest-verified identical (positionwise codes store
  byte-identical stripes) and restore round-tripped.

``python -m benchmarks.fig_streaming [--mb 8]``
"""
from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from benchmarks.util import emit
from repro.core import codes, streaming
from repro.storage import archive as arc
from repro.storage import object_store as obj

BUDGETS_MB = (4, 16, 64, 256)


def network_model(n: int = 16, k: int = 11, l: int = 16, nc: int = 8,
                  obj_bytes: int = 1 << 30) -> list[dict]:
    """Footprint-vs-throughput table for one large object, per budget."""
    code = codes.make("rapidraid", n, k, l=l)
    wb = l // 8
    total_words = obj_bytes // (k * wb)
    mono_bytes = streaming.estimate_stripe_bytes(code, total_words)
    rows = []
    for budget_mb in BUDGETS_MB:
        budget = budget_mb << 20
        sc = streaming.superchunk_words_for(budget, code, nc)
        plan = streaming.plan_stream(total_words, sc, l=l, num_chunks=nc)
        est = streaming.estimate_stripe_bytes(code, plan.sc_words)
        S = plan.num_superchunks
        seq_ticks = S * (nc + n - 1)
        pipe_ticks = S * nc + n - 1
        rows.append({
            "budget_mb": budget_mb,
            "superchunk_words": plan.sc_words,
            "stripes": S,
            "est_stripe_bytes": est,
            "footprint_reduction": round(mono_bytes / est, 3),
            "overlap_speedup": round(seq_ticks / pipe_ticks, 3),
        })
    return rows


def real_streaming(mb: int = 8, n: int = 8, k: int = 4, l: int = 8,
                   nc: int = 4, budget_kb: int = 256) -> dict:
    """Measured archive wall-clock at a smoke-scale object: monolithic vs
    streamed under ``budget_kb``, outputs digest-verified identical."""
    acfg = arc.ArchiveConfig(n=n, k=k, l=l, seed=5, num_chunks=nc)
    code = acfg.code()
    wb = l // 8
    granule_b = 4 * wb * nc * 4   # LANES[8]=4 words * wb, x4 safety
    B = (mb << 20) // k // granule_b * granule_b
    rng = np.random.default_rng(0)
    blocks = rng.integers(0, 256, size=(k, B), dtype=np.uint8)
    sc_words = streaming.superchunk_words_for(budget_kb << 10, code, nc)

    def archive_once(superchunk_bytes):
        with tempfile.TemporaryDirectory() as root:
            store = obj.NodeStore(root, n)
            arc.hot_save(store, 1, blocks, acfg)
            t0 = time.perf_counter()
            m = arc.archive_step(store, 1, acfg, use_devices=None,
                                 superchunk_bytes=superchunk_bytes)
            dt = time.perf_counter() - t0
            if superchunk_bytes is not None:
                np.testing.assert_array_equal(
                    arc.restore_blocks(store, 1, acfg), blocks)
            return m, dt

    m_mono, mono_s = archive_once(None)
    m_strm, strm_s = archive_once(sc_words * wb)
    assert m_strm["coded_digests"] == m_mono["coded_digests"], \
        "streamed archive is not byte-identical to the monolithic path"
    return {
        "object_mb": round(k * B / 2 ** 20, 2),
        "budget_kb": budget_kb,
        "stripes": m_strm["streaming"]["num_superchunks"],
        "mono_s": round(mono_s, 4),
        "stream_s": round(strm_s, 4),
        "stream_mb_per_s": round(k * B / 2 ** 20 / strm_s, 2),
        "ratio": round(mono_s / strm_s, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mb", type=int, default=8)
    # tolerate the benchmarks.run driver's own flags (--only ...)
    args, _ = ap.parse_known_args()
    print("== model: 1 GiB object under per-device budgets (blocking) ==")
    for row in network_model():
        emit("streaming_model", row)
        # the acceptance lines: the planned stripe fits its budget, tighter
        # budgets shrink the footprint, and the cross-stripe overlap never
        # costs throughput
        assert row["est_stripe_bytes"] <= row["budget_mb"] << 20, row
        assert row["footprint_reduction"] >= 1.0, row
        assert row["overlap_speedup"] >= 1.0, row
    print("== real: smoke-scale archive wall-clock (advisory) ==")
    emit("streaming_real", real_streaming(mb=args.mb))


if __name__ == "__main__":
    main()
