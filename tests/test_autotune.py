"""Autotuner: tuning-cache plumbing, tuned-config parity, zero-probe warm runs.

Four families:

* **Cache + knobs** — JSON round-trip, corruption ``ValueError``s naming the
  path, mode validation, key canonicalization, stats counters.
* **Tuned-config correctness** — bit-exact parity of every tuned
  ``(block, dispatch, num_chunks)`` configuration against the numpy oracles
  (``encode_np``/``decode_np``/``repair_np``): a tuner may only ever change
  SPEED, never bytes.
* **Search / warm behavior** — a search-mode miss probes and persists; a
  warm cache resolves with ZERO probes; a warm tuning cache adds zero
  recompiles (jitcache trace counts, multi-device subprocess).
* **Calibration** — ``fit_chain_constants`` recovers known constants from
  synthetic sweeps; the model-based chunk fallback engages only when a
  measured calibration exists.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, gf, topology
from repro.core import rapidraid as rr
from repro.kernels.gf_encode import ops
from tests.subproc import run_with_devices


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets a private tuning cache and a clean module state."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "tune.json"))
    monkeypatch.setenv(autotune.TUNE_ENV, "cached")
    autotune.reset()
    yield
    autotune.reset()


def rand_words(rng, k, B, l):
    return rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])


# ---------------------------------------------------------------------------
# knobs + cache plumbing
# ---------------------------------------------------------------------------


def test_mode_validation(monkeypatch):
    for m in ("off", "cached", "search"):
        monkeypatch.setenv(autotune.TUNE_ENV, m)
        assert autotune.mode() == m
    monkeypatch.delenv(autotune.TUNE_ENV)
    assert autotune.mode() == "cached"
    monkeypatch.setenv(autotune.TUNE_ENV, "fastest")
    with pytest.raises(ValueError, match="fastest"):
        autotune.mode()


def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "rt.json")
    c = autotune.TuningCache(path)
    assert c.entries == {}                       # missing file = empty cache
    c.put("k1", {"value": 256, "timings_s": {"256": 0.001}})
    c.save()
    c2 = autotune.TuningCache(path)
    assert c2.get("k1") == {"value": 256, "timings_s": {"256": 0.001}}
    raw = json.loads((tmp_path / "rt.json").read_text())
    assert raw["version"] == autotune.CACHE_VERSION


@pytest.mark.parametrize("payload,match", [
    ("{not json", "not valid JSON"),
    ('["a", "b"]', "entries"),
    ('{"version": 999, "entries": {}}', "version"),
    ('{"version": 1, "entries": {"k": 5}}', "config dicts"),
])
def test_cache_corruption_value_errors(tmp_path, payload, match):
    path = tmp_path / "bad.json"
    path.write_text(payload)
    with pytest.raises(ValueError, match=match) as ei:
        autotune.TuningCache(str(path))
    assert "bad.json" in str(ei.value)           # the path is named


def test_corrupt_cache_surfaces_through_lookups(tmp_path, monkeypatch):
    path = tmp_path / "corrupt.json"
    path.write_text("{boom")
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.reset()
    with pytest.raises(ValueError, match="not valid JSON"):
        autotune.kernel_block("encode_packed", 16, 1024, heuristic=512)
    # mode=off never opens the cache, so a corrupt file cannot break it
    monkeypatch.setenv(autotune.TUNE_ENV, "off")
    autotune.reset()
    assert autotune.kernel_block("encode_packed", 16, 1024,
                                 heuristic=512) == 512


def test_stats_and_reset():
    assert autotune.stats() == {"hits": 0, "misses": 0, "probes": 0}
    autotune.kernel_block("encode_packed", 16, 64, heuristic=64)
    assert autotune.stats()["misses"] == 1
    autotune.cache().put(autotune._key("encode_packed", "l=16", "Bp=64"),
                         {"value": 32})
    assert autotune.kernel_block("encode_packed", 16, 64, heuristic=64) == 32
    assert autotune.stats()["hits"] == 1
    autotune.reset()
    assert autotune.stats() == {"hits": 0, "misses": 0, "probes": 0}


def test_key_includes_backend_and_codespec():
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=3)
    key = autotune._key("encode", code.spec, "B=4096")
    assert key.startswith("encode|cpu|")
    for part in ("family=rapidraid", "n=6", "k=4", "l=16", "seed=3",
                 "B=4096"):
        assert part in key


# ---------------------------------------------------------------------------
# satellite: pick_tick_block divisor fix + MXU default routing
# ---------------------------------------------------------------------------


def test_pick_tick_block_divisor_cases():
    assert ops.pick_tick_block(4096) == 512        # aligned: preferred
    assert ops.pick_tick_block(100) == 100         # short: whole chunk
    # ragged long chunk: largest divisor <= preferred, NOT one whole tile
    assert ops.pick_tick_block(1280) == 320
    assert ops.pick_tick_block(768) == 384
    assert 1536 % ops.pick_tick_block(1536, preferred=500) == 0
    assert ops.pick_tick_block(1536, preferred=500) == 384
    # prime: no useful divisor — whole-chunk tile, never a per-word grid
    assert ops.pick_tick_block(1031) == 1031
    assert ops.pick_tick_block(2 * 997) == 2 * 997   # fitting divisor is 2


def test_mxu_default_block_routed_through_picker():
    assert ops.kernel.DEFAULT_MXU_BLOCK == 1024
    # short buffers clamp to the covering power of two, as the VPU path does
    assert ops.pick_block(100, ops.kernel.DEFAULT_MXU_BLOCK) == 128
    assert ops.pick_block(4096, ops.kernel.DEFAULT_MXU_BLOCK) == 1024


# ---------------------------------------------------------------------------
# tuned-config parity: blocks / dispatch (single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("block", [64, 256, 2048])
def test_tuned_block_parity(l, block, monkeypatch):
    """A cached tile width changes bytes NEVER: encode_packed under any
    tuned block is bit-exact vs the numpy oracle."""
    code = rr.RapidRAIDCode.make(8, 5, l=l, seed=1)
    rng = np.random.default_rng(0)
    B = 1152 * gf.LANES[l]                       # ragged vs every block above
    data = rand_words(rng, code.k, B, l)
    autotune.cache().put(
        autotune._key("encode_packed", f"l={l}", f"Bp={B // gf.LANES[l]}"),
        {"value": block})
    got = np.asarray(ops.encode_words(code.G, jnp.asarray(data), l))
    np.testing.assert_array_equal(got, code.encode_np(data))


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("dispatch", ["vpu", "mxu"])
def test_tuned_dispatch_parity(l, dispatch):
    """Both dispatch decisions produce identical bytes, 2-D and batched."""
    code = rr.RapidRAIDCode.make(6, 4, l=l, seed=2)
    rng = np.random.default_rng(1)
    B = 96 * gf.LANES[l]
    autotune.cache().put(
        autotune._key("dispatch", f"l={l}", f"rows={code.n}", f"k={code.k}",
                      f"B={B}"),
        {"value": dispatch})
    data = rand_words(rng, code.k, B, l)
    got = np.asarray(ops.encode_auto(code.G, jnp.asarray(data), l))
    np.testing.assert_array_equal(got, code.encode_np(data))
    objs = np.stack([data, data[:, ::-1]])
    got_b = np.asarray(ops.encode_auto(code.G, jnp.asarray(objs), l))
    np.testing.assert_array_equal(
        got_b, np.stack([code.encode_np(o) for o in objs]))


def test_dispatch_cache_hit_is_honored():
    code = rr.RapidRAIDCode.make(6, 4, l=8, seed=0)
    B = 64 * gf.LANES[8]
    key = autotune._key("dispatch", "l=8", f"rows={code.n}", f"k={code.k}",
                        f"B={B}")
    autotune.cache().put(key, {"value": "mxu"})
    assert autotune.dispatch_for(8, code.n, code.k, B) == "mxu"
    autotune.cache().put(key, {"value": "nonsense"})   # stale/garbage entry
    assert autotune.dispatch_for(8, code.n, code.k, B) == "vpu"


# ---------------------------------------------------------------------------
# search mode: probe + persist, then warm with zero probes
# ---------------------------------------------------------------------------


def test_search_probes_persist_and_warm_hits(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.TUNE_ENV, "search")
    autotune.reset()
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=1)
    rng = np.random.default_rng(2)
    B = 512 * gf.LANES[16]
    data = jnp.asarray(rand_words(rng, code.k, B, 16))
    blk = ops.encode_block_for(code.G, data, 16)
    st = autotune.stats()
    assert st["probes"] == 1 and blk in autotune.block_candidates(512, 512)
    entry = autotune.cache().get(
        autotune._key("encode_packed", "l=16", "Bp=512"))
    assert entry["value"] == blk and entry["timings_s"]  # evidence persisted

    # a NEW process (reset) with the same cache file: pure hit, zero probes
    autotune.reset()
    monkeypatch.setenv(autotune.TUNE_ENV, "cached")
    assert ops.encode_block_for(code.G, data, 16) == blk
    st = autotune.stats()
    assert st == {"hits": 1, "misses": 0, "probes": 0}


def test_search_mode_never_probes_tracers(monkeypatch):
    """Traced call sites resolve cache-only even under search mode."""
    import jax
    monkeypatch.setenv(autotune.TUNE_ENV, "search")
    autotune.reset()
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=1)
    rng = np.random.default_rng(3)
    data = rand_words(rng, code.k, 128 * gf.LANES[16], 16)

    @jax.jit
    def traced(d):
        return ops.encode_words(code.G, d, 16)

    got = np.asarray(traced(jnp.asarray(data)))
    np.testing.assert_array_equal(got, code.encode_np(data))
    assert autotune.stats()["probes"] == 0


def test_tune_tick_block_persists_divisor(monkeypatch):
    monkeypatch.setenv(autotune.TUNE_ENV, "search")
    autotune.reset()
    S = 256
    blk = autotune.tune_tick_block(16, S, max_b=2)
    assert S % blk == 0
    assert autotune.stats()["probes"] == 1
    # the traced lookup path returns the tuned value, probe-free
    autotune.reset()
    monkeypatch.setenv(autotune.TUNE_ENV, "cached")
    assert autotune.tick_block(16, S, heuristic=999) == blk
    assert autotune.stats() == {"hits": 1, "misses": 0, "probes": 0}
    # a cached width that no longer divides S falls back to the heuristic
    autotune.cache().put(autotune._key("tick_block", "l=16", "S=300"),
                         {"value": 7})
    assert autotune.tick_block(16, 300, heuristic=100) == 100


def test_prewarm_requires_search_mode():
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=0)
    with pytest.raises(ValueError, match="search"):
        autotune.prewarm(code)


# ---------------------------------------------------------------------------
# plan parameters: num_chunks / stagger resolution
# ---------------------------------------------------------------------------


def test_num_chunks_default_without_calibration():
    """No cache entry, no calibration: the hand-tuned default, exactly as
    before the autotuner existed (tier-1 determinism)."""
    code = rr.RapidRAIDCode.make(8, 5, l=16, seed=0)
    assert autotune.num_chunks_for("encode", code, 4096) == 8
    assert autotune.stats()["probes"] == 0


def test_num_chunks_cached_value_validated():
    code = rr.RapidRAIDCode.make(8, 5, l=16, seed=0)
    key = autotune._key("encode", code.spec, "B=4096", "chain=8",
                        "num_chunks")
    autotune.cache().put(key, {"value": 16})
    assert autotune.num_chunks_for("encode", code, 4096) == 16
    # a tuned count that no longer divides the geometry is rejected
    autotune.cache().put(key, {"value": 3})
    assert autotune.num_chunks_for("encode", code, 4096) == 8


def test_num_chunks_model_fallback_needs_calibration():
    """The makespan-model fallback engages ONLY with a measured calibration
    (uncalibrated defaults have zero tick overhead, so the model would
    always pick the finest chunking — a silent behavior change)."""
    code = rr.RapidRAIDCode.make(8, 5, l=16, seed=0)
    B = 4096
    # big per-tick overhead: the model must pick a COARSE chunking
    autotune.cache().put(autotune._key("chain_calib", "l=16"),
                         {"compute_rate": 1e9, "tick_overhead": 1e-2})
    got = autotune.num_chunks_for("encode", code, B)
    topo = autotune.calibrated_topology(code.n)
    cands = autotune.chunk_candidates_for(16, B)
    want = min(cands, key=lambda c: topology.chain_makespan(
        topo, range(code.n), code.k, B * 2, c))
    assert got == want == 1


def test_calibrated_topology_roundtrip():
    t_default = autotune.calibrated_topology(6)
    assert t_default.compute_rate == topology.Topology.uniform(6).compute_rate
    assert autotune.calibrated_topology(6, fallback=False) is None
    autotune.cache().put(autotune._key("chain_calib", "l=16"),
                         {"compute_rate": 123.0, "tick_overhead": 4.5e-6})
    t = autotune.calibrated_topology(6)
    assert t.compute_rate == (123.0,) * 6 and t.tick_overhead == 4.5e-6
    assert t.nic_bw == (topology.CALIBRATION_NIC_BW,) * 6


def test_stagger_resolution():
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=0)
    assert autotune.stagger_for(code, 4, 8) == 1           # default
    autotune.cache().put(autotune._key("stagger", code.spec, "b=4", "nc=8"),
                         {"value": 8})
    assert autotune.stagger_for(code, 4, 8) == 8
    autotune.cache().put(autotune._key("stagger", code.spec, "b=4", "nc=8"),
                         {"value": 40})                    # out of range
    assert autotune.stagger_for(code, 4, 8) == 1


def test_plan_chain_topo_none_uses_calibration():
    from repro.core import scheduler
    autotune.cache().put(autotune._key("chain_calib", "l=16"),
                         {"compute_rate": 4e8, "tick_overhead": 1e-4})
    plan = scheduler.plan_chain(None, 4, 1 << 20, n=6)
    topo = autotune.calibrated_topology(6)
    want = scheduler.plan_chain(topo, 4, 1 << 20)
    assert plan == want
    with pytest.raises(ValueError, match="n="):
        scheduler.plan_chain(None, 4, 1 << 20)
    many = scheduler.plan_many(None, 3, 6, 4, 1 << 20)
    assert many.plans[0].num_chunks == plan.num_chunks


def test_mode_off_bypasses_everything(monkeypatch):
    monkeypatch.setenv(autotune.TUNE_ENV, "off")
    autotune.reset()
    code = rr.RapidRAIDCode.make(8, 5, l=16, seed=0)
    autotune.cache_path()                       # path resolves fine
    assert autotune.num_chunks_for("encode", code, 4096) == 8
    assert autotune.stagger_for(code, 4, 8) == 1
    assert autotune.stats() == {"hits": 0, "misses": 0, "probes": 0}


# ---------------------------------------------------------------------------
# calibration fit
# ---------------------------------------------------------------------------


def test_fit_chain_constants_recovers_known_topology():
    n, k, bb = 8, 5, float(1 << 20)
    true = topology.Topology.uniform(
        n, compute_rate=2e8, nic_bw=topology.CALIBRATION_NIC_BW,
        hop_latency=0.0, tick_overhead=5e-5)
    samples = [(c, topology.chain_makespan(true, range(n), k, bb, c))
               for c in (1, 2, 4, 8, 16, 32)]
    topo, pred = topology.fit_chain_constants(samples, n, k, bb)
    assert topo.compute_rate[0] == pytest.approx(2e8, rel=1e-4)
    assert topo.tick_overhead == pytest.approx(5e-5, rel=1e-4)
    np.testing.assert_allclose(pred, [t for _, t in samples], rtol=1e-5)


def test_fit_chain_constants_recovers_cache_pressure_term():
    """A sweep generated WITH a quadratic working-set term refits all three
    constants; the linear 2-count fallback pins quad to zero."""
    n, k, bb = 16, 11, float(1 << 18)
    true = topology.Topology.uniform(
        n, compute_rate=3.5e7, nic_bw=topology.CALIBRATION_NIC_BW,
        hop_latency=0.0, tick_overhead=1.7e-4, tick_quad=1.1e-12)
    counts = (1, 2, 4, 8, 16, 32)
    samples = [(c, topology.chain_makespan(true, range(n), k, bb, c))
               for c in counts]
    topo, pred = topology.fit_chain_constants(samples, n, k, bb)
    assert topo.tick_quad == pytest.approx(1.1e-12, rel=1e-3)
    assert topo.compute_rate[0] == pytest.approx(3.5e7, rel=1e-3)
    np.testing.assert_allclose(pred, [t for _, t in samples], rtol=1e-5)
    # two distinct counts cannot identify the quadratic: it stays 0
    topo2, _ = topology.fit_chain_constants(samples[:2], n, k, bb)
    assert topo2.tick_quad == 0.0


def test_fit_chain_constants_noisy_within_tolerance():
    n, k, bb = 6, 4, float(1 << 18)
    true = topology.Topology.uniform(
        n, compute_rate=5e7, nic_bw=topology.CALIBRATION_NIC_BW,
        hop_latency=0.0, tick_overhead=2e-5)
    rng = np.random.default_rng(0)
    samples = [(c, topology.chain_makespan(true, range(n), k, bb, c)
                * (1 + rng.normal(0, 0.03)))
               for c in (1, 2, 4, 8, 16)]
    topo, pred = topology.fit_chain_constants(samples, n, k, bb)
    rel = [abs(p - t) / t for (_, t), p in zip(samples, pred)]
    assert max(rel) < 0.15                       # the acceptance threshold


def test_fit_chain_constants_input_validation():
    with pytest.raises(ValueError, match="distinct chunk counts"):
        topology.fit_chain_constants([(4, 0.1), (4, 0.2)], 8, 5, 1e6)
    with pytest.raises(ValueError, match="bad samples"):
        topology.fit_chain_constants([(1, 0.1), (2, -0.5)], 8, 5, 1e6)


def test_calibrate_chain_needs_valid_sweep():
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=0)
    with pytest.raises(ValueError, match="chunk counts"):
        autotune.calibrate_chain(code, nwords=64, chunk_counts=(64, 128))


# ---------------------------------------------------------------------------
# multi-device: tuned pipeline parity + zero probes/recompiles when warm
# ---------------------------------------------------------------------------

TUNED_PIPELINE_SNIPPET = """
import os, json
os.environ["RAPIDRAID_TUNE"] = "search"
os.environ["RAPIDRAID_TUNE_CACHE"] = r"{cache}"
import numpy as np
from repro.core import autotune, gf, jitcache, rapidraid as rr
from repro.storage import chain, multi, repair as rep

n, k, l = 6, 4, 16
code = rr.RapidRAIDCode.make(n, k, l=l, seed=13)
rng = np.random.default_rng(0)
B = gf.LANES[l] * 16 * 24
data = rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])
objs = rng.integers(0, 1 << l, size=(2, k, B)).astype(gf.WORD_DTYPE[l])
want = code.encode_np(data)

# SEARCH: tune num_chunks + tick blocks against the real entry points
nc = autotune.num_chunks_for(
    "encode", code, B,
    probe=lambda c: chain.pipelined_encode(code, data, num_chunks=c))
for c in autotune.chunk_candidates_for(l, B):
    autotune.tune_tick_block(l, (B // gf.LANES[l]) // c)
assert autotune.stats()["probes"] > 0

# WARM process: fresh module state, cached mode, fresh jit cache
autotune.reset()
os.environ["RAPIDRAID_TUNE"] = "cached"
jitcache.clear()

got = np.asarray(chain.pipelined_encode(code, data))     # tuned num_chunks
np.testing.assert_array_equal(got, want)                 # parity, tuned cfg
before = jitcache.stats()
again = np.asarray(chain.pipelined_encode(code, data))
after = jitcache.stats()
assert after["misses"] == before["misses"], (before, after)
assert after["hits"] > before["hits"]
np.testing.assert_array_equal(got, again)

ids = list(range(1, k + 2))
dec = np.asarray(chain.pipelined_decode(code, ids, want[ids]))
np.testing.assert_array_equal(dec, code.decode_np(ids, want[ids]))

missing = [0]
alive = [i for i in range(n) if i not in missing]
got_r = np.asarray(rep.pipelined_repair(code, alive, want[alive], missing))
np.testing.assert_array_equal(
    got_r, rep.repair_np(code, missing, alive, want[alive]))

cws = np.stack([code.encode_np(o) for o in objs])
got_m = np.asarray(multi.pipelined_encode_many(code, objs))
np.testing.assert_array_equal(got_m, cws)

# the whole warm phase ran ZERO search probes and each program traced once
st = autotune.stats()
assert st["probes"] == 0, st
assert st["hits"] > 0, st
counts = jitcache.compile_counts()
assert counts and all(v in (1, -1) for v in counts.values()), counts
print("TUNED-OK nc=%d stats=%s" % (nc, json.dumps(st)))
"""


@pytest.mark.multidevice
def test_tuned_pipeline_parity_and_zero_probe_warm(tmp_path):
    """Search-tuned (num_chunks, tick blocks) stay bit-exact vs the numpy
    oracles; the warm run probes zero times and recompiles nothing."""
    out = run_with_devices(
        TUNED_PIPELINE_SNIPPET.format(cache=str(tmp_path / "tune.json")),
        ndev=6, timeout=900)
    assert "TUNED-OK" in out


CALIBRATION_SNIPPET = """
import os
os.environ["RAPIDRAID_TUNE"] = "search"
os.environ["RAPIDRAID_TUNE_CACHE"] = r"{cache}"
import numpy as np
from repro.core import autotune, rapidraid as rr

code = rr.RapidRAIDCode.make(6, 4, l=16, seed=0)
# 1 << 15 words: at 1 << 13 a chain call takes a few ms on the CPU and
# dispatch jitter, not coding, sets the sweep's shape
entry = autotune.calibrate_chain(code, nwords=1 << 15,
                                 chunk_counts=(1, 2, 4, 8), iters=3)
assert entry["compute_rate"] > 0
assert entry["max_rel_err"] < 0.5, entry      # sanity, not the 15% gate
topo = autotune.calibrated_topology(code.n)
assert topo.compute_rate[0] == entry["compute_rate"]
for s in entry["samples"]:
    assert s["measured_s"] > 0 and s["model_s"] > 0
    assert "hlo_pred_s" in s and "hlo_bytes" in s
print("CALIB-OK", entry["max_rel_err"])
"""


@pytest.mark.multidevice
def test_calibrate_chain_real_sweep(tmp_path):
    """calibrate_chain on a real 6-device sweep: persists a usable topology
    and HLO cross-check evidence per sample."""
    out = run_with_devices(
        CALIBRATION_SNIPPET.format(cache=str(tmp_path / "tune.json")),
        ndev=6, timeout=900)
    assert "CALIB-OK" in out


@pytest.mark.multidevice
def test_autotune_cli_prewarms_cache(tmp_path):
    """python -m repro.autotune forces host devices in its own process and
    fills the cache end to end."""
    import os
    import subprocess
    import sys

    from tests.subproc import REPO
    cache = tmp_path / "cli.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["RAPIDRAID_TUNE_CACHE"] = str(cache)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("RAPIDRAID_TUNE", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.autotune", "--n", "6", "--k", "4",
         "--nwords", "4096", "--b-obj", "2", "--chunk-counts", "1,2,4"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr
    assert "probes run:" in proc.stdout
    raw = json.loads(cache.read_text())
    keys = "\n".join(raw["entries"])
    for family in ("encode_packed", "encode_mxu", "dispatch", "tick_block",
                   "chain_calib", "num_chunks", "stagger"):
        assert family in keys, (family, keys)
