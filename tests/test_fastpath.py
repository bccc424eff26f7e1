"""Warm fast path: compiled-program cache + fused Pallas tick parity.

Two families of regression tests:

* **Trace counts** — every distributed entry point must compile exactly ONE
  program per (code, mesh, shapes, num_chunks) key: a second call with
  identical shapes hits ``repro.core.jitcache`` (hits grow, misses don't)
  and never retraces (each cached program's jit-cache size stays 1).
* **Bit-exact parity** — the per-tick step now runs through the fused
  Pallas kernels (``chain_step``/``repair_step``); outputs must stay
  bit-exact against the numpy references (``encode_np``/``decode_np``/
  ``repair_np``) for GF(2^8) and GF(2^16), ragged chunk sizes (S not a
  multiple of the preferred tile), and every loss count 1..n-k.

Multi-device paths run in subprocesses (``tests/subproc.py``); the
host-side cache plumbing tests run inline.
"""
import os

import numpy as np
import pytest

from tests.subproc import run_with_devices

TRACE_COUNT_SNIPPET = """
import numpy as np, jax
from repro.core import gf, jitcache, rapidraid as rr
from repro.storage import chain, multi, repair as rep

n, k, l, nc = {n}, {k}, {l}, {chunks}
code = rr.RapidRAIDCode.make(n, k, l=l, seed=13)
rng = np.random.default_rng(0)
B = gf.LANES[l] * nc * 6
data = rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])
objs = rng.integers(0, 1 << l, size=(3, k, B)).astype(gf.WORD_DTYPE[l])
cw = code.encode_np(data)
ids = list(range(1, k + 2))
missing = [0]
alive = [i for i in range(n) if i not in missing]

def warm(fn):
    first = np.asarray(fn())
    before = jitcache.stats()
    second = np.asarray(fn())
    after = jitcache.stats()
    assert after["misses"] == before["misses"], (before, after)
    assert after["hits"] > before["hits"], (before, after)
    np.testing.assert_array_equal(first, second)

warm(lambda: chain.pipelined_encode(code, data, num_chunks=nc))
warm(lambda: chain.pipelined_decode(code, ids, cw[ids], num_chunks=nc))
warm(lambda: rep.pipelined_repair(code, alive, cw[alive], missing,
                                  num_chunks=nc))
warm(lambda: multi.pipelined_encode_many(code, objs, num_chunks=nc))
# no cached program may have traced more than one signature (-1 means the
# jax version exposes no jit-cache introspection; the hit/miss assertions
# above still hold there)
counts = jitcache.compile_counts()
assert counts and all(v in (1, -1) for v in counts.values()), counts
print("OK", jitcache.stats())
"""


@pytest.mark.multidevice
@pytest.mark.parametrize("n,k,l,chunks", [(8, 4, 16, 4), (6, 4, 8, 3)])
def test_warm_calls_do_not_recompile(n, k, l, chunks):
    """Second identical-shape call of every entry point: cache hit, 1 trace."""
    out = run_with_devices(
        TRACE_COUNT_SNIPPET.format(n=n, k=k, l=l, chunks=chunks), ndev=n)
    assert "OK" in out


PARITY_SNIPPET = """
import numpy as np, jax
from repro.core import gf, rapidraid as rr
from repro.storage import chain, multi, repair as rep

n, k, l = {n}, {k}, {l}
code = rr.RapidRAIDCode.make(n, k, l=l, seed=7)
rng = np.random.default_rng(1)
# RAGGED chunks: S = 7 uint32 lanes per chunk — far from the 512-lane tile,
# so the per-tick kernels run the whole-chunk-tile path
nc = 4
B = gf.LANES[l] * nc * 7
data = rng.integers(0, 1 << l, size=(k, B)).astype(gf.WORD_DTYPE[l])
want = code.encode_np(data)
got = np.asarray(chain.pipelined_encode(code, data, num_chunks=nc))
np.testing.assert_array_equal(got, want)

ids = list(range(1, k + 2))
dec = np.asarray(chain.pipelined_decode(code, ids, want[ids], num_chunks=nc))
np.testing.assert_array_equal(dec, code.decode_np(ids, want[ids]))
np.testing.assert_array_equal(dec, data)

# every loss count 1..n-k, against the numpy repair reference
for n_lost in range(1, n - k + 1):
    missing = list(range(0, 2 * n_lost, 2))[:n_lost]
    alive = [i for i in range(n) if i not in missing]
    ref = rep.repair_np(code, missing, alive, want[alive])
    np.testing.assert_array_equal(ref, want[missing])
    got_r = np.asarray(rep.pipelined_repair(code, alive, want[alive],
                                            missing, num_chunks=nc))
    np.testing.assert_array_equal(got_r, ref)

# staggered multi-object variants on the same ragged geometry
objs = rng.integers(0, 1 << l, size=(3, k, B)).astype(gf.WORD_DTYPE[l])
cws = np.stack([code.encode_np(o) for o in objs])
got_m = np.asarray(multi.pipelined_encode_many(code, objs, num_chunks=nc))
np.testing.assert_array_equal(got_m, cws)
dec_m = np.asarray(multi.pipelined_decode_many(code, ids, cws[:, ids],
                                               num_chunks=nc))
np.testing.assert_array_equal(dec_m, objs)
alive = [i for i in range(n) if i != 1]
rep_m = np.asarray(rep.pipelined_repair_many(code, alive, cws[:, alive],
                                             [1], num_chunks=nc))
np.testing.assert_array_equal(rep_m, cws[:, [1]])
print("OK")
"""


@pytest.mark.multidevice
@pytest.mark.parametrize("n,k,l", [(8, 4, 8), (8, 4, 16), (6, 4, 16)])
def test_fused_tick_parity_ragged(n, k, l):
    """Kernel-routed ticks bit-exact vs numpy refs on ragged chunk sizes."""
    out = run_with_devices(PARITY_SNIPPET.format(n=n, k=k, l=l), ndev=n)
    assert "OK" in out


def test_jitcache_get_and_stats():
    from repro.core import jitcache
    jitcache.clear()
    built = []

    def builder():
        built.append(1)
        return lambda x: x + 1

    key = ("unit", 1, 2)
    fn1 = jitcache.get(key, builder)
    fn2 = jitcache.get(key, builder)
    assert fn1 is fn2 and built == [1]
    st = jitcache.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["size"] == 1
    # non-jit programs report -1 in compile_counts (no introspection)
    assert jitcache.compile_counts() == {repr(key): -1}
    assert jitcache.programs("unit") == [fn1]
    assert jitcache.programs("other") == []
    jitcache.clear()
    assert jitcache.stats() == {"hits": 0, "misses": 0, "size": 0}


@pytest.fixture
def compile_cache_config():
    """Restore JAX's compile-cache directory after a test changes it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_persistent_cache_honours_env(monkeypatch, tmp_path,
                                      compile_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX keeps the cache there and
    nothing is set in code."""
    import jax
    from repro.core import jitcache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert jitcache.enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_persistent_cache_fixed_path_in_checkout(monkeypatch,
                                                 compile_cache_config):
    """Without the variable the cache goes to one fixed directory inside
    the checkout, the same on every call (it is part of the cache key)."""
    import jax
    from repro.core import jitcache
    from tests.subproc import REPO
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = jitcache.enable_persistent_cache()
    assert first == jitcache.enable_persistent_cache()
    assert first == jitcache.CHECKOUT_CACHE_DIR
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_bitplane_table_matches_scalar_consts():
    from repro.core import gf
    rng = np.random.default_rng(3)
    for l in (8, 16):
        M = rng.integers(0, 1 << l, size=(3, 5)).astype(gf.WORD_DTYPE[l])
        table = gf.bitplane_table(M, l)
        assert table.shape == (3, 5, l) and table.dtype == np.uint32
        for i in range(3):
            for j in range(5):
                assert table[i, j].tolist() == gf.bitplane_consts(
                    int(M[i, j]), l)


def test_vectorized_planes_match_schedule():
    """bitplane_coeff_planes/column_bitplanes: table op == per-scalar loop."""
    from repro.core import gf, rapidraid as rr
    from repro.storage import chain
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=5)
    bp_psi, bp_xi = chain.bitplane_coeff_planes(code)
    sched = code.chain
    for i in range(code.n):
        for s in range(sched.max_blocks):
            for j in range(code.l):
                a = 1 << j
                assert bp_psi[i, s, j] == gf.gf_mul_scalar(
                    int(sched.psi[i, s]), a, code.l)
                assert bp_xi[i, s, j] == gf.gf_mul_scalar(
                    int(sched.xi[i, s]), a, code.l)
    M = np.asarray([[1, 2], [3, 0], [7, 255]], dtype=np.uint8)
    cb = chain.column_bitplanes(M, 8)
    assert cb.shape == (2, 3, 8)
    for c in range(2):
        for r in range(3):
            assert cb[c, r].tolist() == gf.bitplane_consts(int(M[r, c]), 8)


def test_build_local_blocks_gather_matches_schedule():
    from repro.core import gf, rapidraid as rr
    from repro.storage import chain
    code = rr.RapidRAIDCode.make(6, 4, l=16, seed=2)
    rng = np.random.default_rng(4)
    data = rng.integers(0, 1 << 16, size=(4, 32)).astype(np.uint16)
    out = chain.build_local_blocks(code, data)
    sched = code.chain
    assert out.shape == (code.n, sched.max_blocks, 32)
    for i in range(code.n):
        for s in range(sched.max_blocks):
            if sched.block_valid[i, s]:
                np.testing.assert_array_equal(
                    out[i, s], data[sched.local_blocks[i, s]])
            else:
                assert not out[i, s].any()


def test_precondition_value_errors():
    """User-facing shape/divisibility preconditions raise ValueError."""
    from repro.core import rapidraid as rr
    from repro.storage import chain, multi, repair as rep
    code = rr.RapidRAIDCode.make(8, 4, l=16, seed=0)
    data = np.zeros((4, 64), dtype=np.uint16)
    with pytest.raises(ValueError, match="k=4"):
        chain.pipelined_encode(code, data[:3])
    with pytest.raises(ValueError, match="chunks"):
        chain.pipelined_encode(code, data[:, :10], num_chunks=8)
    with pytest.raises(ValueError, match="len\\(ids\\)=5"):
        chain.pipelined_decode(code, [0, 1, 2, 3, 4], data)
    with pytest.raises(ValueError, match="B_obj"):
        multi.pipelined_encode_many(code, data)
    with pytest.raises(ValueError, match="chunks"):
        multi.pipelined_encode_many(code, np.zeros((2, 4, 10), np.uint16),
                                    num_chunks=8)
    with pytest.raises(ValueError, match="len\\(ids\\)=5"):
        rep.pipelined_repair(code, [0, 1, 2, 3, 4], data, [5])
    with pytest.raises(ValueError, match="chunks"):
        rep.pipelined_repair(code, [0, 1, 2, 3, 4],
                             np.zeros((5, 10), np.uint16), [5], num_chunks=8)


def test_measure_compute_rates_cached_kernel():
    """Calibration reuses one jitted combine: repeat calls add no traces."""
    from repro.core import topology
    r1 = topology.measure_compute_rates(l=16, nwords=1 << 8, iters=1)
    fn = topology._calibration_kernel(16)
    cache_size = getattr(fn, "_cache_size", None)
    size_after_first = cache_size() if callable(cache_size) else None
    r2 = topology.measure_compute_rates(l=16, nwords=1 << 8, iters=1)
    assert topology._calibration_kernel(16) is fn
    if size_after_first is not None:
        # the repeat calibration added NO traced signatures
        assert fn._cache_size() == size_after_first
    assert len(r1) == len(r2) == 1 and all(v > 0 for v in r1 + r2)


# ---------------------------------------------------------------------------
# device-direct checkpoint programs: one trace per (code, layout, shapes) key
# ---------------------------------------------------------------------------


def test_device_direct_ckpt_traces_once(tmp_path):
    """Repeated same-shaped save_sharded/restore_sharded calls reuse ONE
    compiled program (fused single-host path)."""
    import jax.numpy as jnp
    from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
    from repro.core import jitcache

    mgr = CheckpointManager(CheckpointConfig(root=str(tmp_path),
                                             archive_old=False))

    def state(seed):
        rng = np.random.default_rng(seed)
        return {"w": jnp.asarray(rng.standard_normal((24, 16)), jnp.float32),
                "c": jnp.asarray(int(rng.integers(100)), jnp.int32),
                "step": np.int64(seed)}

    mgr.save_sharded(1, state(1))
    before = jitcache.stats()
    mgr.save_sharded(2, state(2))
    mgr.save_sharded(3, state(3))
    after = jitcache.stats()
    assert after["misses"] == before["misses"], (before, after)
    assert after["hits"] >= before["hits"] + 2

    mgr.restore_sharded(1, state(0))
    before = jitcache.stats()
    r2 = mgr.restore_sharded(2, state(0))
    after = jitcache.stats()
    assert after["misses"] == before["misses"], (before, after)
    np.testing.assert_array_equal(np.asarray(r2["w"]),
                                  np.asarray(state(2)["w"]))
    assert int(r2["step"]) == 2

    for entry in ("ckpt_save", "ckpt_restore"):
        counts = jitcache.entry_counts(entry)
        assert counts and all(v in (1, -1) for v in counts.values()), (
            entry, counts)


CKPT_TRACE_SNIPPET = """
import tempfile
import numpy as np, jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.core import jitcache

mesh = Mesh(np.asarray(jax.devices()).reshape(4, 4), ("data", "model"))
sh = NamedSharding(mesh, P("data", "model"))
mgr = CheckpointManager(CheckpointConfig(root=tempfile.mkdtemp(),
                                         archive_old=False))

def state(seed):
    rng = np.random.default_rng(seed)
    return {"w": jax.device_put(
                rng.standard_normal((16, 8)).astype(np.float32), sh),
            "step": np.int64(seed)}

mgr.save_sharded(1, state(1), mesh=mesh)      # chain path: 16-device encode
before = jitcache.stats()
mgr.save_sharded(2, state(2), mesh=mesh)
mgr.save_sharded(3, state(3), mesh=mesh)
after = jitcache.stats()
assert after["misses"] == before["misses"], (before, after)
assert after["hits"] >= before["hits"] + 2

mgr.restore_sharded(1, state(0), mesh=mesh)
before = jitcache.stats()
r2 = mgr.restore_sharded(2, state(0), mesh=mesh)
after = jitcache.stats()
assert after["misses"] == before["misses"], (before, after)
np.testing.assert_array_equal(np.asarray(r2["w"]), np.asarray(state(2)["w"]))
assert int(r2["step"]) == 2

for entry in ("ckpt_save", "ckpt_restore"):
    counts = jitcache.entry_counts(entry)
    assert counts and all(v in (1, -1) for v in counts.values()), (
        entry, counts)
print("CKPT-TRACE-OK", jitcache.stats())
"""


@pytest.mark.multidevice
def test_device_direct_ckpt_chain_traces_once():
    """Chain-path (training-mesh) saves/restores also compile once per key."""
    out = run_with_devices(CKPT_TRACE_SNIPPET, ndev=16)
    assert "CKPT-TRACE-OK" in out
