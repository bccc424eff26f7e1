"""The main path's kernels and programs compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see the TPU compiler's
refusals: tiles not aligned to the layout, more VMEM than a kernel may use,
a program that does not fit HBM. Here each kernel and jitted program of the
archival path is compiled ahead of time for a DESCRIBED ``v5e:2x2``
topology, at the widths ``chip_smoke.py`` runs: the paper's (16,11) code
over GF(2^16) with 64 MiB blocks, and the (4,3) chain of the four-chip
path. Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import), so
under pytest-xdist only the worker given this file loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import codes, fault_tolerance, gf
from repro.kernels.gf_encode import kernel, ops
from repro.storage import chain, multi, repair

BLOCK_BYTES = 64 << 20
WORDS = BLOCK_BYTES // 2            # GF(2^16) words per block
LANES_U32 = WORDS // 2              # packed uint32 lanes per block
TICK = LANES_U32 // 8               # one chunk of the 8-chunk pipeline


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile cannot be read back without the chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Programs that resolve ``interpret`` while tracing see the CPU
    backend here; steer them to the compiled kernel."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)


def _code(n=16, k=11, l=16):
    return codes.make("rapidraid", n, k, l=l, seed=0)


def _key(M):
    return tuple(tuple(int(v) for v in row) for row in np.asarray(M))


def _compile(fn, *args) -> str:
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no compiled Pallas kernel in program"
    return text


@pytest.mark.parametrize("batch", [None, 2])
def test_gf_encode_kernel_64mib_block(one_chip, batch):
    G = _code().G
    shape = (11, LANES_U32) if batch is None else (batch, 11, LANES_U32)
    _compile(jax.jit(lambda x: kernel.gf_encode_kernel(
        G, x, 16, interpret=False)),
        jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip))


def test_chain_step_kernel_tick(one_chip):
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
    _compile(jax.jit(lambda x, loc, a, b: kernel.chain_step_kernel(
        x, loc, a, b, 16, interpret=False)),
        sds((1, TICK)), sds((2, TICK)), sds((2, 16)), sds((2, 16)))


def test_repair_step_kernel_tick(one_chip):
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
    _compile(jax.jit(lambda x, loc, bp: kernel.repair_step_kernel(
        x, loc, bp, 16, interpret=False)),
        sds((5, TICK)), sds((1, TICK)), sds((5, 16)))


def test_gf_encode_mxu_kernel_gf8(one_chip):
    G = _code(l=8).G
    _compile(jax.jit(lambda x: kernel.gf_encode_mxu_kernel(
        G, x, 8, interpret=False)),
        jax.ShapeDtypeStruct((11, BLOCK_BYTES), jnp.int32, sharding=one_chip))


def _repair_R():
    code = _code()
    lost = [0, 3, 5, 8, 12]
    _, R = fault_tolerance.repair_plan(
        code, lost, [p for p in range(16) if p not in lost])
    return R


@pytest.mark.parametrize("entry", ["archive", "archive_many", "repair"])
def test_fused_encode_program(one_chip, entry):
    """The jitted ``encode_packed`` program behind ``archive_step`` (one
    object), ``archive_many`` (two in one launch) and ``repair_many`` (the
    five-row repair matrix) at the paper's object size."""
    M = _repair_R() if entry == "repair" else _code().G
    objs = 2 if entry == "archive_many" else 1
    x = jax.ShapeDtypeStruct((objs, 11, LANES_U32), jnp.uint32,
                             sharding=one_chip)
    block = ops.pick_block(LANES_U32)
    _compile(ops._encode_packed_jit, x, _key(M), 16, block, False)


def test_pack_unpack_program(one_chip):
    """Word <-> lane packing of a whole object fits HBM (no padded minor
    axis)."""
    x = jax.ShapeDtypeStruct((1, 11, WORDS), jnp.uint16, sharding=one_chip)
    compiled = jax.jit(lambda w: gf.unpack_u32(gf.pack_u32(w, 16), 16)) \
        .lower(x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 11 * WORDS


@pytest.mark.parametrize("program", ["encode", "encode_many", "repair"])
def test_four_chip_chain_program(topo, compiled_kernels, program):
    """The shard_map chain programs of ``chip_smoke.py --chips 4`` — a
    (4,3) code at 64 MiB blocks — compile for four chips, one chain
    position per device."""
    code = _code(4, 3)
    if program == "repair":
        helpers, R = fault_tolerance.repair_plan(code, [2], [0, 1, 3])
        mesh = Mesh(np.asarray(topo.devices[:3]), (chain.AXIS,))
        fn = repair._build_repair(code, (2,), tuple(helpers), R, mesh, 8)
        shape = (3, WORDS)
    elif program == "encode_many":
        mesh = Mesh(np.asarray(topo.devices[:4]), (chain.AXIS,))
        fn = multi._build_encode_many(code, mesh, 8, 1)
        shape = (2, 3, WORDS)
    else:
        mesh = Mesh(np.asarray(topo.devices[:4]), (chain.AXIS,))
        fn = chain._build_encode(code, mesh, 8)
        shape = (3, WORDS)
    x = jax.ShapeDtypeStruct(shape, jnp.uint16,
                             sharding=NamedSharding(mesh, P()))
    text = _compile(fn, x)
    assert f"num_partitions={mesh.size}" in text
    assert "collective-permute" in text
