"""The generator's draws and the timed node store."""
import numpy as np

from harness import spec
from harness.timed_store import TimedStore
from repro.storage.object_store import NodeStore


class _Client:
    store = None


def _draws(root, seed, n=200):
    read = spec.op(spec.load(root), "read")
    params = {"op": "read", "range_bytes": 1, "zipf_constant": 0.99}
    cfg = {"n": 4, "k": 3, "l": 16, "block_bytes": 100}
    m = read(params, cfg, _Client(), seed, None)
    m.plan()
    out = []
    for i in range(n):
        m.prepare(i)
        out.append(m.offset)
    return out


def test_zipf_draw_repeats_for_a_seed(tiny_root):
    big = 2**33 + 12345
    assert _draws(tiny_root, big) == _draws(tiny_root, big)
    assert _draws(tiny_root, big) != _draws(tiny_root, big + 1)
    counts = np.bincount(_draws(tiny_root, 7, 5000))
    assert counts.max() > 20 * np.median(counts[counts > 0])   # skewed


def test_zipf_weights_as_the_program_draws(tiny_root):
    from repro.storage.workload import zipf_weights
    read = spec._module(spec.load(tiny_root)["dir"] + "/traffic/ops/read.py",
                        "bench_op_read")
    assert np.allclose(read.zipf_weights(704, 0.99), zipf_weights(704, 0.99))


def test_timed_store_returns_what_node_store_does(tmp_path):
    plain = NodeStore(str(tmp_path / "plain"), 3)
    timed = TimedStore(str(tmp_path / "timed"), 3)
    blob = np.random.default_rng(0).bytes(10000)
    for s in (plain, timed):
        s.put(1, "a/b.bin", blob)
        with s.put_stream(2, "c.bin") as w:
            w.write(blob[:6000])
            w.write(blob[6000:])
    for args in ((1, "a/b.bin"), (2, "c.bin")):
        assert timed.get(*args) == plain.get(*args) == blob
        assert timed.get_range(*args, 123, 4567) == \
            plain.get_range(*args, 123, 4567)
        assert list(timed.get_stream(*args, 3000)) == \
            list(plain.get_stream(*args, 3000))
        assert timed.has(*args) and plain.has(*args)
    timed.delete(1, "a/b.bin")
    assert not timed.has(1, "a/b.bin")
    assert timed.seconds > 0
    assert {"store.put", "store.get", "store.get_range", "store.put_stream",
            "store.get_stream", "store.has", "store.delete"} <= set(timed.calls)
