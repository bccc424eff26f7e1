"""Every cell runs end to end on the CPU at a tiny size, and a new cell
needs only new files and entries."""
import json
import os
import time

import pytest

from conftest import shrink
from harness import session

SEED = 2**33 + 99
CELLS = ["rr16.archive", "rr16.repair", "lrc12.archive", "rr16.read"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(tiny_root, cell, trace):
    out = session.run_cell(tiny_root, cell, SEED, 0.3, trace,
                           time.perf_counter())
    assert out["correct"], out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])}
    # the CPU has no device ops: the metrics read from a device trace stay
    # silent there; every other metric of the cell is reported
    want -= {m["name"] for m in bench[kind] if m["source"] == "device_trace"}
    assert want <= set(out["metrics"])
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_cell_from_new_files_only(tiny_root):
    """A GF(2^8) (6,4) RapidRAID configuration, a degraded-read mix, a new
    kind of operation (reads of an object still hot) and a median-latency
    metric: new files and new entries only."""
    bench_dir = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench_dir, "configs",
                           "rapidraid-16-11-gf16.json")) as f:
        cfg = json.load(f)
    cfg.update(name="rapidraid-6-4-gf8", n=6, k=4, l=8)
    with open(os.path.join(bench_dir, "configs", "rr6.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench_dir, "traffic", "degraded.json"), "w") as f:
        json.dump({"op": "read", "range_bytes": 4096, "zipf_constant": 0.99,
                   "lost_shards": 1}, f)
    with open(os.path.join(bench_dir, "traffic", "hot.json"), "w") as f:
        json.dump({"op": "read_hot", "range_bytes": 4096}, f)
    with open(os.path.join(bench_dir, "traffic", "ops", "read_hot.py"),
              "w") as f:
        f.write(HOT_READ_OP)
    with open(os.path.join(bench_dir, "metrics", "read_p50_ms.py"), "w") as f:
        f.write("import numpy as np\n\n\ndef read(run):\n"
                "    return 1e3 * float(np.percentile(run.durations, 50))\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "rapidraid-6-4-gf8", "source": "x",
                             "file": "bench/configs/rr6.json", "reduced": [],
                             "why": "x"})
    for name, traffic in (("rr6.degraded", "degraded"), ("rr6.hot", "hot")):
        bench["workloads"].append({"name": name,
                                   "config": "rapidraid-6-4-gf8",
                                   "traffic": traffic, "chips": 1,
                                   "why": "x"})
    bench["end_to_end"].append({"name": "read_p50_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["rr6.degraded", "rr6.hot"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    shrink(os.path.join(bench_dir, "configs", "rr6.json"), block_bytes=32768)
    for cell in ("rr6.degraded", "rr6.hot"):
        out = session.run_cell(tiny_root, cell, SEED, 0.3, False,
                               time.perf_counter())
        assert out["correct"], out["checks"]
        assert {"read_p50_ms", "setup_s"} <= set(out["metrics"])


HOT_READ_OP = '''"""read_hot: ranges read from an object still in the hot tier."""
import numpy as np

from harness.op import Base


class Op(Base):
    label = "read_range"

    def setup(self):
        self.data = self.ingest(0).reshape(-1)
        self.size = self.p["range_bytes"]
        self.wrong = 0

    def prepare(self, i):
        self.offset = int(self.rng.integers(self.data.size // self.size))
        self.offset *= self.size

    def call(self, i):
        self.got = self.client.read_range(0, self.offset, self.size).data

    def after(self, i):
        want = self.data[self.offset:self.offset + self.size]
        self.wrong += int(np.count_nonzero(
            np.frombuffer(self.got, np.uint8) != want))

    def checks(self):
        return {"read_bytes_wrong": self.wrong}
'''
