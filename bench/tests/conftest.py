"""Shared set-up of the benchmark's own tests (run on the CPU):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

``tiny_root`` builds a checkout-like directory holding ``BENCHMARK.json``
and a copy of ``bench/``, with every configuration cut to 32 KiB blocks
and every read to 4 KiB ranges, so a cell runs in seconds on the CPU.
"""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

TINY_BLOCK = 32768
TINY_RANGE = 4096


def make_tiny_root(path: str) -> str:
    shutil.copytree(BENCH, os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for c in bench["configs"]:
        shrink(os.path.join(path, c["file"]), block_bytes=TINY_BLOCK)
    for name in os.listdir(os.path.join(path, "bench", "traffic")):
        p = os.path.join(path, "bench", "traffic", name)
        if name.endswith(".json") and "range_bytes" in json.load(open(p)):
            shrink(p, range_bytes=TINY_RANGE)
    return path


def shrink(path: str, **sizes) -> None:
    with open(path) as f:
        d = json.load(f)
    d.update(sizes)
    with open(path, "w") as f:
        json.dump(d, f)


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(str(tmp_path))
