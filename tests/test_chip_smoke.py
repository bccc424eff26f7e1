"""``chip_smoke.py`` on the CPU backend: it refuses to report, and its
phases run end to end at a tiny block size under Pallas interpret mode."""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from tests.subproc import REPO, run_with_devices

SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, SCRIPT], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_end_to_end(smoke, tmp_path):
    recs = smoke.run_one_chip(seed=0, block_bytes=8192, window_bytes=2048,
                              workdir=str(tmp_path))
    by = {r["phase"]: r for r in recs}
    assert list(by) == ["write_hot", "archive", "oracle", "lose", "repair",
                        "read", "write_hot_many", "archive_many",
                        "oracle_many"]
    for name in ("archive", "repair", "archive_many"):
        assert by[name]["coding"] == "device", by[name]
        assert by[name]["host_coded_words"] == 0
        assert by[name]["interpret"] == [True]          # CPU backend
        assert by[name]["tpu_custom_call"] is False
    assert by["archive_many"]["kernel_launches"] == 1
    assert len(by["lose"]["lost"]) == 5
    assert by["repair"]["repaired"] == by["lose"]["lost"]
    assert by["read"]["coding"] == "host"               # ROADMAP B2
    assert by["read"]["served_from"] == ["coded"]
    assert by["oracle"]["bytes_checked"] == 3 * 2048 * 16
    assert by["oracle_many"]["bytes_checked"] == 2 * 3 * 2048 * 16
    assert not (tmp_path / "store_one").exists()        # disk bounded
    for r in recs:
        json.dumps(r)                                   # printable record


FOUR_CHIP_SNIPPET = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
recs = smoke.run_four_chips(seed=0, block_bytes=32768, window_bytes=4096)
print("PHASES", json.dumps([r["phase"] for r in recs]))
"""


@pytest.mark.multidevice
def test_four_chip_path_on_virtual_devices():
    """The ``--chips 4`` path on four forced host devices: each chain
    program spans its own device per position and matches the fused
    one-device encode and the ``encode_np`` windows."""
    out = run_with_devices(FOUR_CHIP_SNIPPET.format(script=SCRIPT), ndev=4)
    assert 'PHASES ["chain_encode", "chain_encode_many", "chain_repair"]' \
        in out, out
