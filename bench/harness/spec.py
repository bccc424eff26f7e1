"""What ``BENCHMARK.json`` names, found by name under the benchmark's files.

* a cell: ``workloads[]`` entry; its configuration ``configs[].file`` and
  its mix ``<bench>/traffic/<traffic>.json``;
* a mix's kind of operation: ``<bench>/traffic/ops/<op>.py`` (see
  ``harness.op``), named by the mix's ``"op"``;
* a configuration's plain reference: ``<bench>/reference/<reference>.py``,
  named by the configuration file's ``"reference"``;
* a metric: ``<bench>/metrics/<name>.py``, whose ``read(run)`` returns the
  value or ``None`` where the run holds nothing for it to read; a metric
  ``<family>.<part>`` with no file of its own is read by
  ``<bench>/metrics/<family>.py`` (one body for ``store_io_share.archive``,
  ``store_io_share.read``, ...);

where ``<bench>`` is the first of ``paths``.

Adding a configuration, a mix, a kind of operation or a metric adds files
and entries; no existing file changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["dir"] = os.path.join(root, bench["paths"][0])
    return bench


@functools.cache
def _module(path: str, name: str):
    """The module at ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(root: str, bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic mix) of cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(bench["dir"], "traffic",
                           f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    return w, cfg, mix


def reference(bench: dict, cfg: dict):
    name = cfg["reference"]
    return _module(os.path.join(bench["dir"], "reference", f"{name}.py"),
                   f"bench_reference_{name}")


def op(bench: dict, name: str):
    """The class ``Op`` of the kind of operation ``name``."""
    return _module(os.path.join(bench["dir"], "traffic", "ops", f"{name}.py"),
                   f"bench_op_{name}").Op


def metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones:
    those that list the cell, or list no cells."""
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def reader(bench: dict, name: str):
    path = os.path.join(bench["dir"], "metrics", f"{name}.py")
    if not os.path.exists(path):
        name = name.split(".")[0]
        path = os.path.join(bench["dir"], "metrics", f"{name}.py")
    return _module(path, f"bench_metric_{name}").read
