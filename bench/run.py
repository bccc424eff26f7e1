"""Chip benchmark of the archival system: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, in one process that holds the chip. The
cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; everything the run stores or reads is made from ``--seed``
in a temporary directory that is removed at exit. Set-up (JAX start, the
compile or compile-cache load, data and warm-up) counts as ``setup_s``;
then the mix's client verb is timed for ``--seconds`` of calls. With
``--trace 1`` the per-layer metrics are read from a profiler trace of part
of that window instead of the end-to-end ones.

Earlier lines of standard output describe the run; the last is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced), then ``checks``, every number compared
with its limit, which also close standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result. JAX's compilation cache is kept in ``.jax_cache`` at the root of
the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from harness import session, spec
    from repro.core import jitcache

    bench = spec.load(ROOT)
    work, _, _ = spec.cell(ROOT, bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < work["chips"]:
        print(f"bench/run.py: cell {args.workload} needs {work['chips']} "
              f"TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    jitcache.enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = session.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
