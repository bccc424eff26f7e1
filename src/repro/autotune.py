"""Pre-warm the RapidRAID tuning cache: ``python -m repro.autotune``.

Runs the full ``repro.core.autotune.prewarm`` search for one code geometry
— kernel tile widths, MXU-vs-VPU dispatch, per-tick tile widths, the chain
calibration sweep (fitting the makespan model's compute_rate and
tick_overhead), and the pipeline plan parameters (num_chunks, stagger) —
and persists everything to the JSON tuning cache, so production runs under
``RAPIDRAID_TUNE=cached`` (the default) start warm and never probe.

The chain probes need ``n`` local jax devices. On the CPU backend the CLI
asks for ``n`` forced host devices before JAX starts (forced host devices
share one CPU, which is exactly the geometry the cached plans will serve
under test/CI runs), so everything runs in this one process. On an
accelerator the probes run on the chips the host has, and the CLI stops
with an error when that is fewer than ``n``.

Examples::

    python -m repro.autotune                      # (8,5) l=16 defaults
    python -m repro.autotune --n 16 --k 11 --nwords 131072
    RAPIDRAID_TUNE_CACHE=/tmp/t.json python -m repro.autotune --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.autotune",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--family", default="rapidraid",
                    help="code family (default rapidraid)")
    ap.add_argument("--n", type=int, default=8, help="codeword blocks")
    ap.add_argument("--k", type=int, default=5, help="data blocks")
    ap.add_argument("--l", type=int, default=16, choices=(8, 16),
                    help="GF field size")
    ap.add_argument("--seed", type=int, default=0, help="code seed")
    ap.add_argument("--nwords", type=int, default=1 << 14,
                    help="object words per block for the probes")
    ap.add_argument("--b-obj", type=int, default=4,
                    help="batch size for the multi-object probes")
    ap.add_argument("--chunk-counts", default="1,2,4,8,16",
                    help="comma-separated calibration sweep chunk counts")
    ap.add_argument("--json", action="store_true",
                    help="print the full tuning report as JSON")
    return ap


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)

    # search is the point of the CLI: force it on unless the user pinned a
    # mode explicitly (off would make the whole run a no-op — reject it)
    from repro.core import autotune
    mode = os.environ.get(autotune.TUNE_ENV)
    if mode is None:
        os.environ[autotune.TUNE_ENV] = "search"
    elif autotune.mode() != "search":
        print(f"repro.autotune: {autotune.TUNE_ENV}={mode!r} disables "
              f"searching; unset it or set it to 'search'", file=sys.stderr)
        return 2

    import jax

    from repro.core import jitcache

    # must precede the backend's start-up; it changes only the CPU backend
    jax.config.update("jax_num_cpu_devices", args.n)
    jitcache.enable_persistent_cache()
    devices = jax.devices()
    if len(devices) < args.n:
        print(f"repro.autotune: the chain probes of an n={args.n} code need "
              f"{args.n} devices; this host has {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2

    from repro.core.codes import registry

    code = registry.make(args.family, n=args.n, k=args.k, l=args.l,
                         seed=args.seed)
    chunk_counts = tuple(int(c) for c in args.chunk_counts.split(","))
    report = autotune.prewarm(code, nwords=args.nwords, b_obj=args.b_obj,
                              chunk_counts=chunk_counts)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True))
    else:
        print(f"tuning cache: {report['cache']}")
        print(f"backend: {report['backend']}  spec: {report['spec']}")
        print(f"encode_packed block: {report['encode_packed_block']}  "
              f"encode_mxu block: {report['encode_mxu_block']}  "
              f"dispatch: {report['dispatch']}")
        print(f"tick blocks: {report['tick_blocks']}")
        cal = report.get("calibration")
        if cal:
            print(f"calibrated compute_rate {cal['compute_rate']:.3g} B/s, "
                  f"tick_overhead {cal['tick_overhead']:.3g} s "
                  f"(max fit error {cal['max_rel_err']:.1%})")
            print(f"num_chunks: encode={report['num_chunks_encode']} "
                  f"encode_many={report['num_chunks_encode_many']} "
                  f"stagger={report['stagger']}")
        else:
            print(report.get("skipped", "calibration skipped"))
        print(f"probes run: {report['stats']['probes']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
