"""The control of a cell: the run with the client built over the program's
own field below the configuration's (``harness.session.LOWER``), the
answers still held to the configuration's reference, on several seeds in
one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed: ``correct`` and every number compared with
its limit. A sound comparison reads ``correct: false`` on every seed. The
benchmark's own runs never switch the control on.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from harness import session

    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        out = session.run_cell(ROOT, args.workload, seed, args.seconds,
                               False, time.perf_counter(), control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": True, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
