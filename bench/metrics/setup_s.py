"""setup_s: seconds from process start to the first timed call (JAX start,
compile or compile-cache load, data from the seed, the warm-up calls)."""


def read(run):
    return run.setup_s
