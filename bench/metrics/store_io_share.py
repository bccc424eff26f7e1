"""store_io_share.<verb>: share of the timed calls of the cell's verb spent
inside the node store's calls (``harness.timed_store``), host clock, in %.
One body for every verb; ``BENCHMARK.json`` names the cells of each."""


def read(run):
    if not run.durations:
        return None
    return 100.0 * sum(run.store_s) / sum(run.durations)
