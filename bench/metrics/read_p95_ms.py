"""read_p95_ms: 95th percentile of the wall time of every
``StorageClient.read_range`` call in the window."""
import numpy as np


def read(run):
    if not run.durations:
        return None
    return 1e3 * float(np.percentile(run.durations, 95))
