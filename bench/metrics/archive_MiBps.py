"""archive_MiBps: user MiB migrated from the replicated to the coded tier,
over the summed wall time of every ``StorageClient.archive`` call in the
window."""


def read(run):
    if not run.durations or not run.op.user_bytes:
        return None
    return len(run.durations) * run.op.user_bytes / 2**20 / sum(run.durations)
