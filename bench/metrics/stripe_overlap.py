"""stripe_overlap.<verb>: share of the device's busy time inside the traced
calls of the cell's verb that lies under an open ``stripe_read`` or
``stripe_write`` span of the program, in %: device work hidden behind a
streamed archive's host I/O (the next stripe's range reads, the last
stripe's framed writes). Nothing is read where no such span lies inside a
call (an archive coded in one launch, or a program without them) or the
device did no work there."""
from harness.spans import _calls, _inside

STRIPE_IO = ("stripe_read", "stripe_write")


def read(run):
    calls = _calls(run)
    if calls is None:
        return None
    under = _inside(run, calls, lambda n: n in STRIPE_IO)
    busy = sum(run.trace.busy(a, b) for a, b in calls)
    if not under or busy <= 0:
        return None
    return 100.0 * sum(run.trace.busy(a, b) for a, b in under) / busy
