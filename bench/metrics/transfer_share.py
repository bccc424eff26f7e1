"""transfer_share.<verb>: share of the traced calls of the cell's verb
spent in host-device transfers (the program's ``h2d`` and ``d2h`` spans)
while no op ran on the device, in %. ``d2h`` waits for the kernel; the
device's busy time inside it is left out, so the kernel is not counted
twice. One body for every verb."""
from harness.spans import share


def read(run):
    return share(run, ("h2d", "d2h"), idle_only=True)
