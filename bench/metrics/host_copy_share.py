"""host_copy_share.<verb>: share of the traced calls of the cell's verb
spent copying payload on the host (the program's ``host_copy`` spans), in
%. One body for every verb."""
from harness.spans import share


def read(run):
    return share(run, ("host_copy",))
