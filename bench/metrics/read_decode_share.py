"""read_decode_share.<verb>: share of the traced calls of the cell's verb
spent choosing helpers and decoding on the host (the program's
``read_plan`` and ``read_decode`` spans), in %."""
from harness.spans import share


def read(run):
    return share(run, ("read_plan", "read_decode"))
