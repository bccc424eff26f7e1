"""unattributed_share.<verb>: share of the traced calls of the cell's verb
in which no other host span is open (no span of the program, of the store
or of JAX), in %: the time no layer's metric explains. One body for every
verb."""
from harness.spans import unattributed


def read(run):
    return unattributed(run)
