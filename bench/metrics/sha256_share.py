"""sha256_share.<verb>: share of the traced calls of the cell's verb spent
hashing (the program's ``sha256`` spans: every digest of a hot replica, a
coded row, a helper or a repaired row), in %. One body for every verb."""
from harness.spans import share


def read(run):
    return share(run, ("sha256",))
