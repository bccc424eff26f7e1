"""What every kind of traffic shares: objects made from the seed, ingested
hot, and their stored rows taken for the comparison.

A mix (``bench/traffic/<name>.json``) names its kind of operation,
``"op"``, and sets that operation's parameters. The operation is the
module ``bench/traffic/ops/<op>.py``; its class ``Op`` subclasses ``Base``
and supplies the hooks the harness calls:

* ``setup()``: everything the window needs, with every program the window
  runs warmed up through the client verbs;
* ``prepare(i)``, clock stopped: what call ``i`` needs in place;
* ``call(i)``, on the clock: the one client verb;
* ``after(i)``, clock stopped: take the call's answers;
* ``checks()``, after the window: every count that a sound run leaves at 0;

and the fields its metrics read: ``label`` (the verb's name, marked in the
trace around every call), ``user_bytes`` (user bytes one call moves between
tiers) and ``kernel_bytes`` (bytes the coding kernel must move in one call,
from shapes). A new kind of traffic is a new module and a mix that names it.
"""
from __future__ import annotations

import numpy as np

from harness import answers
from repro.storage import archive as arc


def seed_words(seed: int) -> list[int]:
    """A run's seed as non-negative 64-bit words for numpy's seeding."""
    return [seed & (2**64 - 1), (seed >> 64) & (2**64 - 1)]


class Base:
    label = ""
    user_bytes = 0
    kernel_bytes = 0

    def __init__(self, params: dict, cfg: dict, client, seed: int, ref):
        self.p = params
        self.cfg = cfg
        self.ref = ref
        self.client = client
        self.store = client.store
        self.seed = seed
        self.k, self.n, self.B = cfg["k"], cfg["n"], cfg["block_bytes"]
        self.refs: dict[int, answers.DataRef] = {}
        self.rows: list[answers.RowAnswer] = []
        self.rng = np.random.default_rng(seed_words(seed) + [1])

    def setup(self) -> None:
        pass

    def prepare(self, i: int) -> None:
        pass

    def call(self, i: int) -> None:
        raise NotImplementedError

    def after(self, i: int) -> None:
        pass

    def checks(self) -> dict[str, int]:
        """The stored rows taken, against the reference's generator."""
        return answers.compare(self.ref.generator(self.cfg), self.refs,
                               self.rows)

    # -- objects ----------------------------------------------------------------

    def blocks(self, obj: int) -> np.ndarray:
        """Object ``obj``'s (k, B) data blocks, drawn from the seed."""
        rng = np.random.default_rng(seed_words(self.seed) + [2, obj])
        return np.frombuffer(rng.bytes(self.k * self.B),
                             np.uint8).reshape(self.k, self.B)

    def ingest(self, obj: int) -> np.ndarray:
        """Store object ``obj`` hot; keep the reference's view of it."""
        blocks = self.blocks(obj)
        rng = np.random.default_rng(seed_words(self.seed) + [3, obj])
        self.refs[obj] = answers.DataRef.of(blocks, self.cfg["l"], rng)
        self.client.put_hot(obj, blocks)
        return blocks

    def archive(self, obj: int) -> bool:
        """Migrate ``obj`` to the coded tier, its hot replicas reclaimed (the
        configurations' ``hot_reclaimed`` guarantee); -> whether the
        manifest says it is archived."""
        return self.client.archive(obj, reclaim_hot=True)["tier"] == "archive"

    def node_rel(self, obj: int, row: int) -> tuple[int, str]:
        perm = self.client.manifest(obj)["perm"]
        return perm[row], arc.ARC.format(step=obj, i=row)

    def take_row(self, obj: int, row: int) -> None:
        """Fold coded row ``row`` of ``obj`` as stored on its node."""
        path = self.store.path(*self.node_rel(obj, row))
        self.rows.append(answers.read_row(path, obj, row, self.refs[obj]))

    def drop(self, obj: int, rows) -> None:
        """Delete the stored coded rows ``rows`` of ``obj``."""
        for row in rows:
            self.store.delete(*self.node_rel(obj, row))
