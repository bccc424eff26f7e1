"""archive: one migrator archiving back-to-back objects.

Each object is made from the seed and ingested hot by ``put_hot`` with the
clock stopped, then migrated by ``StorageClient.archive`` on the clock.
After each call every stored coded row is folded from its file, the hot
replicas are checked gone, and the rows are deleted (an object's shards
stay on disk only until they are folded). Mix parameters: none.
"""
from harness.device import gf_apply_bytes
from harness.op import Base
from repro.storage import archive as arc


class Op(Base):
    label = "archive"

    def __init__(self, *args):
        super().__init__(*args)
        self.user_bytes = self.k * self.B
        self.kernel_bytes = gf_apply_bytes(self.k, self.n, self.B)
        self.faults = {"wrong_result": 0, "hot_left": 0}

    def setup(self) -> None:
        self.ingest(0)
        self.archive(0)
        self.drop(0, range(self.n))

    def prepare(self, i: int) -> None:
        self.ingest(i + 1)

    def call(self, i: int) -> None:
        self.archived = self.archive(i + 1)

    def after(self, i: int) -> None:
        obj = i + 1
        self.faults["wrong_result"] += not self.archived
        for row in range(self.n):
            self.take_row(obj, row)
        m = self.client.manifest(obj)
        self.faults["hot_left"] += sum(
            self.store.has(node, arc.HOT.format(step=obj, j=j))
            for node, held in enumerate(m["placement"]) for j in held)
        self.drop(obj, range(self.n))

    def checks(self) -> dict[str, int]:
        return {**self.faults, **super().checks()}
