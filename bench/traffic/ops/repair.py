"""repair: one archived object, healed again and again.

Set-up archives the object and draws ``losses`` of its coded rows from the
seed. Before each call those rows are deleted with the clock stopped, and
``StorageClient.repair`` heals them on the clock; the rows it placed are
then folded from their files. Mix parameters: ``losses``.
"""
from harness.device import gf_apply_bytes
from harness.op import Base


class Op(Base):
    label = "repair"

    def __init__(self, *args):
        super().__init__(*args)
        self.faults = {"wrong_result": 0}

    def setup(self) -> None:
        self.ingest(0)
        self.archive(0)
        self.lost = sorted(int(r) for r in self.rng.choice(
            self.n, self.p["losses"], replace=False))
        self.kernel_bytes = gf_apply_bytes(
            self.ref.repair_reads(self.cfg, self.lost), len(self.lost),
            self.B)
        self.drop(0, self.lost)
        self.client.repair(0)

    def prepare(self, i: int) -> None:
        self.drop(0, self.lost)

    def call(self, i: int) -> None:
        self.repaired = self.client.repair(0)

    def after(self, i: int) -> None:
        self.faults["wrong_result"] += sorted(self.repaired) != self.lost
        for row in self.lost:
            self.take_row(0, row)

    def checks(self) -> dict[str, int]:
        return {**self.faults, **super().checks()}
