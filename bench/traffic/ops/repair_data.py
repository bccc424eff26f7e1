"""repair_data: as ``repair``, but the lost rows are drawn from the k data
rows of a systematic code only (rows 0..k-1), so every repair of the cell
reads the same number of helpers. A draw over all n rows would mix a local
repair and a global one across seeds. Mix parameters: ``losses``.
"""
import os

from harness import spec
from harness.device import gf_apply_bytes

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
Repair = spec.op({"dir": BENCH}, "repair")


class Op(Repair):
    def setup(self) -> None:
        self.ingest(0)
        self.archive(0)
        self.lost = sorted(int(r) for r in self.rng.choice(
            self.k, self.p["losses"], replace=False))
        self.kernel_bytes = gf_apply_bytes(
            self.ref.repair_reads(self.cfg, self.lost), len(self.lost),
            self.B)
        self.drop(0, self.lost)
        self.client.repair(0)
