"""read: one client reading ranges of one archived object.

Set-up archives the object, folds its stored coded rows, and deletes
``lost_shards`` of them drawn from the seed. Each call reads
``range_bytes`` aligned to their size through
``StorageClient.read_range``; the range is drawn by a Zipfian of constant
``zipf_constant`` over the object's ranges, scrambled by a permutation
drawn from the seed (YCSB's scrambled Zipfian). Every byte read is
compared with the data. Mix parameters: ``range_bytes``,
``zipf_constant``, ``lost_shards``.
"""
import numpy as np

from harness.op import Base


def zipf_weights(ranks: int, constant: float) -> np.ndarray:
    """P(rank r) proportional to 1/(r+1)^constant over ``ranks`` ranks
    (as ``repro.storage.workload.zipf_weights``)."""
    w = 1.0 / np.power(np.arange(1, ranks + 1, dtype=np.float64), constant)
    return w / w.sum()


class Op(Base):
    label = "read_range"

    def __init__(self, *args):
        super().__init__(*args)
        self.faults = {"read_bytes_wrong": 0}

    def setup(self) -> None:
        self.data = self.ingest(0).reshape(-1)
        self.archive(0)
        for row in range(self.n):
            self.take_row(0, row)
        self.drop(0, [int(r) for r in self.rng.choice(
            self.n, self.p["lost_shards"], replace=False)])
        self.plan()
        self.prepare(0)
        self.client.read_range(0, self.offset, self.size)

    def plan(self) -> None:
        """The range size, the Zipfian and its scrambling permutation."""
        self.size = self.p["range_bytes"]
        ranges = self.k * self.B // self.size
        self.weights = zipf_weights(ranges, self.p["zipf_constant"])
        self.scramble = self.rng.permutation(ranges)

    def prepare(self, i: int) -> None:
        rank = int(self.rng.choice(len(self.weights), p=self.weights))
        self.offset = int(self.scramble[rank]) * self.size

    def call(self, i: int) -> None:
        self.got = self.client.read_range(0, self.offset, self.size).data

    def after(self, i: int) -> None:
        want = self.data[self.offset:self.offset + self.size]
        got = np.frombuffer(self.got, np.uint8)
        self.faults["read_bytes_wrong"] += (
            int(np.count_nonzero(got != want))
            if got.shape == want.shape else self.size)

    def checks(self) -> dict[str, int]:
        return {**self.faults, **super().checks()}
