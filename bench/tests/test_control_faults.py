"""The comparison fails the control and every fault a cell can have.

The control is the client built over the program's own GF(2^8) path
(``harness.session.LOWER``), its answers held to the GF(2^16) reference.
The faults are planted in the timed path underneath a whole run that skips
only the look for a chip: a call that leaves its state unchanged, half of
its work left out, and one answer altered where it is produced. (Every
cell runs on one chip, so there is no exchange between chips to leave
out.)
"""
import time

import numpy as np
import pytest

from harness import session, spec
from repro.storage import archive as arc

SEED = 2**33 + 4242


def _run(root, cell, **kw):
    return session.run_cell(root, cell, SEED, 0.2, False,
                            time.perf_counter(), **kw)


@pytest.mark.parametrize("cell", ["rr16.archive", "lrc12.archive",
                                  "rr16.repair", "rr16.read"])
def test_control_is_not_correct(tiny_root, cell):
    out = _run(tiny_root, cell, control=True)
    assert not out["correct"]
    assert out["failed"] == 0          # the control runs; its answers fail


def _encode_fault(kind):
    full = arc._fused_encode

    def encode(code, objs_w, l):
        out = full(code, objs_w, l).copy()
        if kind == "unchanged":        # the rows come back as the input
            out[:] = 0
            out[:, :code.k] = objs_w
        elif kind == "half":           # half of each row left out
            out[..., out.shape[-1] // 2:] = 0
        else:                          # one word altered
            out[0, 3, 17] ^= 1
        return out
    return encode


def _repair_fault(kind):
    full = arc._place_repaired

    def place(store, step, manifest, missing, repaired, replacement_nodes):
        if kind == "unchanged":        # nothing placed
            return None
        repaired = repaired.copy()
        if kind == "half":
            repaired[:, repaired.shape[1] // 2:] = 0
        else:
            repaired[0, 5] ^= 1
        return full(store, step, manifest, missing, repaired,
                    replacement_nodes)
    return place


def _read_fault(kind):
    full = arc.read_range_ex

    def read(*args, **kw):
        res = full(*args, **kw)
        data = np.frombuffer(res.data, np.uint8).copy()
        if kind == "unchanged":        # a buffer never filled
            data[:] = 0
        elif kind == "half":
            data[len(data) // 2:] = 0
        else:
            data[7] ^= 1
        return arc.ReadResult(data.tobytes(), res.served_from, res.nodes,
                              res.healed, res.step)
    return read


FAULTS = {"rr16.archive": ("_fused_encode", _encode_fault),
          "lrc12.archive": ("_fused_encode", _encode_fault),
          "rr16.repair": ("_place_repaired", _repair_fault),
          "rr16.read": ("read_range_ex", _read_fault)}


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, kind):
    """The fault is in place during the timed calls only."""
    name, make = FAULTS[cell]
    bench = spec.load(tiny_root)
    op = spec.op(bench, spec.cell(tiny_root, bench, cell)[2]["op"])
    call = op.call

    def faulty(self, i):
        with monkeypatch.context() as mp:
            mp.setattr(arc, name, make(kind))
            return call(self, i)

    monkeypatch.setattr(op, "call", faulty)
    out = _run(tiny_root, cell)
    assert not out["correct"], out["checks"]
