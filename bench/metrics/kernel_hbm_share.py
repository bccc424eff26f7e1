"""kernel_hbm_share.<verb>: the bytes the GF(2^l) coding kernel must move in
the traced calls of the cell's verb (the operation's ``kernel_bytes``,
from shapes: rows read plus rows written, at the configuration's block
size), over the chip's published HBM bandwidth, over the summed device
time of the Pallas kernels that ran inside those calls, in %. One body for
every verb.

A Pallas kernel is any device op that the trace names as a
``tpu_custom_call``, whatever program launched it. No integer VPU peak of
the chip is published, so this is a lower bound on the kernel's roofline
share. Where no Pallas kernel ran inside the calls, nothing is read and the
metric is left out; the harness names it on standard error.
"""
from harness.device import peak


def kernel(name: str) -> bool:
    return "tpu_custom_call" in name


def read(run):
    if run.trace is None or not run.op.kernel_bytes:
        return None
    span = run.trace.span_extent(run.op.label)
    if span is None:
        return None
    t = run.trace.op_seconds(kernel, *span)
    if t <= 0:
        return None
    calls = sum(1 for name, _, _ in run.trace.spans if name == run.op.label)
    moved = calls * run.op.kernel_bytes
    return 100.0 * moved / peak(run.device_kind, "hbm_bytes_per_s") / t
