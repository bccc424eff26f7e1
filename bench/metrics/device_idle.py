"""device_idle.<verb>: share of the traced calls of the cell's verb, from
the first one's start to the last one's end, in which no operation ran on
the device, in %. One body for every verb."""


def read(run):
    if run.trace is None:
        return None
    span = run.trace.span_extent(run.op.label)
    if span is None:
        return None
    lo, hi = span
    return 100.0 * (1.0 - run.trace.busy(lo, hi) / (hi - lo))
