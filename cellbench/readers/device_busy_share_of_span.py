"""Of the time the host spent inside ``span`` while the profiler ran,
the share (%) in which an operation ran on the device: the union of the
device-op intervals of the trace over the union of the spans clipped to
the traced part. Two durations, so the two clocks need not agree. Sound
while every device operation of the cell is issued inside ``span``."""

from .. import stats


def read(cell, span):
    if cell.spans is None or not cell.devtrace or not cell.trace_span:
        return None
    lo, hi = cell.trace_span
    inside = stats.union_length(stats.clip(
        [(t0, t1) for t0, t1, _ in cell.spans.named(span)], lo, hi))
    if inside <= 0:
        return None
    return 100.0 * cell.devtrace["busy_s"] / inside
