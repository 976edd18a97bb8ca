"""Roofline share (%) of the GF apply on the device: the least time the
chip could take for the calls of ``span`` made while the profiler ran
(bytes and operations from their shapes, cellbench/roofline.py, over
the peaks of cellbench/peaks.json for this device kind) over the device
time of the trace — every device operation, or only those whose name
matches ``match``. A call that straddles an end of the traced part
counts by the share of it that lies inside."""

import re

from .. import roofline


def read(cell, span, match=None):
    if cell.spans is None or not cell.devtrace or not cell.trace_span:
        return None
    if cell.devtrace["busy_s"] <= 0:
        return None
    lo, hi = cell.trace_span
    least, bound = 0.0, {}
    peak = roofline.peaks(cell.device_kind)
    for t0, t1, a in cell.spans.named(span):
        part = (min(t1, hi) - max(t0, lo)) / (t1 - t0) if t1 > t0 else 0.0
        if part > 0:
            sec, which = roofline.least_seconds([a], peak)
            least += part * sec
            bound[which] = bound.get(which, 0) + 1
    if match is None:
        device_s = cell.devtrace["busy_s"]
    else:
        device_s = sum(t for n, t in cell.devtrace["ops_all"].items()
                       if re.search(match, n)) / max(1, cell.devtrace["chips"])
    cell.notes.setdefault("roofline_bound", {})[span] = bound
    return 100.0 * least / device_s if device_s > 0 and least > 0 else None
