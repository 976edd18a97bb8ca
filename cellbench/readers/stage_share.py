"""Seconds the program spent in one stage of a request path, as a share
(%) of the path's total, both from ``cubefs_request_stage_seconds``
summed over the window (the histogram's ``_sum``, not the span store)."""

from .. import registry

METRIC = "cubefs_request_stage_seconds_sum"


def read(cell, path, stage, over="total"):
    whole = registry.total(cell.registry, METRIC, path=path, stage=over)
    if whole <= 0:
        return None
    part = registry.total(cell.registry, METRIC, path=path, stage=stage)
    return 100.0 * part / whole
