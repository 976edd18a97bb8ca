"""Mean of one of the program's histograms over the window:
delta of ``_sum`` over delta of ``_count``, times ``scale``."""

from .. import registry


def read(cell, metric, labels=None, scale=1.0):
    labels = labels or {}
    n = registry.total(cell.registry, metric + "_count", **labels)
    if n <= 0:
        return None
    return scale * registry.total(cell.registry, metric + "_sum",
                                  **labels) / n
