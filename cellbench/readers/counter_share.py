"""Share (%) of a counter's window delta that carries ``labels``."""

from .. import registry


def read(cell, metric, labels):
    whole = registry.total(cell.registry, metric)
    if whole <= 0:
        return None
    return 100.0 * registry.total(cell.registry, metric, **labels) / whole
