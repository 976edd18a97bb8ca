"""A counter's delta over the window: the sum of every series of
``metric`` that carries ``labels``. Nothing where the program has no
such counter (a parent commit that lacks it): the line then leaves the
metric out. A counter the program has and that did not move reads 0."""

from .. import registry


def read(cell, metric, labels=None):
    labels = labels or {}
    if not any(name == metric for name, _ in cell.registry):
        return None
    return registry.total(cell.registry, metric, **labels)
