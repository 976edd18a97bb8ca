"""The program's counters and histograms, read through its public
Prometheus text (``metrics.DEFAULT.render_text()``) before and after the
window; a per-layer reader works on the difference. The span store is
not read: it keeps 2048 traces and a window makes more."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="([^"]*)"')

Series = dict[tuple[str, frozenset], float]


def parse(text: str) -> Series:
    out: Series = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        out[(name, frozenset(_LABEL.findall(labels or "")))] = float(value)
    return out


def snapshot() -> Series:
    from cubefs_tpu.utils import metrics

    return parse(metrics.DEFAULT.render_text())


def delta(before: Series, after: Series) -> Series:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def total(series: Series, name: str, **labels) -> float:
    """Sum of every series of ``name`` whose labels include ``labels``
    (histogram buckets excluded: ask for ``<name>_sum`` / ``_count``)."""
    want = set(labels.items())
    return sum(v for (n, lb), v in series.items()
               if n == name and want <= lb)

