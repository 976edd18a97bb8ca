"""``q``-quantile (0..1) of one of the program's histograms over the
window, times ``scale``: from the window delta of its ``_bucket``
series, interpolated inside the bucket the rank falls in (what
Prometheus' ``histogram_quantile`` does; a rank past the last finite
bound reads that bound). Nothing under ``MIN_SAMPLES`` observations, or
where the program has no such histogram."""

MIN_SAMPLES = 20


def read(cell, metric, q, labels=None, scale=1.0):
    want = set((labels or {}).items())
    by_bound: dict[str, float] = {}  # le -> cumulative count, series summed
    for (name, lb), v in cell.registry.items():
        if name == metric + "_bucket" and want <= lb:
            le = dict(lb)["le"]
            by_bound[le] = by_bound.get(le, 0.0) + v
    n = by_bound.pop("+Inf", 0.0)
    if n < MIN_SAMPLES or not by_bound:
        return None
    rank = q * n
    lo, below = 0.0, 0.0
    for hi, cum in sorted((float(le), c) for le, c in by_bound.items()):
        if cum >= rank:
            inside = cum - below
            share = (rank - below) / inside if inside > 0 else 1.0
            return scale * (lo + (hi - lo) * share)
        lo, below = hi, cum
    return scale * lo
