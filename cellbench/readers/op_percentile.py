"""``q``-th percentile of the client-side latency of ``kinds``, in ms,
over the operations completed in the window. A failed operation is in
``failed`` and has no latency."""

from .. import stats


def read(cell, kinds, q):
    lat = [(o[2] - o[1]) * 1e3 for o in cell.window_ops(*kinds) if o[4]]
    cell.notes.setdefault("samples", {})["/".join(kinds) + f".p{q}"] = len(lat)
    return stats.percentile(lat, q)
