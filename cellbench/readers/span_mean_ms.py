"""Mean duration (ms) of the benchmark's own span ``span`` over the
window (host clock; a traced run only)."""


def read(cell, span, where=None):
    if cell.spans is None:
        return None
    got = [t1 - t0
           for t0, t1, a in cell.spans.named(span, cell.t0, cell.t_end)
           if all(a.get(k) == v for k, v in (where or {}).items())]
    cell.notes.setdefault("samples", {})[span] = len(got)
    return 1e3 * sum(got) / len(got) if got else None
