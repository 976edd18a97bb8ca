"""The benchmark's own spans, put round calls into the program's layers
from here (spans inside the program are the tracing PR's). Each span is
recorded on the host clock — idle gaps of the device are labelled from
these records, moved onto the trace's clock by the ``trace_window``
marker both clocks saw — and, while the profiler runs, also written into
the profiler's trace as ``cellbench:<name>`` for whoever opens it.

Installed only in a traced run (``--trace 1``): the end-to-end numbers
are taken with none of this in the path.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

PREFIX = "cellbench:"


class SpanLog:
    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[tuple[str, float, float, dict]] = []

    @contextmanager
    def span(self, name: str, **attrs):
        import jax.profiler

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(PREFIX + name):
                yield
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self._spans.append((name, t0, t1, attrs))

    def all(self) -> list[tuple[str, float, float]]:
        with self._lock:
            return [(n, t0, t1) for n, t0, t1, _ in self._spans]

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> list[tuple[float, float, dict]]:
        """(start, end, attrs) of the spans ``name`` that ended in [lo, hi]."""
        with self._lock:
            return [(t0, t1, a) for n, t0, t1, a in self._spans
                    if n == name and lo <= t1 <= hi]


def instrument(dep, spans: SpanLog):
    """Wrap the device engine's two calls (``engine.call``: H2D + kernel
    + D2H, it ends in ``np.asarray``) and the node clients' shard calls
    (``storage.put_shard`` / ``storage.get_shard``). Returns the function
    that takes the engine wrappers off again — the engine instance is
    process-wide."""
    import numpy as np

    from cubefs_tpu.codec.engine import get_engine

    eng = get_engine(dep.access.cfg.engine)
    enc0, app0 = eng.encode_parity, eng.matrix_apply

    def shape(op: str, arr, rows: int) -> dict:
        d = np.asarray(arr)
        return {"op": op, "b": int(np.prod(d.shape[:-2])) if d.ndim > 2 else 1,
                "c": int(d.shape[-2]), "r": int(rows), "s": int(d.shape[-1])}

    def encode_parity(data, n_parity):
        with spans.span("engine.call", **shape("encode", data, n_parity)):
            return enc0(data, n_parity)

    def matrix_apply(coeff, shards):
        with spans.span("engine.call", **shape(
                "apply", shards, np.asarray(coeff).shape[0])):
            return app0(coeff, shards)

    eng.encode_parity, eng.matrix_apply = encode_parity, matrix_apply
    dep.wrap_node_calls(lambda call: _tapped(call, spans))

    def restore():
        del eng.encode_parity, eng.matrix_apply

    return restore


def _tapped(call, spans: SpanLog):
    def wrapped(method, args=None, body=b"", timeout=30.0):
        if method in ("put_shard", "get_shard"):
            with spans.span(f"storage.{method}", bytes=len(body)):
                return call(method, args, body, timeout)
        return call(method, args, body, timeout)

    return wrapped
