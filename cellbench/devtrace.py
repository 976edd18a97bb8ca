"""From a profiler trace to device busy time, top device operations and
labelled idle gaps. The reduction works on plain lists, so the tests
check it on a synthetic trace; ``load`` turns an ``.xplane.pb`` into
those lists with nothing but JAX.

Device events are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane (every line but the step and module summaries
where a plane has no such line). Host spans are hostspans.py's records,
moved onto the trace's clock by ``on_trace_clock`` (an annotation that
was open when the profiler started is not in the trace; the record is).
All times are seconds on the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re

from . import stats
from .hostspans import PREFIX

WINDOW_SPAN = "trace_window"
_SUMMARY_LINES = ("steps", "xla modules", "step", "framework")


_HLO = re.compile(r"^(%?[\w.\-]+) = .*?[\]})] ([a-z][\w\-]*)\(")


def short_op(name: str) -> str:
    """The trace names a device op by its whole HLO line; keep the
    result's name and the opcode: ``%apply.1 custom-call``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def load(trace_dir: str) -> dict:
    """{"device": {plane name: [(name, start_s, end_s)]},
        "host": [(span name, start_s, end_s)], "lines": {...}}"""
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"device": {}, "host": [], "lines": {}}
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    lines: dict[str, dict[str, int]] = {}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        plane_lines = [(ln.name, list(ln.events)) for ln in plane.lines]
        if is_dev:
            lines[plane.name] = {n: len(ev) for n, ev in plane_lines}
            ops = [ev for n, ev in plane_lines if n == "XLA Ops"]
            if not ops:
                ops = [ev for n, ev in plane_lines
                       if not n.lower().startswith(_SUMMARY_LINES)]
            device[plane.name] = [
                (short_op(e.name), e.start_ns / 1e9,
                 (e.start_ns + e.duration_ns) / 1e9)
                for ev in ops for e in ev]
        elif plane.name.startswith("/host:"):
            for _, ev in plane_lines:
                host.extend(
                    (e.name[len(PREFIX):], e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in ev if e.name.startswith(PREFIX))
    return {"device": device, "host": host, "lines": lines}


def on_trace_clock(spans: list, window_host: tuple, annotated: list) -> list:
    """The host-clock ``spans`` [(name, t0, t1)] on the trace's clock,
    with the ``trace_window`` marker first: the marker was timed on the
    host clock (``window_host``) and is in the trace (``annotated``)."""
    marker = next(((s, e) for n, s, e in annotated if n == WINDOW_SPAN),
                  None)
    if marker is None or window_host is None:
        return list(annotated)
    off = marker[0] - window_host[0]
    return [(WINDOW_SPAN, *marker)] + [(n, t0 + off, t1 + off)
                                       for n, t0, t1 in spans]


def reduce(device: dict[str, list], host: list, top: int = 10) -> dict:
    """busy_s (union of device-op intervals, mean over the chips),
    window_s (the ``trace_window`` host span), idle share, the ``top``
    device ops by summed time and the ``top`` idle-gap labels by summed
    time. A trace without the marker reduces to nothing."""
    window = next(((s, e) for n, s, e in host if n == WINDOW_SPAN), None)
    every = [(s, e) for evs in device.values() for _, s, e in evs]
    if window is None:
        return {"busy_s": 0.0, "window_s": 0.0, "idle_share": None,
                "device_ops": [], "idle_gaps": [], "chips": 0, "ops_all": {},
                "inside_window_share": None}
    lo, hi = window
    total_busy = stats.union_length(every)
    inside = stats.union_length(stats.clip(every, lo, hi))
    per_chip, ops, gaps = [], {}, {}
    label_at = _labeller(host)
    for evs in device.values():
        iv = stats.clip([(s, e) for _, s, e in evs], lo, hi)
        per_chip.append(stats.union_length(iv))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        busy_iv = stats.merged(iv)
        edges = [lo] + [t for s, e in busy_iv for t in (s, e)] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = label_at((g0 + g1) / 2)
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0)
    chips = max(1, len(per_chip))
    busy = sum(per_chip) / chips
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "window_s": hi - lo,
            "idle_share": 1.0 - busy / (hi - lo) if hi > lo else None,
            "device_ops": [[n, t] for n, t in by_time(ops)],
            "idle_gaps": [[n, t / chips] for n, t in by_time(gaps)],
            "chips": len(per_chip), "ops_all": ops,
            "inside_window_share": inside / total_busy if total_busy else None}


def _labeller(host: list):
    import numpy as np

    spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    starts = np.array([s for _, s, _ in spans], dtype=np.float64)
    ends = np.array([e for _, _, e in spans], dtype=np.float64)
    lens = ends - starts

    def label_at(t: float) -> str:
        open_ = np.nonzero((starts <= t) & (t < ends))[0]
        if not open_.size:
            return "no_benchmark_span"
        return spans[int(open_[np.argmin(lens[open_])])][0]

    return label_at


def gap_label(host: list, t: float) -> str:
    """The innermost (shortest) benchmark span open at time ``t``."""
    return _labeller(host)(t)
