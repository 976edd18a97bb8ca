"""Chain-slope timing for device kernels.

Run K dependency-chained iterations of a self-composing wrapper around
the kernel, force completion by fetching ONE element of the final
output, do that for two values of K, and report the slope
(T(k2)-T(k1))/(k2-k1). Dispatch latency and the fixed fetch cost cancel
in the subtraction; what remains is per-iteration device time for data
already resident on the device. It says nothing about host staging or
transfers, and its error bar on today's machine is not measured — the
served-path benchmark (ROADMAP S1) times with ``block_until_ready`` and
a profiler trace instead.

Used by bench.py and benchmarks/pallas_tuning*.py — one definition so
the method cannot diverge between them.
"""

from __future__ import annotations

import statistics
import sys
import time


def fetch_one(out) -> None:
    """Force completion of everything `out` depends on by pulling a
    single element of the (first) output leaf to the host."""
    import jax
    import numpy as np

    leaf = out[0] if isinstance(out, tuple) else out
    np.asarray(jax.device_get(leaf.ravel()[0:1]))


def run_chain(fn, x, k: int) -> float:
    out = fn(x)
    t0 = time.perf_counter()
    for _ in range(k):
        out = fn(out)
    fetch_one(out)
    return time.perf_counter() - t0


def timed_slope(fn, x, k1: int, k2: int, repeats: int = 3) -> float:
    """Per-iteration device time of self-composable fn via chain slope.

    A non-positive slope means timing noise swamped the signal for that
    repeat; such repeats are discarded. If every repeat is non-positive,
    fall back to total-time/k2 of the longest chain — that INCLUDES the
    fixed fetch cost, so it over-estimates the per-iteration time and the
    derived throughput is a safe under-estimate (never an astronomical
    artifact in the judged JSON)."""
    fetch_one(fn(x))  # compile + warm
    est, totals = [], []
    for _ in range(repeats):
        t_a = run_chain(fn, x, k1)
        t_b = run_chain(fn, x, k2)
        totals.append(t_b)
        slope = (t_b - t_a) / (k2 - k1)
        if slope > 0:
            est.append(slope)
    if not est:
        dt = min(totals) / k2
        print(
            f"benchtime: slope signal lost in noise (k1={k1}, k2={k2}); "
            f"falling back to total/k2 = {dt:.3e}s (conservative)",
            file=sys.stderr,
        )
        return dt
    return statistics.median(est)
