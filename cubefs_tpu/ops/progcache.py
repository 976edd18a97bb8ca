"""Shared capped LRU for compiled codec kernels and programs.

ops/msr.py and ops/xorprog.py compile per-matrix artifacts —
product-matrix rows, scheduled XOR programs — and ops/rs_kernel.py,
ops/pallas_gf.py and the batcher's dp path keep one jitted GF apply
program per shape (the matrix is their operand); all of these used to
live in unbounded maps. A long-lived repair worker that touches many
geometries (every distinct survivor set is a distinct decode matrix,
every object size a distinct shape) grows such maps forever.
This module is the single bound: one process-wide LRU shared by every
kernel family, keyed ``(family, key)``, capacity
``CUBEFS_CODEC_PROGCACHE_CAP`` entries (default 256), instrumented as
``cubefs_codec_program_cache_total{family,event=hit|miss|evict}`` plus
a resident-entries gauge. ``cubefs-cli metrics codec`` renders the hit
ratio.

The ``cached(family)`` decorator is the lru_cache drop-in the kernel
modules use; it keeps a functools-compatible ``cache_info()`` so
existing hit-count assertions keep working.
"""

from __future__ import annotations

import collections
import functools
import os
import threading

from ..utils import metrics

CacheInfo = collections.namedtuple(
    "CacheInfo", ["hits", "misses", "maxsize", "currsize"])


def _capacity() -> int:
    try:
        return max(8, int(os.environ.get("CUBEFS_CODEC_PROGCACHE_CAP", 256)))
    except ValueError:
        return 256


class ProgramCache:
    """Thread-safe LRU of compiled artifacts, evicting least-recently-
    used entries past ``capacity``. Builds run OUTSIDE the lock: two
    threads racing on one cold key may both compile (compiles are pure),
    but neither ever blocks behind another family's slow build."""

    def __init__(self, capacity: int | None = None):
        self.capacity = capacity if capacity is not None else _capacity()
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, family: str, key):
        full = (family, key)
        with self._lock:
            if full in self._entries:
                self._entries.move_to_end(full)
                metrics.codec_program_cache.inc(family=family, event="hit")
                return True, self._entries[full]
        metrics.codec_program_cache.inc(family=family, event="miss")
        return False, None

    def put(self, family: str, key, value) -> None:
        full = (family, key)
        with self._lock:
            self._entries[full] = value
            self._entries.move_to_end(full)
            while len(self._entries) > self.capacity:
                old_full, _ = self._entries.popitem(last=False)
                metrics.codec_program_cache.inc(
                    family=old_full[0], event="evict")
            metrics.codec_program_cache_entries.set(len(self._entries))

    def get_or_build(self, family: str, key, build):
        hit, value = self.get(family, key)
        if hit:
            return value
        value = build()
        self.put(family, key, value)
        return value

    def keys(self, family: str) -> list:
        """Keys resident for one family (a `cached` function's are its
        qualified name followed by its arguments)."""
        with self._lock:
            return [k for f, k in self._entries if f == family]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            metrics.codec_program_cache_entries.set(0)


# The process-wide instance every kernel family shares — one bound, not
# one per module, so the cap means what it says.
SHARED = ProgramCache()


def cached(family: str):
    """lru_cache drop-in routing through the SHARED capped cache.

    Hashable positional args only (the kernel-module convention).
    Exposes ``cache_info()`` (functools-shaped, per-function counters)
    and ``cache_clear()`` (drops only this function's entries)."""

    def deco(fn):
        stats = {"hits": 0, "misses": 0}
        prefix = fn.__module__ + "." + fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args):
            key = (prefix,) + args
            hit, value = SHARED.get(family, key)
            if hit:
                stats["hits"] += 1
                return value
            stats["misses"] += 1
            value = fn(*args)
            SHARED.put(family, key, value)
            return value

        def cache_info():
            return CacheInfo(stats["hits"], stats["misses"],
                             SHARED.capacity, len(SHARED))

        def cache_clear():
            with SHARED._lock:
                doomed = [k for k in SHARED._entries
                          if k[0] == family and k[1][0] == prefix]
                for k in doomed:
                    del SHARED._entries[k]
                metrics.codec_program_cache_entries.set(len(SHARED._entries))
            stats["hits"] = stats["misses"] = 0

        wrapper.cache_info = cache_info
        wrapper.cache_clear = cache_clear
        wrapper.cache_family = family
        return wrapper

    return deco
