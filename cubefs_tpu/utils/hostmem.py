"""What the host's allocator does with a large array, and the one pool
of large arrays a process keeps across requests instead of asking for
new ones.

glibc serves an allocation above ``MALLOC_MMAP_MAX`` (its
DEFAULT_MMAP_THRESHOLD_MAX on 64-bit) from a mapping of its own, every
time: each page of such an array is a fault at first touch (~4-5 us a
4 KiB page where the host has no transparent huge pages, PERF.md §6),
whatever the memory bandwidth. Below it free() grows the heap's
threshold to the sizes the process frees, and malloc hands them back
mapped. glibc refuses an ``M_MMAP_THRESHOLD`` above that same 32 MiB, so
no heap policy brings a larger array back warm: such arrays come from
``KEPT``, one ``KeptArrays`` a process, capped at ``KEPT_BYTES``. Its
three callers are a PUT's data rows over the threshold
(``blob/access.py:_take_stripe_rows``), a device step's result over it
(``codec/engine.py:_to_host``) and every repair step's array
(``blob/worker.py:_step_array``). A kept buffer is handed out again only
when nothing references it: whoever holds a view of it holds it.

Left dynamic, free() also gives the top of the heap back to the system
once more than twice that threshold lies free there (64 MiB at most). A
repair task holds its survivors — a thousand reads of half a megabyte —
until its step, and frees them together: whether they go back, and the
next task faults every page in again, then depends on whether anything
happens to sit above them. ``keep_freed_heap`` fixes both thresholds,
once a process; its one caller is a repair worker's first lease
(``blob/worker.py:RepairWorker.run_once``), so a process whose worker
never leases a task keeps glibc's dynamic thresholds.
"""

import ctypes
import math
import sys
import threading

import numpy as np

MALLOC_MMAP_MAX = 32 << 20
HEAP_KEPT_BYTES = 1 << 30
# what KEPT keeps at most: a repair process's largest step array and its
# results, a PUT process's rows and results
KEPT_BYTES = 1536 << 20

# mallopt(3) parameters (malloc.h)
M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3

# what keep_freed_heap asks of mallopt, fixed at import: code that moves
# MALLOC_MMAP_MAX to take the kept-array paths moves no threshold
_POLICY = ((M_MMAP_THRESHOLD, MALLOC_MMAP_MAX),
           (M_TRIM_THRESHOLD, HEAP_KEPT_BYTES))
_kept: bool | None = None  # what the process's first call got
_lock = threading.Lock()


def _mallopt():
    mallopt = ctypes.CDLL(None).mallopt
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    return mallopt


def keep_freed_heap() -> bool:
    """For the rest of the process: allocations up to ``MALLOC_MMAP_MAX``
    come from the heap from the start (where the dynamic threshold ends
    up), and free() gives the heap's top back only past
    ``HEAP_KEPT_BYTES``, so what one request frees the next one reuses
    without a fault. Returns whether the allocator took both; False
    where it is not glibc's. The first call sets them; a later one
    returns what the first got and asks nothing of the allocator."""
    global _kept
    with _lock:
        if _kept is None:
            try:
                mallopt = _mallopt()
            except (OSError, AttributeError):
                _kept = False
            else:
                _kept = all(mallopt(param, value) == 1
                            for param, value in _POLICY)
        return _kept


class KeptArrays:
    """Flat uint8 buffers handed out as views of their head. A buffer is
    handed out only when nothing but this list references it — a view,
    a view of a view, a future's slice or a device array aliasing it all
    hold it —, so a reference kept too long makes the pool allocate
    fresh, never share. A new buffer is kept while the cap allows; past
    it the least recently handed out leave the list first (one still
    held lives on with its holder). Nothing is given back explicitly."""

    def __init__(self, cap: int = KEPT_BYTES):
        self.cap = cap
        self._kept: list[np.ndarray] = []  # least recently handed out first
        self._lock = threading.Lock()

    def take(self, shape: tuple) -> tuple[np.ndarray, str]:
        """(an uninitialised C-contiguous uint8 array of `shape`,
        "reused" or "fresh"): a view of the smallest unreferenced kept
        buffer that fits — of equal ones the one handed out last, whose
        pages the host touched last —, else of a new one of exactly its
        bytes."""
        size = math.prod(shape)
        with self._lock:  # a PUT's rows, two queues' results, a repair
            kept, best = self._kept, None
            for k in range(len(kept) - 1, -1, -1):  # newest first
                # 2: the list's reference and getrefcount's argument
                if (size <= kept[k].size
                        and (best is None or kept[k].size < kept[best].size)
                        and sys.getrefcount(kept[k]) == 2):
                    best = k
            if best is not None:
                buf, came = kept.pop(best), "reused"
                kept.append(buf)
            else:
                buf, came = np.empty(size, dtype=np.uint8), "fresh"
                if size <= self.cap:
                    kept.append(buf)
                    while sum(b.nbytes for b in kept) > self.cap:
                        del kept[0]
            return buf[:size].reshape(shape), came


KEPT = KeptArrays()
