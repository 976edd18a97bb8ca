"""What the host's allocator does with a large array, for the code that
keeps such arrays across requests instead of asking for new ones.

glibc serves an allocation above ``MALLOC_MMAP_MAX`` (its
DEFAULT_MMAP_THRESHOLD_MAX on 64-bit) from a mapping of its own, every
time: each page of such an array is a fault at first touch (~4-5 us a
4 KiB page where the host has no transparent huge pages, PERF.md §6),
whatever the memory bandwidth. Below it free() grows the heap's
threshold to the sizes the process frees, and malloc hands them back
mapped. So an array above it that a hot path makes again and again is
kept for the next use of its shape: a PUT's data rows
(``blob/access.py``) and a device step's result (``codec/engine.py``).

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
import threading

MALLOC_MMAP_MAX = 32 << 20
HEAP_KEPT_BYTES = 1 << 30

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
