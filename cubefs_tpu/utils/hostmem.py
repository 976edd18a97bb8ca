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
"""

MALLOC_MMAP_MAX = 32 << 20
