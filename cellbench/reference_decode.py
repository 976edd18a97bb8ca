"""The plain reference for a degraded read, beside ``cellbench/reference.py``
and built on it alone: it imports nothing from ``cubefs_tpu``, so it
checks the program's decode (a bit matrix on the device, composed from
the program's own inverse) against a table inverse in numpy.

Semantics (klauspost/reedsolomon's ``ReconstructData``, which upstream's
``stream_get.go`` calls): any n units of an RS(n, n + m) stripe are
rows ``idx`` of the systematic encode matrix E times the data units, so
the data units are ``inv(E[idx])`` times those n units.
"""

from __future__ import annotations

import numpy as np

from . import reference


def decode(units: dict[int, bytes | np.ndarray], n: int, m: int
           ) -> np.ndarray:
    """The (n, S) data units of a stripe from exactly n of its units,
    given as {index in 0..n + m - 1: the unit's S bytes}."""
    idx = sorted(units)
    if len(idx) != n or not all(0 <= i < n + m for i in idx):
        raise ValueError(f"need n = {n} distinct unit indexes of "
                         f"0..{n + m - 1}, got {idx}")
    rows = np.stack([np.frombuffer(bytes(units[i]), dtype=np.uint8)
                     for i in idx])
    inverse = reference._invert(reference.encode_matrix(n, n + m)[idx])
    return reference.matmul(inverse, rows)
