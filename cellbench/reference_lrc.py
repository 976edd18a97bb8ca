"""The plain reference for an LRC codemode's stripe, beside
``cellbench/reference.py`` and built on it alone: it imports nothing
from ``cubefs_tpu`` and composes no matrix, so it checks the program's
one step of composed rows against the two levels computed in turn.

Semantics (upstream's ``blobstore/common/ec/lrcencoder.go``): the
global stripe is ``reference.stripe`` — RS(n, n + m) over the blob's n
data shards; then in each of the ``azs`` AZs the local parity is
RS(k, k + l / azs), k = (n + m) / azs, over that AZ's k units in
upstream's AZ layout order (``codemode.go`` ``GetECLayoutByAZ``, written
out below): the AZ's data shards, then its global parity shards, then
its local parity shards.
"""

from __future__ import annotations

import numpy as np

from . import reference


def az_layout(n: int, m: int, l: int, azs: int) -> list[list[int]]:
    """Shard indices of each AZ's local stripe, in layout order."""
    dn, dm, dl = n // azs, m // azs, l // azs
    return [[az * dn + i for i in range(dn)]
            + [n + az * dm + i for i in range(dm)]
            + [n + m + az * dl + i for i in range(dl)]
            for az in range(azs)]


def stripe(blob: bytes, n: int, m: int, l: int, azs: int,
           min_shard: int) -> np.ndarray:
    """The full (n + m + l, S) stripe of one blob."""
    glob = reference.stripe(blob, n, m, min_shard)
    out = np.zeros((n + m + l, glob.shape[1]), dtype=np.uint8)
    out[: n + m] = glob
    k = (n + m) // azs
    local = reference.encode_matrix(k, k + l // azs)[k:]
    for units in az_layout(n, m, l, azs):
        out[units[k:]] = reference.matmul(local, out[units[:k]])
    return out
