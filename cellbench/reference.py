"""The plain reference that decides ``correct``: Reed-Solomon over
GF(2^8) by table lookup in numpy, and zlib's CRC-32. It imports nothing
from ``cubefs_tpu`` — the same operations on the same data must give the
same bytes.

Semantics (upstream's, klauspost/reedsolomon defaults): field polynomial
0x11D with generator 2; the systematic encode matrix is V * inv(V[:n])
for the Vandermonde matrix V[r][c] = r^c; a blob of L bytes is laid
row-major into n data shards of S = max(ceil(L / n), min_shard) bytes,
zero padded; parity row j is the GF dot product of matrix row n + j with
the data shards.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

POLY = 0x11D


def _mul_slow(a: int, b: int) -> int:
    """Carry-less multiply, reduced by POLY (shift-and-add)."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= POLY
        b >>= 1
    return out


@functools.cache
def mul_table() -> np.ndarray:
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(a, 256):
            t[a, b] = t[b, a] = _mul_slow(a, b)
    return t


def _inv(a: int) -> int:
    row = mul_table()[a]
    return int(np.nonzero(row == 1)[0][0])


def _pow(a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = _mul_slow(out, a)
    return out


def matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(m, k) x (k, s) over GF(2^8), one table gather per coefficient."""
    mt = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(a.shape[1]):
            c = int(a[i, j])
            if c:
                out[i] ^= mt[c][b[j]]
    return out


def _invert(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    mt = mul_table()
    w = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        piv = next(r for r in range(col, n) if w[r, col])
        if piv != col:
            w[[col, piv]] = w[[piv, col]]
        w[col] = mt[_inv(int(w[col, col]))][w[col]]
        for r in range(n):
            if r != col and w[r, col]:
                w[r] ^= mt[int(w[r, col])][w[col]]
    return w[:, n:]


@functools.cache
def encode_matrix(n: int, total: int) -> np.ndarray:
    v = np.array([[_pow(r, c) for c in range(n)] for r in range(total)],
                 dtype=np.uint8)
    return matmul(v, _invert(v[:n]))


def shard_size(length: int, n: int, min_shard: int) -> int:
    return max(-(-length // n), min_shard)


def stripe(blob: bytes, n: int, m: int, min_shard: int) -> np.ndarray:
    """The full (n + m, S) stripe of one blob."""
    s = shard_size(len(blob), n, min_shard)
    out = np.zeros((n + m, s), dtype=np.uint8)
    out.reshape(-1)[:len(blob)] = np.frombuffer(blob, dtype=np.uint8)
    out[n:] = matmul(encode_matrix(n, n + m)[n:], out[:n])
    return out


def crc32(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF
