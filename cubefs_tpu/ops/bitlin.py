"""GF(2)-linear reformulation of GF(2^8) codes — the TPU-first trick.

A GF(2^8) multiply by a fixed coefficient c is linear over GF(2): there is
an 8x8 bit-matrix L_c with byte_out_bits = L_c @ byte_in_bits (mod 2).
Therefore a whole Reed-Solomon encode  parity = C (MxN over GF(256)) x
shards  is ONE bit-matrix multiply  (8M x 8N) @ (8N x S)  with mod-2
accumulation. That removes every byte-table gather (hostile on TPU — the
reference instead uses AVX2 nibble shuffles, vendor/github.com/klauspost/
reedsolomon/galois_amd64.s) and maps the hot loop directly onto the MXU as
an int8 matmul followed by a parity (&1) and a bit-pack.

Bit order convention: LSB-first within each byte; row index b*8+k holds
bit k of byte b.
"""

from __future__ import annotations

import functools

import numpy as np

from . import gf256


def coeff_bitmatrix(c: int) -> np.ndarray:
    """8x8 GF(2) matrix L_c for y = gf_mul(c, x): column j holds the bits
    of gf_mul(c, 1 << j)."""
    cols = gf256.gf_mul(np.full(8, c, np.uint8), (1 << np.arange(8)).astype(np.uint8))
    return ((cols[None, :] >> np.arange(8)[:, None]) & 1).astype(np.int8)


@functools.cache
def _coeff_bitmatrices() -> np.ndarray:
    """(256, 8, 8): L_c for every coefficient, built once."""
    return np.stack([coeff_bitmatrix(c) for c in range(256)])


def gf_matrix_to_bits(m: np.ndarray) -> np.ndarray:
    """Expand an (R, C) GF(2^8) matrix into its (8R, 8C) GF(2) form
    (one table gather: a survivor set seen for the first time pays this
    inside a request)."""
    m = np.asarray(m, dtype=np.uint8)
    r, c = m.shape
    blocks = _coeff_bitmatrices()[m]  # (R, C, 8, 8)
    return np.ascontiguousarray(
        blocks.transpose(0, 2, 1, 3)).reshape(8 * r, 8 * c)


def bitmajor_perm(n_bytes: int) -> np.ndarray:
    """Permutation mapping byte-major bit index (b*8+k) to bit-major
    (plane-major) position (k*n_bytes+b). Plane-major is the layout the
    TPU kernel prefers: unpacking to (8, N, T)->(8N, T) concatenates
    whole planes instead of interleaving bits per byte (measured 4x
    faster in Mosaic than the byte-major interleave)."""
    idx = np.arange(8 * n_bytes)
    b, k = idx // 8, idx % 8
    return k * n_bytes + b


def w_to_bitmajor(w: np.ndarray, rows_bytes: int, cols_bytes: int) -> np.ndarray:
    """Permute an (8R, 8C) byte-major GF(2) matrix so it consumes
    plane-major inputs and produces plane-major outputs."""
    rp = bitmajor_perm(rows_bytes)
    cp = bitmajor_perm(cols_bytes)
    out = np.zeros_like(w)
    out[rp[:, None], cp[None, :]] = w
    return out


def unpack_bits_np(x: np.ndarray) -> np.ndarray:
    """(..., B, S) uint8 -> (..., 8B, S) int8 bit planes (numpy golden)."""
    bits = (x[..., :, None, :] >> np.arange(8)[None, :, None]) & 1
    return bits.reshape(*x.shape[:-2], x.shape[-2] * 8, x.shape[-1]).astype(np.int8)


def pack_bits_np(bits: np.ndarray) -> np.ndarray:
    b8 = bits.reshape(*bits.shape[:-2], bits.shape[-2] // 8, 8, bits.shape[-1])
    return (b8.astype(np.uint16) << np.arange(8)[None, :, None]).sum(-2).astype(np.uint8)
