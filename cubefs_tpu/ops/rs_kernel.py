"""Reed-Solomon encode/reconstruct as TPU matmuls (JAX).

The hot path of the reference's erasure-coding plane — GF(2^8)
matrix-times-shards in blobstore/common/ec/encoder.go:114 (encode) and
blobnode/worker_slice_recover.go:865 (reconstruct) — expressed as a single
int8 MXU matmul over the GF(2) bit expansion (see cubefs_tpu/ops/bitlin.py
for why this is exact and gather-free).

Shapes: shards are (..., B, S) uint8 — leading batch dims (stripes), B
shards of S bytes. The GF coefficient matrix is tiny ((M, N) with
M, N <= 36) and is baked into the compiled kernel as a constant.

Bit-identical guarantee: every step (bit unpack, 0/1 int matmul, mod-2,
bit pack) is exact integer arithmetic; combined with the same encode
matrix as the reference engine (gf256.encode_matrix), outputs match the
reference byte-for-byte.
"""

from __future__ import annotations

import functools
import hashlib
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import bitlin, gf256, msr, progcache

_BITS = (1 << np.arange(8)).astype(np.int32)
_log = logging.getLogger("cubefs.codec")


def _use_pallas() -> bool:
    """On real TPU the fused plane-major Pallas kernel avoids the 8x bit
    tensor in HBM; CUBEFS_NO_PALLAS=1 forces the jnp path (debugging /
    A-B measurement)."""
    if os.environ.get("CUBEFS_NO_PALLAS"):
        return False
    from . import pallas_gf

    return pallas_gf.on_tpu()


def _pallas_profitable(s: int) -> bool:
    """Pallas pads S up to a tile multiple: only dispatch when the pad
    waste is bounded (exact multiple, or >=4 tiles so waste <= 25%) —
    small/tiny-extent shards stay on the jnp path, which is exact in S."""
    from . import pallas_gf

    tile = pallas_gf.DEFAULT_TILE
    return s % tile == 0 or s >= 4 * tile


# Matrices the gate refused this process: (rows, cols, sha256[:12] of
# the coefficients) -> cause. A refused matrix is served by the exact
# jnp path for the life of the process; chip_smoke.py fails the run
# when this is non-empty.
pallas_refusals: dict[tuple[int, int, str], str] = {}


@functools.lru_cache(maxsize=None)
def _pallas_verified(coeff_bytes: bytes, rows: int, cols: int) -> bool:
    """Once-per-process bit-identity gate for the production dispatch:
    the fused kernel must match the jnp path on-device for this exact
    coefficient matrix at DEFAULT_TILE before it may serve real data.
    Mosaic has silently miscompiled this kernel at some tile sizes —
    unlike repair (whose extras integrity leg fails loudly), encode has
    no downstream check, so wrong parity would only surface at
    reconstruct time, after the data shards are gone.

    Every refusal — a mismatch, or the gate itself raising (a Mosaic
    compile error lands here) — is logged with its cause and recorded
    in ``pallas_refusals``; the matrix then rides the jnp path."""
    from . import pallas_gf

    coeff = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(rows, cols)
    key = (rows, cols, hashlib.sha256(coeff_bytes).hexdigest()[:12])
    try:
        ok = pallas_gf.verify_tile(coeff, pallas_gf.DEFAULT_TILE)
    except Exception as e:
        _log.exception("pallas gate raised for matrix %s at tile=%d; "
                       "serving it from the jnp path", key,
                       pallas_gf.DEFAULT_TILE)
        pallas_refusals[key] = f"gate raised {type(e).__name__}: {e}"
        return False
    if not ok:
        _log.error("pallas kernel MISCOMPILES for matrix %s at tile=%d; "
                   "serving it from the jnp path", key,
                   pallas_gf.DEFAULT_TILE)
        pallas_refusals[key] = (
            f"mismatch vs jnp path at tile={pallas_gf.DEFAULT_TILE}")
    return ok


def serves_fused(coeff: np.ndarray, s: int) -> bool:
    """The one dispatch decision: does the fused Pallas kernel serve
    this coefficient matrix at shard size ``s``? (TPU backend, pad
    waste bounded, matrix blessed by the gate.)"""
    return (_use_pallas() and _pallas_profitable(s)
            and _pallas_verified(coeff.tobytes(), coeff.shape[0],
                                 coeff.shape[1]))


def unpack_bits(x: jax.Array) -> jax.Array:
    """(..., B, S) uint8 -> (..., 8B, S) int8, LSB-first per byte."""
    *lead, b, s = x.shape
    planes = (x[..., :, None, :].astype(jnp.int32) >> jnp.arange(8)[None, :, None]) & 1
    return planes.reshape(*lead, 8 * b, s).astype(jnp.int8)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., 8B, S) int -> (..., B, S) uint8."""
    *lead, b8, s = bits.shape
    planes = bits.reshape(*lead, b8 // 8, 8, s).astype(jnp.int32)
    return (planes << jnp.arange(8)[None, :, None]).sum(-2).astype(jnp.uint8)


def gf_apply_bits(
    w_bits: jax.Array, shards: jax.Array, psum_axis: str | None = None
) -> jax.Array:
    """Apply a GF(2)-expanded coefficient matrix to shard bytes.

    w_bits: (8M, 8N) int8 0/1; shards: (..., N, S) uint8 -> (..., M, S).
    The contraction K = 8N <= 288 keeps the accumulator far below int32
    limits; XLA lowers the int8 x int8 -> int32 dot onto the MXU.

    psum_axis: inside shard_map with the shard axis N split across mesh
    axis `psum_axis`, pass its name — partial int32 products are summed
    across devices BEFORE the mod-2, which is exact (parity of a sum ==
    XOR of parities).
    """
    with jax.named_scope("gf256.bits.unpack"):
        x = unpack_bits(shards)
    with jax.named_scope("gf256.bits.dot"):
        y = jax.lax.dot_general(
            w_bits,
            x,
            ((( 1,), (x.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8M, ..., S)
        if x.ndim > 2:
            y = jnp.moveaxis(y, 0, -2)
        if psum_axis is not None:
            y = jax.lax.psum(y, psum_axis)
    with jax.named_scope("gf256.bits.pack"):
        return pack_bits(y & 1)


def _as_const(bits: np.ndarray) -> jax.Array:
    return jnp.asarray(bits, dtype=jnp.int8)


@progcache.cached("rs_jit")
def _encode_fn(n: int, m: int):
    w = bitlin.gf_matrix_to_bits(gf256.parity_matrix(n, m))

    @jax.jit
    def encode(data: jax.Array) -> jax.Array:
        return gf_apply_bits(_as_const(w), data)

    return encode


def encode_parity(data: jax.Array, n_parity: int) -> jax.Array:
    """data: (..., N, S) uint8 -> parity (..., M, S) uint8."""
    n = int(data.shape[-2])
    coeff = np.ascontiguousarray(
        gf256.parity_matrix(n, n_parity), dtype=np.uint8)
    if serves_fused(coeff, int(data.shape[-1])):
        from . import pallas_gf

        return pallas_gf.gf_matrix_apply_pallas(coeff, data)
    return _encode_fn(n, n_parity)(data)


@progcache.cached("rs_jit")
def _matrix_apply_fn(coeff_bytes: bytes, rows: int, cols: int):
    coeff = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(rows, cols)
    w = bitlin.gf_matrix_to_bits(coeff)

    @jax.jit
    def apply(shards: jax.Array) -> jax.Array:
        return gf_apply_bits(_as_const(w), shards)

    return apply


def gf_matrix_apply(coeff: np.ndarray, shards: jax.Array) -> jax.Array:
    """shards: (..., C, S) uint8, coeff: (R, C) GF(256) -> (..., R, S).

    General building block for reconstruct (decode-matrix rows) and
    verify (parity rows). The coefficient matrix is static per call site
    (per codemode / per missing-shard pattern), so each distinct matrix
    compiles once and is cached.
    """
    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    if serves_fused(coeff, int(shards.shape[-1])):
        from . import pallas_gf

        return pallas_gf.gf_matrix_apply_pallas(coeff, shards)
    fn = _matrix_apply_fn(coeff.tobytes(), coeff.shape[0], coeff.shape[1])
    return fn(shards)


def reconstruct_rows(
    n_data: int, n_total: int, present: list[int], wanted: list[int]
) -> np.ndarray:
    """GF matrix mapping the first n_data present shards to the wanted
    shard indices (data rows come from the inverted submatrix, parity rows
    from re-encoding — same algebra as the reference engine's
    Reconstruct, vendor reedsolomon.go reconstruct())."""
    present = sorted(present)[:n_data]
    dec = gf256.decode_matrix(n_data, n_total, present)
    enc = gf256.encode_matrix(n_data, n_total)
    return gf256.gf_matmul(enc[np.asarray(wanted)], dec)


def lrc_reconstruct_rows(
    n_data: int, n_total: int, stripes: list[list[int]], ln: int,
    present: list[int], wanted: list[int],
) -> np.ndarray:
    """reconstruct_rows over the FULL two-level LRC shard space.

    `present` must index the global stripe (< n_total: data + global
    parity), but `wanted` may include local-parity indices (>= n_total).
    A local parity is the local code's re-encode of its stripe's first
    `ln` members — all global-space indices — so its row is the local
    encode row composed with the global solve: one matrix, same batched
    apply as every other repair. This is what lets a repair rebuild a
    local parity when its entire stripe's AZ is dark."""
    present = sorted(present)[:n_data]
    dec = gf256.decode_matrix(n_data, n_total, present)
    enc = gf256.encode_matrix(n_data, n_total)
    rows = np.zeros((len(wanted), n_data), dtype=np.uint8)
    for r, w in enumerate(wanted):
        if w < n_total:
            rows[r] = enc[w]
            continue
        stripe = next(s for s in stripes if w in s)
        local = gf256.encode_matrix(ln, len(stripe))
        members = enc[np.asarray(stripe[:ln])]
        rows[r] = gf256.gf_matmul(local[[stripe.index(w)]], members)[0]
    return gf256.gf_matmul(rows, dec)


def reconstruct_stripes(
    surviving: jax.Array,
    present: list[int],
    wanted: list[int],
    n_data: int,
    n_total: int,
) -> jax.Array:
    """surviving: (..., n_data, S) uint8 = the first n_data present shards
    stacked in ascending shard-index order; returns (..., len(wanted), S)."""
    rows = reconstruct_rows(n_data, n_total, present, wanted)
    return gf_matrix_apply(rows, surviving)


# ---------------- product-matrix MSR (regenerating-code) kernels --------
# Row construction lives in ops/msr.py (tiny exact host math, lru-cached
# per geometry/failed-slot/helper-set); these wrappers are the kernel
# surface the codec engines and the blob plane consume. Like RS, the
# byte work is ONE gf_matrix_apply — the same bit-matmul (jax/pallas)
# or table (numpy/cpp) engines serve both families, and admitted
# callers coalesce MSR sub-shard steps with RS stripes for free.

msr_encode_rows = msr.encode_rows
msr_helper_rows = msr.helper_rows
msr_repair_rows = msr.repair_rows
msr_verify_rows = msr.verify_rows
msr_reconstruct_rows = msr.reconstruct_rows


def msr_subshards(shards: jax.Array, alpha: int) -> jax.Array:
    """(..., B, S) -> (..., B*alpha, S/alpha): expose each shard's alpha
    sub-shards as rows so MSR coefficient matrices can apply. S must be
    alpha-divisible (MsrEncoder.shard_size guarantees it on write)."""
    *lead, b, s = shards.shape
    if s % alpha:
        raise ValueError(f"shard size {s} not divisible by alpha={alpha}")
    return shards.reshape(*lead, b * alpha, s // alpha)


def msr_join_subshards(sub: jax.Array, alpha: int) -> jax.Array:
    """Inverse of msr_subshards: (..., B*alpha, beta) -> (..., B, S)."""
    *lead, rows, beta = sub.shape
    return sub.reshape(*lead, rows // alpha, alpha * beta)


def msr_encode_parity(data: jax.Array, k: int, total: int, d: int) -> jax.Array:
    """data: (..., k, S) uint8 -> parity (..., total-k, S) uint8 via the
    product-matrix generator (jax path; engines route the same rows
    through their own matrix_apply)."""
    alpha = d - k + 1
    rows = msr.encode_rows(k, total, d)
    sub = msr_subshards(np.asarray(data), alpha)
    return msr_join_subshards(gf_matrix_apply(rows, sub), alpha)


def msr_repair_shard(payloads: jax.Array, k: int, total: int, d: int,
                     failed: int, helpers: tuple[int, ...]) -> jax.Array:
    """payloads: (..., d, beta) helper symbols (in `helpers` order) ->
    the failed shard (..., S=alpha*beta) — repair traffic d*beta bytes
    instead of the conventional k*alpha*beta."""
    rows = msr.repair_rows(k, total, d, failed, helpers)
    out = gf_matrix_apply(rows, payloads)  # (..., alpha, beta)
    *lead, alpha, beta = out.shape
    return out.reshape(*lead, alpha * beta)
