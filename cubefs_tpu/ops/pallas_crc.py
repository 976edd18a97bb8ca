"""Pallas TPU kernel for the batched CRC32 linear stage.

The jnp path (crc32_kernel.linear_crc_bits) materializes the 8x bit
expansion of every chunk in HBM before the (bits @ W) dot — on TPU that
makes batched CRC traffic-bound at ~9x the payload. This kernel fuses
unpack -> dot per VMEM tile, exactly the pallas_gf.py recipe:

    HBM uint8 tile (TB blocks, L chunk bytes) -> VMEM
      -> unpack to plane-major bits (TB, 8L) (VPU shifts)
      -> (TB, 8L) @ Wt(8L, 32) int8 dot (MXU) -> & 1 -> (TB, 32) int8

so HBM sees payload-in plus a 32/L-sized parts-out (3% at L=1KiB). The
cross-chunk fold (shift matrices) and the packing stay in the jnp
epilogue — they touch only the tiny (B, C, 32) parts tensor.

Bit-identical to the jnp path by construction; tests compare against
zlib.crc32 per block (interpret mode off-TPU). Same Mosaic caveat as
the GF kernel: verify_tile() must bless a tile size on real hardware
before an autotuner trusts its numbers.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import bitlin, crc32_kernel
from .pallas_gf import on_tpu

# blocks per grid step; VMEM per step ~ TB*L (bytes) + TB*8L (bits) +
# 8L*32 (Wt) + TB*32*4 — at TB=256, L=1024 that is ~2.6 MiB
DEFAULT_TILE_BLOCKS = int(os.environ.get("CUBEFS_PALLAS_CRC_TB", "256"))
TILE_CANDIDATES = (128, 256, 512)


def _crc_kernel(wt_ref, x_ref, o_ref):
    x = x_ref[:].astype(jnp.int32)  # (TB, L) chunk bytes
    planes = [((x >> k) & 1).astype(jnp.int8) for k in range(8)]
    bits = jnp.concatenate(planes, axis=1)  # (TB, 8L) plane-major cols
    wt = wt_ref[:]  # (8L, 32) int8, plane-major rows
    y = jax.lax.dot_general(
        bits, wt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    ) & 1  # (TB, 32)
    o_ref[:] = y.astype(jnp.int8)


@functools.lru_cache(maxsize=None)
def _parts_fn(chunk_len: int, tile_blocks: int, interpret: bool):
    # numpy in the closure (tracer-safety: see crc32_kernel._crc_block_fn)
    w = crc32_kernel.chunk_matrix(chunk_len).astype(np.int8)  # (32, 8L)
    w_pm = np.zeros_like(w)
    w_pm[:, bitlin.bitmajor_perm(chunk_len)] = w
    wt_np = np.ascontiguousarray(w_pm.T)  # (8L, 32)

    @jax.jit
    def parts(chunks: jax.Array) -> jax.Array:
        """(R, L) uint8 chunk rows -> (R, 32) int8 raw-CRC bit parts.
        R must be a tile_blocks multiple (callers pad)."""
        wt = jnp.asarray(wt_np)
        r = chunks.shape[0]
        kwargs = {}
        if not interpret:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            )
        return pl.pallas_call(
            _crc_kernel,
            out_shape=jax.ShapeDtypeStruct((r, 32), jnp.int8),
            grid=(r // tile_blocks,),
            in_specs=[
                pl.BlockSpec((8 * chunk_len, 32), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_blocks, chunk_len), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tile_blocks, 32), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            **kwargs,
        )(wt, chunks)

    return parts


@functools.lru_cache(maxsize=None)
def _fold_fn(block_len: int, chunk_len: int, interpret: bool):
    n_chunks = block_len // chunk_len
    shifts_np = np.stack(
        [crc32_kernel.zeros_matrix((n_chunks - 1 - k) * chunk_len)
         for k in range(n_chunks)]
    ).astype(np.int8)  # (C, 32, 32)
    const_bits = crc32_kernel._state_bits(
        crc32_kernel.crc32_zeros(block_len)).astype(np.int32)

    @jax.jit
    def fold(parts: jax.Array) -> jax.Array:
        """(B, C, 32) int8 per-chunk parts -> (B,) uint32 CRCs."""
        folded = jnp.einsum(
            "cij,bcj->bi", jnp.asarray(shifts_np),
            parts.astype(jnp.int32), preferred_element_type=jnp.int32
        ) & 1
        return crc32_kernel.pack_crc_bits(
            folded ^ jnp.asarray(const_bits)[None, :])

    return fold


def crc32_blocks_pallas(blocks, chunk_len: int = 1024,
                        tile_blocks: int = DEFAULT_TILE_BLOCKS,
                        interpret: bool | None = None) -> jax.Array:
    """Batched zlib-compatible CRC32 via the fused Pallas linear stage.

    blocks: (B, block_len) uint8 -> (B,) uint32, bit-identical to
    zlib.crc32 per block. chunk_len is fitted to a divisor of block_len
    (crc32_kernel.fit_chunk_len semantics).
    """
    if interpret is None:
        interpret = not on_tpu()
    blocks = jnp.asarray(blocks)
    b, block_len = blocks.shape
    chunk_len = crc32_kernel.fit_chunk_len(chunk_len, block_len)
    n_chunks = block_len // chunk_len
    rows = b * n_chunks
    chunks = blocks.reshape(rows, chunk_len)
    pad = (-rows) % tile_blocks
    if pad:
        chunks = jnp.pad(chunks, ((0, pad), (0, 0)))
    parts = _parts_fn(chunk_len, tile_blocks, bool(interpret))(chunks)
    if pad:
        parts = parts[:rows]
    return _fold_fn(block_len, chunk_len, bool(interpret))(
        parts.reshape(b, n_chunks, 32))


def verify_tile(block_len: int, chunk_len: int, tile_blocks: int,
                seed: int = 0) -> bool:
    """Trust-but-verify for the autotuner: Mosaic was observed to
    miscompile the sibling GF kernel at large tiles, so a candidate tile
    must produce zlib-identical CRCs on random data before its timing
    counts."""
    import zlib

    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, (max(2 * tile_blocks // max(
        block_len // crc32_kernel.fit_chunk_len(chunk_len, block_len), 1),
        4), block_len), dtype=np.uint8)
    got = np.asarray(jax.block_until_ready(
        crc32_blocks_pallas(blocks, chunk_len, tile_blocks)))
    want = np.array([zlib.crc32(row.tobytes()) for row in blocks],
                    dtype=np.uint32)
    return bool(np.array_equal(got, want))
