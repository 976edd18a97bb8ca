"""Multi-chip codec kernels: shard_map over (dp, tp, sp) with XLA
collectives on ICI.

Distribution recipe (replaces the reference's socket fan-out,
datanode/repl + access/stream quorum writes, with mesh collectives):

  * GF(2^8) matrix apply (encode / reconstruct): the contraction axis is
    the shard axis N. With shards split over ``tp``, each device computes
    the partial int32 bit-matmul of its local shards and the mod-2 XOR
    combine is ``psum`` over ``tp`` followed by ``& 1`` — exact because
    parity of a sum is the XOR of parities. Byte axis splits over ``sp``
    with no communication (GF math is byte-local).

  * CRC32: byte segments split over ``sp``. Each device computes the
    GF(2)-linear CRC part of its contiguous segment; device d's
    contribution is shifted by the zero-extension matrix A^(bytes after
    d) and the shifted parts XOR-combine via ``psum`` over ``sp``.

Both collectives are tiny relative to shard bytes ((8M, S/sp) int32 for
psum-tp, (B, 32) for psum-sp), so multi-chip scaling is compute-bound,
not ICI-bound.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import bitlin, crc32_kernel, gf256, rs_kernel


def gf_apply_sharded(mesh: Mesh, n_in: int) -> callable:
    """Build a shard_map'd fn: ((8R, 8*n_in) int8 bit matrix, (B, n_in, S)
    uint8) -> (B, R, S) uint8 with the shards sharded (dp, tp, sp), the
    matrix replicated (an operand: one program serves every matrix of
    its shape) and the output (dp, None, sp) — every device in a tp
    group holds the full result rows for its byte slice, like every
    blobnode holding the full parity it must write."""
    tp = mesh.shape["tp"]
    if n_in % tp:
        raise ValueError(f"shard axis {n_in} not divisible by tp={tp}")
    cols_per = 8 * (n_in // tp)

    def body(w_all: jax.Array, shards_local: jax.Array) -> jax.Array:
        idx = jax.lax.axis_index("tp")
        w_local = jax.lax.dynamic_slice_in_dim(w_all, idx * cols_per, cols_per, 1)
        return rs_kernel.gf_apply_bits(w_local, shards_local, psum_axis="tp")

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("dp", "tp", "sp")),
        out_specs=P("dp", None, "sp"),
    )


def gf_matrix_apply_sharded(
    mesh: Mesh, coeff: np.ndarray, n_in: int
) -> callable:
    """gf_apply_sharded with ``coeff``'s bit matrix bound:
    (B, n_in, S) uint8 -> (B, R, S) uint8."""
    w = bitlin.gf_matrix_to_bits(np.ascontiguousarray(coeff, dtype=np.uint8))
    fn = gf_apply_sharded(mesh, n_in)
    return lambda shards: fn(jnp.asarray(w), shards)


def encode_sharded(mesh: Mesh, n_data: int, n_parity: int) -> callable:
    """(B, N, S) data -> (B, M, S) parity, data sharded over the mesh."""
    return gf_matrix_apply_sharded(
        mesh, gf256.parity_matrix(n_data, n_parity), n_data
    )


def crc32_sharded(mesh: Mesh, seg_len_total: int, chunk_len: int = 512) -> callable:
    """Build a shard_map'd fn: (B, seg_len_total) uint8 -> (B,) uint32
    zlib-compatible CRC32 per row, bytes sharded over sp."""
    sp = mesh.shape["sp"]
    if seg_len_total % sp:
        raise ValueError(f"segment {seg_len_total} not divisible by sp={sp}")
    local_len = seg_len_total // sp
    chunk_len = crc32_kernel.fit_chunk_len(chunk_len, local_len)
    # device d's local linear part must be zero-extended by the bytes that
    # come AFTER it: (sp-1-d) * local_len.
    shifts = np.stack(
        [crc32_kernel.zeros_matrix((sp - 1 - d) * local_len) for d in range(sp)]
    ).astype(np.int8)
    const_bits = crc32_kernel._state_bits(crc32_kernel.crc32_zeros(seg_len_total))

    def body(seg_local: jax.Array) -> jax.Array:
        d = jax.lax.axis_index("sp")
        linear = crc32_kernel.linear_crc_bits(seg_local, chunk_len)  # (B, 32)
        shift = jax.lax.dynamic_index_in_dim(jnp.asarray(shifts), d, 0, False)
        contrib = jax.lax.dot_general(
            linear, shift, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        total = jax.lax.psum(contrib, "sp") & 1  # XOR across devices
        return crc32_kernel.pack_crc_bits(total ^ jnp.asarray(const_bits, jnp.int32))

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp", "sp"),),
        out_specs=P("dp"),
    )
