"""Pallas TPU kernel for the GF(2^8) bit-matmul (encode/reconstruct).

The jnp path (rs_kernel.gf_apply_bits) materializes the 8x bit expansion
in HBM: unpack (8N, S) int8 -> dot -> pack. On TPU that makes the kernel
HBM-bound at ~8x the payload traffic. This kernel fuses the whole chain
per VMEM tile:

    HBM uint8 tile (N, T) -> VMEM -> unpack bits (VPU shifts)
        -> (8M, 8N) @ (8N, T) int8 dot (MXU) -> & 1 -> pack -> (M, T)

so HBM sees only payload-in + parity-out. The coefficient bit-matrix is
tiny (<= 288x288), goes in as an OPERAND and stays resident in VMEM
across the grid: one program per shape serves every coefficient matrix
(encode rows, every survivor set's decode rows, LRC and MSR rows), so a
survivor set nobody warmed costs a lookup or a 83 KB upload, never a
compile.

Bit-identical to the jnp path by construction (same exact integer math);
tests compare both on every codemode (interpret mode off-TPU). Which
shapes this program serves is rs_kernel.plan's decision alone.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import metrics
from . import progcache

# Bytes of shard per grid step. VMEM per step ~ (C + 8C + 4*8R + R) * T
# for C input shards and R output rows: at T=32KiB and RS(12+4) repair
# (C=12, R<=6) that is ~8 MiB — comfortably inside a v5e core's ~16 MiB
# VMEM while amortizing grid overhead far better than tiny tiles.
# Whoever tunes over TILE_CANDIDATES on real hardware MUST verify
# bit-identity per tile first (verify_tile below): Mosaic was
# observed to MISCOMPILE this kernel at tile >= 65536 (silent wrong
# parity), so an unvalidated autotune can "win" with garbage output.
# CUBEFS_PALLAS_TILE pins the production tile if a deployment's
# tuning says otherwise.
DEFAULT_TILE = int(os.environ.get("CUBEFS_PALLAS_TILE", "32768"))
TILE_CANDIDATES = (8192, 16384, 32768)


def _kernel(w_ref, x_ref, o_ref):
    # Plane-major (bit-major) layout throughout: bits row k*N+b = bit k
    # of byte-row b. The per-byte interleave (row b*8+k) forces Mosaic
    # into sublane shuffles that dominated the kernel (17 -> 58 GiB/s on
    # the judged shape when switched); the coefficient matrix is
    # permuted to match on the host, once per matrix
    # (rs_kernel.device_bits -> bitlin.w_to_bitmajor), so the
    # math is unchanged.
    x = x_ref[:].astype(jnp.int32)  # (N, T) bytes
    n, t = x.shape
    planes = [(x >> k) & 1 for k in range(8)]
    # one int8 convert on the concatenated block: per-plane converts
    # cost eight relayouts instead of one
    bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8N, T)
    w = w_ref[:]  # (8M, 8N) int8 0/1, plane-major both sides
    y = jax.lax.dot_general(
        w, bits, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )  # (8M, T) plane-major rows
    y = y & 1
    r = y.shape[0] // 8
    acc = y[0:r, :]
    for k in range(1, 8):
        acc = acc | (y[k * r : (k + 1) * r, :] << k)
    o_ref[:] = acc.astype(jnp.uint8)


@progcache.cached("pallas_gf")
def _apply_fn(rows: int, cols: int, shape: tuple, tile: int,
              interpret: bool):
    """The one program for this shape: ``run(w, shards)`` with the
    (8R, 8C) plane-major bit matrix as an operand, so what Mosaic
    compiles depends on (rows, cols, shape, tile) and never on the
    coefficients — all 209 two-loss repair matrices of EC12P4 run the
    same executables. Pad to the tile, flatten the leading axes into
    the grid, run the kernel, slice back: the pad and the slice are
    dispatched on their own, as they were when the matrix was a
    constant — fused into one jit with the kernel XLA chose a slower
    pad and slice on the chip (PERF.md section 6, PR 28)."""
    *lead, c, s = shape
    pad = (-s) % tile
    kwargs = {}
    if not interpret:
        # every grid step writes a disjoint output tile: let Mosaic
        # schedule them in any order / overlapping DMA
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        )
    kernel = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((rows, s + pad), jnp.uint8),
        grid=((s + pad) // tile,),
        in_specs=[
            pl.BlockSpec((8 * rows, 8 * cols), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="gf256_apply",
        **kwargs,
    )

    # jitted on its own, then vmapped: vmap of the bare pallas_call
    # would rename the custom call (`vmap_gf256_apply_`), and the
    # device trace knows the kernel by `gf256_apply`
    @jax.jit
    def apply(w: jax.Array, shards: jax.Array) -> jax.Array:
        """((8R, 8C) int8, (C, S) uint8) -> (R, S) uint8."""
        return kernel(w, shards)

    def run(w: jax.Array, shards) -> jax.Array:
        # a host array goes up as it is: jnp.pad of a numpy array would
        # compile a convert_element_type of its own on first sight
        shards = jnp.asarray(shards)
        if pad:
            with jax.named_scope("gf256.pad"):
                shards = jnp.pad(
                    shards, [*([(0, 0)] * len(lead)), (0, 0), (0, pad)])
        with jax.named_scope("gf256.relayout"):
            flat = shards.reshape(-1, c, s + pad)
        outs = jax.vmap(apply, in_axes=(None, 0))(w, flat)
        with jax.named_scope("gf256.unpad"):
            out = outs.reshape(*lead, rows, s + pad)
            return out[..., :s] if pad else out

    metrics.codec_programs.inc(kernel="gf256_apply")
    return run


def on_tpu() -> bool:
    """True when this process's default JAX backend is a TPU. A backend
    that fails to initialise raises here: a process asked onto the chip
    must not learn "no TPU" from a swallowed error and serve from CPU."""
    return jax.devices()[0].platform == "tpu"


def gf_matrix_apply_pallas(coeff: np.ndarray, shards, tile: int = DEFAULT_TILE,
                           interpret: bool | None = None):
    """Fused GF apply. shards: (..., C, S) uint8 -> (..., R, S).

    interpret=None compiles for the chip on a TPU backend and uses the
    Pallas interpreter on any other (slow; correctness tests only).
    S is zero-padded to the tile size — exact for GF codes (parity of
    zero bytes is zero) and sliced back before returning. The matrix
    comes from rs_kernel's device-resident cache and goes in as an
    operand.
    """
    from . import rs_kernel

    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    if interpret is None:
        interpret = not on_tpu()
    shards = jnp.asarray(shards)
    fn = _apply_fn(coeff.shape[0], coeff.shape[1], tuple(shards.shape),
                   tile, bool(interpret))
    return fn(rs_kernel.device_bits(coeff, True), shards)


def verify_tile(coeff: np.ndarray, tile: int, seed: int = 0) -> bool:
    """On-device bit-identity check of one (rows, cols, tile) program on
    one matrix: runs the fused kernel on one random tile and compares
    (on device) against the jnp bit-matmul path. MUST pass before an
    autotuner may use this tile, and — for several matrices, see
    rs_kernel._pallas_verified — before the production dispatch may use
    the program: Mosaic has miscompiled large tiles silently.

    The golden deliberately bypasses rs_kernel.gf_matrix_apply: that
    entry point dispatches back to THIS kernel on TPU, which would make
    the gate a tautology (Pallas compared against itself)."""
    import jax.numpy as _jnp

    from . import rs_kernel

    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    rng = np.random.default_rng(seed)
    # the gate may fire lazily from inside an outer jit trace (first
    # dispatch of a program); ensure_compile_time_eval keeps this
    # concrete computation out of that trace
    with jax.ensure_compile_time_eval():
        x = jnp.asarray(
            rng.integers(0, 256, (coeff.shape[1], tile), dtype=np.uint8))
        got = gf_matrix_apply_pallas(coeff, x, tile=tile)
        want = rs_kernel._bits_fn(*coeff.shape, tuple(x.shape))(
            rs_kernel.device_bits(coeff, False), x)
        return bool(jax.device_get(_jnp.array_equal(got, want)))
