"""Pallas TPU kernel for the GF(2^8) bit-matmul (encode/reconstruct).

The jnp path (rs_kernel.gf_apply_bits) materializes the 8x bit expansion
in HBM: unpack (8N, S) int8 -> dot -> pack. On TPU that makes the kernel
HBM-bound at ~8x the payload traffic. This kernel fuses the whole chain
per VMEM tile:

    HBM uint8 tile (N, T) -> VMEM -> unpack bits (VPU shifts)
        -> (8M, 8N) @ (8N, T) int8 dot (MXU) -> & 1 -> pack -> (M, T)

so HBM sees only payload-in + parity-out. The coefficient bit-matrix is
tiny (<= 288x288) and stays resident in VMEM across the grid.

Bit-identical to the jnp path by construction (same exact integer math);
tests compare both on every codemode (interpret mode off-TPU).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import bitlin

# Bytes of shard per grid step. VMEM per step ~ (C + 8C + 4*8R + R) * T
# for C input shards and R output rows: at T=32KiB and RS(12+4) repair
# (C=12, R<=6) that is ~8 MiB — comfortably inside a v5e core's ~16 MiB
# VMEM while amortizing grid overhead far better than tiny tiles.
# bench.py autotunes over TILE_CANDIDATES on real hardware — and MUST
# verify bit-identity per tile first (verify_tile below): Mosaic was
# observed to MISCOMPILE this kernel at tile >= 65536 (silent wrong
# parity), so an unvalidated autotune can "win" with garbage output.
# CUBEFS_PALLAS_TILE pins the production tile if a deployment's
# autotune says otherwise.
DEFAULT_TILE = int(os.environ.get("CUBEFS_PALLAS_TILE", "32768"))
TILE_CANDIDATES = (8192, 16384, 32768)


def _kernel(w_ref, x_ref, o_ref):
    # Plane-major (bit-major) layout throughout: bits row k*N+b = bit k
    # of byte-row b. The per-byte interleave (row b*8+k) forces Mosaic
    # into sublane shuffles that dominated the kernel (17 -> 58 GiB/s on
    # the judged shape when switched); the coefficient matrix is
    # permuted to match at trace time (bitlin.w_to_bitmajor), so the
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


@functools.lru_cache(maxsize=None)
def _apply_fn(coeff_bytes: bytes, rows: int, cols: int, tile: int,
              interpret: bool):
    coeff = np.frombuffer(coeff_bytes, dtype=np.uint8).reshape(rows, cols)
    # keep numpy in the closure: converting here would capture a tracer
    # when the first call happens inside an outer jit trace (the cached
    # closure would then leak it into later traces)
    w_np = bitlin.w_to_bitmajor(bitlin.gf_matrix_to_bits(coeff), rows, cols)

    @jax.jit
    def apply(shards: jax.Array) -> jax.Array:
        """(N, S) uint8 -> (R, S) uint8; S must be a tile multiple."""
        w = jnp.asarray(w_np, dtype=jnp.int8)
        n, s = shards.shape
        grid = (s // tile,)
        kwargs = {}
        if not interpret:
            # every grid step writes a disjoint output tile: let Mosaic
            # schedule them in any order / overlapping DMA
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            )
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((rows, s), jnp.uint8),
            grid=grid,
            in_specs=[
                pl.BlockSpec((8 * rows, 8 * cols), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((n, tile), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((rows, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_apply",
            **kwargs,
        )(w, shards)

    return apply


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
    zero bytes is zero) and sliced back before returning.
    """
    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    if interpret is None:
        interpret = not on_tpu()
    shards = jnp.asarray(shards)
    *lead, c, s = shards.shape
    pad = (-s) % tile
    if pad:
        with jax.named_scope("gf256.pad"):
            shards = jnp.pad(
                shards, [*([(0, 0)] * len(lead)), (0, 0), (0, pad)])
    with jax.named_scope("gf256.relayout"):
        flat = shards.reshape(-1, c, s + pad)
    fn = _apply_fn(coeff.tobytes(), coeff.shape[0], coeff.shape[1], tile,
                   bool(interpret))
    outs = jax.vmap(fn)(flat)
    with jax.named_scope("gf256.unpad"):
        out = outs.reshape(*lead, coeff.shape[0], s + pad)
        return out[..., :s] if pad else out


def verify_tile(coeff: np.ndarray, tile: int, seed: int = 0) -> bool:
    """On-device bit-identity gate for one tile size: runs the fused
    kernel on one random tile and compares (on device) against the jnp
    bit-matmul path. MUST pass before an autotuner (or the production
    dispatch in rs_kernel) may use this tile — Mosaic has miscompiled
    large tiles silently.

    The golden deliberately bypasses rs_kernel.gf_matrix_apply: that
    entry point dispatches back to THIS kernel on TPU, which would make
    the gate a tautology (Pallas compared against itself)."""
    import jax.numpy as _jnp

    from . import rs_kernel

    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    rng = np.random.default_rng(seed)
    # the gate may fire lazily from inside an outer jit trace (first
    # dispatch for a matrix); ensure_compile_time_eval keeps this
    # concrete computation out of that trace
    with jax.ensure_compile_time_eval():
        x = jnp.asarray(
            rng.integers(0, 256, (coeff.shape[1], tile), dtype=np.uint8))
        got = gf_matrix_apply_pallas(coeff, x, tile=tile)
        want = rs_kernel._matrix_apply_fn(
            coeff.tobytes(), coeff.shape[0], coeff.shape[1])(x)
        return bool(jax.device_get(_jnp.array_equal(got, want)))


class PallasEngine:
    """codec engine backed by the fused kernel (--ec-engine=tpu-pallas)."""

    name = "tpu-pallas"

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        return self._apply("apply", coeff, shards)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        from . import gf256

        return self._apply(
            "encode", gf256.parity_matrix(data.shape[-2], n_parity), data)

    def _apply(self, op: str, coeff: np.ndarray, shards: np.ndarray
               ) -> np.ndarray:
        # same miscompile gate as the rs_kernel dispatch: even when the
        # operator forces this engine, a matrix Mosaic miscompiles must
        # fall back to the exact jnp path rather than write bad parity
        from ..codec.engine import device_call
        from . import rs_kernel

        coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
        if on_tpu() and not rs_kernel._pallas_verified(
            coeff.tobytes(), coeff.shape[0], coeff.shape[1]
        ):
            program = rs_kernel._matrix_apply_fn(
                coeff.tobytes(), coeff.shape[0], coeff.shape[1])
        else:
            def program(x):
                return gf_matrix_apply_pallas(coeff, x)
        return device_call(self, op, program, np.asarray(shards))


def register() -> None:
    from ..codec import engine

    engine.register_engine("tpu-pallas", PallasEngine)


register()
