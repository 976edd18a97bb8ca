"""Kernel benchmark: EC encode+repair GiB/s/chip + CRC GB/s, on the chip.

Replicates BASELINE.json's five configs as device kernels over data
already resident in HBM:

  1. RS(6+3), 1MiB shards, single-stripe encode — CPU engine vs device
     engine (the size-class crossover measurement)
  2. RS(12+4), 4MiB shards, batched encode, 1024 stripes streamed
  3. RS(12+4), 4MiB shards, reconstruct 2 missing, with the fused
     Pallas kernel autotuned over tile sizes
  4. extent-store CRC32 verify, 10k x 128KiB blocks, batched
  5. full-disk migrate replay: mixed RS(12+4)/RS(6+3) task stream
     (the scheduler's disk-repair shape)

It needs a TPU and exits non-zero without one: a rate or a time comes
only from a chip run, and a CPU number is never printed under these
names. A failed phase fails the run. This is NOT the served-path
benchmark (ROADMAP S1) — nothing here crosses host->device per step —
and its timing method (chain-slope, cubefs_tpu/utils/benchtime.py) and
metrics are that PR's to replace.

Prints ONE JSON line. `value` is the repair number (config 3);
vs_baseline is value / 8 GiB/s — the BASELINE.json target for v5e-1.
"""

from __future__ import annotations

import json
import time


def main() -> None:
    from cubefs_tpu import ops

    dev = ops.require_tpu()[0]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cubefs_tpu.codec import engine as ec_engine
    from cubefs_tpu.models import repair
    from cubefs_tpu.ops import crc32_kernel, pallas_crc, pallas_gf, rs_kernel
    from cubefs_tpu.utils.benchtime import timed_slope

    rng = np.random.default_rng(7)

    # ---- config 1: RS(6+3), 1MiB shards, SINGLE stripe encode ----------
    # (the CPU-vs-device crossover backing the size-class policy: one
    # small stripe cannot amortize device dispatch)
    s63 = 1 << 20
    one_stripe = rng.integers(0, 256, (6, s63), dtype=np.uint8)
    cpu_eng = ec_engine.get_engine("numpy")
    t0 = time.perf_counter()
    cpu_iters = 3
    for _ in range(cpu_iters):
        cpu_eng.encode_parity(one_stripe, 3)
    rs63_cpu_gibs = cpu_iters * 6 * s63 / (time.perf_counter() - t0) / (1 << 30)
    # native SIMD CPU engine (gfcpu.cc): the real CPU leg of the
    # size-class crossover (numpy stays as the golden baseline above)
    cpp_eng = ec_engine.get_engine("cpp")
    cpp_eng.encode_parity(one_stripe, 3)  # warm
    t0 = time.perf_counter()
    for _ in range(8):
        cpp_eng.encode_parity(one_stripe, 3)
    rs63_cpp_gibs = 8 * 6 * s63 / (time.perf_counter() - t0) / (1 << 30)
    # in memory only: a run never writes into the checkout
    crossover = ec_engine.measure_crossover(save=False)
    x1 = jax.device_put(one_stripe, dev)
    chain1 = jax.jit(lambda a: jnp.tile(rs_kernel.encode_parity(a, 3), (2, 1)))
    dt = timed_slope(chain1, x1, k1=4, k2=68)
    rs63_dev_gibs = 6 * s63 / dt / (1 << 30)

    # ---- config 2: RS(12+4), 4MiB shards, 1024 stripes streamed --------
    # encode_parity dispatches to the Pallas kernel on TPU (the
    # production path); the forced-jnp A/B leg is measured separately so
    # the Pallas-vs-jnp comparison stays real
    n, m = 12, 4
    S = 4 << 20
    B = 8  # stripes resident per device step
    batch = rng.integers(0, 256, (B, n, S), dtype=np.uint8)
    x2 = jax.device_put(batch, dev)
    chain2 = jax.jit(
        lambda a: jnp.tile(rs_kernel.encode_parity(a, m), (1, 3, 1))
    )
    # k2 - k1 = 128 chained steps x B=8 stripes = the 1024-stripe stream
    dt = timed_slope(chain2, x2, k1=4, k2=132, repeats=2)
    encode_gibs = B * n * S / dt / (1 << 30)

    # ---- config 3 (JUDGED): RS(12+4) reconstruct, 2 missing ------------
    plan = repair.make_plan(n, m, bad=[1, 7])
    rows = np.ascontiguousarray(plan.rows, dtype=np.uint8)
    Br = 4
    surv = jax.device_put(
        rng.integers(0, 256, (Br, n, S), dtype=np.uint8), dev
    )  # any bytes; throughput only (math is data-independent)
    reps = -(-n // len(rows))  # tile recovered rows back up to n inputs
    # forced-jnp baseline (bypasses the dispatch, so this leg stays an
    # independent A/B even though gf_matrix_apply routes to Pallas now)
    jnp_apply = rs_kernel._bits_fn(*rows.shape, (Br, n, S))
    w_bits = rs_kernel.device_bits(rows, False)
    chain3 = jax.jit(
        lambda a: jnp.tile(jnp_apply(w_bits, a), (1, reps, 1))[:, :n, :]
    )
    dt = timed_slope(chain3, surv, k1=2, k2=34)
    repair_jnp_gibs = Br * n * S / dt / (1 << 30)
    repair_gibs = repair_jnp_gibs

    # fused pallas path: avoids the 8x bit tensor in HBM; autotune the
    # tile size on the chip
    pallas_gibs, pallas_tile = None, None
    for tile in pallas_gf.TILE_CANDIDATES:
        chain_p = jax.jit(
            lambda a, _t=tile: jnp.tile(
                pallas_gf.gf_matrix_apply_pallas(rows, a, tile=_t),
                (1, reps, 1),
            )[:, :n, :]
        )
        # bit-identity gate first: Mosaic has silently miscompiled this
        # kernel at large tiles — a wrong tile must not win the autotune
        if not pallas_gf.verify_tile(rows, tile):
            raise SystemExit(f"bench: pallas tile {tile} MISCOMPILES")
        dt = timed_slope(chain_p, surv, k1=1, k2=9, repeats=2)
        gibs = Br * n * S / dt / (1 << 30)
        if pallas_gibs is None or gibs > pallas_gibs:
            pallas_gibs, pallas_tile = gibs, tile
    repair_gibs = max(repair_gibs, pallas_gibs)

    # ---- config 4: CRC32 verify, 10k x 128KiB blocks -------------------
    nblk = 10_000
    blocks = jax.device_put(
        rng.integers(0, 256, (nblk, 128 << 10), dtype=np.uint8), dev
    )
    chain4 = jax.jit(
        lambda a: a
        ^ crc32_kernel.crc32_blocks(a, chunk_len=4096).astype(jnp.uint8)[:, None]
    )
    dt = timed_slope(chain4, blocks, k1=1, k2=4, repeats=2)
    crc_gbs = nblk * (128 << 10) / dt / 1e9

    # fused pallas CRC linear stage: dodges the 9x HBM bit expansion,
    # same verify-then-trust autotune as the GF kernel
    crc_pallas_gbs, crc_pallas_tb = None, None
    for tb in pallas_crc.TILE_CANDIDATES:
        chain4p = jax.jit(
            lambda a, _tb=tb: a
            ^ pallas_crc.crc32_blocks_pallas(
                a, chunk_len=1024, tile_blocks=_tb
            ).astype(jnp.uint8)[:, None]
        )
        if not pallas_crc.verify_tile(128 << 10, 1024, tb):
            raise SystemExit(f"bench: pallas crc tb {tb} MISCOMPILES")
        dtp = timed_slope(chain4p, blocks, k1=1, k2=4, repeats=2)
        gbs = nblk * (128 << 10) / dtp / 1e9
        if crc_pallas_gbs is None or gbs > crc_pallas_gbs:
            crc_pallas_gbs, crc_pallas_tb = gbs, tb
    crc_gbs = max(crc_gbs, crc_pallas_gbs)

    # ---- config 5: full-disk migrate replay, mixed codemodes -----------
    # the scheduler's disk-repair stream: alternating RS(12+4)@4MiB and
    # RS(6+3)@1MiB stripe batches through the fused repair step (the
    # worker's reconstruct+verify+CRC graph), one task pair per step
    plan63 = repair.make_plan(6, 3, bad=[2])
    s63m = 1 << 20
    p124, p63 = len(plan.present), len(plan63.present)
    surv124 = jax.device_put(
        rng.integers(0, 256, (Br, p124, S), dtype=np.uint8), dev
    )
    surv63 = jax.device_put(
        rng.integers(0, 256, (Br * 2, p63, s63m), dtype=np.uint8), dev
    )
    r124 = -(-p124 // len(plan.wanted))
    r63 = -(-p63 // len(plan63.wanted))

    @jax.jit
    def chain5(pair):
        a, b = pair
        rec_a, _, _ = repair.repair_step(plan, a, chunk_len=4096)
        rec_b, _, _ = repair.repair_step(plan63, b, chunk_len=4096)
        return (
            jnp.tile(rec_a, (1, r124, 1))[:, :p124, :],
            jnp.tile(rec_b, (1, r63, 1))[:, :p63, :],
        )

    dt = timed_slope(chain5, (surv124, surv63), k1=2, k2=18, repeats=2)
    migrate_gibs = (surv124.size + surv63.size) / dt / (1 << 30)

    target_gibs = 8.0  # BASELINE.json: >=8 GiB/s/chip RS(12+4) repair on v5e-1
    print(
        json.dumps(
            {
                "metric": "RS(12+4) 4MiB-shard reconstruct(2 missing) GiB/s/chip",
                "value": round(repair_gibs, 3),
                "unit": "GiB/s",
                "vs_baseline": round(repair_gibs / target_gibs, 3),
                "extras": {
                    "rs63_1mib_single_cpu_gibs": round(rs63_cpu_gibs, 3),
                    "rs63_1mib_single_cpp_gibs": round(rs63_cpp_gibs, 3),
                    "crossover_policy": crossover,
                    "rs63_1mib_single_dev_gibs": round(rs63_dev_gibs, 3),
                    "encode_1024stripes_gibs": round(encode_gibs, 3),
                    "repair_jnp_gibs": round(repair_jnp_gibs, 3),
                    "crc32_gbs": round(crc_gbs, 3),
                    "crc32_pallas_gbs": round(crc_pallas_gbs, 3),
                    "crc32_pallas_tile_blocks": crc_pallas_tb,
                    "migrate_mixed_gibs": round(migrate_gibs, 3),
                    "pallas_repair_gibs": round(pallas_gibs, 3),
                    "pallas_tile": pallas_tile,
                    "platform": dev.platform,
                    "device_kind": dev.device_kind,
                    "shard_bytes": S,
                    "stripes_per_step": Br,
                    "timing": "chain-slope (cubefs_tpu/utils/benchtime.py)",
                },
            }
        )
    )


if __name__ == "__main__":
    main()
