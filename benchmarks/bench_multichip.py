"""Multichip codec benchmark: sharded vs single-chip at REAL sizes.

Round-1 VERDICT flagged that the (dp, tp, sp) mesh sharding was only
ever validated at toy sizes — nothing showed the split is PROFITABLE
(splitting a 16-shard stripe across chips may be ICI-latency-bound).
This script measures exactly that, whenever more than one device is
visible:

  * single-device RS(12+4) repair throughput (the bench.py config)
  * the same work sharded over the full mesh (stripes over dp, shards
    over tp with psum XOR-combine, bytes over sp)

and reports the speedup. On one device it measures the single-chip
number only and says so. Usable today on the virtual CPU mesh
(JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
python benchmarks/bench_multichip.py — numbers are NOT meaningful perf,
only a plumbing check) and on real multi-chip hardware unchanged.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, iters: int = 3) -> float:
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(
    shard_bytes: int | None = None,
    batch: int | None = None,
    out_path: str | None = None,
) -> dict:
    import jax
    import numpy as np

    from cubefs_tpu.models import repair
    from cubefs_tpu.ops import rs_kernel
    from cubefs_tpu.parallel import mesh as meshlib

    n_dev = jax.device_count()
    on_tpu = jax.devices()[0].platform == "tpu"
    S = shard_bytes or ((4 << 20) if on_tpu else (1 << 18))
    B = batch or (8 if on_tpu else 4)
    n, m = 12, 4
    plan = repair.make_plan(n, m, bad=[1, 7])
    rows = plan.rows
    rng = np.random.default_rng(3)
    surv = rng.integers(0, 256, (B, n, S), dtype=np.uint8)

    dev0 = jax.devices()[0]
    x1 = jax.device_put(surv, dev0)
    dt = _time(lambda a: rs_kernel.gf_matrix_apply(rows, a), x1)
    single_gibs = B * n * S / dt / (1 << 30)

    out = {"devices": n_dev, "platform": jax.devices()[0].platform,
           "shard_bytes": S, "stripes": B,
           "single_device_gibs": round(single_gibs, 3)}
    if not on_tpu:
        out["note"] = (
            "virtual CPU mesh: devices share host cores, so speedups are "
            "NOT meaningful perf — this artifact is a sharding-plumbing "
            "check only; rerun on a real multi-chip mesh for profitability"
        )
    if n_dev > 1:
        mesh = meshlib.make_mesh(n_dev)
        dp, tp, sp = (mesh.shape[a] for a in ("dp", "tp", "sp"))
        # batch/shape must divide the mesh axes
        Bm = max(B, dp) - (max(B, dp) % dp or 0) or dp
        Sm = S - (S % sp)
        surv_m = rng.integers(0, 256, (Bm, n, Sm), dtype=np.uint8)
        xs = jax.device_put(surv_m, meshlib.stripe_sharding(mesh))

        def sharded(a):
            rec, _ = repair.sharded_repair_step(mesh, plan, a)
            return rec

        dt = _time(sharded, xs)
        sharded_gibs = Bm * n * Sm / dt / (1 << 30)
        out.update({
            "mesh": {"dp": dp, "tp": tp, "sp": sp},
            "sharded_gibs": round(sharded_gibs, 3),
            "speedup_vs_single": round(sharded_gibs / single_gibs, 2),
        })

        # dp-only mesh: stripes are independent, so this axis has no
        # collectives at all — the profitable default for repair fleets
        dpm = meshlib.make_mesh(n_dev, dims={"dp": n_dev, "tp": 1, "sp": 1})
        Bd = ((B + n_dev - 1) // n_dev) * n_dev
        surv_d = rng.integers(0, 256, (Bd, n, S), dtype=np.uint8)
        xd = jax.device_put(surv_d, meshlib.stripe_sharding(dpm))

        def dp_sharded(a):
            rec, _ = repair.sharded_repair_step(dpm, plan, a)
            return rec

        dt = _time(dp_sharded, xd)
        dp_gibs = Bd * n * S / dt / (1 << 30)
        out.update({
            "dp_only_stripes": Bd,
            "dp_only_gibs": round(dp_gibs, 3),
            "dp_only_speedup_vs_single": round(dp_gibs / single_gibs, 2),
        })
    else:
        out["note"] = "one device visible: sharded comparison skipped"
    print(json.dumps(out))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--shard-bytes", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    main(a.shard_bytes, a.batch, a.out)
