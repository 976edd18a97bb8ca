"""AOT-compile the judged bench graphs for a real v5e TPU target — no chip.

A pre-flight that costs no chip time: the north-star kernels
(BASELINE.json configs 1-5) are lowered and compiled for a TPU target
from a CPU-only box. `jax.experimental.topologies.get_topology_desc(
"v5e:2x2")` (PJRT TPU compile-only client over the baked-in libtpu)
yields real v5e devices to lower + compile against, including Mosaic
compilation of the fused Pallas GF kernel (cubefs_tpu/ops/pallas_gf.py)
for every tile candidate. Whether the results are RIGHT on hardware is
not something a compile can say — chip_smoke.py decides that.

Artifacts (committed under artifacts/aot_v5e/):
  AOT_v5e.json          one record per graph: compiled ok, memory
                        analysis (temp/arg/output/code bytes), flops
  <graph>.stablehlo.mlir  the lowered StableHLO fed to XLA
  ROOFLINE.md           written roofline estimate per pallas tile

Reference parity: the graphs are the SIMD erasure-code hot path of
/root/reference/blobstore/common/ec/encoder.go:114 (encode/reconstruct
via vendor/github.com/klauspost/reedsolomon AVX2 assembly) and the
datanode CRC verify of /root/reference/datanode/storage/extent.go:626.

Run: JAX_PLATFORMS=cpu python -m cubefs_tpu.tool.aot_tpu
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

# The compile-only TPU client still wants the pod-env vars libtpu probes
# at init; any placeholder satisfies it (no worker is ever contacted).
_TOPO_ENV = {
    "TPU_WORKER_HOSTNAMES": "localhost",
    "TPU_ACCELERATOR_TYPE": "v5litepod-4",
    "TPU_SKIP_MDS_QUERY": "1",
}

TOPOLOGY = "v5e:2x2"  # smallest v5e topology the PJRT client accepts

# Public v5e per-chip numbers used for the roofline estimates only
# (cloud.google.com/tpu/docs/v5e; pallas guide: ~16 MiB VMEM/core).
V5E_HBM_GBS = 819.0  # HBM bandwidth, GB/s
V5E_INT8_TOPS = 394.0  # MXU int8, Tera-ops/s
V5E_VPU_TOPS = 4.0  # conservative VPU int32 elementwise estimate


def v5e_topology():
    for k, v in _TOPO_ENV.items():
        os.environ.setdefault(k, v)
    from jax.experimental import topologies

    return topologies.get_topology_desc(TOPOLOGY, "tpu")


def _single_chip_sharding(topo):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(topo.devices)[:1], ("chip",))
    return NamedSharding(mesh, PartitionSpec())


def _compile_one(name: str, fn, arg_structs, out_dir: Path | None):
    """Lower + compile `fn` for the v5e target; return a result record."""
    import jax

    rec: dict = {"graph": name, "ok": False}
    t0 = time.perf_counter()
    try:
        lowered = jax.jit(fn).lower(*arg_structs)
        if out_dir is not None:
            text = lowered.as_text()
            if len(text) > (256 << 10):  # big constant blocks: store gzipped
                import gzip

                (out_dir / f"{name}.stablehlo.mlir.gz").write_bytes(
                    gzip.compress(text.encode())
                )
            else:
                (out_dir / f"{name}.stablehlo.mlir").write_text(text)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        rec.update(
            ok=True,
            compile_s=round(time.perf_counter() - t0, 2),
            temp_bytes=int(m.temp_size_in_bytes),
            argument_bytes=int(m.argument_size_in_bytes),
            output_bytes=int(m.output_size_in_bytes),
            code_bytes=int(m.generated_code_size_in_bytes),
        )
        try:
            cost = compiled.cost_analysis()
            if cost and cost.get("flops"):
                rec["flops"] = float(cost["flops"])
        except Exception:
            pass
    except Exception as e:  # record, don't abort the sweep
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
    return rec


def compile_judged_graphs(out_dir: Path | None = None) -> list[dict]:
    """Compile every BASELINE.json config's graph for the v5e target.

    Shapes are BASELINE.json's judged ones (4MiB shards, judged
    stripes-per-step), so a green record here means the judged
    configuration itself compiles for the chip.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cubefs_tpu.models import repair
    from cubefs_tpu.ops import crc32_kernel, pallas_gf, rs_kernel

    topo = v5e_topology()
    sharding = _single_chip_sharding(topo)

    def arg(shape, dtype=jnp.uint8):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    S, Br, B = 4 << 20, 4, 8  # BASELINE.json's judged shapes
    plan = repair.make_plan(12, 4, bad=[1, 7])
    rows = plan.rows
    records = []

    # config 2: batched encode RS(12+4), 8 stripes resident
    records.append(
        _compile_one(
            "encode_rs12p4_b8_4mib",
            lambda a: rs_kernel.encode_parity(a, 4),
            [arg((B, 12, S))],
            out_dir,
        )
    )
    # config 3 (JUDGED): reconstruct 2 missing, jnp path
    records.append(
        _compile_one(
            "repair_jnp_rs12p4_b4_4mib",
            lambda a: rs_kernel.gf_matrix_apply(rows, a),
            [arg((Br, 12, S))],
            out_dir,
        )
    )
    # config 3, fused pallas kernel, every tile candidate — through the
    # public wrapper, so the compiled graph is the one its callers run
    for tile in pallas_gf.TILE_CANDIDATES:
        records.append(
            _compile_one(
                f"repair_pallas_rs12p4_tile{tile}",
                lambda a, tile=tile: pallas_gf.gf_matrix_apply_pallas(
                    rows, a, tile=tile, interpret=False
                ),
                [arg((Br, 12, S))],
                out_dir,
            )
        )
    # config 4: CRC32 verify, 10k x 128KiB blocks
    records.append(
        _compile_one(
            "crc32_verify_10k_128kib",
            lambda a: crc32_kernel.crc32_blocks(a, chunk_len=4096),
            [arg((10_000, 128 << 10))],
            out_dir,
        )
    )
    # config 4, fused pallas CRC linear stage, every tile candidate
    from cubefs_tpu.ops import pallas_crc

    for tb in pallas_crc.TILE_CANDIDATES:
        records.append(
            _compile_one(
                f"crc32_pallas_10k_128kib_tb{tb}",
                lambda a, tb=tb: pallas_crc.crc32_blocks_pallas(
                    a, chunk_len=1024, tile_blocks=tb, interpret=False
                ),
                [arg((10_000, 128 << 10))],
                out_dir,
            )
        )
    # config 5: fused repair_step (reconstruct + verify + CRC) graph
    records.append(
        _compile_one(
            "repair_step_rs12p4_b4_4mib",
            lambda a: repair.repair_step(plan, a, chunk_len=4096),
            [arg((Br, len(plan.present), S))],
            out_dir,
        )
    )
    return records


def roofline_md(records: list[dict]) -> str:
    """Roofline estimate for the judged repair config per pallas tile.

    Model (per stripe: C=12 survivors in, R=2 rows out, payload = C*S):
      HBM time  = (C+R)/C * payload / HBM_BW   (fused kernel: payload-only)
      MXU time  = 2 * 8R * 8C * S / INT8_TOPS  (bit-matmul (8R,8C)@(8C,S))
      VPU time  = (16*C + 24*R)/C * payload / VPU_TOPS
                  (unpack: shift+and per bit-plane; pack: mul+add+shift)
    Estimated payload GiB/s = payload / max of the three. The jnp path
    adds an 8x bit tensor round-trip to HBM: its HBM term is
    (C + 8C + 8R + R)/C * payload.
    """
    C, R = 12, 2
    payload = 1.0  # per-byte model; ratios only
    hbm_fused = (C + R) / C / V5E_HBM_GBS
    hbm_jnp = (C + 8 * C + 8 * R + R) / C / V5E_HBM_GBS
    mxu = 2 * 8 * R * 8 * C / C / (V5E_INT8_TOPS * 1000)  # per payload-byte
    vpu = (16 * C + 24 * R) / C / (V5E_VPU_TOPS * 1000)
    est_fused = payload / max(hbm_fused, mxu, vpu)
    est_jnp = payload / max(hbm_jnp, mxu, vpu)
    lines = [
        "# Roofline estimate — RS(12+4) reconstruct(2 missing), v5e-1",
        "",
        "Per-chip model constants (public v5e figures): "
        f"HBM {V5E_HBM_GBS} GB/s, MXU int8 {V5E_INT8_TOPS} TOPS, "
        f"VPU elementwise ~{V5E_VPU_TOPS} TOPS (conservative).",
        "",
        "| path | HBM traffic / payload byte | bound | est. payload GB/s |",
        "|---|---|---|---|",
        f"| fused pallas (any tile) | {(C+R)/C:.2f}x | "
        f"{'VPU' if vpu >= max(hbm_fused, mxu) else ('HBM' if hbm_fused >= mxu else 'MXU')} "
        f"| ~{est_fused:.0f} |",
        f"| jnp (bit tensor in HBM) | {(C+8*C+8*R+R)/C:.2f}x | "
        f"{'HBM' if hbm_jnp >= max(mxu, vpu) else 'VPU'} | ~{est_jnp:.0f} |",
        "",
        "Both estimates sit far above the 8 GiB/s/chip BASELINE target, so",
        "the target is expected to be met with wide margin once a chip is",
        f"reachable; the fused kernel's advantage is the ~{hbm_jnp/hbm_fused:.1f}x lower HBM",
        "traffic (and measured compiled temp memory below). Tile size",
        "(8/16/32 KiB) only changes grid amortization, not the roofline —",
        "the served tile is pallas_gf.DEFAULT_TILE (32 KiB).",
        "",
        "## Compiled memory per graph (from XLA memory_analysis)",
        "",
        "| graph | temp MiB | arg MiB | out MiB | code KiB |",
        "|---|---|---|---|---|",
    ]
    for r in records:
        if r.get("ok"):
            lines.append(
                f"| {r['graph']} | {r['temp_bytes']/2**20:.1f} "
                f"| {r['argument_bytes']/2**20:.1f} "
                f"| {r['output_bytes']/2**20:.1f} "
                f"| {r['code_bytes']/2**10:.1f} |"
            )
        else:
            lines.append(f"| {r['graph']} | FAILED: {r.get('error','?')} | | | |")
    lines += [
        "",
        "The jnp repair graph's temp footprint (the 8x bit tensor) vs the",
        "pallas kernels' confirms the fusion claim quantitatively.",
    ]
    return "\n".join(lines) + "\n"


def main() -> None:
    out_dir = Path(__file__).resolve().parents[2] / "artifacts" / "aot_v5e"
    out_dir.mkdir(parents=True, exist_ok=True)
    records = compile_judged_graphs(out_dir)
    summary = {
        "target": TOPOLOGY,
        "libtpu_compile_only": True,
        "graphs": records,
        "all_ok": all(r.get("ok") for r in records),
    }
    (out_dir / "AOT_v5e.json").write_text(json.dumps(summary, indent=1))
    (out_dir / "ROOFLINE.md").write_text(roofline_md(records))
    print(json.dumps({k: v for k, v in summary.items() if k != "graphs"}))
    for r in records:
        print(
            " ", r["graph"], "ok" if r.get("ok") else f"FAIL {r.get('error')}"
        )
    if not summary["all_ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
