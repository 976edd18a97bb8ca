"""The judged kernels must COMPILE for a real v5e TPU target in CI.

Tier-1 runs on CPU and never touches the chip, so this is the standing
proof that the north-star graphs are valid TPU programs:
jax.experimental.topologies builds a compile-only v5e client from the
baked-in libtpu, XLA+Mosaic compile the graphs, and memory_analysis gives
real HBM footprints (this path caught a 41.9 GB OOM in the CRC graph that
interpret-mode tests could never see).

Reference parity: blobstore/common/ec/encoder.go:114 (the AVX2 SIMD
encode/reconstruct hot path this kernel replaces).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cubefs_tpu.models import repair
from cubefs_tpu.ops import pallas_gf, rs_kernel
from cubefs_tpu.tool import aot_tpu


@pytest.fixture(scope="module")
def v5e_sharding():
    try:
        topo = aot_tpu.v5e_topology()
    except Exception as e:  # pragma: no cover - libtpu missing/broken only
        pytest.skip(f"no compile-only TPU client: {e}")
    return aot_tpu._single_chip_sharding(topo)


def _compile(fn, *structs):
    compiled = jax.jit(fn).lower(*structs).compile()
    return compiled.memory_analysis()


def test_repair_graphs_compile_for_v5e(v5e_sharding):
    """jnp + fused-pallas repair compile for TPU; the fused kernel's HBM
    temp footprint must beat the jnp path's 8x bit tensor (the entire
    point of the pallas kernel)."""
    plan = repair.make_plan(12, 4, bad=[1, 7])
    rows = np.ascontiguousarray(plan.rows, dtype=np.uint8)
    s = 1 << 20  # smaller than bench's 4MiB: compile-validity, fast
    struct = jax.ShapeDtypeStruct((2, 12, s), jnp.uint8, sharding=v5e_sharding)

    m_jnp = _compile(lambda a: rs_kernel.gf_matrix_apply(plan.rows, a), struct)
    m_pl = _compile(
        lambda a: pallas_gf.gf_matrix_apply_pallas(rows, a, interpret=False),
        struct,
    )
    assert m_pl.temp_size_in_bytes < m_jnp.temp_size_in_bytes / 4
    # and the per-step working set must fit a v5e core's ~16 MiB VMEM
    # many times over at HBM scale: total temp stays under 1 GiB here
    assert m_pl.temp_size_in_bytes < 1 << 30


def test_fused_kernel_keeps_its_name_in_the_compiled_program(v5e_sharding):
    """The device trace knows the kernel by its instruction's name
    (`%gf256_apply.1 custom-call`; cellbench's readers and PERF.md match
    on it), and XLA takes that name from the source locations — which a
    process that keeps a compile cache cuts to their innermost frame
    (ops/__init__.py)."""
    import re

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 1)
    try:
        program = pallas_gf._apply_fn.__wrapped__(
            4, 12, (2, 12, 65536), 32768, False)
        w = jax.ShapeDtypeStruct((32, 96), jnp.int8, sharding=v5e_sharding)
        x = jax.ShapeDtypeStruct((2, 12, 65536), jnp.uint8,
                                 sharding=v5e_sharding)
        text = jax.jit(program).lower(w, x).compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert re.findall(r"(%\S+) = \S+ custom-call\(", text) == [
        "%gf256_apply.1"]


def test_all_judged_graphs_green_in_artifact():
    """Every BASELINE config's graph compiled for v5e in the committed
    artifact sweep (regenerated by `python -m cubefs_tpu.tool.aot_tpu`),
    and the bench-shape CRC graph fits 16 GiB HBM (regression: pre
    micro-batching it allocated 41.9 GB)."""
    import json
    from pathlib import Path

    art = Path(__file__).resolve().parents[1] / "artifacts/aot_v5e/AOT_v5e.json"
    data = json.loads(art.read_text())
    assert data["all_ok"], [g for g in data["graphs"] if not g.get("ok")]
    names = {g["graph"] for g in data["graphs"]}
    assert {
        "encode_rs12p4_b8_4mib",
        "repair_jnp_rs12p4_b4_4mib",
        "crc32_verify_10k_128kib",
        "repair_step_rs12p4_b4_4mib",
    } <= names
    crc = next(g for g in data["graphs"] if g["graph"].startswith("crc32"))
    assert crc["temp_bytes"] + crc["argument_bytes"] < 16 * (1 << 30)


@pytest.mark.skipif(
    not __import__("os").environ.get("CUBEFS_SLOW_AOT"),
    reason="~150s XLA compile; run with CUBEFS_SLOW_AOT=1 (tool sweep covers it)",
)
def test_crc_graph_fits_v5e_hbm_live(v5e_sharding):
    from cubefs_tpu.ops import crc32_kernel

    struct = jax.ShapeDtypeStruct(
        (10_000, 128 << 10), jnp.uint8, sharding=v5e_sharding
    )
    m = _compile(lambda a: crc32_kernel.crc32_blocks(a, chunk_len=4096), struct)
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 16 * (1 << 30)


def test_pallas_crc_compiles_for_v5e(v5e_sharding):
    """The fused CRC linear-stage kernel is a valid TPU program with a
    bounded footprint: its whole point is dodging the jnp path's HBM
    bit expansion, so its temp size must be a small multiple of the
    payload, not ~9x."""
    from cubefs_tpu.ops import crc32_kernel, pallas_crc

    nblk, block_len = 512, 128 << 10  # 64 MiB payload slice
    struct = jax.ShapeDtypeStruct((nblk, block_len), jnp.uint8,
                                  sharding=v5e_sharding)
    m_pl = _compile(
        lambda a: pallas_crc.crc32_blocks_pallas(a, chunk_len=1024,
                                                 interpret=False),
        struct)
    m_jnp = _compile(
        lambda a: crc32_kernel.crc32_blocks(a, chunk_len=4096), struct)
    payload = nblk * block_len
    assert m_pl.temp_size_in_bytes < 3 * payload, (
        m_pl.temp_size_in_bytes, payload)
    assert m_pl.temp_size_in_bytes < m_jnp.temp_size_in_bytes / 2, (
        m_pl.temp_size_in_bytes, m_jnp.temp_size_in_bytes)


@pytest.mark.parametrize("rows,cols,stripes,tiles", [
    (2, 3, 64, 3),    # EC3P3's widest rung: the jnp program, 64 stripes
    (2, 6, 8, 4),     # EC6P6: the fused program's narrowest rung
    (2, 6, 32, 22),   # EC6P6 at a full 4 MiB object's shard
    (2, 12, 16, 11),  # EC12P4's narrowest rung
    (2, 18, 64, 18),  # EC16P20L2's local stripe at a 64 MiB object's shard
    (2, 16, 64, 18),  # ... and its global fallback
    (2, 8, 8, 4),     # EC6P10L2's local stripe, the fused program's narrowest
])
def test_repair_step_programs_compile_for_v5e(v5e_sharding, rows, cols,
                                              stripes, tiles):
    """The repair worker's steps (PR 36): two rows by a codemode's n
    columns at (stripe rung, n, width rung) — shapes `RepairWorker.ready`
    builds on the chip before the first task. The program is the one
    `rs_kernel.plan` picks there: fused from four tiles up."""
    tile = pallas_gf.DEFAULT_TILE
    shape = (stripes, cols, tiles * tile)
    assert rs_kernel.repair_step_shape(
        stripes - 1, shape[2] - 5, 64) == (stripes, shape[2])
    if rs_kernel._pallas_profitable(shape[2]):
        program = pallas_gf._apply_fn.__wrapped__(rows, cols, shape, tile,
                                                  False)
    else:
        program = rs_kernel.gf_apply_bits
    w = jax.ShapeDtypeStruct((8 * rows, 8 * cols), jnp.int8,
                             sharding=v5e_sharding)
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=v5e_sharding)
    m = jax.jit(program).lower(w, x).compile().memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 4 * (1 << 30)


@pytest.mark.parametrize("stripes,tiles", [
    (8, 18),  # a 64 MiB PUT: 8 blobs of 524,288 B shards at the 18-tile rung
    (1, 4),   # the fused program's narrowest rung, one blob
])
def test_lrc_step_programs_compile_for_v5e(v5e_sharding, stripes, tiles):
    """An EC16P20L2 PUT's one step (PR 42): the composed 22 x 16 rows,
    global and both AZs' local parity, at the shapes
    `AccessHandler.ready` builds on the chip — the benchmark's first
    step bound by the chip's int8 rate rather than by HBM."""
    from cubefs_tpu.codec import codemode as cm
    from cubefs_tpu.codec.encoder import _lrc_rows

    rows = _lrc_rows(cm.tactic(cm.CodeMode.EC16P20L2))
    assert rows.shape == (22, 16)
    tile = pallas_gf.DEFAULT_TILE
    shape = (stripes, 16, tiles * tile)
    assert rs_kernel._pallas_profitable(shape[2])
    program = pallas_gf._apply_fn.__wrapped__(22, 16, shape, tile, False)
    w = jax.ShapeDtypeStruct((8 * 22, 8 * 16), jnp.int8,
                             sharding=v5e_sharding)
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=v5e_sharding)
    m = jax.jit(program).lower(w, x).compile().memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < 4 * (1 << 30)


@pytest.mark.parametrize("rows,cols,stripes,tiles", [
    (22, 16, 8, 18),  # ingest-lrc: a 64 MiB EC16P20L2 PUT's one step
    (2, 12, 64, 22),  # disk-repair: 64 bids' two rows at the 22-tile rung
])
def test_a_large_results_blocks_are_cut_without_a_relayout_on_v5e(
        v5e_sharding, rows, cols, stripes, tiles):
    """The result leaves the step's program in the layout the chip
    keeps for its shape (rows-major for these), and the splitter that
    cuts it into column blocks for the host (`engine._splitter`, fed
    that layout) compiles to one fusion of strided copies: no relayout,
    no copy of its own."""
    import re

    from jax.experimental.layout import Format

    from cubefs_tpu.codec import engine

    tile = pallas_gf.DEFAULT_TILE
    shape = (stripes, cols, tiles * tile)
    program = pallas_gf._apply_fn.__wrapped__(rows, cols, shape, tile, False)
    w = jax.ShapeDtypeStruct((8 * rows, 8 * cols), jnp.int8,
                             sharding=v5e_sharding)
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=v5e_sharding)
    out = jax.jit(program).lower(w, x).compile().output_formats
    order = tuple(out.layout.major_to_minor)
    assert order == (1, 0, 2)
    result = (stripes, rows, tiles * tile)
    split, bounds = engine._splitter(result, order)
    y = jax.ShapeDtypeStruct(result, jnp.uint8,
                             sharding=Format(out.layout, v5e_sharding))
    text = split.lower(y).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r" fusion\(", entry)) == 1
    assert not re.findall(r" copy\(", entry)
    assert len(bounds) >= 8
