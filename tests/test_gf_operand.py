"""The GF apply takes its bit matrix as an operand (PR 28): one program
per shape serves every coefficient matrix, the matrices live behind a
bounded device-resident cache, and nothing is keyed by coefficient bytes
in a map that grows for ever. CPU: the fused kernel through the Pallas
interpreter at small S, the jnp bit-matmul as the `tpu` engine runs it
off the chip — each bit-identical to the table engine."""

import itertools

import jax
import numpy as np
import pytest

from cubefs_tpu.blob.worker import solve_and_wanted
from cubefs_tpu.codec import codemode as cm
from cubefs_tpu.codec.batcher import BatchCodec
from cubefs_tpu.codec.encoder import CodecConfig, new_encoder
from cubefs_tpu.codec.engine import get_engine
from cubefs_tpu.ops import gf256, msr, pallas_gf, rs_kernel
from cubefs_tpu.utils import metrics

TILE = 256
NUMPY = get_engine("numpy")


def programs() -> dict[str, float]:
    return {k: metrics.codec_programs.value(kernel=k)
            for k in ("gf256_apply", "bits")}


def built_since(before: dict[str, float]) -> dict[str, int]:
    return {k: int(v - before[k]) for k, v in programs().items()
            if v != before[k]}


def fused(rows, x):
    return np.asarray(pallas_gf.gf_matrix_apply_pallas(
        rows, x, tile=TILE, interpret=True))


def bits(rows, x):
    return get_engine("tpu").matrix_apply(rows, x)


KERNELS = {"gf256_apply": fused, "bits": bits}


def worker_rows(t, bad: int, other: int | None) -> np.ndarray:
    """The repair matrix blob/worker.py builds for lost unit ``bad`` of
    an RS stripe whose unit ``other`` is lost too (None: a one-loss
    stripe): survivors in index order past both, the first n solve, the
    next one is rebuilt beside the lost one as the check."""
    solve, wanted = solve_and_wanted(
        [i for i in range(t.n + t.m) if i not in (bad, other)][:t.n + 1],
        t.n, bad)
    return rs_kernel.reconstruct_rows(t.n, t.n + t.m, solve, wanted)


# ------------------------------------------------ every matrix, one program

@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("bad", range(16))
def test_every_ec12p4_repair_matrix_of_a_lost_unit(kernel, bad, rng):
    """The one-loss matrix of unit ``bad`` and its 15 two-loss matrices
    (16 + 240 over the parametrisation), as the worker builds them."""
    t = cm.tactic(cm.CodeMode.EC12P4)
    x = rng.integers(0, 256, (2, 12, 300), dtype=np.uint8)
    KERNELS[kernel](worker_rows(t, bad, None), x)  # the shape's program
    before = programs()
    seen = set()
    for other in [None] + [i for i in range(16) if i != bad]:
        rows = worker_rows(t, bad, other)
        assert rows.shape == (2, 12)
        seen.add(rows.tobytes())
        assert np.array_equal(KERNELS[kernel](rows, x),
                              NUMPY.matrix_apply(rows, x)), (bad, other)
    assert len(seen) >= 12  # they are different matrices
    assert built_since(before) == {}  # and one program


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_all_923_ec6p6_survivor_sets_are_one_program(kernel, rng):
    """Every set of 6 survivors of EC6P6 but the data shards themselves,
    with the (6, 6) decode matrix codec/encoder.py asks for."""
    t = cm.tactic(cm.CodeMode.EC6P6)
    data = rng.integers(0, 256, (6, 64), dtype=np.uint8)
    stripe = np.concatenate([data, NUMPY.encode_parity(data, 6)])
    sets = [s for s in itertools.combinations(range(12), 6)
            if s != tuple(range(6))]
    assert len(sets) == 923
    KERNELS[kernel](np.eye(6, dtype=np.uint8), stripe[None, :6])
    before = programs()
    for present in sets:
        wanted = [i for i in range(t.n) if i not in present]
        rows = np.zeros((6, 6), dtype=np.uint8)
        rows[:len(wanted)] = rs_kernel.reconstruct_rows(
            6, 12, list(present), wanted)
        got = KERNELS[kernel](rows, stripe[None, list(present)])
        assert np.array_equal(got[0, :len(wanted)], data[wanted]), present
    assert built_since(before) == {}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("seed", range(3))
def test_random_matrices_at_the_288_bound(kernel, seed):
    """36 x 36 coefficients = 288 x 288 bits, the largest matrix a
    tactic makes (MSR sub-shard rows)."""
    r = np.random.default_rng([28, seed])
    x = r.integers(0, 256, (2, 36, 200), dtype=np.uint8)
    KERNELS[kernel](np.eye(36, dtype=np.uint8), x)
    before = programs()
    for _ in range(4):
        rows = r.integers(0, 256, (36, 36), dtype=np.uint8)
        assert rs_kernel.device_bits(rows, True).shape == (288, 288)
        assert np.array_equal(KERNELS[kernel](rows, x),
                              NUMPY.matrix_apply(rows, x))
    assert built_since(before) == {}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("family", ["lrc", "msr_encode", "msr_repair"])
def test_lrc_and_msr_rows_go_through_the_same_apply(kernel, family, rng):
    if family == "lrc":
        t = cm.tactic(cm.CodeMode.EC6P10L2)
        stripes, ln = t.ec_layout_by_az(), (t.n + t.m) // t.az_count
        present = list(range(1, t.n + 1))
        mats = [rs_kernel.lrc_reconstruct_rows(
            t.n, t.n + t.m, stripes, ln, present, [0, w])
            for w in (t.n + t.m, t.n + t.m + 1)]  # a local parity each
    elif family == "msr_encode":
        t = cm.tactic(cm.CodeMode.EC6P6MSR)
        mats = [msr.encode_rows(t.n, t.total, t.d)]
    else:
        t = cm.tactic(cm.CodeMode.EC6P6MSR)
        mats = [msr.repair_rows(t.n, t.total, t.d, failed,
                                tuple(i for i in range(t.total)
                                      if i != failed)[:t.d])
                for failed in (0, 5, 7)]
    x = rng.integers(0, 256, (2, mats[0].shape[1], 120), dtype=np.uint8)
    KERNELS[kernel](mats[0], x)
    before = programs()
    for rows in mats:
        assert np.array_equal(KERNELS[kernel](rows, x),
                              NUMPY.matrix_apply(rows, x))
    assert built_since(before) == {}


def test_encode_and_decode_of_a_square_geometry_share_the_program(rng):
    """EC6P6 / EC3P3: the (n, n, S) decode is the encode's own program."""
    eng = get_engine("tpu")
    data = rng.integers(0, 256, (1, 6, 96), dtype=np.uint8)
    eng.encode_parity(data, 6)
    before = programs()
    rows = rs_kernel.reconstruct_rows(6, 12, [1, 2, 3, 4, 5, 9], list(range(6)))
    eng.matrix_apply(rows, data)
    assert built_since(before) == {}


# ------------------------------------------------ the served path

def test_the_tpu_engine_serves_every_matrix_with_one_program_per_shape(rng):
    """All 256 EC12P4 two-loss repair matrices and all 923 EC6P6
    survivor sets through ``JaxEngine.matrix_apply``, bit-identical to
    ``NumpyEngine``: two programs, and a matrix cache that stays inside
    its bound."""
    eng = get_engine("tpu")
    t12 = cm.tactic(cm.CodeMode.EC12P4)
    x12 = rng.integers(0, 256, (3, 12, 80), dtype=np.uint8)
    x6 = rng.integers(0, 256, (3, 6, 80), dtype=np.uint8)
    mats12 = [worker_rows(t12, bad, other)
              for bad in range(16)
              for other in [None] + [i for i in range(16) if i != bad]]
    mats6 = [rs_kernel.reconstruct_rows(6, 12, list(s), list(range(6)))
             for s in itertools.combinations(range(12), 6)
             if s != tuple(range(6))]
    assert (len(mats12), len(mats6)) == (256, 923)
    eng.matrix_apply(mats12[0], x12)
    eng.matrix_apply(mats6[0], x6)
    before = programs()
    for rows, x in [(m, x12) for m in mats12] + [(m, x6) for m in mats6]:
        assert np.array_equal(eng.matrix_apply(rows, x),
                              NUMPY.matrix_apply(rows, x))
    assert built_since(before) == {}
    assert len(rs_kernel.matrices) <= rs_kernel.MATRIX_CACHE_CAP < 256 + 923


def test_matrix_cache_is_bounded_and_a_hit_costs_no_upload(monkeypatch):
    cache = rs_kernel.MatrixCache(capacity=8)
    uploads = []
    real = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: uploads.append(1) or real(x, *a, **k))
    r = np.random.default_rng(5)
    mats = [r.integers(0, 256, (2, 12), dtype=np.uint8) for _ in range(20)]
    miss = metrics.codec_matrix_cache.value(op="apply", result="miss")
    hit = metrics.codec_matrix_cache.value(op="apply", result="hit")
    for m in mats:
        w = cache.get(m, True, "apply")
        assert w.shape == (16, 96) and w.dtype == np.int8
    assert len(cache) == 8 and len(uploads) == 20
    assert cache.get(mats[-1], True, "apply") is w  # device-resident
    assert len(uploads) == 20
    cache.get(mats[-1], False, "apply")  # the other layout is its own entry
    assert len(uploads) == 21 and len(cache) == 8
    cache.get(mats[0], True, "apply")  # evicted long ago: a miss again
    assert len(uploads) == 22
    assert metrics.codec_matrix_cache.value(
        op="apply", result="miss") == miss + 22
    assert metrics.codec_matrix_cache.value(
        op="apply", result="hit") == hit + 1


def test_matrix_cache_counts_nothing_with_the_trace_door_shut(monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    before = dict(metrics.codec_matrix_cache.samples())
    cache = rs_kernel.MatrixCache(capacity=2)
    m = np.arange(24, dtype=np.uint8).reshape(2, 12)
    assert np.array_equal(np.asarray(cache.get(m, False, "apply")),
                          np.asarray(cache.get(m, False, "apply")))
    assert dict(metrics.codec_matrix_cache.samples()) == before


def test_a_first_lookup_inside_a_trace_leaves_no_tracer_behind(rng):
    """``gf_matrix_apply`` is called under outer jits (models/repair.py,
    the AOT sweep): the cached matrix must be a concrete array."""
    rows = rng.integers(0, 256, (3, 5), dtype=np.uint8)  # seen nowhere else
    x = rng.integers(0, 256, (5, 64), dtype=np.uint8)
    inside = jax.jit(lambda a: rs_kernel.gf_matrix_apply(rows, a))(x)
    outside = rs_kernel.gf_matrix_apply(rows, x)  # a hit on that entry
    want = NUMPY.matrix_apply(rows, x)
    assert np.array_equal(inside, want) and np.array_equal(outside, want)
    fused_in = jax.jit(lambda a: pallas_gf.gf_matrix_apply_pallas(
        rows, a, tile=64, interpret=True))(x)
    assert np.array_equal(fused_in, want)
    assert np.array_equal(fused(rows, np.tile(x, (1, 4))),
                          np.tile(want, (1, 4)))


def test_admission_drops_a_queue_that_is_empty_and_idle(rng):
    """A step still has one matrix, so the key still carries it; but 256
    repair matrices must not leave 256 queue objects behind."""
    bc = BatchCodec()
    t = cm.tactic(cm.CodeMode.EC12P4)
    x = rng.integers(0, 256, (2, 12, 64), dtype=np.uint8)
    for bad in range(16):
        for other in range(16):
            if other != bad:
                rows = worker_rows(t, bad, other)
                assert np.array_equal(bc.submit_apply("numpy", rows, x),
                                      NUMPY.matrix_apply(rows, x))
    assert bc._queues == {} and bc._pending == 0 and bc._n_busy == 0
    bc.submit_encode("numpy", x, 4)
    assert bc._queues == {}
    # a queue with a submission parked in it stays until it is drained
    fut = bc.submit_apply_async("numpy", worker_rows(t, 0, 1), x)
    assert len(bc._queues) == 1
    fut.result()
    assert bc._queues == {}


def test_concurrent_submitters_of_many_matrices_all_get_their_answer(rng):
    """Queues come and go under the lock while eight threads submit
    thirty matrices each: every result is its own matrix's."""
    from concurrent.futures import ThreadPoolExecutor

    bc = BatchCodec()
    t = cm.tactic(cm.CodeMode.EC12P4)
    x = rng.integers(0, 256, (1, 12, 48), dtype=np.uint8)
    mats = [worker_rows(t, bad, other) for bad in range(16)
            for other in range(16) if other != bad]

    def work(k):
        for rows in mats[k::8]:
            got = bc.submit_apply("numpy", rows, x)
            assert np.array_equal(got, NUMPY.matrix_apply(rows, x))
        return True

    with ThreadPoolExecutor(8) as ex:
        assert all(ex.map(work, range(8)))
    assert bc._queues == {} and bc._pending == 0


# ------------------------------------------------ decode ready with encode

@pytest.fixture
def compiles():
    """Backend compilations JAX reports (what cellbench counts)."""
    import jax.monitoring as mon

    seen = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    mon.register_event_duration_secs_listener(on)
    yield seen
    mon.unregister_event_duration_listener(on)


@pytest.mark.parametrize("mode", ["EC12P4", "EC6P6", "EC3P3"])
def test_decode_program_is_compiled_with_the_geometrys_encode(
        mode, rng, compiles):
    """Warm the encode shape the way cellbench/generators/common.py does
    (``enc.engine.encode_parity`` of zeros) and nothing else: every
    survivor set's ``reconstruct_data`` then runs without a compile."""
    from cubefs_tpu.codec import batcher

    t = cm.tactic(cm.CodeMode[mode])
    enc = new_encoder(CodecConfig(mode=cm.CodeMode[mode], engine="tpu"))
    s = 1000 + 8 * t.n  # a size no other test uses
    enc.engine.encode_parity(np.zeros((1, t.n, s), dtype=np.uint8), t.m)
    stripe = np.zeros((t.n + t.m, s), dtype=np.uint8)
    stripe[:t.n] = rng.integers(0, 256, (t.n, s), dtype=np.uint8)
    enc.encode(stripe)
    del compiles[:]
    before = programs()
    lost_sets = [c for k in range(1, t.m + 1)
                 for c in itertools.combinations(range(t.n + t.m), k)
                 if any(i < t.n for i in c)]
    for lost in rng.permutation(len(lost_sets))[:40]:
        bad = list(lost_sets[int(lost)])
        broken = stripe.copy()
        broken[bad] = 0
        enc.reconstruct_data(broken, bad)
        assert np.array_equal(broken[:t.n], stripe[:t.n]), bad
    assert built_since(before) == {} and compiles == []
    assert batcher.DEFAULT._queues == {}


def test_a_zero_warm_up_covers_real_arrays_bare_and_phased(
        program, rng, compiles, monkeypatch):
    """Set-up warms with zeros, through whichever of the engine call's
    two forms comes up; the window brings real bytes (views, copies)
    through both. Nothing may compile then — on the chip one
    ``convert_element_type`` of a host array did, before the fused
    program took its input with ``jnp.asarray`` (PERF.md section 6,
    PR 28)."""
    eng = get_engine("tpu")
    monkeypatch.setattr(eng, "_phase_due", 0.0, raising=False)
    # a process plans a shape once, so its decode is readied once a
    # width rung: each program gets a rung of its own here
    s = {"bits": 20_000, "fused": 60_000}[program]
    eng.encode_parity(np.zeros((4, 12, s), dtype=np.uint8), 4)
    del compiles[:]
    for trial in range(4):
        # the bare call first, then one taken apart into its phases
        monkeypatch.setattr(eng, "_phase_due",
                            0.0 if trial % 2 else float("inf"),
                            raising=False)
        wide = rng.integers(0, 256, (4, 16, s), dtype=np.uint8)
        data = wide[:, :12] if trial < 2 else np.ascontiguousarray(
            wide[:, :12])
        assert np.array_equal(eng.encode_parity(data, 4),
                              NUMPY.encode_parity(data, 4))
        rows = rs_kernel.reconstruct_rows(
            12, 16, list(range(11)) + [12 + trial], list(range(12)))
        assert np.array_equal(eng.matrix_apply(rows, data[:1]),
                              NUMPY.matrix_apply(rows, data[:1]))
    assert compiles == []


def test_plan_hands_the_device_engine_the_fused_program_off_the_chip(
        rng, monkeypatch):
    """Where `serves_fused` says so, `plan` is plane-major and its
    program the Pallas one — interpreted off the chip, so the one device
    engine drives it here: encode, apply and the readied decode."""
    from cubefs_tpu.codec import engine

    monkeypatch.setattr(rs_kernel, "serves_fused", lambda coeff, s: True)
    monkeypatch.setattr(pallas_gf, "DEFAULT_TILE", TILE)
    s = 3000  # its step runs at a rung no other test uses
    rung_b, rung_s = rs_kernel.step_shape(6, 2, s)
    shape = (rung_b, 6, rung_s)
    assert shape[2] > s and shape[2] % TILE == 0  # whole tiles: no pad
    coeff = np.ascontiguousarray(gf256.parity_matrix(6, 3))
    planes, program = rs_kernel.plan(coeff, shape)
    assert planes is True
    assert program is pallas_gf._apply_fn(3, 6, shape, TILE, True)
    eng = engine.JaxEngine()
    data = rng.integers(0, 256, (2, 6, s), dtype=np.uint8)
    before = programs()
    assert np.array_equal(eng.encode_parity(data, 3),
                          NUMPY.encode_parity(data, 3))
    # the encode's program was plan's above; its decode came with it
    assert built_since(before) == {"gf256_apply": 1}
    rows = rs_kernel.reconstruct_rows(6, 9, [0, 2, 3, 5, 6, 8],
                                      list(range(6)))
    assert np.array_equal(eng.matrix_apply(rows, data[:1]),
                          NUMPY.matrix_apply(rows, data[:1]))
    assert built_since(before) == {"gf256_apply": 1}


def test_reconstruct_of_parity_rows_keeps_the_decode_shape(rng):
    """``reconstruct`` (data and parity wanted) pads to the same n rows."""
    enc = new_encoder(CodecConfig(mode=cm.CodeMode.EC12P4, engine="tpu"))
    stripe = np.zeros((16, 640), dtype=np.uint8)
    stripe[:12] = rng.integers(0, 256, (12, 640), dtype=np.uint8)
    enc.encode(stripe)
    enc.reconstruct_data(stripe.copy(), [0])  # the shape's program
    before = programs()
    for bad in ([3, 13], [12, 15], [0, 1, 2, 14]):
        broken = stripe.copy()
        broken[bad] = 0
        assert np.array_equal(enc.reconstruct(broken, bad), stripe)
    assert built_since(before) == {}


def test_gate_table_and_refusals_are_keyed_by_program_not_matrix():
    assert all(len(k) == 3 and all(isinstance(v, int) for v in k)
               for k in list(rs_kernel._gate) + list(
                   rs_kernel.pallas_refusals))
    # nothing left that caches per coefficient bytes without a bound
    assert not hasattr(rs_kernel, "_matrix_apply_fn")
    assert not hasattr(rs_kernel, "_encode_fn")
    assert pallas_gf._apply_fn.cache_family == "pallas_gf"
    assert rs_kernel._bits_fn.cache_family == "rs_jit"
