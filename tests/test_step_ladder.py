"""The ladder of step shapes (PR 34): every codec step runs at the
smallest rung (B_rung, S_rung) of ``rs_kernel``'s ladder that holds it,
so a size nobody warmed costs no compile and PUTs of different sizes
share a step — and nothing of a rung's pad is ever stored. CPU, small
sizes (they reach few rungs), seeded."""

import threading

import numpy as np
import pytest

from cellbench import reference
from cellbench.deployment import CompileClock
from cubefs_tpu.blob import access as access_mod
from cubefs_tpu.blob.access import AccessConfig
from cubefs_tpu.codec import batcher as batcher_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec.batcher import BatchCodec, admit
from cubefs_tpu.codec.engine import get_engine
from cubefs_tpu.ops import gf256, pallas_gf, rs_kernel
from cubefs_tpu.utils import hostmem, metrics, rpc
from test_blob_e2e import Cluster
from test_put_stripe_rows import (BLOB, MODES, assert_stored_equals_reference,
                                  scribble, stripe_buffers)

TILE = pallas_gf.DEFAULT_TILE
NUMPY = get_engine("numpy")


# ---------------- the ladder itself ----------------

def test_width_rungs_are_aligned_geometric_and_hold_the_full_blob_shard():
    rungs, s = [], 1
    while s <= 64 << 20:
        w = rs_kernel.rung_width(s)
        assert w >= s and rs_kernel.rung_width(w) == w
        rungs.append(w)
        s = w + 1
    assert rungs == sorted(set(rungs)) and rungs[0] == TILE
    assert [r // TILE for r in rungs[:12]] == [
        1, 2, 3, 4, 5, 6, 7, 9, 11, 14, 18, 22]
    for lo, hi in zip(rungs, rungs[1:]):
        # whole tiles (so 128 lanes): one tile apart up to 7, then a
        # step of at most 2/9 of the upper rung
        assert hi % TILE == 0
        assert hi - lo == TILE or (hi - lo) / hi <= 2 / 9 + 1e-9
    # a row's pad: under one tile up to 4 tiles, under a quarter beyond
    rng = np.random.default_rng(34)
    for s in rng.integers(1, 8 << 20, 2000):
        w = rs_kernel.rung_width(int(s))
        assert w - s < max(TILE, 0.25 * w)
    # the shard of a full 8 MiB blob over 12 (4 MiB over 6) is a rung
    # with under one tile of pad; of 8 MiB over 6 and over 3: 3.2%
    for full in (699_051, 1_398_102, 2_796_203):
        assert 0 <= rs_kernel.rung_width(full) - full < 0.032 * full
    assert rs_kernel.rung_width(699_051) == 22 * TILE


def test_batch_rungs_double_to_the_step_bound_then_grow_by_a_quarter():
    assert [rs_kernel.rung_batch(b) for b in range(1, 10)] == [
        1, 2, 4, 4, 8, 8, 8, 8, 10]
    for b in range(1, 5000):
        r = rs_kernel.rung_batch(b)
        assert r >= b and rs_kernel.rung_batch(r) == r
        if b > rs_kernel.STEP_BATCH:
            assert (r - b) / b < 0.25
    # a 64 MiB PUT's 8 stripes and a repair task's 64 go up as they are
    assert rs_kernel.rung_batch(8) == 8 and rs_kernel.rung_batch(64) == 64


@pytest.mark.parametrize("cols,lo,hi,stripes", [
    (3, 2048, 87_382, 1), (6, 43_691, 699_051, 1), (12, 349_526, 699_051, 2),
    (12, 699_051, 699_051, 8), (24, 100, 5_000_000, 3)])
def test_the_enumeration_holds_every_rung_a_step_can_reach(
        cols, lo, hi, stripes):
    """Finite, sorted, and closed: whatever submissions of lo..hi bytes
    of shard and 1..stripes stripes the batcher joins within its bounds,
    the step's shape is in the list."""
    step_bytes, max_batch = 64 << 20, rs_kernel.STEP_BATCH
    shapes = rs_kernel.ladder(cols, lo, hi, step_bytes, max_batch, stripes)
    assert shapes == sorted(set(shapes), key=lambda bs: (bs[1], bs[0]))
    assert len(shapes) < 100
    rng = np.random.default_rng([cols, lo])
    for _ in range(400):
        s = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        width = rs_kernel.rung_width(s)
        cap = rs_kernel.batch_cap(cols, width, step_bytes, max_batch)
        assert cap * cols * width <= step_bytes or cap == 1
        # one submission alone, or any join the cap allows
        for b in (int(rng.integers(1, stripes + 1)),
                  int(rng.integers(1, cap + 1))):
            assert rs_kernel.step_shape(cols, b, s) in shapes
    assert rs_kernel.batch_cap(12, 22 * TILE, step_bytes, max_batch) == 4


def test_the_configurations_three_codemodes_ask_for_under_a_hundred():
    """access-tpu-1az-randsize: objects up to 16 MiB, 8 MiB blobs."""
    count = 0
    for n, m, lo, hi, stripes in ((3, 3, 1, 256 << 10, 1),
                                  (6, 6, (256 << 10) + 1, 4 << 20, 1),
                                  (12, 4, (4 << 20) + 1, 8 << 20, 2)):
        shapes = rs_kernel.ladder(
            n, max(2048, -(-lo // n)), max(2048, -(-hi // n)), 64 << 20,
            rs_kernel.STEP_BATCH, stripes)
        decodes = {s for _, s in shapes} if n != m else ()
        count += len(shapes) + len(decodes)
    assert count == 75


# ---------------- admission by rung ----------------

class _Held(BatchCodec):
    """The first step parks until released, so what arrives meanwhile
    is one drained step."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.steps: list[tuple] = []
        self.entered, self.release = threading.Event(), threading.Event()

    def _engine_call(self, key, coeff, arr):
        self.steps.append(arr.shape)
        self.entered.set()
        assert self.release.wait(30.0)
        return super()._engine_call(key, coeff, arr)


def test_three_submissions_of_three_widths_share_a_step(rng):
    """One queue a width rung: three encodes of 100, 5,000 and 32,768
    bytes of shard ride one (4, n, 32768) step and each gets its own
    rows back, bit-identical, at its own width."""
    bc = _Held()
    n, m = 6, 3
    first = rng.integers(0, 256, (1, n, 777), dtype=np.uint8)
    inputs = [rng.integers(0, 256, (1, n, w), dtype=np.uint8)
              for w in (100, 5_000, 32_768)]
    payload0 = metrics.codec_step_bytes.value(op="encode", kind="payload")
    pad0 = metrics.codec_step_bytes.value(op="encode", kind="pad")
    outs: dict[int, np.ndarray] = {}
    opener = threading.Thread(target=lambda: outs.__setitem__(
        -1, bc.submit_encode("numpy", first, m)))
    opener.start()
    assert bc.entered.wait(10.0)
    futs = [bc.submit_encode_async("numpy", d, m) for d in inputs]
    bc.release.set()
    opener.join(10.0)
    for d, f in zip(inputs, futs):
        out = f.result(10.0)
        assert out.shape == (1, m, d.shape[2])
        assert np.array_equal(out, NUMPY.encode_parity(d, m))
    assert np.array_equal(outs[-1], NUMPY.encode_parity(first, m))
    assert bc.steps == [(1, n, 32_768), (4, n, 32_768)]
    assert bc._queues == {}
    payload = n * (777 + 100 + 5_000 + 32_768)
    assert metrics.codec_step_bytes.value(
        op="encode", kind="payload") - payload0 == payload
    assert metrics.codec_step_bytes.value(
        op="encode", kind="pad") - pad0 == 5 * n * 32_768 - payload


def test_another_width_rung_is_another_queue_and_the_cap_is_on_the_rung(rng):
    bc = _Held(max_step_bytes=3 * 6 * 32_768)
    n, m = 6, 3
    first = rng.integers(0, 256, (1, n, 10), dtype=np.uint8)
    opener = threading.Thread(
        target=lambda: bc.submit_encode("numpy", first, m))
    opener.start()
    assert bc.entered.wait(10.0)
    # three stripes of 9 bytes are 162 bytes of input, but a step is
    # reckoned at its rung: two stripes of 32 KiB rows fit, not four
    small = [bc.submit_encode_async(
        "numpy", rng.integers(0, 256, (1, n, 9), dtype=np.uint8), m)
        for _ in range(3)]
    wide = rng.integers(0, 256, (1, n, 32_769), dtype=np.uint8)
    bc.release.set()
    assert np.array_equal(bc.submit_encode("numpy", wide, m),
                          NUMPY.encode_parity(wide, m))
    for f in small:
        f.result(10.0)
    opener.join(10.0)
    assert sorted(bc.steps) == [(1, n, 32_768), (1, n, 32_768),
                                (1, n, 65_536), (2, n, 32_768)]


def test_a_rung_shaped_submission_goes_up_as_it_is(rng):
    seen = []

    class Seeing(BatchCodec):
        def _engine_call(self, key, coeff, arr):
            seen.append(arr)
            return super()._engine_call(key, coeff, arr)

    eng = admit("numpy", Seeing())
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:2]
    wide = np.zeros((4, 6, 32_768), dtype=np.uint8)
    wide[:, :, :5_000] = rng.integers(0, 256, (4, 6, 5_000), dtype=np.uint8)
    out = eng.matrix_apply(rows, wide, width=5_000)
    assert seen[0] is wide and out.shape == (4, 2, 5_000)
    assert np.array_equal(out, NUMPY.matrix_apply(rows, wide[:, :, :5_000]))
    # three stripes are not a rung: copied into four
    out = eng.matrix_apply(rows, wide[:3], width=5_000)
    assert seen[1].shape == (4, 6, 32_768) and not np.shares_memory(
        seen[1], wide)
    assert np.array_equal(out, NUMPY.matrix_apply(rows, wide[:3, :, :5_000]))


def test_the_device_engine_pads_what_reaches_it_in_any_other_shape(rng):
    """A caller past the batcher (a tool, a test): same rung program."""
    eng = get_engine("tpu")
    data = rng.integers(0, 256, (2, 3, 6, 1_234), dtype=np.uint8)
    built = metrics.codec_programs.value(kernel="bits")
    assert np.array_equal(eng.encode_parity(data, 3),
                          NUMPY.encode_parity(data, 3))
    first = metrics.codec_programs.value(kernel="bits")
    for shape in ((5, 6, 17), (7, 6, 16_000), (6, 6_000)):
        x = rng.integers(0, 256, shape, dtype=np.uint8)
        assert np.array_equal(eng.encode_parity(x, 3),
                              NUMPY.encode_parity(x, 3))
    # (6, 6, 1234), (5, 6, 17) and (7, 6, 16000) ran at (8, 6, 32768)
    assert metrics.codec_programs.value(kernel="bits") - first <= 2
    assert first - built <= 2


# ---------------- stored shards: what the parent stores ----------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """One cluster for every case below, its codec callers on the
    device engine; the kept arrays serve the tests' sizes."""
    mp = pytest.MonkeyPatch()
    mp.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    c = Cluster(tmp_path_factory.mktemp("ladder"), n_nodes=4,
                disks_per_node=4)
    c.cm.allow_colocated_units = True
    c.access.cfg.engine = "tpu"
    yield c
    mp.undo()


@pytest.mark.parametrize("case", range(64))
@pytest.mark.parametrize("mode", MODES, ids=[m.name for m in MODES])
def test_a_put_of_any_size_stores_the_reference_stripe(cluster, kept,
                                                       monkeypatch, mode,
                                                       case):
    """A seeded random byte count (1 B .. 3 blobs, log-uniform) through
    an array that last held 0xFF everywhere, pad columns too: stored
    shards, parity and CRCs are the reference stripe's — what the code
    before the ladder stored — and every stored shard is exactly S."""
    acc = cluster.access
    r = np.random.default_rng([int(mode), case])
    size = int(np.exp(r.uniform(0.0, np.log(3 * BLOB))))
    data = r.integers(0, 256, size, dtype=np.uint8).tobytes()
    shapes, take = [], acc._take_stripe_rows
    monkeypatch.setattr(acc, "_take_stripe_rows", lambda shape: shapes.append(
        shape) or take(shape))
    acc.put(b"\xff" * size, codemode=mode)
    scribble(kept)
    reused = stripe_buffers()[0]
    loc = acc.put(data, codemode=mode)
    assert stripe_buffers()[0] == reused + 1  # a kept buffer, 0xFF
    enc = acc._encoder(int(mode))
    assert shapes[1][2] == enc.row_width(enc.shard_size(min(size, BLOB)))
    assert loc.crc == reference.crc32(data)
    assert_stored_equals_reference(cluster, loc, data)
    assert acc.get(loc) == data


# ---------------- ready: no program after it ----------------

def test_after_ready_no_size_builds_a_program(tmp_path, rng, monkeypatch):
    """`AccessHandler.ready(largest object)`, then PUTs of sizes never
    seen, a degraded GET of each and a repair worker's matrix apply at
    sizes never seen: `cubefs_codec_programs_total` and JAX's compile
    count stand still."""
    monkeypatch.setattr(batcher_mod.DEFAULT, "dp_enabled", False)
    c = Cluster(tmp_path, n_nodes=4, disks_per_node=4)
    c.cm.allow_colocated_units = True
    c.access.cfg = AccessConfig(
        blob_size=BLOB, engine="tpu",
        policies=[cmode.Policy("EC10P4", 0, 1 << 62)])
    largest = 3 * BLOB
    # a repair group's program is (rows, stripes) of its own: its first
    # step builds it, as before; its size no longer matters in a rung
    codec = admit("tpu")
    rep = gf256.decode_matrix(10, 14, [0, 2, 3, 5, 6, 8, 9, 11, 12, 13])[:2]
    codec.matrix_apply(rep, np.zeros((4, 10, 11), dtype=np.uint8))
    built = lambda: sum(v for _, v in metrics.codec_programs.samples())
    before = built()
    steps = c.access.ready(largest)
    # widths 2048..6554 are one rung; 1, 2 and 4 stripes a PUT alone,
    # up to 8 joined: four encode programs, and the (10, 10) decode at
    # the same four stripe rungs that concurrent degraded GETs meet in,
    # unless an earlier test of this process has built some of them
    assert steps == 4 and built() - before <= 8
    after = built()
    assert c.access.ready(largest) == 4 and built() == after

    clock = CompileClock()
    before = built()
    real = access_mod.AccessHandler._read_shard
    try:
        for size in rng.integers(1, largest, 12):
            data = rng.integers(0, 256, int(size), dtype=np.uint8).tobytes()
            loc = c.access.put(data)
            assert c.access.get(loc) == data
            lost = int(size) % 10

            def failing(self, vol, idx, bid):
                if idx == lost:
                    return idx, None, rpc.ServiceUnavailable(503, "drill")
                return real(self, vol, idx, bid)

            monkeypatch.setattr(access_mod.AccessHandler, "_read_shard",
                                failing)
            assert c.access.get(loc) == data  # decoded from survivors
            monkeypatch.setattr(access_mod.AccessHandler, "_read_shard",
                                real)
            shards = rng.integers(0, 256, (3, 10, 1 + int(size) % 9_000),
                                  dtype=np.uint8)
            assert np.array_equal(codec.matrix_apply(rep, shards),
                                  NUMPY.matrix_apply(rep, shards))
        assert built() == before and clock.mark()["compiles"] == 0
    finally:
        clock.close()
    assert metrics.reconstruct_reads.value(path="global") > 0


def test_a_large_gathered_array_is_kept_and_leaks_nothing(rng, monkeypatch):
    """The copy of a large submission that is not rung-shaped goes into
    the array the step before it left — 0xFF everywhere here — and the
    result is what a fresh array gives."""
    monkeypatch.setattr(batcher_mod, "SPARE_MIN_BYTES", 0)
    seen = []

    class Seeing(BatchCodec):
        def _engine_call(self, key, coeff, arr):
            seen.append(arr)
            return super()._engine_call(key, coeff, arr)

    bc = Seeing()
    for b, width, reused in [(3, 5_000, False), (3, 70, True), (2, 70, False),
                             (1, 32_000, False)]:
        data = rng.integers(0, 256, (b, 6, width), dtype=np.uint8)
        out = bc.submit_encode("numpy", data, 3)
        assert np.array_equal(out, NUMPY.encode_parity(data, 3))
        assert seen[-1].shape == (rs_kernel.rung_batch(b), 6, 32_768)
        assert (len(seen) > 1 and seen[-1] is seen[-2]) == reused
        assert not seen[-1][b:].any() and not seen[-1][:, :, width:].any()
        assert bc._spare is seen[-1]
        bc._spare[:] = 0xFF
