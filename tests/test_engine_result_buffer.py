"""A device step's result over malloc's mmap threshold lands in a host
buffer the process keeps (`hostmem.KEPT`), not in a fresh mapping whose
every page faults at first touch. What such a result holds must be byte
for byte what the table engine computes, and a buffer is handed out
again only when nothing holds it (the cap: `test_hostmem.py`). Run on
the device engine on the CPU, with the threshold lowered so the tests'
sizes engage the path as a 104 MB LRC result does."""

import gc
import math
import os
import sys
import threading

import numpy as np
import pytest

from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec import encoder, engine
from cubefs_tpu.ops import rs_kernel
from cubefs_tpu.utils import hostmem, metrics

S = 4096  # a shard; the step runs at the one-tile rung


@pytest.fixture
def results(kept, monkeypatch):
    """The process's kept arrays, empty for the test; the threshold at
    64 KiB, under a (4, 4, 32768) result."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 64 << 10)
    return kept


def counts() -> tuple[float, float]:
    """(reused, fresh) of `cubefs_codec_result_buffers_total` so far."""
    return (metrics.codec_result_buffers.value(result="reused"),
            metrics.codec_result_buffers.value(result="fresh"))


def _encode(rng, b):
    """An RS(12+4) encode: (b, 12, S) data -> (b, 4, S) parity."""
    data = rng.integers(0, 256, (b, 12, S), dtype=np.uint8)
    return "encode_parity", (data, 4)


def _lrc(rng, b):
    """EC16P20L2's composed rows: (b, 16, S) data -> (b, 22, S)."""
    rows = encoder._lrc_rows(cmode.tactic(cmode.CodeMode.EC16P20L2))
    return "matrix_apply", (rows, rng.integers(0, 256, (b, 16, S),
                                               dtype=np.uint8))


def _decode(rng, b):
    """Two lost units of RS(12+4) from 12 survivors: (b, 12, S) -> 2 rows."""
    rows = rs_kernel.reconstruct_rows(12, 16, list(range(2, 14)), [0, 1])
    return "matrix_apply", (rows, rng.integers(0, 256, (b, 12, S),
                                               dtype=np.uint8))


@pytest.mark.parametrize("side", ["under", "over"])
@pytest.mark.parametrize("step", [_encode, _lrc, _decode],
                         ids=["encode", "lrc_rows", "two_row_decode"])
def test_a_result_is_the_table_engines_over_and_under_the_threshold(
        results, monkeypatch, step, side):
    rng = np.random.default_rng([len(side), len(step.__name__)])
    method, args = step(rng, 4)
    tpu = engine.get_engine("tpu")
    nbytes = getattr(tpu, method)(*args).shape[-2] * 4 * 32768  # the rung
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX",
                        nbytes - (side == "over"))
    getattr(tpu, method)(*args)  # a buffer of the shape, if it takes one
    before = counts()
    got = getattr(tpu, method)(*args)
    want = getattr(engine.get_engine("numpy"), method)(*args)
    assert np.array_equal(got, want)
    assert any(got.base is buf for buf in results._kept) == (side == "over")
    # a call under the threshold counts nothing; over it, one a call
    assert sum(counts()) - sum(before) == (side == "over")


def test_a_held_result_is_never_written_by_the_next_call(results):
    tpu = engine.get_engine("tpu")
    rng = np.random.default_rng(7)
    first = tpu.encode_parity(rng.integers(0, 256, (4, 12, S),
                                           dtype=np.uint8), 4)
    kept = first.copy()
    reused, fresh = counts()
    for _ in range(3):
        tpu.encode_parity(rng.integers(0, 256, (4, 12, S),
                                       dtype=np.uint8), 4)
        assert np.array_equal(first, kept)
    # the first call's buffer was held each time: the next one is new,
    # then free again after its call
    assert counts() == (reused + 2, fresh + 1)


def test_once_the_views_are_dropped_the_next_call_reuses_the_buffer(
        results):
    tpu = engine.get_engine("tpu")
    data = np.zeros((4, 12, S), dtype=np.uint8)
    out = tpu.encode_parity(data, 4)
    buf = id(out.base)  # not a reference: that would hold it
    del out
    gc.collect()
    reused, fresh = counts()
    again = tpu.encode_parity(data, 4)
    assert id(again.base) == buf
    assert counts() == (reused + 1, fresh)


def test_threads_calling_at_once_never_share_a_buffer(results):
    """Each caller holds its last result across its next call and the
    others' calls; a buffer handed to two would change under one. More
    callers than cores, the interpreter switching threads every few
    microseconds."""
    tpu = engine.get_engine("tpu")
    ref = engine.get_engine("numpy")
    tpu.encode_parity(np.zeros((4, 12, S), dtype=np.uint8), 4)
    callers = (os.cpu_count() or 4) + 2
    faults = []
    start = threading.Barrier(callers)

    def caller(k):
        rng = np.random.default_rng(k)
        prev = None
        start.wait()
        for _ in range(8):
            data = rng.integers(0, 256, (4, 12, S), dtype=np.uint8)
            out = tpu.encode_parity(data, 4)
            want = ref.encode_parity(data, 4)
            if prev is not None and not np.array_equal(*prev):
                faults.append(k)
            if not np.array_equal(out, want):
                faults.append(k)
            prev = (out, want)

    threads = [threading.Thread(target=caller, args=(k,))
               for k in range(callers)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not faults
    reused, _ = counts()
    assert reused > 0  # the buffers went round


# ---------------- the way a large result comes back ----------------

# EC16P20L2's PUT step and a repair's two-row step, at a rung of 18
# blocks of 1024 columns under the fixture's threshold
LRC, REPAIR = (8, 22, 9 * 2048), (64, 2, 9 * 2048)


def _on_device(shape, order, seed):
    """(a device array of `shape` held in the layout `order`, major to
    minor — the TPU keeps a (B, R, S) result rows-major, (1, 0, 2) —,
    the host bytes it holds)."""
    import jax
    from jax.experimental.layout import Format, Layout

    host = np.random.default_rng(seed).integers(0, 256, shape,
                                                dtype=np.uint8)
    where = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return jax.device_put(host, Format(Layout(major_to_minor=order),
                                       where)), host


@pytest.mark.parametrize("cut", [None, 1.0, 8 / 9, 0.51, 0.5, 0.0],
                         ids=["no_width", "rung", "eight_ninths",
                              "inside_a_block", "half", "one_column"])
@pytest.mark.parametrize("order", [(1, 0, 2), (0, 1, 2)],
                         ids=["rows_major", "stripes_major"])
@pytest.mark.parametrize("shape", [LRC, REPAIR], ids=["lrc", "repair"])
def test_a_large_result_is_asarray_of_it_up_to_its_width_in_a_kept_buffer(
        results, shape, order, cut):
    """Up to the block that holds its last payload column a result over
    the threshold comes back bit-identical to np.asarray of the whole
    result; the blocks past it never cross, so the kept buffer, handed
    out again, still holds the last call's bytes there."""
    width = None if cut is None else max(1, round(cut * shape[-1]))
    first, first_host = _on_device(shape, order, 1)
    out = engine._to_host(first)
    assert np.array_equal(out, np.asarray(first))
    buf = id(out.base)
    del out
    gc.collect()
    reused, fresh = counts()
    y, host = _on_device(shape, order, 2)
    got = engine._to_host(y, width)
    assert id(got.base) == buf and counts() == (reused + 1, fresh)
    _, bounds = engine._splitter(shape, order)
    end = shape[-1] if width is None else next(
        z for _, z in bounds if z >= width)
    assert np.array_equal(got[..., :end], np.asarray(y)[..., :end])
    assert np.array_equal(got[..., end:], first_host[..., end:])
    assert width is None or end - width < bounds[0][1]


@pytest.mark.parametrize("shape,width,came", [
    ((8, 22, 589824), 524288, 8), ((64, 2, 589824), 524288, 8),
    ((64, 2, 720896), 699051, 11), ((8, 4, 1441792), 1398102, 11)],
    ids=["ingest_lrc", "lrc_disk_repair", "disk_repair", "rs_encode"])
def test_a_real_steps_blocks_leave_its_pad_behind_under_half_the_threshold(
        shape, width, came):
    """At the real threshold a cell's step is cut into blocks of equal
    width, none over half the threshold, at least eight; the blocks that
    carry its payload are `came` of them."""
    _, bounds = engine._splitter(shape, (1, 0, 2))
    cols = {z - a for a, z in bounds}
    assert len(cols) == 1 and len(bounds) >= 8
    assert math.prod(shape[:-1]) * cols.pop() <= hostmem.MALLOC_MMAP_MAX // 2
    assert sum(a < width for a, _ in bounds) == came
