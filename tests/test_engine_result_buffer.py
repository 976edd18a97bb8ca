"""A device step's result over malloc's mmap threshold lands in a host
buffer the engine keeps (`engine.ResultBuffers`), not in a fresh mapping
whose every page faults at first touch. What such a result holds must be
byte for byte what the table engine computes; a buffer is handed out
again only when nothing holds it; the kept bytes stay under the cap.
Run on the device engine on the CPU, with the threshold lowered so the
tests' sizes engage the path as a 104 MB LRC result does."""

import gc
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
def results(monkeypatch):
    """The engine's buffers, fresh for the test; the threshold at 64 KiB,
    under a (4, 4, 32768) result."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 64 << 10)
    kept = engine.ResultBuffers()
    monkeypatch.setattr(engine, "RESULTS", kept)
    return kept


def counts() -> tuple[float, float]:
    """(reused, fresh) of `cubefs_codec_result_buffers_total` so far."""
    return (metrics.codec_result_buffers.value(result="reused"),
            metrics.codec_result_buffers.value(result="fresh"))


def kept_bytes(kept: engine.ResultBuffers) -> int:
    return sum(buf.nbytes for buf in kept._kept)


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


def test_the_kept_bytes_never_exceed_the_cap(monkeypatch):
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    small = engine.ResultBuffers(cap=3 * (4 * 4 * 32768))
    monkeypatch.setattr(engine, "RESULTS", small)
    tpu = engine.get_engine("tpu")
    outs = []  # every result held: each call takes a buffer of its own
    for b in (1, 2, 4, 4, 4, 8, 2):
        outs.append(tpu.encode_parity(np.zeros((b, 12, S), np.uint8), 4))
        assert kept_bytes(small) <= small.cap
    # a buffer larger than the cap is handed out and never kept
    buf, came = small.take((16, 4, 32768))
    assert came == "fresh" and buf.nbytes > small.cap
    assert all(kept is not buf for kept in small._kept)
    assert kept_bytes(small) <= small.cap
