"""Batched codec admission layer (codec/batcher.py): bit-identity,
coalescing, per-submission error fan-back, backpressure, step-size
bounds, the AdmittedEngine facade, and the CodecService RPC arg validation that guards it.

Every test constructs a PRIVATE BatchCodec so nothing leaks into the
process-wide DEFAULT instance other callers share."""

import threading

import numpy as np
import pytest

from cubefs_tpu.codec import batcher as B
from cubefs_tpu.codec.batcher import (AdmittedEngine, BackpressureError,
                                      BatchCodec, CodecAdmissionError, admit)
from cubefs_tpu.codec.engine import get_engine
from cubefs_tpu.utils import metrics, rpc


class _CountingCodec(BatchCodec):
    """BatchCodec that counts device steps (each _engine_call is ONE
    engine dispatch) without touching the global metrics registry."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.steps = 0

    def _engine_call(self, key, coeff, arr):
        self.steps += 1
        return super()._engine_call(key, coeff, arr)


class _BlockingCodec(_CountingCodec):
    """Device step parks on an event — lets a test hold a drain in
    flight while it probes admission behaviour."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.entered = threading.Event()
        self.release = threading.Event()

    def _engine_call(self, key, coeff, arr):
        self.entered.set()
        assert self.release.wait(30.0)
        return super()._engine_call(key, coeff, arr)


def _stripes(rng, b, n, s):
    return rng.integers(0, 256, (b, n, s), dtype=np.uint8)


# ---------------- bit-identity ----------------

def test_concurrent_submits_bit_identical(rng):
    """32 synthetic PUT/repair submitters race one BatchCodec; every
    result matches the raw single-submission engine output byte for
    byte (GF math has no rounding; coalescing must be invisible)."""
    bc = _CountingCodec()
    eng = get_engine("numpy")
    n, m, s = 6, 3, 128
    inputs = [_stripes(rng, 2, n, s) for _ in range(32)]
    rows = np.ascontiguousarray(
        np.arange(1, n * 2 + 1, dtype=np.uint8).reshape(2, n))
    golden_enc = [eng.encode_parity(d, m) for d in inputs]
    golden_app = [eng.matrix_apply(rows, d) for d in inputs]
    outs: dict[int, np.ndarray] = {}
    start = threading.Barrier(32)

    def submitter(tid):
        start.wait()
        d = inputs[tid]
        if tid % 2 == 0:
            outs[tid] = bc.submit_encode("numpy", d, m)
        else:
            outs[tid] = bc.submit_apply("numpy", rows, d)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tid in range(32):
        want = golden_enc[tid] if tid % 2 == 0 else golden_app[tid]
        assert np.array_equal(outs[tid], want), f"submitter {tid}"


def test_async_pipeline_coalesces_into_one_step(rng):
    """Pipelined async submissions park until the first collector
    drains them — 10 submissions, ONE device step, bit-identical."""
    bc = _CountingCodec(max_batch=1024)  # the shipped bound is 8 stripes
    n, m, s = 4, 2, 64
    inputs = [_stripes(rng, 3, n, s) for _ in range(10)]
    futs = [bc.submit_encode_async("numpy", d, m) for d in inputs]
    assert bc.steps == 0  # nothing drained yet: all parked
    outs = [f.result() for f in futs]
    assert bc.steps == 1  # collector-drains swallowed the whole queue
    eng = get_engine("numpy")
    for d, out in zip(inputs, outs):
        assert np.array_equal(out, eng.encode_parity(d, m))
    # resolved futures are idempotent to collect
    assert np.array_equal(futs[0].result(), outs[0])


def test_mixed_geometry_does_not_coalesce(rng):
    """Different (n, m, s) keys never share a device step."""
    bc = _CountingCodec()
    a = bc.submit_encode_async("numpy", _stripes(rng, 1, 4, 64), 2)
    b = bc.submit_encode_async("numpy", _stripes(rng, 1, 6, 64), 3)
    a.result()
    b.result()
    assert bc.steps == 2


# ---------------- error fan-back (seeded chaos) ----------------

def test_midbatch_bad_submission_fails_alone(rng):
    """A malformed submission inside a drained batch is rejected back
    to exactly its submitter; batch-mates proceed bit-identically —
    the admission layer must never amplify one caller's bug."""
    bc = _CountingCodec()
    n, m, s = 5, 2, 96
    good = [_stripes(rng, 2, n, s) for _ in range(8)]
    futs = [bc.submit_encode_async("numpy", d, m) for d in good[:4]]
    bad = bc.submit_encode_async(
        "numpy", rng.random((2, n, s)).astype(np.float32), m)
    futs += [bc.submit_encode_async("numpy", d, m) for d in good[4:]]
    err0 = metrics.codec_batch_errors.value(op="encode", kind="dtype")
    with pytest.raises(CodecAdmissionError, match="uint8"):
        bad.result()
    assert metrics.codec_batch_errors.value(
        op="encode", kind="dtype") == err0 + 1
    eng = get_engine("numpy")
    for d, f in zip(good, futs):
        assert np.array_equal(f.result(), eng.encode_parity(d, m))
    # the error is sticky: re-collecting re-raises, never half-resolves
    with pytest.raises(CodecAdmissionError):
        bad.result()


def test_engine_failure_fans_back_to_whole_step(rng):
    class _Dying(BatchCodec):
        def _engine_call(self, key, coeff, arr):
            raise RuntimeError("DEVICE_LOST mid step")

    bc = _Dying()
    futs = [bc.submit_encode_async("numpy", _stripes(rng, 1, 4, 32), 2)
            for _ in range(3)]
    for f in futs:
        with pytest.raises(RuntimeError, match="DEVICE_LOST"):
            f.result()


# ---------------- backpressure ----------------

def test_backpressure_bounds_pending_stripes(rng):
    bc = _BlockingCodec(max_pending=4)
    first = bc.submit_encode_async("numpy", _stripes(rng, 4, 4, 32), 2)
    collector = threading.Thread(target=first.result)
    collector.start()
    assert bc.entered.wait(10.0)  # drain in flight, 4 stripes pending
    bp0 = metrics.codec_batch_backpressure.value(op="encode")
    with pytest.raises(BackpressureError):
        bc.submit_encode_async("numpy", _stripes(rng, 2, 4, 32), 2,
                               timeout=0.15)
    assert metrics.codec_batch_backpressure.value(op="encode") == bp0 + 1
    bc.release.set()
    collector.join(timeout=30.0)
    assert not collector.is_alive()
    # once the drain lands, admission reopens
    assert bc.submit_encode("numpy", _stripes(rng, 2, 4, 32), 2).shape \
        == (2, 2, 32)


def test_idle_submitter_never_parks_itself(rng):
    """The backpressure loop must only block when a drain in flight
    will free space — a lone submitter over the bound proceeds (it IS
    the drainer)."""
    bc = _CountingCodec(max_pending=1)
    out = bc.submit_encode("numpy", _stripes(rng, 4, 4, 32), 2)
    assert out.shape == (4, 2, 32)


# ---------------- step-size bounds ----------------

def test_max_batch_splits_steps(rng):
    bc = _CountingCodec(max_batch=4)
    futs = [bc.submit_encode_async("numpy", _stripes(rng, 3, 4, 32), 2)
            for _ in range(3)]
    for f in futs:
        f.result()
    # 9 stripes, cap 4, whole submissions only: 3+3 > 4 -> three steps
    assert bc.steps == 3


def test_max_step_bytes_splits_steps(rng):
    n, s = 4, 64
    bc = _CountingCodec(max_step_bytes=2 * n * s)  # two stripes of input
    futs = [bc.submit_encode_async("numpy", _stripes(rng, 2, n, s), 2)
            for _ in range(4)]
    for f in futs:
        f.result()
    assert bc.steps == 4


# ---------------- AdmittedEngine facade ----------------

def test_admitted_engine_shapes(rng):
    eng = AdmittedEngine(_CountingCodec(), "numpy")
    raw = get_engine("numpy")
    rows = np.ascontiguousarray(
        np.arange(1, 13, dtype=np.uint8).reshape(2, 6))
    d2 = _stripes(rng, 1, 6, 32)[0]
    assert np.array_equal(eng.encode_parity(d2, 3),
                          raw.encode_parity(d2, 3))
    assert np.array_equal(eng.matrix_apply(rows, d2),
                          raw.matrix_apply(rows, d2))
    d3 = _stripes(rng, 4, 6, 32)
    assert np.array_equal(eng.encode_parity(d3, 3),
                          raw.encode_parity(d3, 3))
    d4 = _stripes(rng, 6, 6, 32).reshape(2, 3, 6, 32)
    out = eng.encode_parity(d4, 3)
    assert out.shape == (2, 3, 3, 32)
    assert np.array_equal(out.reshape(6, 3, 32),
                          raw.encode_parity(d4.reshape(6, 6, 32), 3))
    with pytest.raises(ValueError):
        eng.encode_parity(np.zeros(8, dtype=np.uint8), 3)


def test_admit_rejects_unknown_engine():
    with pytest.raises(KeyError):
        admit("no-such-engine")
    assert admit("numpy").batcher is B.DEFAULT
    mine = BatchCodec()
    assert admit("auto", batcher=mine).batcher is mine


def test_submit_shape_validation(rng):
    bc = BatchCodec()
    with pytest.raises(ValueError, match=r"\(B, N, S\)"):
        bc.submit_encode("numpy", np.zeros((4, 32), dtype=np.uint8), 2)
    with pytest.raises(ValueError, match=r"\(B, C, S\)"):
        bc.submit_apply("numpy", np.eye(4, dtype=np.uint8),
                        np.zeros(32, dtype=np.uint8))


# ---------------- occupancy metrics ----------------

def test_step_metrics_account_per_swap(rng):
    sub0 = metrics.codec_batch_submissions.value(op="encode")
    bc = BatchCodec()
    futs = [bc.submit_encode_async("numpy", _stripes(rng, 2, 4, 32), 2)
            for _ in range(5)]
    for f in futs:
        f.result()
    assert metrics.codec_batch_submissions.value(op="encode") \
        == sub0 + 10  # stripes, not calls
    occ = dict(metrics.codec_batch_stripes.samples())[("encode",)]
    assert occ["count"] >= 1 and occ["sum"] >= 10


# ---------------- dp-wise sharding of drained steps ----------------

def test_dp_sharded_step_bit_identical(rng):
    """A drained step wide enough for the mesh splits dp-wise across
    the 8 virtual devices and stays bit-identical (the MULTICHIP_r06
    recipe). `tpu` here is the jax engine on the CPU backend."""
    bc = _CountingCodec()
    bc.dp_min_bytes = 0  # every step qualifies regardless of size
    dp0 = sum(v for _, v in metrics.codec_batch_dp_steps.samples())
    d = _stripes(rng, 8, 6, 256)
    out = bc.submit_encode("tpu", d, 3)
    assert np.array_equal(out, get_engine("numpy").encode_parity(d, 3))
    rows = np.ascontiguousarray(
        np.arange(1, 19, dtype=np.uint8).reshape(3, 6))
    out2 = bc.submit_apply("tpu", rows, d)
    assert np.array_equal(out2, get_engine("numpy").matrix_apply(rows, d))
    assert sum(v for _, v in metrics.codec_batch_dp_steps.samples()) \
        >= dp0 + 2


def test_dp_disabled_by_door(rng, monkeypatch):
    monkeypatch.setenv("CUBEFS_CODEC_DP", "0")
    bc = BatchCodec()
    assert bc.dp_enabled is False
    assert bc._maybe_dp("tpu", None,
                        _stripes(rng, 8, 6, 256), 3) is None


# ---------------- CodecService RPC arg validation ----------------

@pytest.fixture(scope="module")
def svc():
    from cubefs_tpu.codec.service import CodecService

    return CodecService(engine="numpy")


def _code(excinfo):
    return excinfo.value.code


def test_service_rejects_nonpositive_geometry(svc):
    body = bytes(6 * 8)
    for bad in ({"n": 0, "m": 3, "shard_size": 8},
                {"n": 6, "m": -1, "shard_size": 8},
                {"n": 6, "m": 3, "shard_size": 0},
                {"n": 6, "m": 3, "shard_size": 8, "batch": 0},
                {"n": "six", "m": 3, "shard_size": 8},
                {"m": 3, "shard_size": 8}):
        with pytest.raises(rpc.RpcError) as ei:
            svc.rpc_encode(bad, body)
        assert _code(ei) == 400, bad


def test_service_rejects_bad_indices(svc):
    base = {"n": 4, "total": 6, "shard_size": 8}
    ok_present = [0, 1, 2, 3]
    for present, wanted in (([0, 1, 2, 9], [4]),   # out of range
                            ([0, 1, 2, -1], [4]),  # negative
                            ([0, 1, 2, 2], [4]),   # duplicate
                            (ok_present, [6]),     # wanted out of range
                            ([3, 2, 1, 0], [4])):  # unsorted present
        with pytest.raises(rpc.RpcError) as ei:
            svc.rpc_reconstruct(
                dict(base, present=present, wanted=wanted),
                bytes(4 * 8))
        assert _code(ei) == 400, (present, wanted)
    with pytest.raises(rpc.RpcError) as ei:
        svc.rpc_reconstruct(  # too few survivors
            dict(base, present=[0, 1], wanted=[4]), bytes(2 * 8))
    assert _code(ei) == 400
    with pytest.raises(rpc.RpcError) as ei:
        svc.rpc_reconstruct(  # total < n
            dict(base, total=3, present=[0, 1, 2], wanted=[1]),
            bytes(3 * 8))
    assert _code(ei) == 400


def test_service_encode_roundtrip_through_admission(svc, rng):
    """Happy path still lands after validation: the service's shard
    math rides the admitted facade (service.codec is an
    AdmittedEngine), so a valid encode must be bit-identical."""
    assert isinstance(svc.codec, AdmittedEngine)
    d = _stripes(rng, 2, 4, 16)
    hdr, out = svc.rpc_encode(
        {"n": 4, "m": 2, "shard_size": 16, "batch": 2}, d.tobytes())
    assert hdr["shape"] == [2, 2, 16]
    want = get_engine("numpy").encode_parity(d, 2)
    assert out == np.ascontiguousarray(want).tobytes()


# ---------------- async encode admission (PendingEncode) ----------------

def test_encoder_encode_async_matches_sync(rng):
    """encode_async().wait() lands the same parity rows in place that a
    blocking encode() would, through a private batcher."""
    from cubefs_tpu.codec.codemode import CodeMode
    from cubefs_tpu.codec.encoder import CodecConfig, new_encoder

    bc = _CountingCodec(max_wait_ms=1.0)
    enc = new_encoder(CodecConfig(mode=CodeMode.EC6P3, engine="numpy"))
    enc.engine = AdmittedEngine(bc, "numpy")
    stripes = np.zeros((2, enc.t.total, 64), dtype=np.uint8)
    stripes[:, : enc.t.n, :] = _stripes(rng, 2, enc.t.n, 64)
    ref = enc.encode(stripes.copy())

    pending = enc.encode_async(stripes)
    out = pending.wait()
    assert out is stripes  # parity landed into the caller's array
    assert np.array_equal(out, ref)
    assert pending.resolved
    assert bc.steps >= 1


def test_lrc_encode_async_matches_sync(rng):
    """LRC: the global and the per-AZ local parity ride the batcher
    as one step of the composed rows (PR 42)."""
    from cubefs_tpu.codec.codemode import CodeMode
    from cubefs_tpu.codec.encoder import CodecConfig, new_encoder

    bc = _CountingCodec(max_wait_ms=1.0)
    enc = new_encoder(CodecConfig(mode=CodeMode.EC4P4L2, engine="numpy"))
    enc.engine = AdmittedEngine(bc, "numpy")
    stripes = np.zeros((2, enc.t.total, 32), dtype=np.uint8)
    stripes[:, : enc.t.n, :] = _stripes(rng, 2, enc.t.n, 32)
    ref = enc.encode(stripes.copy())

    out = enc.encode_async(stripes).wait()
    assert np.array_equal(out, ref)
    assert enc.verify(out)


@pytest.mark.parametrize("admitted", [True, False], ids=["admitted", "raw"])
@pytest.mark.parametrize("mode", ["EC6P3", "EC4P4L2", "EC4P4MSR"])
def test_encode_rows_async_returns_the_parity_rows(rng, mode, admitted):
    """encode_rows_async(data rows, shard size).wait() is the parity
    rows a blocking encode() of the whole stripe lands, RS, LRC and MSR;
    rows built at the encoder's row_width (the step's width rung, zeros
    past the shard) are read by the step as they are (no copy on the way
    in) and left as they were. An encoder over a raw engine (no
    admission surface) has encoded inline by the time the handle is
    back."""
    from cubefs_tpu.codec.codemode import CodeMode
    from cubefs_tpu.codec.encoder import CodecConfig, new_encoder
    from cubefs_tpu.codec.engine import NumpyEngine

    seen = []

    class Seeing(_CountingCodec):
        def _engine_call(self, key, coeff, arr):
            seen.append(arr)
            return super()._engine_call(key, coeff, arr)

    class SeeingRaw(NumpyEngine):
        def matrix_apply(self, coeff, shards):
            seen.append(shards)
            return super().matrix_apply(coeff, shards)

    enc = new_encoder(CodecConfig(mode=CodeMode[mode], engine="numpy"))
    enc.engine = (AdmittedEngine(Seeing(max_wait_ms=1.0), "numpy")
                  if admitted else SeeingRaw())
    t = enc.t
    # MSR rows are cut into alpha sub-shards, each a rung wide here
    size = (32768 if mode == "EC4P4MSR" else 12) * getattr(enc, "alpha", 1)
    data = np.zeros((4, t.n, enc.row_width(size)), dtype=np.uint8)
    data[:, :, :size] = _stripes(rng, 4, t.n, size)
    stripes = np.zeros((4, t.total, size), dtype=np.uint8)
    stripes[:, : t.n, :] = data[:, :, :size]
    ref = enc.encode(stripes)
    seen.clear()

    before = data.copy()
    pending = enc.encode_rows_async(data, size)
    assert pending.resolved is (not admitted)
    parity = pending.wait()
    assert parity.shape == (4, t.total - t.n, size)
    assert np.array_equal(parity, ref[:, t.n:, :])
    assert np.array_equal(data, before)
    assert np.shares_memory(seen[0], data)
    assert pending.wait() is parity  # collected once, kept
    with pytest.raises(Exception, match="wide"):  # narrower than a shard
        enc.encode_rows_async(data[:, :, :size - 1], size)
