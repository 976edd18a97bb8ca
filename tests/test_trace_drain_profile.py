"""PR 39's two profiler annotations, beside test_trace_obs.py's: while
a profiler session runs, a drain is `cubefs:codec.drain` and a gathered
step's copy `cubefs:codec.gather` in the profile's host plane — on the
device trace's clock, nested inside the drainer's own stage — and
neither is entered with the door closed."""

import numpy as np
import pytest

from cubefs_tpu.codec import batcher
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import trace as tracelib
from test_trace_obs import _host_events, _tpu_cluster

MODE = cmode.CodeMode.EC6P3
NEW = {"cubefs:codec.drain", "cubefs:codec.gather"}


def _profiled_put(tmp_path, rng):
    """One PUT under a profiler session, with another caller's stripe
    parked in its codec queue before it, so the PUT's client thread
    drains a step that is a copy of two submissions."""
    import jax.profiler

    c = _tpu_cluster(tmp_path)
    data = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    c.access.put(data, codemode=MODE)  # compiles the step of one
    enc = c.access._encoder(int(MODE))
    shard = enc.shard_size(len(data))

    def park():
        return enc.encode_rows_async(np.zeros(
            (1, enc.t.n, enc.row_width(shard)), dtype=np.uint8), shard)

    first = park()
    c.access.put(data, codemode=MODE)  # ... and the step of two
    first.wait()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        parked = park()
        c.access.put(data, codemode=MODE)
        assert parked._fut.done  # the PUT's drain resolved it
        parked.wait()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path / "prof"), tracelib.PROFILE_PREFIX)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_profile_shows_the_drain_and_the_gather_inside_the_wait(
        tmp_path, rng, monkeypatch, name):
    monkeypatch.delenv("CUBEFS_TRACE", raising=False)
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    by_thread = _profiled_put(tmp_path, rng)
    mine = next(evs for evs in by_thread.values()
                if any(n == "cubefs:access.put" for n, _, _ in evs))
    span = {n: (lo, hi) for n, lo, hi in mine}
    assert NEW <= set(span), sorted(span)
    wait = span["cubefs:blob.put/encode_admission"]
    drain, step = span["cubefs:codec.drain"], span["cubefs:blob.put/codec_step"]
    lo, hi = span[name]
    # the drain lies inside the PUT's wait for its step; the copy inside
    # the drain and before the engine call
    assert wait[0] <= drain[0] <= lo and hi <= drain[1] <= wait[1]
    if name == "cubefs:codec.gather":
        assert hi <= step[0] and step[1] <= drain[1]


def test_with_the_door_closed_neither_annotation_is_entered(
        tmp_path, rng, monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    by_thread = _profiled_put(tmp_path, rng)
    names = {n for evs in by_thread.values() for n, _, _ in evs}
    # the engine's phases are the engine's (PR 26); the batcher's two
    # and every span and stage stay out
    assert not names & NEW and "cubefs:access.put" not in names, sorted(names)
