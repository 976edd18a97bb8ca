"""The host memory a process keeps. The heap a repair worker runs on:
what one task frees, the next task reuses without a page fault
(`hostmem.keep_freed_heap`), set once a process, by the worker's first
lease and by nothing else. The large arrays above the heap's reach
(`hostmem.KeptArrays`, one `KEPT` a process): a buffer goes to one
holder at a time, whichever of its three callers — a PUT's data rows, a
device step's result, a repair step's array — holds it; best fit; one
cap; and what a PUT and a repair store through shared buffers is the
plain reference's (cellbench/reference.py), byte for byte."""

import gc
import os
import resource
import sys
import threading
import weakref

import numpy as np
import pytest

from cubefs_tpu.blob import worker as worker_mod
from cubefs_tpu.blob.access import AccessHandler
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec import engine
from cubefs_tpu.utils import hostmem, metrics
from cubefs_tpu.utils import trace as tracelib
from test_put_stripe_rows import (assert_stored_equals_reference, holders,
                                  scribble)
from test_repair_rungs import fill, fleet, lose
from test_repair_step_array import (assert_rebuilt, fill_one_size, keeper,
                                    new_volume)

TASK_BYTES = 64 << 20


def _faults() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _task(read_bytes: int) -> int:
    """Page faults of one task's worth of reads held and freed together,
    as a repair task holds its survivors until its step."""
    before = _faults()
    held = [b"\x07" * read_bytes for _ in range(TASK_BYTES // read_bytes)]
    del held
    return _faults() - before


@pytest.mark.parametrize("read_bytes", [512 << 10, 2 << 20])
def test_a_freed_task_comes_back_without_faults(read_bytes):
    assert hostmem.keep_freed_heap()
    # what a PUT path leaves: a large mapping freed, which would raise a
    # dynamic threshold — and with it the trim threshold — to its size
    big = bytearray(20 << 20)
    del big
    _task(read_bytes)
    pages = TASK_BYTES // resource.getpagesize()
    assert _task(read_bytes) < pages // 4


class Sched:
    """A scheduler that leases `tasks`, one a call, then none."""

    def __init__(self, tasks=1):
        self.tasks = [{"task_id": f"t{i}", "type": "shard_repair"}
                      for i in range(tasks)]
        self.calls: list[str] = []

    def call(self, method, args=None, body=b"", timeout=30.0):
        self.calls.append(method)
        if method == "acquire_task":
            task = self.tasks.pop(0) if self.tasks else None
            return {"task": task, "siblings": []}, b""
        return {}, b""


@pytest.fixture
def mallopt(monkeypatch):
    """A process whose heap policy is not set yet, and a recording
    mallopt that answers `mallopt.answer` (1: taken, as glibc's)."""
    def fake(param, value):
        fake.calls.append((param, value))
        return fake.answer

    fake.answer, fake.calls = 1, []
    monkeypatch.setattr(hostmem, "_kept", None)
    monkeypatch.setattr(hostmem, "_mallopt", lambda: fake)
    return fake


def worker(tasks=1) -> worker_mod.RepairWorker:
    w = worker_mod.RepairWorker(Sched(tasks), None, None, engine="numpy")
    w._execute_traced = lambda tasks, sp: {}
    return w


def test_the_first_lease_keeps_the_freed_heap(mallopt):
    w = worker()
    assert w.run_once() and w.completed == 1
    assert mallopt.calls == [
        (hostmem.M_MMAP_THRESHOLD, hostmem.MALLOC_MMAP_MAX),
        (hostmem.M_TRIM_THRESHOLD, hostmem.HEAP_KEPT_BYTES)]
    assert hostmem._kept is True and w._heap == "kept"


def test_a_second_lease_asks_the_allocator_nothing(mallopt):
    w = worker(tasks=2)
    assert w.run_once() and w.run_once() and w.completed == 2
    assert len(mallopt.calls) == 2  # the first lease's two thresholds
    assert worker().run_once()  # another worker of the same process
    assert len(mallopt.calls) == 2 and hostmem.keep_freed_heap()


def test_a_test_threshold_moves_no_policy(mallopt, monkeypatch):
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    assert worker().run_once()
    assert (hostmem.M_MMAP_THRESHOLD, 32 << 20) in mallopt.calls


@pytest.mark.parametrize("how", ["constructed", "no_task", "ready"])
def test_a_worker_that_leases_nothing_leaves_the_heap_dynamic(mallopt, how):
    w = worker(tasks=0)
    if how == "no_task":
        assert w.run_once() is False
        assert w.sched.calls == ["acquire_task"]
    elif how == "ready":
        assert w.ready(1 << 20, policies=[], blob_size=1 << 20) == 0
    assert mallopt.calls == [] and hostmem._kept is None
    assert w._heap == "dynamic"


@pytest.mark.parametrize("answer,heap", [(1, "kept"), (0, "dynamic")])
def test_each_lease_counts_under_its_heap(mallopt, answer, heap):
    mallopt.answer = answer
    w = worker(tasks=3)
    before = {h: metrics.repair_leases.value(heap=h)
              for h in ("kept", "dynamic")}
    tracelib.reset_collector()
    while w.run_once():
        pass
    got = {h: metrics.repair_leases.value(heap=h) - before[h]
           for h in before}
    assert got[heap] == 3 and sum(got.values()) == 3
    root = [s["tags"] for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert [x["heap"] for x in root] == [heap] * 3


def test_the_trace_door_closes_the_count(mallopt, monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    before = metrics.repair_leases.value(heap="kept")
    assert worker().run_once()
    assert metrics.repair_leases.value(heap="kept") == before
    assert hostmem._kept is True  # the policy is no part of the trace


def test_a_real_lease_runs_on_the_kept_heap(tmp_path):
    """A unit repair through the fleet, on this process's own allocator:
    the lease and the span read `kept` where it is glibc's."""
    c = fleet(tmp_path)
    vid = fill(c, cmode.CodeMode.EC3P3, seed=5, count=3)[0][1].slices[0].vid
    lose(c, vid, 1)
    tracelib.reset_collector()
    assert c.worker.run_once() and c.worker.completed == 1
    heap = "kept" if hostmem.keep_freed_heap() else "dynamic"
    root = [s["tags"] for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert [x["heap"] for x in root] == [heap]


# ---------------- the kept arrays ----------------

def test_a_take_is_the_head_of_a_flat_buffer_fresh_then_reused(kept):
    assert hostmem.KeptArrays().cap == hostmem.KEPT_BYTES == 1536 << 20
    a, came = kept.take((2, 3, 4096))
    assert came == "fresh" and a.shape == (2, 3, 4096)
    assert a.dtype == np.uint8 and a.flags.c_contiguous
    assert a.base.ndim == 1 and a.base.size == a.size
    assert a.ctypes.data == a.base.ctypes.data
    buf = weakref.ref(a.base)  # not a reference: that would hold it
    del a
    b, came = kept.take((3, 4096))  # smaller: the same buffer's head
    assert came == "reused" and b.base is buf()
    assert b.ctypes.data == buf().ctypes.data and b.flags.c_contiguous
    assert [x.size for x in kept._kept] == [2 * 3 * 4096]


@pytest.mark.parametrize("want,hold,got", [
    (100, None, 128), (128, None, 128), (129, None, 256), (100, 128, 256),
    (257, None, 1024), (257, 1024, None)])
def test_best_fit_hands_out_the_smallest_unheld_buffer_that_fits(
        kept, want, hold, got):
    """Buffers of 256, 64, 128 and 1024 bytes, at most one of them held."""
    arrays = {n: kept.take((n,))[0] for n in (256, 64, 128, 1024)}
    at = {n: a.ctypes.data for n, a in arrays.items()}
    held = arrays.pop(hold, None)
    del arrays
    a, came = kept.take((want,))
    if got is None:  # nothing unheld fits
        assert came == "fresh" and a.ctypes.data not in at.values()
    else:
        assert came == "reused" and a.ctypes.data == at[got]
    assert held is None or held.ctypes.data == at[hold]


def test_of_equal_fits_the_one_handed_out_last_goes_again(kept):
    """Unheld buffers of one size: a take gets the one handed out last
    (the pages the host touched last), not the oldest; the list keeps
    the order of hand-outs, which the cap drops from."""
    arrays = [kept.take((4096,))[0] for _ in range(3)]
    at = [a.ctypes.data for a in arrays]
    del arrays  # all three free, the third handed out last
    a, came = kept.take((4096,))
    assert came == "reused" and a.ctypes.data == at[2]
    b, _ = kept.take((4096,))  # the third held: the second
    assert b.ctypes.data == at[1]
    del a
    c, _ = kept.take((4096,))  # the newest free: the third again
    assert c.ctypes.data == at[2]
    assert [x.ctypes.data for x in kept._kept] == at


@pytest.mark.parametrize("hold", ["view", "view_of_a_view", "slice",
                                  "memoryview", "frombuffer"])
def test_a_held_buffer_is_never_handed_out(kept, hold):
    a, _ = kept.take((4, 4096))
    holder = {"view": lambda x: x,
              "view_of_a_view": lambda x: x[1:].reshape(-1)[7:],
              "slice": lambda x: x[2, :100],
              "memoryview": memoryview,
              "frombuffer": lambda x: np.frombuffer(x, dtype=np.uint32),
              }[hold](a)
    at = a.ctypes.data
    del a
    b, came = kept.take((4, 4096))
    assert came == "fresh" and b.ctypes.data != at
    del b, holder
    c, came = kept.take((4, 4096))
    assert came == "reused"


def test_an_array_larger_than_the_cap_is_handed_out_and_not_kept():
    kept = hostmem.KeptArrays(cap=1 << 16)
    small, _ = kept.take((1 << 15,))
    big, came = kept.take((1 << 17,))
    assert came == "fresh" and big.shape == (1 << 17,)
    assert [x.size for x in kept._kept] == [1 << 15]
    del big
    again, came = kept.take((1 << 17,))
    assert came == "fresh"


def test_past_the_cap_the_least_recently_handed_out_leave_first():
    kept = hostmem.KeptArrays(cap=3000)
    first, x, y = (kept.take((1000,))[0] for _ in range(3))
    b = weakref.ref(x.base)  # not a reference: that would hold it
    del x  # its buffer free
    again, came = kept.take((1000,))  # handed out again: the newest
    assert came == "reused" and again.base is b()
    new, came = kept.take((1500,))  # past the cap: the two oldest leave
    assert came == "fresh"
    assert [id(z) for z in kept._kept] == [id(b()), id(new.base)]
    assert sum(z.nbytes for z in kept._kept) <= kept.cap
    first[:] = 1  # the ones held live on with their holders
    y[:] = 2
    assert (first.base == 1).all() and (y.base == 2).all()


def test_threads_taking_at_once_never_share_a_buffer(kept):
    """More takers than cores, each holding its array across its next
    take and writing its own byte into it: a buffer handed to two
    would change under one."""
    takers = (os.cpu_count() or 4) + 2
    faults, start = [], threading.Barrier(takers)

    def taker(k):
        start.wait()
        prev = None
        for i in range(200):
            a, _ = kept.take((k % 3 + 1, 4096))
            a.fill(k)
            if prev is not None and not (prev == k).all():
                faults.append(k)
            prev = a

    threads = [threading.Thread(target=taker, args=(k,))
               for k in range(takers)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads) and not faults
    assert sum(x.nbytes for x in kept._kept) <= kept.cap


def _caller(name: str):
    """take(shape) -> a held array, through one of KEPT's three callers
    as it ships (the threshold cut to 0)."""
    if name == "rows":
        return AccessHandler(None, None)._take_stripe_rows
    if name == "result":
        import jax

        return lambda shape: engine._to_host(
            jax.device_put(np.full(shape, 7, dtype=np.uint8)))
    return worker()._step_array


CALLERS = ["rows", "result", "step_array"]


@pytest.mark.parametrize("caller", CALLERS)
def test_the_kept_bytes_never_exceed_the_cap(kept, monkeypatch, caller):
    """The cap (`KEPT_BYTES` as shipped) cut to three (4, 4, 32768)
    arrays: every array held, each take gets a buffer of its own and the
    list stays under the cap; an array larger than the cap is handed
    out and never kept."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    small = hostmem.KeptArrays(cap=3 * 4 * 4 * 32768)
    monkeypatch.setattr(hostmem, "KEPT", small)
    take = _caller(caller)
    outs = []
    for b in (1, 2, 4, 4, 4, 8, 2):
        outs.append(take((b, 4, 32768)))
        assert sum(x.nbytes for x in small._kept) <= small.cap
    big = take((16, 4, 32768))
    assert big.nbytes > small.cap
    assert all(x is not big.base for x in small._kept)
    assert len({x.ctypes.data for x in outs + [big]}) == len(outs) + 1


@pytest.mark.parametrize("taker", CALLERS)
@pytest.mark.parametrize("holder", CALLERS)
def test_a_buffer_one_caller_holds_goes_to_no_other(kept, monkeypatch,
                                                    holder, taker):
    """What one caller holds a view of — a PUT's rows, a result, a
    step's array — no caller is handed while it lives; once it is gone
    the next take of its size reuses it."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    shape = (2, 4, 8192)
    held = _caller(holder)(shape)
    at = held.ctypes.data
    got = _caller(taker)(shape)
    assert not np.shares_memory(got, held) and got.ctypes.data != at
    assert len(kept._kept) == 2
    del held
    gc.collect()  # a device array aliasing it on the CPU
    again = _caller(taker)(shape)
    assert again.ctypes.data == at


def test_a_put_and_a_repair_over_one_pool_store_the_reference(
        tmp_path, kept, monkeypatch):
    """Large PUTs of one volume interleaved with the repair of others,
    in turn and then from two threads, every array through the one pool
    (the threshold cut as the tiny cells cut it) and every buffer nothing
    holds scribbled with 0xFF between them: each stored and rebuilt shard
    is the plain reference's, and buffers went from one caller to the
    other."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    blob, mode = 64 << 10, cmode.CodeMode.EC6P6
    c = keeper(tmp_path, engine="tpu")
    c.access.cfg.blob_size = blob
    c.access.cfg.engine = "tpu"
    callers: dict[int, set] = {}
    take = kept.take

    def recording(shape):
        arr, came = take(shape)
        callers.setdefault(arr.ctypes.data, set()).add(
            sys._getframe(1).f_code.co_name)
        return arr, came

    monkeypatch.setattr(kept, "take", recording)
    volumes = []
    for seed in range(3):
        new_volume(c)
        objects = fill_one_size(c, mode, seed, 8, 6 * 10_000)
        c.sched.manual_migrate(objects[0][1].slices[0].vid, seed + 1)
        volumes.append((objects, seed + 1))
    new_volume(c)
    rng = np.random.default_rng(11)
    puts = []

    def put():
        data = rng.integers(0, 256, 8 * blob - 99, dtype=np.uint8).tobytes()
        puts.append((data, c.access.put(data, codemode=mode)))
        scribble(kept)

    for _ in range(2):  # in turn
        put()
        assert c.worker.run_once()
        scribble(kept)
    repair = threading.Thread(target=c.drain_worker)  # and at once
    repair.start()
    for _ in range(4):
        put()
    repair.join(timeout=120)
    assert not repair.is_alive()
    assert (c.worker.completed, c.worker.failed) == (3, 0)
    for data, loc in puts:
        assert_stored_equals_reference(c, loc, data)
        assert c.access.get(loc) == data
    for objects, bad in volumes:
        assert_rebuilt(c, objects, mode, [bad])
    shared = [who for who in callers.values()
              if "_step_array" in who and len(who) > 1]
    assert shared


def test_a_drain_lets_go_of_a_submission_once_its_step_has_run(
        kept, monkeypatch):
    """A swap of two submissions, one step each: while the second step
    runs, the drain holds neither the first submission's rows nor its
    future (whose result views a kept buffer), so both buffers are free
    as soon as their callers drop their own views — not when the swap's
    last step ends."""
    from cubefs_tpu.codec.batcher import BatchCodec

    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    bc = BatchCodec(max_batch=1)
    first, _ = kept.take((1, 4, 4096))
    first[:] = 1
    rows_at = first.ctypes.data
    # nobody collects the first: only the drain holds its future
    bc.submit_encode_async("tpu", first, 2)
    del first
    second = bc.submit_encode_async("tpu", np.full((1, 4, 4096), 2,
                                                   dtype=np.uint8), 2)
    seen, call = [], bc._engine_call

    def engine_call(key, coeff, arr):
        if seen:  # the second step
            seen.append((holders(kept, rows_at), holders(kept, seen[0])))
        out, served = call(key, coeff, arr)
        if not seen:
            seen.append(out.ctypes.data)  # the first result's buffer
        return out, served

    bc._engine_call = engine_call
    assert second.result().shape == (1, 2, 4096)
    assert seen[1:] == [(0, 0)]
