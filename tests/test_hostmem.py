"""The host heap a repair worker runs on: what one task frees, the next
task reuses without a page fault (`hostmem.keep_freed_heap`), set once a
process, by the worker's first lease and by nothing else."""

import resource

import pytest

from cubefs_tpu.blob import worker as worker_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import hostmem, metrics
from cubefs_tpu.utils import trace as tracelib
from test_repair_rungs import fill, fleet, lose

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
