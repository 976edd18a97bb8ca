"""The host heap a repair worker runs on: what one task frees, the next
task reuses without a page fault (`hostmem.keep_freed_heap`), and the
worker's ready door sets it."""

import resource

import pytest

from cubefs_tpu.blob import worker as worker_mod
from cubefs_tpu.utils import hostmem

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


def test_ready_keeps_the_freed_heap(monkeypatch):
    calls = []
    monkeypatch.setattr(hostmem, "keep_freed_heap",
                        lambda: calls.append(1) or True)
    w = worker_mod.RepairWorker(None, None, None, engine="numpy")
    assert w.ready(1 << 20, policies=[], blob_size=1 << 20) == 0
    assert calls == [1]
