"""A PUT forks after its bid allocation (PR 35): the data-shard writes
and the location's CRC go to the handler's pool before the wait for the
codec step and run under it; only the parity writes follow the step.
What is stored, what is acknowledged and when the rows array is reused
are the sequential order's. Every wait here has a timeout of its own: a
PUT runs in a thread that is joined with one, and a gate that nobody
opened fails the test instead of hanging it."""

import threading
import time
import zlib
from concurrent.futures import wait
from types import SimpleNamespace

import numpy as np
import pytest

from cellbench import reference, run, spec
from cubefs_tpu.blob import access as access_mod
from cubefs_tpu.blob.access import PutQuorumError
from cubefs_tpu.codec import batcher
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import metrics, rpc
# `cluster`: 16 units, and the kept arrays serve the tests' sizes
from test_put_stripe_rows import (BLOB, assert_stored_equals_reference,
                                  cluster,  # noqa: F401 (a fixture)
                                  holders, rows_taken)

WAIT_S = 30.0
RS = [cmode.CodeMode.EC3P3, cmode.CodeMode.EC6P6, cmode.CodeMode.EC12P4]


class Gate:
    """The process's admitted engine with its step held on an Event:
    the real batcher, the real drain by the collecting client thread,
    and an engine call that starts only when the test says so (and
    raises `fail` then, if given one)."""

    def __init__(self, monkeypatch, fail: BaseException | None = None):
        self.open, self.reached = threading.Event(), threading.Event()
        call = batcher.DEFAULT._engine_call

        def held(key, coeff, arr):
            self.reached.set()
            if not self.open.wait(WAIT_S):
                raise TimeoutError("the test never opened the gate")
            if fail is not None:
                raise fail
            return call(key, coeff, arr)

        monkeypatch.setattr(batcher.DEFAULT, "_engine_call", held)


class Submits:
    """Every task the handler hands its pool, as (fn, args, future)."""

    def __init__(self, acc, monkeypatch):
        self.tasks, submit = [], acc._submit
        self.write = acc._write_shard

        def recording(fn, *args):
            fut = submit(fn, *args)
            self.tasks.append((fn, args, fut))
            return fut

        monkeypatch.setattr(acc, "_submit", recording)

    def writes(self, keep=lambda unit: True):
        """Futures of the shard writes whose unit `keep` accepts."""
        return [fut for fn, args, fut in list(self.tasks)
                if fn == self.write and keep(args[1])]

    def others(self):
        return [fut for fn, _, fut in list(self.tasks) if fn != self.write]


def put_in_thread(acc, data, mode, at_end=lambda: None):
    """`at_end()` is what the client sees the moment its PUT ends."""
    out = {}

    def client():
        try:
            out["loc"] = acc.put(data, codemode=mode)
        except BaseException as e:  # handed to the test's thread
            out["exc"] = e
        out["at_end"] = at_end()

    th = threading.Thread(target=client)
    th.start()
    return th, out


def join(th):
    th.join(WAIT_S)
    assert not th.is_alive()


def wait_for(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


@pytest.mark.parametrize("blobs", [1, 2], ids=["one_blob", "two_blobs"])
def test_data_writes_and_crc_end_under_the_held_step(
        cluster, rng, monkeypatch, blobs):
    acc, mode = cluster.access, cmode.CodeMode.EC12P4
    t = cmode.tactic(mode)
    gate, subs = Gate(monkeypatch), Submits(acc, monkeypatch)
    crc_threads = []

    def crc32(data):
        crc_threads.append(threading.get_ident())
        return zlib.crc32(data)

    monkeypatch.setattr(access_mod, "zlib", SimpleNamespace(crc32=crc32))
    data = rng.integers(0, 256, (blobs - 1) * BLOB + 999, dtype=np.uint8
                        ).tobytes()
    th, out = put_in_thread(acc, data, mode)
    try:
        assert gate.reached.wait(WAIT_S)  # the client drains: step held
        early = subs.writes() + subs.others()
        assert len(subs.writes(lambda u: u.index < t.n)) == t.n * blobs
        assert len(subs.others()) == 1  # the CRC task
        assert wait(early, WAIT_S).not_done == set()
        # every data shard is on its blobnode, no parity shard is, and
        # the CRC ran off the client's thread — all before the step ran
        assert subs.writes(lambda u: u.index >= t.n) == []
        for fut in subs.writes():
            # a pool future is (its wait for a thread, the task's result)
            assert fut.result()[1][2] is None
        assert subs.others()[0].result()[1] == reference.crc32(data)
        assert crc_threads and th.ident not in crc_threads
        assert not out  # the PUT has not ended
    finally:
        gate.open.set()
    join(th)
    assert len(subs.writes(lambda u: u.index >= t.n)) == t.m * blobs
    loc = out["loc"]
    assert loc.crc == reference.crc32(data)
    assert_stored_equals_reference(cluster, loc, data)
    assert acc.get(loc) == data


SIZES = {"one_blob": BLOB - 321, "two_blobs": BLOB + 12_345}


@pytest.mark.parametrize("size", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("mode", RS, ids=[m.name for m in RS])
def test_forked_put_stores_the_sequential_reference(cluster, rng, mode, size):
    """Stored shards, stored CRCs and the location's CRC are what the
    sequential order stored: the plain reference's stripe of each blob,
    the blobnode's CRC of each shard, zlib's of the payload."""
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    loc = cluster.access.put(data, codemode=mode)
    assert loc.crc == reference.crc32(data) == zlib.crc32(data)
    assert loc.size == size and loc.slices[0].count == -(-size // BLOB)
    assert_stored_equals_reference(cluster, loc, data)
    sl = loc.slices[0]
    for u in cluster.cm.get_volume(sl.vid).units:
        for bid in range(sl.min_bid, sl.min_bid + sl.count):
            shard, crc = cluster.node_of(u.node_addr).get_shard(
                u.disk_id, u.chunk_id, bid)
            assert crc == reference.crc32(shard)
    assert cluster.access.get(loc) == data


def test_failed_encode_raises_after_the_started_writes_and_keeps_no_rows(
        cluster, kept, rng, monkeypatch):
    acc, mode = cluster.access, cmode.CodeMode.EC6P6
    t = cmode.tactic(mode)
    gate = Gate(monkeypatch, fail=RuntimeError("step fell over"))
    subs = Submits(acc, monkeypatch)
    running, guard = [0], threading.Lock()
    write = acc._write_shard

    def slow_write(*a):
        with guard:
            running[0] += 1
        try:
            gate.open.wait(WAIT_S)  # still running when the step fails
            time.sleep(0.05)
            return write(*a)
        finally:
            with guard:
                running[0] -= 1

    subs.write = slow_write
    monkeypatch.setattr(acc, "_write_shard", slow_write)
    taken = rows_taken(acc, monkeypatch)
    data = rng.integers(0, 256, BLOB + 5, dtype=np.uint8).tobytes()
    th, out = put_in_thread(acc, data, mode, at_end=lambda: running[0])
    try:
        assert gate.reached.wait(WAIT_S)
        wait_for(lambda: running[0] > 0, "a data write to start")
    finally:
        gate.open.set()
    join(th)
    assert isinstance(out.get("exc"), RuntimeError), out
    assert "step fell over" in str(out["exc"]) and "loc" not in out
    # the PUT raised only after every write it had started ended, it
    # started no parity write, and its array goes to no other PUT while
    # what saw the failure (its error's frames) may still hold it
    started = subs.writes()
    assert len(started) == 2 * t.n
    assert all(f.done() for f in started) and out["at_end"] == 0
    assert subs.writes(lambda u: u.index >= t.n) == []
    assert holders(kept, taken[0][0]) > 0
    with pytest.raises(RuntimeError, match="step fell over"):
        acc.put(data, codemode=mode)  # the gate is open: fails at once
    assert taken[1] != taken[0]


def test_quorum_counts_data_and_parity_writes_together(
        cluster, kept, rng, monkeypatch):
    """A refused data write and a refused parity write, on distinct
    units: the quorum is counted over both groups, both shards of every
    bid are queued for repair, and one failure too many fails the PUT —
    after every write has ended, so the next PUT is handed its array."""
    acc, mode = cluster.access, cmode.CodeMode.EC6P6
    t = cmode.tactic(mode)
    assert (t.n, t.total, t.put_quorum) == (6, 12, 11)
    refused, write = {2, 9}, acc._write_shard  # a data and a parity unit

    def write_or_refuse(vol, unit, bid, shard):
        if unit.index in refused:
            return bid, unit.index, rpc.ServiceUnavailable(503, "refused")
        return write(vol, unit, bid, shard)

    monkeypatch.setattr(acc, "_write_shard", write_or_refuse)
    taken = rows_taken(acc, monkeypatch)
    data = rng.integers(0, 256, BLOB + 77, dtype=np.uint8).tobytes()
    # the codemode's own quorum, 11 of 12: one of each group is 10
    with pytest.raises(PutQuorumError, match="10/12"):
        acc.put(data, codemode=mode)
    assert cluster.repair_q.poll() == []
    assert holders(kept, taken[0][0]) == 0
    # either of the two alone is inside it: neither group is exempt
    for alone in (2, 9):
        refused.clear()
        refused.add(alone)
        acc.put(data, codemode=mode)
    acked = [off for off, _ in cluster.repair_q.poll()]
    assert len(acked) == 4  # two bids of each of the two PUTs
    cluster.repair_q.ack(max(acked))
    # with room for two failures the PUT is acknowledged at 10 of 12
    # and each refused shard of each bid goes to the repair queue
    refused.update({2, 9})
    acc.cfg.put_quorum_override = 10
    loc = acc.put(data, codemode=mode)
    sl = loc.slices[0]
    queued = sorted((m["bid"], m["bad_index"])
                    for _, m in cluster.repair_q.poll()
                    if m["type"] == "shard_repair" and m["vid"] == sl.vid)
    assert queued == [(sl.min_bid + k, idx)
                      for k in range(2) for idx in (2, 9)]
    assert acc.get(loc) == data  # a degraded read: shard 2 is missing


@pytest.mark.parametrize("blobs", [1, 2], ids=["one_blob", "two_blobs"])
def test_counter_reads_writes_that_ended_under_the_encode(
        cluster, rng, monkeypatch, blobs):
    acc, mode = cluster.access, cmode.CodeMode.EC12P4
    read = lambda w: metrics.access_shard_writes.value(when=w)
    data = rng.integers(0, 256, blobs * BLOB, dtype=np.uint8).tobytes()
    gate, subs = Gate(monkeypatch), Submits(acc, monkeypatch)
    under0, after0 = read("under_encode"), read("after_encode")
    th, out = put_in_thread(acc, data, mode)
    try:
        assert gate.reached.wait(WAIT_S)
        assert wait(subs.writes(), WAIT_S).not_done == set()
    finally:
        gate.open.set()
    join(th)
    assert "loc" in out, out
    assert (read("under_encode") - under0, read("after_encode") - after0) \
        == (12 * blobs, 4 * blobs)
    # the door closed: the same PUT counts nothing
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    assert acc.get(acc.put(data, codemode=mode)) == data
    assert (read("under_encode") - under0, read("after_encode") - after0) \
        == (12 * blobs, 4 * blobs)


# ------------------------- the benchmark's data that reads the counter

COUNTER = "cubefs_access_shard_writes_total"
ENTRIES = {"access.early_write_share": ("put_rate", "ingest-large"),
           "access.early_write_share-cont": ("put_rate", "mix-continuous"),
           "access.early_write_share-small": ("put_p99_ms", "put-small")}


def _cell(series):
    cell = run.Cell({}, {}, 1, 4.0, True)
    cell.registry = series
    return cell


def test_early_write_share_reads_the_counter_and_nothing_on_the_parent():
    sp = spec.metric_spec("per_layer", "access.early_write_share")
    assert sp["reader"] == "counter_share"
    read = lambda series: spec.reader(sp["reader"]).read(
        _cell(series), **sp["params"])
    series = {(COUNTER, frozenset({("when", "under_encode")})): 96.0,
              (COUNTER, frozenset({("when", "after_encode")})): 32.0,
              ("cubefs_access_stripe_buffers_total",
               frozenset({("result", "reused")})): 5.0}
    assert read(series) == pytest.approx(75.0)
    # the parent has no such counter: nothing, not 0
    del series[(COUNTER, frozenset({("when", "under_encode")}))]
    del series[(COUNTER, frozenset({("when", "after_encode")}))]
    assert read(series) is None
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_entries_resolve_to_the_one_layer_file(name):
    moves, cell = ENTRIES[name]
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "front door",
                     "moves": moves, "workloads": [cell]}
    assert entry in spec.metric_entries(bench, cell, "per_layer")
    # a tagged name reads the base's file
    assert spec.metric_spec("per_layer", name) == spec.metric_spec(
        "per_layer", "access.early_write_share")
    assert metrics.access_shard_writes.name == COUNTER
