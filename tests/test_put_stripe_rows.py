"""A PUT's data rows (PR 29): one (blobs, n, S) array filled once from
the payload, taken by the codec step as it is and, over malloc's mmap
threshold, a view of a buffer the process keeps (`hostmem.KEPT`). What
reaches each blobnode must be byte for byte the reference stripe's
shard — also from a buffer that last held 0xFF everywhere — and a
buffer is handed to another PUT only when no step and no shard write of
its PUT can still read it."""

import gc
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from cellbench import reference
from cubefs_tpu.blob.access import PutQuorumError
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.ops import msr
from cubefs_tpu.utils import hostmem, metrics
from test_blob_e2e import Cluster

BLOB = 64 << 10  # the test cluster's blob size


@pytest.fixture
def cluster(tmp_path, monkeypatch):
    # every size is "above the allocator's own reuse": the kept arrays
    # serve the tests' sizes as they serve 64 MiB objects
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    c = Cluster(tmp_path, n_nodes=4, disks_per_node=4)  # 16 units: EC12P4
    c.cm.allow_colocated_units = True
    return c


def scribble(kept) -> None:
    """0xFF into every kept buffer nothing holds (once the garbage that
    may hold one is collected)."""
    gc.collect()
    with kept._lock:
        for k in range(len(kept._kept)):
            if sys.getrefcount(kept._kept[k]) == 2:
                kept._kept[k].fill(0xFF)


def holders(kept, address: int) -> int:
    """How many references but the list's hold the kept buffer at
    `address`, once the garbage is collected."""
    gc.collect()
    with kept._lock:
        for k in range(len(kept._kept)):
            if kept._kept[k].ctypes.data == address:
                return sys.getrefcount(kept._kept[k]) - 2
    raise AssertionError(f"no kept buffer at {address:#x}")


def stripe_buffers() -> tuple[float, float]:
    """(reused, fresh) of `cubefs_access_stripe_buffers_total` so far."""
    return (metrics.access_stripe_buffers.value(result="reused"),
            metrics.access_stripe_buffers.value(result="fresh"))


def rows_taken(acc, monkeypatch) -> list[tuple[int, int]]:
    """(address, bytes) of the data rows of every PUT from now on."""
    taken, take = [], acc._take_stripe_rows

    def taking(shape):
        rows = take(shape)
        taken.append((rows.ctypes.data, rows.nbytes))
        return rows

    monkeypatch.setattr(acc, "_take_stripe_rows", taking)
    return taken


def reference_stripe(blob: bytes, t: cmode.Tactic) -> np.ndarray:
    """(total, S) by table GF(2^8): RS rows from cellbench/reference.py;
    LRC local parity as RS over each AZ's local stripe; MSR parity as
    the product-matrix rows over the alpha sub-shards of each shard."""
    if t.is_msr():
        per = reference.shard_size(len(blob), t.n, t.min_shard_size)
        s = -(-per // t.alpha) * t.alpha
        out = np.zeros((t.total, s), dtype=np.uint8)
        out.reshape(-1)[:len(blob)] = np.frombuffer(blob, dtype=np.uint8)
        sub = out[:t.n].reshape(t.n * t.alpha, s // t.alpha)
        rows = msr.encode_rows(t.n, t.total, t.d)
        out[t.n:] = reference.matmul(rows, sub).reshape(t.total - t.n, s)
        return out
    glob = reference.stripe(blob, t.n, t.m, t.min_shard_size)
    if not t.l:
        return glob
    out = np.zeros((t.total, glob.shape[1]), dtype=np.uint8)
    out[:t.n + t.m] = glob
    for az in range(t.az_count):
        idx, ln, lm = t.local_stripe_in_az(az)
        out[idx[ln:]] = reference.matmul(
            reference.encode_matrix(ln, ln + lm)[ln:], out[idx[:ln]])
    return out


def stored_shards(c: Cluster, loc) -> list[list[bytes]]:
    """Per blob, the shard stored on each unit, in unit index order."""
    sl = loc.slices[0]
    vol = c.cm.get_volume(sl.vid)
    return [[c.node_of(u.node_addr).get_shard(u.disk_id, u.chunk_id,
                                              sl.min_bid + k)[0]
             for u in sorted(vol.units, key=lambda u: u.index)]
            for k in range(sl.count)]


def assert_stored_equals_reference(c: Cluster, loc, data: bytes) -> None:
    """Every stripe of a PUT has the first blob's shard size S: blob k
    is stored as the stripe of its bytes zero-padded to n * S."""
    t = cmode.tactic(cmode.CodeMode(loc.codemode))
    s = reference_stripe(data[:BLOB], t).shape[1]
    for k, shards in enumerate(stored_shards(c, loc)):
        want = reference_stripe(
            data[k * BLOB:(k + 1) * BLOB].ljust(t.n * s, b"\0"), t)
        assert len(shards) == t.total
        for idx, got in enumerate(shards):
            assert got == want[idx].tobytes(), (k, idx)


MODES = [cmode.CodeMode.EC3P3, cmode.CodeMode.EC6P6, cmode.CodeMode.EC12P4,
         cmode.CodeMode.EC4P4L2, cmode.CodeMode.EC4P4MSR]
SIZES = {"exact_multiple": 3 * BLOB, "short_last_blob": 2 * BLOB + 12_345,
         "one_byte": 1}


@pytest.mark.parametrize("size", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("mode", MODES, ids=[m.name for m in MODES])
def test_stored_shards_equal_the_reference_stripe(cluster, kept, rng, mode,
                                                 size):
    """Through an array that last held 0xFF everywhere: the PUT before
    has the same stripe shape, so a kept buffer fits, and every kept
    buffer is scribbled over."""
    cluster.access.put(b"\xff" * size, codemode=mode)
    scribble(kept)
    reused = stripe_buffers()[0]
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    loc = cluster.access.put(data, codemode=mode)
    assert stripe_buffers()[0] == reused + 1
    assert loc.crc == reference.crc32(data)
    assert_stored_equals_reference(cluster, loc, data)
    assert cluster.access.get(loc) == data


@pytest.mark.parametrize("mode", [cmode.CodeMode.EC6P3, cmode.CodeMode.EC12P4],
                         ids=["EC6P3", "EC12P4"])
def test_reused_rows_leak_nothing(cluster, kept, mode):
    """0xFF everywhere, then a shorter payload of the same stripe
    shape: every pad byte stored is 0, the payload comes back."""
    t = cmode.tactic(mode)
    cluster.access.put(b"\xff" * (2 * BLOB), codemode=mode)
    scribble(kept)  # the pad bytes too
    reused = stripe_buffers()[0]
    short = b"\xaa" * (BLOB + 100)  # two blobs again, the second short
    loc = cluster.access.put(short, codemode=mode)
    assert stripe_buffers()[0] == reused + 1  # a kept buffer, 0xFF
    shards = stored_shards(cluster, loc)
    for k, blob_len in enumerate([BLOB, 100]):
        stored = b"".join(shards[k][:t.n])
        assert stored[:blob_len] == b"\xaa" * blob_len
        assert stored[blob_len:].count(0) == len(stored) - blob_len
    assert_stored_equals_reference(cluster, loc, short)
    assert cluster.access.get(loc) == short


def test_counter_reads_fresh_then_reused(cluster, rng):
    read = lambda r: metrics.access_stripe_buffers.value(result=r)
    fresh0, reused0 = read("fresh"), read("reused")
    data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    for want in [(1, 0), (1, 1), (1, 2)]:
        cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
        assert (read("fresh") - fresh0, read("reused") - reused0) == want
    # a smaller shape fits a kept buffer; a larger one finds none
    cluster.access.put(data[:10], codemode=cmode.CodeMode.EC6P3)
    assert (read("fresh") - fresh0, read("reused") - reused0) == (1, 3)
    cluster.access.put(data * 2, codemode=cmode.CodeMode.EC6P3)
    assert (read("fresh") - fresh0, read("reused") - reused0) == (2, 3)


def test_small_rows_stay_with_the_allocator(tmp_path, kept, rng):
    """As shipped the kept arrays serve only above the allocator's mmap
    ceiling: a small PUT takes and leaves nothing."""
    c = Cluster(tmp_path)
    data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    for _ in range(2):
        loc = c.access.put(data, codemode=cmode.CodeMode.EC6P3)
    assert kept._kept == []
    assert c.access.get(loc) == data


def test_concurrent_puts_never_share_rows(cluster, rng, monkeypatch):
    """Eight clients, more than the cores' worth of switches: a buffer
    is out with one PUT at a time, and every object reads back."""
    acc = cluster.access
    out, guard, clashes = {}, threading.Lock(), []
    take = acc._take_stripe_rows

    def gone(key):
        with guard:
            del out[key]

    def taking(shape):
        rows = take(shape)
        span = (rows.ctypes.data, rows.ctypes.data + rows.nbytes)
        with guard:
            if any(a < span[1] and span[0] < z for a, z in out.values()):
                clashes.append(span)
            out[id(rows)] = span
        # out until the PUT drops its rows: called before the view lets
        # go of its buffer
        weakref.finalize(rows, gone, id(rows))
        return rows

    monkeypatch.setattr(acc, "_take_stripe_rows", taking)
    payloads = [rng.integers(0, 256, 2 * BLOB + 7 * i, dtype=np.uint8)
                .tobytes() for i in range(8)]
    results: dict[int, list] = {}

    def client(i):
        results[i] = [acc.put(payloads[i], codemode=cmode.CodeMode.EC6P3)
                      for _ in range(6)]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    gc.collect()
    assert clashes == [] and out == {}
    assert metrics.access_stripe_buffers.value(result="reused") > 0
    for i, locs in results.items():
        assert len(locs) == 6
        for loc in locs:
            assert acc.get(loc) == payloads[i]
            assert_stored_equals_reference(cluster, loc, payloads[i])


def test_failed_quorum_returns_rows_only_after_every_write(
        cluster, kept, rng, monkeypatch):
    """A PUT that fails its quorum still waits for every shard write,
    and then nothing holds its array: the next PUT is handed it."""
    acc = cluster.access
    for node in cluster.nodes[:2]:
        for d in node.disk_ids:
            node.break_disk(d)
    running, guard = [0], threading.Lock()
    write = acc._write_shard

    def slow_write(*a):
        with guard:
            running[0] += 1
        try:
            time.sleep(0.01)
            return write(*a)
        finally:
            with guard:
                running[0] -= 1

    monkeypatch.setattr(acc, "_write_shard", slow_write)
    taken = rows_taken(acc, monkeypatch)
    data = rng.integers(0, 256, 10_000, dtype=np.uint8).tobytes()
    with pytest.raises(PutQuorumError):
        acc.put(data, codemode=cmode.CodeMode.EC6P3)
    assert running[0] == 0
    assert holders(kept, taken[0][0]) == 0


def test_rows_are_dropped_while_a_write_may_still_read_them(
        cluster, rng, monkeypatch):
    """A PUT that ends in an error while its step or its writes may
    still read the rows: no other PUT is handed them while anything
    holds them. Such a PUT raises only once the writes it started have
    ended, so the gate opens on a timer; a step whose wait timed out
    still holds its submission, as the batcher's queue does."""
    acc = cluster.access
    write, release = acc._write_shard, threading.Event()
    running, guard = [0], threading.Lock()
    threading.Timer(0.2, release.set).start()

    def write_or_die(vol, unit, bid, shard):
        if unit.index == 0:
            raise RuntimeError("boom")
        with guard:
            running[0] += 1
        try:
            release.wait(10)
            return write(vol, unit, bid, shard)
        finally:
            with guard:
                running[0] -= 1

    monkeypatch.setattr(acc, "_write_shard", write_or_die)
    taken = rows_taken(acc, monkeypatch)
    data = rng.integers(0, 256, 10_000, dtype=np.uint8).tobytes()
    try:
        with pytest.raises(RuntimeError, match="boom"):
            acc.put(data, codemode=cmode.CodeMode.EC6P3)
        assert running[0] == 0
    finally:
        release.set()
    # and a PUT whose step never ended: its array goes to no other PUT
    # while the step holds it
    monkeypatch.setattr(acc, "_write_shard", write)

    class NeverEnds:
        def __init__(self, rows):
            self.rows = rows

        def wait(self, timeout=120.0):
            raise TimeoutError("step")

    enc = acc._encoder(int(cmode.CodeMode.EC6P3))
    submit = enc.encode_rows_async
    steps = []
    monkeypatch.setattr(enc, "encode_rows_async", lambda rows, shard_size:
                        steps.append(NeverEnds(rows)) or steps[-1])
    with pytest.raises(TimeoutError):
        acc.put(data, codemode=cmode.CodeMode.EC6P3)
    held = taken[-1]
    monkeypatch.setattr(enc, "encode_rows_async", submit)
    gc.collect()
    for _ in range(2):
        loc = acc.put(data, codemode=cmode.CodeMode.EC6P3)
        assert taken[-1] != held
        assert_stored_equals_reference(cluster, loc, data)
    del steps[:]  # the step lets go
    gc.collect()
    reused = stripe_buffers()[0]
    acc.put(data, codemode=cmode.CodeMode.EC6P3)
    assert stripe_buffers()[0] == reused + 1
