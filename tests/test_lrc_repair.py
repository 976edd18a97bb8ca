"""LRC disk repair inside the AZ: a lost unit of an EC16P20L2 or EC6P10L2
volume is rebuilt by the scheduler and `RepairWorker.run_once` from its
AZ's local stripe — no byte read across AZs, the unit placed back in its
AZ — and each rebuilt shard is checked before its write-back against a
second derivation through the global code from the same reads
(`rs_kernel.lrc_checked_rows`): one wrong survivor, whichever, refuses
the write-back. After `RepairWorker.ready` no repair of an LRC volume
builds a program. CPU, small sizes, seeded; the plain reference is
cellbench/reference_lrc.py."""

import numpy as np
import pytest

from cellbench import reference, reference_lrc
from cellbench.deployment import CompileClock
from cubefs_tpu.blob.access import AccessConfig, AccessHandler
from cubefs_tpu.blob.proxy import ProxyAllocator
from cubefs_tpu.blob.worker import RepairWorker, _Unit
from cubefs_tpu.codec import batcher as batcher_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.ops import gf256, rs_kernel
from cubefs_tpu.utils import metrics, rpc
from cubefs_tpu.utils import trace as tracelib
from test_blob_topology import AZCluster

MODES = [cmode.CodeMode.EC16P20L2, cmode.CodeMode.EC6P10L2]
AZS = ("az0", "az1")
BLOB = 64 << 10  # shards of 2048..4096 B: one width rung


def fleet(tmp_path, engine="auto", disks_per_node=4, **worker_kw
          ) -> AZCluster:
    """Two AZs of 5 nodes x 4 disks — 20 disks an AZ for EC16P20L2's 19
    units of a stripe there — whose PUTs share one volume."""
    c = AZCluster(tmp_path, azs=AZS, nodes_per_az=5,
                  disks_per_node=disks_per_node)
    c.access = AccessHandler(
        c.cm_client, c.pool, AccessConfig(blob_size=BLOB),
        repair_queue=c.repair_q, delete_queue=c.delete_q,
        proxy_client=rpc.Client(ProxyAllocator(c.cm_client)))
    c.worker = RepairWorker(rpc.Client(c.sched), c.cm_client, c.pool,
                            engine=engine, **worker_kw)
    return c


def fill(c, mode, seed, count=6) -> list:
    """`count` one-blob objects of seeded log-uniform sizes in one
    volume: [(payload, Location)]."""
    r = np.random.default_rng([int(mode), seed])
    out = []
    for _ in range(count):
        size = int(np.exp(r.uniform(np.log(1), np.log(BLOB))))
        data = r.integers(0, 256, size, dtype=np.uint8).tobytes()
        out.append((data, c.access.put(data, codemode=mode)))
    assert len({loc.slices[0].vid for _, loc in out}) == 1
    return out


def lose(c, vid: int, index: int, report=True):
    unit = c.cm.get_volume(vid).units[index]
    c.node_of(unit.node_addr).break_disk(unit.disk_id)
    if report:
        assert c.sched.mark_disk_broken(unit.disk_id) == 1
    return unit


def want_shard(data: bytes, t, index: int) -> bytes:
    return reference_lrc.stripe(data, t.n, t.m, t.l, t.az_count,
                                t.min_shard_size)[index].tobytes()


def unit_of(t, az: int, kind: str) -> int:
    """A data, global parity or local parity unit of the AZ's stripe."""
    stripe = t.ec_layout_by_az()[az]
    return {"data": stripe[0], "global_parity": stripe[t.n // t.az_count],
            "local_parity": stripe[-1]}[kind]


def counted(counter) -> dict:
    """{label value: count} of a counter of one label."""
    return {k[0]: v for k, v in counter.samples()}


def moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


# ---------------- rebuilt inside the AZ, as the reference stores it ----

@pytest.mark.parametrize("kind", ["data", "global_parity", "local_parity"])
@pytest.mark.parametrize("az", [0, 1])
@pytest.mark.parametrize("mode", MODES, ids=[m.name for m in MODES])
def test_a_lost_unit_is_rebuilt_inside_its_az(tmp_path, mode, az, kind):
    t = cmode.tactic(mode)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=az)
    vid = objects[0][1].slices[0].vid
    bad = unit_of(t, az, kind)
    pulled0, sources0, checks0 = (counted(metrics.repair_bytes_pulled),
                                  counted(metrics.repair_sources),
                                  counted(metrics.repair_checks))
    old = lose(c, vid, bad)
    tracelib.reset_collector()
    c.drain_worker()
    assert (c.worker.completed, c.worker.failed) == (1, 0)

    for data, loc in objects:
        unit = c.cm.get_volume(vid).units[bad]
        meta, got = c.pool.get(unit.node_addr).call(
            "get_shard", {"disk_id": unit.disk_id, "chunk_id": unit.chunk_id,
                          "bid": loc.slices[0].min_bid})
        assert got == want_shard(data, t, bad)
        assert meta["crc"] == reference.crc32(got)
    unit = c.cm.get_volume(vid).units[bad]
    assert unit.disk_id != old.disk_id
    assert c.cm.disks[unit.disk_id].az == AZS[az]
    pulled = moved(pulled0, counted(metrics.repair_bytes_pulled))
    assert set(pulled) == {"az_local"}  # no byte across AZs
    assert moved(sources0, counted(metrics.repair_sources)) == {"local": 1}
    assert moved(checks0, counted(metrics.repair_checks)) == {
        "derived": len(objects)}
    spans = tracelib.finished_spans()
    assert [s["tags"]["source"] for s in spans
            if s["op"] == "worker.repair"] == ["local"]
    assert {s["tags"]["check"] for s in spans
            if s["op"] == "stage:decode_step"} == {"derived"}
    for data, loc in objects[::3]:
        assert c.access.get(loc) == data


# ---------------- one wrong survivor, whichever, refuses the write-back -

def positions(mode) -> list[tuple]:
    t = cmode.tactic(mode)
    return [(mode, az, lost) for az, stripe in enumerate(t.ec_layout_by_az())
            for lost in range(len(stripe))]


@pytest.mark.parametrize(
    "mode,az,lost", positions(MODES[0]) + positions(MODES[1]),
    ids=lambda v: getattr(v, "name", str(v)))
def test_every_survivor_wrong_in_one_byte_refuses_the_writeback(
        mode, az, lost):
    """The worker's decode of one local stripe, its 18 (or 8) survivors
    as read: right, both rows give the reference's lost unit; with one
    byte of any one survivor flipped, the check refuses and the unit has
    nothing to write back."""
    t = cmode.tactic(mode)
    stripe = tuple(t.ec_layout_by_az()[az])
    ln = (t.n + t.m) // t.az_count
    subs = [p for p in range(len(stripe)) if p != lost]
    rng = np.random.default_rng([int(mode), az, lost])
    blob = rng.integers(0, 256, 3 * t.n * 1000 + 7, dtype=np.uint8).tobytes()
    full = reference_lrc.stripe(blob, t.n, t.m, t.l, t.az_count,
                                t.min_shard_size)
    size = full.shape[1]
    worker = RepairWorker(None, None, None, engine="numpy")

    def decode(shards):
        unit = _Unit({}, lost, None)
        key = (rs_kernel.rung_width(size), tuple(subs))
        worker._decode_groups(t, {key: [(1, size, shards)]}, ln, ln + 1,
                              [unit], False, stripe)
        return unit

    good = [full[stripe[p]].tobytes() for p in subs]
    unit = decode(good)
    assert unit.error is None
    assert unit.writes == [(1, full[stripe[lost]].tobytes())]
    for c in range(ln):
        shards = list(good)
        at = int(rng.integers(0, size))
        wrong = bytearray(shards[c])
        wrong[at] ^= int(rng.integers(1, 256))
        shards[c] = bytes(wrong)
        unit = decode(shards)
        assert isinstance(unit.error, RuntimeError), (c, at)
        assert "global code" in str(unit.error) and unit.writes == []


@pytest.mark.parametrize("mode,lost,wrong", [
    (cmode.CodeMode.EC16P20L2, 37, 33),  # the blind pair of the first 16
    (cmode.CodeMode.EC16P20L2, 0, 36),
    (cmode.CodeMode.EC6P10L2, 7, 1)], ids=["37-33", "0-36", "EC6P10L2"])
def test_a_wrong_survivor_fails_the_task_and_writes_nothing(
        tmp_path, mode, lost, wrong):
    """CRC-consistent and wrong in one byte on its blobnode: the task
    fails with the check's error and no chunk is made at the
    destination. (37, 33) is the pair that the first 16 global
    survivors of az1 would let through."""
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=7, count=3)
    loc = objects[1][1]
    vid, bid = loc.slices[0].vid, loc.slices[0].min_bid
    u = c.cm.get_volume(vid).units[wrong]
    node = c.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    node.put_shard(u.disk_id, u.chunk_id, bid,
                   good[:-1] + bytes([good[-1] ^ 0x5A]))
    lose(c, vid, lost)
    assert c.worker.run_once() and c.worker.failed == 1
    task = next(iter(c.sched.tasks.values()))
    assert "global code" in task.get("last_error", "")
    with pytest.raises(rpc.RpcError, match="no such chunk"):
        c.pool.get(task["dest_addr"]).call(
            "list_chunk", {"disk_id": task["dest_disk"],
                           "chunk_id": task["dest_chunk"]})


# ---------------- the rows ----------------

@pytest.mark.parametrize("mode", MODES + [cmode.CodeMode.EC6P3L3,
                                          cmode.CodeMode.EC4P4L2],
                         ids=lambda m: m.name)
def test_the_checked_rows_derive_the_lost_unit_twice_with_no_blind_column(
        mode):
    """Both rows solve every lost position of every AZ's stripe, and
    they differ at every survivor's column; an AZ with fewer than n
    global units besides the lost one has no such rows."""
    t = cmode.tactic(mode)
    stripes = tuple(map(tuple, t.ec_layout_by_az()))
    ln = (t.n + t.m) // t.az_count
    rng = np.random.default_rng(int(mode))
    blob = rng.integers(0, 256, t.n * 64, dtype=np.uint8).tobytes()
    full = reference_lrc.stripe(blob, t.n, t.m, t.l, t.az_count, 1)
    for stripe in stripes:
        for lost in range(len(stripe)):
            present = tuple(p for p in range(len(stripe)) if p != lost)
            rows = rs_kernel.lrc_checked_rows(t.n, t.n + t.m, stripes, ln,
                                              stripe, present, lost)
            if ln - (lost < ln) < t.n:
                assert rows is None
            if rows is None:
                # EC4P4L2's local parity: its 4 global survivors are the
                # one solving set there is, and it leaves a blind column
                assert mode not in MODES
                continue
            assert rows.shape == (2, ln) and np.all(rows[0] ^ rows[1])
            got = gf256.gf_matmul(rows, full[[stripe[p] for p in present]])
            assert np.array_equal(got[0], full[stripe[lost]])
            assert np.array_equal(got[1], full[stripe[lost]])


# ---------------- ready: no program after it ----------------

@pytest.mark.parametrize("mode", MODES, ids=[m.name for m in MODES])
def test_after_ready_no_lrc_repair_builds_a_program(
        tmp_path, monkeypatch, mode):
    """`ready` from the cluster's policy, then a local repair of a data
    unit, the rebuild of a lost local parity from its stripe, and the
    global fallback of each (a second unit of the stripe refusing
    reads): no codec program is built and JAX compiles nothing."""
    monkeypatch.setattr(batcher_mod.DEFAULT, "dp_enabled", False)
    t = cmode.tactic(mode)
    policies = [cmode.Policy(mode.name, 0, 1 << 62)]
    # four lost disks: spares in the AZ for each
    c = fleet(tmp_path, engine="tpu", disks_per_node=5, batch_stripes=8)
    c.access.cfg.policies = policies
    built = lambda: sum(v for _, v in metrics.codec_programs.samples())
    # one stripe rung and one width rung, by the global stripe's n and
    # the local stripe's columns
    assert c.worker.ready(BLOB, policies, BLOB) == 2
    objects = fill(c, mode, seed=9, count=4)
    vid = objects[0][1].slices[0].vid
    stripe = t.ec_layout_by_az()[0]
    sources0 = counted(metrics.repair_sources)
    clock = CompileClock()
    before = built()
    try:
        for bad, refusing in ((stripe[0], None), (stripe[-1], None),
                              (stripe[1], stripe[2]),
                              (stripe[-1], stripe[3])):
            if refusing is not None:
                lose(c, vid, refusing, report=False)
            done = c.worker.completed
            lose(c, vid, bad)
            c.drain_worker()
            assert c.worker.completed == done + 1 and c.worker.failed == 0
            for data, loc in objects:
                unit = c.cm.get_volume(vid).units[bad]
                _, got = c.pool.get(unit.node_addr).call(
                    "get_shard", {"disk_id": unit.disk_id,
                                  "chunk_id": unit.chunk_id,
                                  "bid": loc.slices[0].min_bid})
                assert got == want_shard(data, t, bad)
        assert built() == before and clock.mark()["compiles"] == 0
    finally:
        clock.close()
    assert moved(sources0, counted(metrics.repair_sources)) == {
        "local": 2, "global": 2}
