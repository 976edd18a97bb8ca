"""End-to-end blob plane: the reference's in-process fake-cluster test
pattern (master/mocktest) — real services, direct-call transport, plus
an HTTP smoke test over the same objects.

The aha slice: put → break disk → scheduler emits repair tasks → worker
reconstructs on the codec engine → clustermgr repoints the unit → get
returns bit-identical data from the repaired volume.
"""

import numpy as np
import pytest

from cubefs_tpu.blob.access import AccessConfig, AccessHandler, GetError, NodePool
from cubefs_tpu.blob.blobnode import BlobNode
from cubefs_tpu.blob.clustermgr import ClusterMgr
from cubefs_tpu.blob.mq import MessageQueue
from cubefs_tpu.blob.scheduler import Scheduler
from cubefs_tpu.blob.types import DiskStatus
from cubefs_tpu.blob.worker import RepairWorker
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import metrics, rpc


class Cluster:
    """In-process blob cluster: n_nodes x disks_per_node disks."""

    def __init__(self, tmp_path, n_nodes=4, disks_per_node=3, data_dir=None):
        self.cm = ClusterMgr(data_dir=data_dir)
        self.cm_client = rpc.Client(self.cm)
        self.pool = NodePool()
        self.nodes: list[BlobNode] = []
        for n in range(n_nodes):
            addr = f"node{n}"
            node = BlobNode(
                node_id=n,
                disk_paths=[str(tmp_path / f"n{n}d{d}") for d in range(disks_per_node)],
                cm_client=self.cm_client,
                addr=addr,
            )
            node.register()
            node.send_heartbeat()
            self.pool.bind(addr, node)
            self.nodes.append(node)
        self.repair_q = MessageQueue()
        self.delete_q = MessageQueue()
        self.access = AccessHandler(
            self.cm_client, self.pool,
            AccessConfig(blob_size=64 << 10),
            repair_queue=self.repair_q, delete_queue=self.delete_q,
        )
        self.sched = Scheduler(self.cm, repair_queue=self.repair_q,
                               delete_queue=self.delete_q, node_pool=self.pool)
        self.worker = RepairWorker(rpc.Client(self.sched), self.cm_client, self.pool)

    def node_of(self, addr: str) -> BlobNode:
        return self.nodes[int(addr.removeprefix("node"))]

    def drain_worker(self, max_tasks=100):
        for _ in range(max_tasks):
            if not self.worker.run_once():
                return
        raise AssertionError("worker did not drain")


@pytest.fixture
def cluster(tmp_path):
    return Cluster(tmp_path)


def payload(rng, n):
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


def test_put_get_roundtrip_multi_blob(cluster, rng):
    data = payload(rng, 200_000)  # 4 blobs of 64KiB
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    assert loc.size == len(data) and loc.slices[0].count == 4
    assert cluster.access.get(loc) == data


def test_degraded_get_with_broken_disk(cluster, rng):
    data = payload(rng, 100_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    # break the disk hosting data shard 0
    u = vol.units[0]
    cluster.node_of(u.node_addr).break_disk(u.disk_id)
    assert cluster.access.get(loc) == data  # reconstructed on the fly
    assert cluster.repair_q.backlog() > 0  # degraded read filed repair msgs


def test_disk_repair_end_to_end(cluster, rng):
    data = payload(rng, 150_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vid = loc.slices[0].vid
    vol_before = cluster.cm.get_volume(vid)
    victim = vol_before.units[2]
    # capture the victim's shards for bit-identity check after rebuild
    victim_node = cluster.node_of(victim.node_addr)
    original = {
        bid: victim_node.get_shard(victim.disk_id, victim.chunk_id, bid)[0]
        for bid, _, _ in victim_node.list_chunk(victim.disk_id, victim.chunk_id)
    }
    victim_node.break_disk(victim.disk_id)

    n_tasks = cluster.sched.mark_disk_broken(victim.disk_id)
    assert n_tasks >= 1
    cluster.drain_worker()

    vol_after = cluster.cm.get_volume(vid)
    new_unit = vol_after.units[2]
    assert (new_unit.disk_id, new_unit.chunk_id) != (victim.disk_id, victim.chunk_id)
    assert vol_after.epoch > vol_before.epoch
    # rebuilt shards are bit-identical to the lost ones
    new_node = cluster.node_of(new_unit.node_addr)
    for bid, blob in original.items():
        rebuilt, _ = new_node.get_shard(new_unit.disk_id, new_unit.chunk_id, bid)
        assert rebuilt == blob
    # source disk fully repaired; GET healthy again
    assert cluster.cm.disks[victim.disk_id].status == DiskStatus.REPAIRED
    assert cluster.access.get(loc) == data


@pytest.mark.parametrize("lost", [(0, 3), (2, 7), (6, 8)],
                         ids=["data+data", "data+parity", "parity+parity"])
def test_two_disks_lost_both_units_rebuilt_bit_identical(cluster, rng, lost):
    """A second disk fails before the first is rebuilt: the volume's
    two tasks are leased together (since PR 38) and each unit is rebuilt
    from one read of the survivors in index order past BOTH lost units
    (the first n solve, the next one is the check before write-back); a
    disk whose unit the lease rebuilds is not asked at all."""
    data = payload(rng, 250_000)  # 4 blobs of 64 KiB
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vid = loc.slices[0].vid
    before = cluster.cm.get_volume(vid)
    original, asked = {}, []
    for idx in lost:
        u = before.units[idx]
        node = cluster.node_of(u.node_addr)
        original[idx] = {
            bid: node.get_shard(u.disk_id, u.chunk_id, bid)[0]
            for bid, _, _ in node.list_chunk(u.disk_id, u.chunk_id)}
        assert len(original[idx]) == 4
        node.break_disk(u.disk_id)
    broken = {before.units[i].disk_id for i in lost}
    for node in cluster.nodes:  # count reads sent to the broken disks
        real = node.get_shard

        def get_shard(disk_id, chunk_id, bid, *a, _real=real, **k):
            if disk_id in broken:
                asked.append((disk_id, bid))
            return _real(disk_id, chunk_id, bid, *a, **k)

        node.get_shard = get_shard
    queued = sum(cluster.sched.mark_disk_broken(d) for d in sorted(broken))
    assert queued >= 2
    cluster.drain_worker()
    assert cluster.worker.failed == 0

    after = cluster.cm.get_volume(vid)
    for idx in lost:
        unit = after.units[idx]
        assert unit.disk_id not in broken
        node = cluster.node_of(unit.node_addr)
        for bid, blob in original[idx].items():
            assert node.get_shard(unit.disk_id, unit.chunk_id, bid)[0] == blob
    # both lost units were left out of the reads from the start
    assert asked == [] and cluster.worker.completed == queued
    assert all(cluster.cm.disks[d].status == DiskStatus.REPAIRED
               for d in broken)
    assert cluster.access.get(loc) == data


def test_msr_disk_repair_pulls_subshards(cluster, rng):
    """EC4P4MSR repair goes down the sub-shard path: helper blobnodes
    serve beta-sized read_subshard combinations instead of full shards,
    and the rebuilt unit is still bit-identical."""
    cluster.cm.allow_colocated_units = True  # 8 units on a 4-node cluster
    data = payload(rng, 60_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC4P4MSR)
    vid = loc.slices[0].vid
    vol_before = cluster.cm.get_volume(vid)
    victim = vol_before.units[2]
    victim_node = cluster.node_of(victim.node_addr)
    original = {
        bid: victim_node.get_shard(victim.disk_id, victim.chunk_id, bid)[0]
        for bid, _, _ in victim_node.list_chunk(victim.disk_id, victim.chunk_id)
    }
    victim_node.break_disk(victim.disk_id)

    sub0 = metrics.repair_subshard_reads.value()
    pulled0 = sum(v for _, v in metrics.repair_bytes_pulled.samples())
    fb0 = sum(v for _, v in metrics.repair_msr_fallbacks.samples())
    assert cluster.sched.mark_disk_broken(victim.disk_id) >= 1
    cluster.drain_worker()

    # the sub-shard protocol carried the repair, without falling back
    n_subshard = metrics.repair_subshard_reads.value() - sub0
    assert n_subshard >= vol_before.tactic.d * len(original)
    assert sum(v for _, v in metrics.repair_msr_fallbacks.samples()) == fb0
    # traffic: d beta-symbols per bid, strictly under one full shard * d
    shard_bytes = max(len(b) for b in original.values())
    pulled = sum(v for _, v in metrics.repair_bytes_pulled.samples()) - pulled0
    assert pulled < vol_before.tactic.d * shard_bytes * len(original)

    vol_after = cluster.cm.get_volume(vid)
    new_unit = vol_after.units[2]
    new_node = cluster.node_of(new_unit.node_addr)
    for bid, blob in original.items():
        rebuilt, _ = new_node.get_shard(new_unit.disk_id, new_unit.chunk_id, bid)
        assert rebuilt == blob
    assert cluster.cm.disks[victim.disk_id].status == DiskStatus.REPAIRED
    assert cluster.access.get(loc) == data


def test_unrecoverable_when_too_many_disks_down(cluster, rng):
    data = payload(rng, 50_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    for u in vol.units[:4]:  # lose 4 > m=3
        cluster.node_of(u.node_addr).break_disk(u.disk_id)
    with pytest.raises(GetError):
        cluster.access.get(loc)


def test_async_delete_via_queue(cluster, rng):
    data = payload(rng, 30_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    cluster.access.delete(loc)
    assert cluster.delete_q.backlog() == 1
    assert cluster.sched.consume_delete_msgs() == 1
    with pytest.raises(GetError):
        cluster.access.get(loc)


def test_put_quorum_failure(cluster, rng):
    # break enough disks that quorum (8 of 9 for EC6P3) cannot be met
    for node in cluster.nodes[:2]:
        for d in node.disk_ids:
            node.break_disk(d)
    data = payload(rng, 10_000)
    from cubefs_tpu.blob.access import PutQuorumError
    with pytest.raises(PutQuorumError):
        cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)


def test_shard_repair_msgs_consumed_into_tasks(cluster, rng):
    data = payload(rng, 20_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    u = vol.units[1]
    cluster.node_of(u.node_addr).break_disk(u.disk_id)
    cluster.access.get(loc)  # degraded read enqueues repair msg
    assert cluster.sched.consume_repair_msgs() >= 1
    cluster.drain_worker()
    vol_after = cluster.cm.get_volume(vol.vid)
    assert vol_after.units[1].disk_id != u.disk_id
    assert cluster.access.get(loc) == data


def test_taskswitch_blocks_collection(cluster):
    cluster.sched.switch.disable("disk_repair")
    assert cluster.sched.collect_broken_disks() == []
    cluster.sched.switch.enable("disk_repair")


def test_clustermgr_persistence(tmp_path, rng):
    d = str(tmp_path / "cm")
    c1 = Cluster(tmp_path, data_dir=d)
    data = payload(rng, 10_000)
    loc = c1.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vid = loc.slices[0].vid
    c1.cm.snapshot()
    c1.cm.set_config("k", "v")
    # reload from snapshot + wal
    cm2 = ClusterMgr(data_dir=d)
    assert cm2.get_volume(vid).to_dict() == c1.cm.get_volume(vid).to_dict()
    assert cm2.get_config("k") == "v"
    assert cm2._next_bid == c1.cm._next_bid


def test_http_transport_smoke(cluster, rng):
    """Same services over real HTTP: put/get through RpcServer sockets."""
    servers = [rpc.RpcServer(rpc.expose(cluster.cm)).start()]
    cm_http = rpc.Client(servers[0].addr)
    pool = NodePool()
    for n, node in enumerate(cluster.nodes):
        s = rpc.RpcServer(rpc.expose(node)).start()
        servers.append(s)
        # rebind the cluster's unit addresses to the HTTP endpoints
        pool.bind(f"node{n}", s.addr)
        pool._clients[f"node{n}"] = rpc.Client(s.addr)
    access = AccessHandler(cm_http, pool, AccessConfig(blob_size=32 << 10))
    try:
        data = payload(rng, 90_000)
        loc = access.put(data, codemode=cmode.CodeMode.EC6P3)
        assert access.get(loc) == data
    finally:
        for s in servers:
            s.stop()


def test_manual_migrate(cluster, rng):
    data = payload(rng, 40_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vid = loc.slices[0].vid
    before = cluster.cm.get_volume(vid)
    cluster.sched.manual_migrate(vid, 4)
    cluster.drain_worker()
    after = cluster.cm.get_volume(vid)
    assert (after.units[4].disk_id, after.units[4].chunk_id) != (
        before.units[4].disk_id, before.units[4].chunk_id)
    assert cluster.access.get(loc) == data


def test_volume_inspector_clean_and_missing(cluster, rng):
    data = payload(rng, 60_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    rep = cluster.sched.inspect_volumes()
    assert rep["checked"] >= 1 and rep["bad"] == 0
    # delete one unit's shard behind the system's back -> inspector queues repair
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    u = vol.units[3]
    node = cluster.node_of(u.node_addr)
    bid = loc.slices[0].min_bid
    node.delete_shard(u.disk_id, u.chunk_id, bid)
    cluster.sched.inspect_volumes()
    assert any(t["reason"].startswith("inspect:") for t in cluster.sched.tasks.values())
    cluster.drain_worker()
    assert cluster.access.get(loc) == data


def test_balancer_moves_from_hot_disk(cluster, rng):
    # load several volumes so placement skews, then force skew manually
    for _ in range(3):
        cluster.access.put(payload(rng, 20_000), codemode=cmode.CodeMode.EC6P3)
    hot = max(cluster.cm.disks.values(), key=lambda d: d.chunk_count)
    hot.chunk_count += 5  # simulate imbalance
    moved = cluster.sched.balance(max_moves=2)
    assert moved >= 1
    cluster.drain_worker()


def test_balance_dedups_and_preserves_cm_counts(cluster, rng):
    for _ in range(2):
        cluster.access.put(payload(rng, 15_000), codemode=cmode.CodeMode.EC6P3)
    hot = max(cluster.cm.disks.values(), key=lambda d: d.chunk_count)
    hot.chunk_count += 5
    before = hot.chunk_count
    m1 = cluster.sched.balance(max_moves=1)
    m2 = cluster.sched.balance(max_moves=1)  # same task dedups -> no move
    assert m1 == 1 and m2 == 0
    assert hot.chunk_count == before  # scheduler never mutates cm records


def test_inspector_isolates_corrupt_data_shard(cluster, rng):
    """A CRC-consistent corrupt DATA shard must be repaired from the
    surviving code, never 'fixed' by recomputing parity from it."""
    data = payload(rng, 30_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    bid = loc.slices[0].min_bid
    u = vol.units[2]  # a data unit
    node = cluster.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    evil = bytes([b ^ 0xA5 for b in good])
    node.put_shard(u.disk_id, u.chunk_id, bid, evil)  # CRC recomputed: reads clean
    rep = cluster.sched.inspect_volumes()
    assert rep["bad"] >= 1
    tasks = [t for t in cluster.sched.tasks.values()
             if "corrupt" in t["reason"]]
    assert tasks and tasks[0]["unit_index"] == 2  # the DATA unit, not parity
    cluster.drain_worker()
    assert cluster.access.get(loc) == data  # original bytes restored


def test_lrc_codemode_through_access(tmp_path, rng):
    """LRC volumes through the full access path: local parity written,
    degraded read, and repair prefer the intra-AZ local stripe."""
    c = Cluster(tmp_path, n_nodes=5, disks_per_node=2)  # 10 disks for EC4P4L2
    c.cm.allow_colocated_units = True  # repair on a fully-spanned volume
    data = payload(rng, 80_000)
    loc = c.access.put(data, codemode=cmode.CodeMode.EC4P4L2)
    assert c.access.get(loc) == data
    vol = c.cm.get_volume(loc.slices[0].vid)
    assert len(vol.units) == 10  # 4 data + 4 global + 2 local parity
    # local parity shards are populated (non-empty on their nodes)
    bid = loc.slices[0].min_bid
    for u in vol.units[8:]:
        shard, _ = c.node_of(u.node_addr).get_shard(u.disk_id, u.chunk_id, bid)
        assert len(shard) > 0
    # degraded read with a broken data disk still works
    u = vol.units[0]
    c.node_of(u.node_addr).break_disk(u.disk_id)
    assert c.access.get(loc) == data
    # repair of the lost unit uses the local stripe (worker LRC path)
    c.sched.mark_disk_broken(u.disk_id)
    c.drain_worker()
    assert c.access.get(loc) == data


def test_clustermgr_raft_replication(tmp_path):
    """3-replica clustermgr: commits through raft, leader redirect for
    followers, state converges, and a restart recovers via the raft wal."""
    import time
    from cubefs_tpu.utils.rpc import NodePool as _Pool

    pool = _Pool()
    peers = ["cma", "cmb", "cmc"]
    cms = {}
    for name in peers:
        c = ClusterMgr(data_dir=str(tmp_path / name), me=name, peers=peers,
                       node_pool=pool, allow_colocated_units=True)
        pool.bind(name, c)
        cms[name] = c
    try:
        deadline = time.time() + 8
        leader = None
        while time.time() < deadline and leader is None:
            leaders = [c for c in cms.values() if c.is_leader()]
            if len(leaders) == 1:
                leader = leaders[0]
            time.sleep(0.05)
        assert leader is not None
        disk_id = leader.register_disk("node0", "/d0")
        for i in range(8):
            leader.register_disk("node0", f"/d{i+1}")
        vol = leader.alloc_volume(13)  # EC6P3
        start = leader.alloc_bids(16)
        leader.set_config("k", "v")
        # replicates to followers
        deadline = time.time() + 8
        while time.time() < deadline:
            if all(len(c.disks) == 9 and vol.vid in c.volumes
                   and c.kv.get("k") == "v" for c in cms.values()):
                break
            time.sleep(0.05)
        for c in cms.values():
            assert len(c.disks) == 9
            assert c.volumes[vol.vid].codemode == 13
            assert c.kv.get("k") == "v"
        # follower mutations redirect
        follower = next(c for c in cms.values() if c is not leader)
        with pytest.raises(rpc.RpcError) as ei:
            follower.rpc_alloc_bids({"count": 4}, b"")
        assert ei.value.code == 421
        # restart one member: raft wal replays the full FSM
        victim_name = follower.raft.me
        follower.raft.stop()
        time.sleep(0.2)
        c2 = ClusterMgr(data_dir=str(tmp_path / victim_name), me=victim_name,
                        peers=peers, node_pool=pool, allow_colocated_units=True)
        pool.bind(victim_name, c2)
        deadline = time.time() + 8
        while time.time() < deadline:
            if vol.vid in c2.volumes and c2.kv.get("k") == "v":
                break
            time.sleep(0.05)
        assert c2.volumes[vol.vid].codemode == 13
        c2.raft.stop()
    finally:
        for c in cms.values():
            if c.raft:
                c.raft.stop()


def test_scheduler_task_persistence_and_recordlog(tmp_path, rng):
    """Scheduler restart resumes pending tasks from its checkpoint; the
    record log captures the task lifecycle."""
    import json as _json
    sdir = str(tmp_path / "sched")
    c = Cluster(tmp_path)
    sched1 = Scheduler(c.cm, repair_queue=c.repair_q, delete_queue=c.delete_q,
                       node_pool=c.pool, data_dir=sdir)
    data = payload(rng, 30_000)
    loc = c.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = c.cm.get_volume(loc.slices[0].vid)
    victim = vol.units[1]
    c.node_of(victim.node_addr).break_disk(victim.disk_id)
    assert sched1.mark_disk_broken(victim.disk_id) >= 1
    # "crash" before any worker ran; a new scheduler restores the task
    sched2 = Scheduler(c.cm, repair_queue=c.repair_q, delete_queue=c.delete_q,
                       node_pool=c.pool, data_dir=sdir)
    assert any(t["state"] == "pending" for t in sched2.tasks.values())
    worker = RepairWorker(rpc.Client(sched2), c.cm_client, c.pool)
    for _ in range(50):
        if not worker.run_once():
            break
    assert c.access.get(loc) == data
    events = [_json.loads(l)["event"]
              for l in open(f"{sdir}/records.jsonl") if l.strip()]
    assert {"queued", "leased", "done"} <= set(events)


def test_compaction_sweep_reclaims(cluster, rng):
    data = payload(rng, 60_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    cluster.access.put(payload(rng, 30_000), codemode=cmode.CodeMode.EC6P3)
    # delete the first blob's shards -> dead space in chunks
    cluster.access._delete_now(loc)
    rep = cluster.sched.compact_chunks()
    assert rep["compacted"] > 0 and rep["reclaimed"] > 0


def test_worker_refuses_writeback_on_corrupt_survivor(cluster, rng):
    """A corrupt (CRC-consistent) survivor makes reconstruction disagree
    with the extra shard: the worker must fail the task, not install
    garbage as the rebuilt unit."""
    data = payload(rng, 25_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    bid = loc.slices[0].min_bid
    # corrupt one survivor in place (put_shard recomputes CRC: reads clean)
    u = vol.units[3]
    node = cluster.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    node.put_shard(u.disk_id, u.chunk_id, bid, bytes(b ^ 0xFF for b in good))
    victim = vol.units[0]
    cluster.node_of(victim.node_addr).break_disk(victim.disk_id)
    cluster.sched.mark_disk_broken(victim.disk_id)
    ran = cluster.worker.run_once()
    assert ran and cluster.worker.failed >= 1  # refused, not silently wrong
    task = next(iter(cluster.sched.tasks.values()))
    assert "disagrees" in task.get("last_error", "")


def test_repeated_failures_park_the_task(cluster, rng):
    data = payload(rng, 20_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = cluster.cm.get_volume(loc.slices[0].vid)
    bid = loc.slices[0].min_bid
    u = vol.units[3]
    node = cluster.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    node.put_shard(u.disk_id, u.chunk_id, bid, bytes(b ^ 0xFF for b in good))
    victim = vol.units[0]
    cluster.node_of(victim.node_addr).break_disk(victim.disk_id)
    cluster.sched.mark_disk_broken(victim.disk_id)
    for _ in range(cluster.sched.MAX_ATTEMPTS + 2):
        if not cluster.worker.run_once():
            break
    task = next(iter(cluster.sched.tasks.values()))
    assert task["state"] == "parked"  # no infinite hot retry
    assert cluster.worker.run_once() is False  # nothing left to lease


def test_mq_compacts_acked_prefix(tmp_path):
    """High-volume topics (per-request S3 audit) must not grow without
    bound: acking past the threshold trims memory AND the on-disk log.
    Offsets are ABSOLUTE: consumers holding pre-compaction offsets keep
    acking safely (the renumbering design destroyed unacked messages
    when an ack crossed the threshold mid-batch), and a crash between
    the log rewrite and anything else replays at-least-once."""
    from cubefs_tpu.blob.mq import MessageQueue

    mq = MessageQueue(str(tmp_path / "q"), topic="t")
    mq.COMPACT_THRESHOLD = 100
    for i in range(250):
        mq.put({"i": i})
    # the scheduler's consume pattern: poll a batch, ack per message —
    # compaction fires MID-BATCH and must not invalidate held offsets
    batch1 = mq.poll(64)
    batch2 = mq.poll(130)[64:130]  # offsets 64..129, held before acks
    for off, _ in batch1:
        mq.ack(off)
    for off, _ in batch2:
        mq.ack(off)  # crosses the threshold mid-way
    assert mq.backlog() == 250 - 130
    assert [m["i"] for _, m in mq.poll(5)] == [130, 131, 132, 133, 134]
    assert len(mq._mem) < 250  # acked prefix actually dropped

    # restart replays ONLY unacked messages, with absolute offsets
    mq2 = MessageQueue(str(tmp_path / "q"), topic="t")
    assert mq2.backlog() == 120
    assert [m["i"] for _, m in mq2.poll(3)] == [130, 131, 132]

    # crash window: a restart that lost the offset-file write but kept
    # the compacted log must not lose messages (base header bounds it)
    import os
    os.unlink(str(tmp_path / "q" / "t.offset"))
    mq3 = MessageQueue(str(tmp_path / "q"), topic="t")
    got = [m["i"] for _, m in mq3.poll(500)]
    assert got[0] <= 130 and got[-1] == 249  # replay, never loss


def test_scheduler_checkpoints_into_cm_kv(tmp_path):
    """Without a data_dir the scheduler checkpoints task state into the
    clustermgr's replicated kvmgr (the reference's design): a brand-new
    scheduler over the same clustermgr restores the tasks, leases reset
    to pending."""
    from cubefs_tpu.blob.scheduler import Scheduler

    cm = ClusterMgr(data_dir=str(tmp_path / "cm"), allow_colocated_units=True)
    s1 = Scheduler(cm)
    with s1._lock:
        s1.tasks["t1"] = {"task_id": "t1", "kind": "repair",
                          "state": "leased", "disk_id": 1}
        s1.tasks["t2"] = {"task_id": "t2", "kind": "repair",
                          "state": "pending", "disk_id": 2}
    s1._kv_flush_now()  # the flusher thread's write, synchronously
    assert cm.kv_get("sched/tasks")  # rode the replicated kvmgr
    # a fresh scheduler (e.g. after node replacement) restores from cm
    s2 = Scheduler(cm)
    assert set(s2.tasks) == {"t1", "t2"}
    assert s2.tasks["t1"]["state"] == "pending"  # lease died with s1
    # standby-clobber guard: a scheduler constructed BEFORE the tasks
    # existed (empty restore) must merge the kv state on its first
    # write instead of overwriting it
    s_empty = Scheduler(cm)
    with s_empty._lock:
        s_empty.tasks.pop("t1", None)
        s_empty.tasks.pop("t2", None)
        s_empty._kv_synced = False
        s_empty.tasks["t3"] = {"task_id": "t3", "kind": "repair",
                               "state": "pending", "disk_id": 3}
    s_empty._kv_flush_now()
    import json as _json
    merged = _json.loads(cm.kv_get("sched/tasks"))
    assert set(merged) == {"t1", "t2", "t3"}, "kv state clobbered"
    # and a cm RESTART preserves the checkpoint (kvmgr persistence)
    cm.snapshot()
    cm2 = ClusterMgr(data_dir=str(tmp_path / "cm"),
                     allow_colocated_units=True)
    s3 = Scheduler(cm2)
    assert set(s3.tasks) == {"t1", "t2", "t3"}


def test_put_admits_encode_before_alloc(cluster, rng):
    """The PUT path admits the parity encode to the codec batcher
    BEFORE its allocation round-trips and the encode resolves before
    quorum commit — observable through the PUT's stage spans."""
    from cubefs_tpu.utils import trace as tracelib

    tracelib.reset_collector()
    data = payload(rng, 200_000)
    loc = cluster.access.put(data, codemode=cmode.CodeMode.EC6P3)
    root = next(s for s in tracelib.finished_spans()
                if s["op"] == "access.put")
    children = sorted((s for s in tracelib.finished_spans(root["trace_id"])
                       if s["parent_id"] == root["span_id"]),
                      key=lambda s: s["start"])
    st = {s["tags"]["stage"]: s for s in children}  # the last of a name
    end = lambda s: s["start"] + s["duration"]
    assert (st["encode_submit"]["start"] <= st["bid_alloc"]["start"]
            <= end(st["bid_alloc"]) <= end(st["encode_admission"])
            <= st["quorum_write"]["start"])
    # since PR 35 the stage is entered twice: the data shards go out
    # after the allocation and before the wait, the parity after it
    first = next(s for s in children if s["tags"]["stage"] == "quorum_write")
    assert first is not st["quorum_write"]
    assert (end(st["bid_alloc"]) <= first["start"] <= end(first)
            <= st["encode_admission"]["start"])
    assert st["encode_admission"]["tags"]["encode_total_ms"] >= \
        st["encode_admission"]["duration"] * 1000
    assert cluster.access.get(loc) == data


def test_disk_drain_planned_in_codec_steps(cluster, rng, monkeypatch):
    """Repair planner sizes a failed disk's drain against
    CUBEFS_CODEC_STEP_BYTES: tasks are grouped into full-width steps,
    steps ~= ceil(total_bytes / step_bytes)."""
    import math
    for _ in range(6):
        cluster.access.put(payload(rng, 60_000), codemode=cmode.CodeMode.EC6P3)
    # break the disk carrying the most volume-units
    disk_id = max(cluster.cm.disks,
                  key=lambda d: len(cluster.cm.volumes_on_disk(d)))
    n = cluster.sched.mark_disk_broken(disk_id)
    tasks = [t for t in cluster.sched.tasks.values()
             if t.get("src_disk") == disk_id]
    assert n == len(tasks) >= 2
    per = [t["drain_bytes"] for t in tasks]
    assert all(b > 0 for b in per)
    total = sum(per)
    # default 64MiB step swallows the whole disk in one step
    assert cluster.sched.last_drain_plan["steps"] == 1

    step_bytes = 2 * max(per)
    monkeypatch.setenv("CUBEFS_CODEC_STEP_BYTES", str(step_bytes))
    plan = cluster.sched.plan_disk_drain(disk_id)
    steps = len({t["drain_step"] for t in tasks})
    want = math.ceil(total / step_bytes)
    assert plan["steps"] == steps
    assert want <= steps <= want + 1  # first-fit over unequal chunks
    assert plan["total_bytes"] == total
