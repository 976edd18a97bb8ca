"""Durable ShardNode: native-KV persistence, kill-and-restart recovery,
range split, clustermgr catalog (blobstore/shardnode/storage/shard.go +
clustermgr/catalog parity)."""

import time

import pytest

from cubefs_tpu.blob.clustermgr import ClusterMgr
from cubefs_tpu.blob.shardnode import Catalog, ShardNode
from cubefs_tpu.utils import rpc
from cubefs_tpu.utils.rpc import NodePool

from test_tools import _kv_call, make_sn_cluster


def _leader_of(nodes, shard_id):
    for sn in nodes:
        r = sn.rafts.get(shard_id)
        if r is not None and r.status()["role"] == "leader":
            return sn
    return None


def test_shard_kill_and_restart_preserves_items(tmp_path):
    pool, nodes = make_sn_cluster(tmp_path)
    try:
        for i in range(8):
            _kv_call(pool, nodes, "kv_put",
                     {"shard_id": 1, "key": f"a{i:02d}"}, f"v{i}".encode())
        _kv_call(pool, nodes, "kv_put", {"shard_id": 2, "key": "zz"}, b"Z")
    finally:
        for sn in nodes:
            sn.stop()
    # full-cluster restart from disk: manifest reopens every shard and
    # its raft group; the native KV already holds the items (no raft
    # snapshot needed to see data)
    pool2 = NodePool()
    nodes2 = []
    for i in range(3):
        sn = ShardNode(i, addr=f"sn{i}", node_pool=pool2,
                       data_dir=str(tmp_path / f"sn{i}"))
        pool2.bind(f"sn{i}", sn)
        nodes2.append(sn)
    try:
        assert set(nodes2[0].shards) == {1, 2}

        # durable store readable immediately on every node that had
        # applied before the kill (at minimum the old leader), before
        # any election or raft replay
        def _direct(shard_id, key):
            n = 0
            for sn in nodes2:
                try:
                    sn.shards[shard_id].get(key)
                    n += 1
                except KeyError:
                    pass
            return n

        assert _direct(1, "a03") >= 1
        assert _direct(2, "zz") >= 1
        # and the replicated write path comes back
        _kv_call(pool2, nodes2, "kv_put", {"shard_id": 1, "key": "post"},
                 b"restart")
        _, v = _kv_call(pool2, nodes2, "kv_get",
                        {"shard_id": 1, "key": "post"})
        assert v == b"restart"
        _, v = _kv_call(pool2, nodes2, "kv_get",
                        {"shard_id": 1, "key": "a07"})
        assert v == b"v7"
    finally:
        for sn in nodes2:
            sn.stop()


def test_shard_split_moves_range_and_survives_restart(tmp_path):
    pool = NodePool()
    nodes = []
    peers = [f"sn{i}" for i in range(3)]
    for i in range(3):
        sn = ShardNode(i, addr=f"sn{i}", node_pool=pool,
                       data_dir=str(tmp_path / f"sn{i}"))
        pool.bind(f"sn{i}", sn)
        nodes.append(sn)
    for sn in nodes:
        sn.create_shard(1, "", "", peers=peers)
    try:
        for i in range(20):
            _kv_call(pool, nodes, "kv_put",
                     {"shard_id": 1, "key": f"k{i:02d}"}, f"v{i}".encode())
        meta = _kv_call(pool, nodes, "shard_split",
                        {"shard_id": 1, "child_id": 2})[0]
        split_key = meta["split_key"]
        assert meta["child_id"] == 2 and split_key == "k10"
        deadline = time.time() + 10  # let followers apply the split
        while time.time() < deadline and not all(
                2 in sn.shards and sn.shards[1].end == split_key
                and sn.shards[1].count() == 10 and sn.shards[2].count() == 10
                for sn in nodes):
            time.sleep(0.05)
        for sn in nodes:
            assert sn.shards[1].end == split_key
            assert sn.shards[2].start == split_key
            assert sn.shards[1].count() == 10
            assert sn.shards[2].count() == 10
        # both halves serve reads and writes through their own groups
        _, v = _kv_call(pool, nodes, "kv_get",
                        {"shard_id": 1, "key": "k04"})
        assert v == b"v4"
        _, v = _kv_call(pool, nodes, "kv_get",
                        {"shard_id": 2, "key": "k15"})
        assert v == b"v15"
        _kv_call(pool, nodes, "kv_put", {"shard_id": 2, "key": "k99"},
                 b"post-split")
    finally:
        for sn in nodes:
            sn.stop()
    # restart: the child shard must come back from the manifest
    pool2 = NodePool()
    nodes2 = []
    for i in range(3):
        sn = ShardNode(i, addr=f"sn{i}", node_pool=pool2,
                       data_dir=str(tmp_path / f"sn{i}"))
        pool2.bind(f"sn{i}", sn)
        nodes2.append(sn)
    try:
        assert set(nodes2[0].shards) == {1, 2}
        assert nodes2[0].shards[1].end == split_key
        # k99 may still be in a restarted follower's unapplied raft WAL
        # suffix: read through the cluster (leader has it by definition)
        _, v = _kv_call(pool2, nodes2, "kv_get",
                        {"shard_id": 2, "key": "k99"})
        assert v == b"post-split"
        _, v = _kv_call(pool2, nodes2, "kv_get",
                        {"shard_id": 2, "key": "k15"})
        assert v == b"v15"
        # raft WAL replay re-applied pre-split puts into the parent and
        # then the split record: the reconcile must leave NO ghost keys
        # >= split_key in any parent replica
        deadline = time.time() + 8
        while time.time() < deadline:
            ghosts = [k for sn in nodes2
                      for k in sn.shards[1].list("", 100)
                      if k >= split_key]
            if not ghosts and all(sn.shards[1].count() <= 10
                                  for sn in nodes2):
                break
            time.sleep(0.2)
        assert not ghosts, f"out-of-range ghosts survived replay: {ghosts}"
    finally:
        for sn in nodes2:
            sn.stop()


def test_split_too_small_rejected(tmp_path):
    pool = NodePool()
    sn = ShardNode(0, addr="sn0", node_pool=pool,
                   data_dir=str(tmp_path / "sn0"))
    pool.bind("sn0", sn)
    sn.create_shard(1, "", "")
    try:
        sn.shards[1].apply({"op": "put", "key": "only",
                            "value_hex": b"x".hex()})
        with pytest.raises(rpc.RpcError) as ei:
            sn.split_shard(1, 2)
        assert ei.value.code == 400
    finally:
        sn.stop()


def test_clustermgr_catalog_space_and_split(tmp_path):
    cm_ = ClusterMgr(data_dir=str(tmp_path / "cm"))
    shards = cm_.create_space("blobs", 4, ["sn0", "sn1", "sn2"])
    assert len(shards) == 4
    assert shards[0]["start"] == "" and shards[-1]["end"] == ""
    assert [s["start"] for s in shards[1:]] == ["4000", "8000", "c000"]
    with pytest.raises(ValueError):
        cm_.create_space("blobs", 2, ["sn0"])
    r = cm_.route_key("blobs", "a-key")
    assert r["start"] <= "a-key" and ("a-key" < r["end"] or not r["end"])
    # split registration narrows the parent and inserts the child
    child_id = cm_.alloc_shard_id()
    cm_.register_split("blobs", r["shard_id"], child_id, "a0")
    assert cm_.route_key("blobs", "a1")["shard_id"] == child_id
    assert cm_.route_key("blobs", "90")["shard_id"] == r["shard_id"]
    # idempotent re-registration (retried caller)
    cm_.register_split("blobs", r["shard_id"], child_id, "a0")
    assert len(cm_.get_space("blobs")) == 5


def test_catalog_client_split_routing():
    cat = Catalog()
    cat.create_space("s", [
        {"shard_id": 1, "start": "", "end": "m", "addrs": ["a"]},
        {"shard_id": 2, "start": "m", "end": "", "addrs": ["b"]},
    ])
    cat.apply_split("s", 1, 3, "g")
    assert cat.route("s", "apple")["shard_id"] == 1
    assert cat.route("s", "house")["shard_id"] == 3
    assert cat.route("s", "zebra")["shard_id"] == 2


def test_shard_repair_replaces_killed_replica(tmp_path):
    """e2e shard-domain repair (shard_disk_repairer.go parity): a
    shardnode dies -> scheduler detects via stale heartbeat -> queues a
    shard_repair task -> worker swaps the replica set -> the new member
    is caught up by raft and the catalog repoints."""
    from cubefs_tpu.blob.scheduler import Scheduler
    from cubefs_tpu.blob.worker import RepairWorker

    pool = NodePool()
    cm_ = ClusterMgr()
    pool.bind("cm", cm_)
    nodes = {}
    for i in range(4):
        sn = ShardNode(i, addr=f"sn{i}", node_pool=pool,
                       data_dir=str(tmp_path / f"sn{i}"))
        pool.bind(f"sn{i}", sn)
        cm_.register_service("shardnode", f"sn{i}")
        cm_.shardnode_heartbeat(f"sn{i}")
        nodes[f"sn{i}"] = sn
    replicas = ["sn0", "sn1", "sn2"]
    cm_.create_space("s", 1, replicas)
    shard_id = cm_.get_space("s")[0]["shard_id"]
    for a in replicas:
        nodes[a].create_shard(shard_id, "", "", peers=replicas)
    live = [nodes[a] for a in replicas]
    try:
        for i in range(10):
            _kv_call(pool, live, "kv_put",
                     {"shard_id": shard_id, "key": f"k{i}"}, f"v{i}".encode())
        # sn1 dies: stop it, and its heartbeat goes stale
        nodes["sn1"].stop()
        pool.bind("sn1", object())
        cm_._sn_heartbeat["sn1"] = time.time() - 60
        sched = Scheduler(cm_, node_pool=pool)
        dead = sched.collect_dead_shardnodes()
        assert dead == ["sn1"]
        # idempotent: a second sweep queues nothing new
        sched.collect_dead_shardnodes()
        pending = [t for t in sched.tasks.values()
                   if t["type"] == "shard_repair"]
        assert len(pending) == 1 and pending[0]["dest_addr"] == "sn3"
        worker = RepairWorker(rpc.Client(sched), rpc.Client(cm_), pool)
        assert worker.run_once()
        assert worker.completed == 1, sched.tasks
        # catalog now points at the replacement
        addrs = cm_.get_space("s")[0]["addrs"]
        assert addrs == ["sn0", "sn3", "sn2"]
        # raft catches the new member up; survivors + newcomer serve
        survivors = [nodes[a] for a in addrs]
        _kv_call(pool, survivors, "kv_put",
                 {"shard_id": shard_id, "key": "post-repair"}, b"ok")
        _, v = _kv_call(pool, survivors, "kv_get",
                        {"shard_id": shard_id, "key": "k3"})
        assert v == b"v3"
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if nodes["sn3"].shards[shard_id].get("k3") == b"v3":
                    break
            except KeyError:
                pass
            time.sleep(0.2)
        assert nodes["sn3"].shards[shard_id].get("k3") == b"v3"
    finally:
        for sn in nodes.values():
            sn.stop()


def test_shard_manual_migrate(tmp_path):
    """shard_migrate.go parity: operator moves one replica off a
    healthy node."""
    from cubefs_tpu.blob.scheduler import Scheduler
    from cubefs_tpu.blob.worker import RepairWorker

    pool = NodePool()
    cm_ = ClusterMgr()
    pool.bind("cm", cm_)
    nodes = {}
    for i in range(4):
        sn = ShardNode(i, addr=f"sn{i}", node_pool=pool,
                       data_dir=str(tmp_path / f"sn{i}"))
        pool.bind(f"sn{i}", sn)
        cm_.register_service("shardnode", f"sn{i}")
        cm_.shardnode_heartbeat(f"sn{i}")
        nodes[f"sn{i}"] = sn
    replicas = ["sn0", "sn1", "sn2"]
    cm_.create_space("s", 1, replicas)
    shard_id = cm_.get_space("s")[0]["shard_id"]
    for a in replicas:
        nodes[a].create_shard(shard_id, "", "", peers=replicas)
    try:
        _kv_call(pool, [nodes[a] for a in replicas], "kv_put",
                 {"shard_id": shard_id, "key": "x"}, b"1")
        sched = Scheduler(cm_, node_pool=pool)
        tid = sched.shard_migrate("s", shard_id, "sn2", "sn3")
        assert tid
        worker = RepairWorker(rpc.Client(sched), rpc.Client(cm_), pool)
        assert worker.run_once() and worker.completed == 1
        assert cm_.get_space("s")[0]["addrs"] == ["sn0", "sn1", "sn3"]
        # the migrated-away node no longer runs this shard's raft group
        assert shard_id not in nodes["sn2"].rafts
        survivors = [nodes[a] for a in ("sn0", "sn1", "sn3")]
        _, v = _kv_call(pool, survivors, "kv_get",
                        {"shard_id": shard_id, "key": "x"})
        assert v == b"1"
    finally:
        for sn in nodes.values():
            sn.stop()


def test_shardnode_durable_over_real_http(tmp_path):
    """Single durable shardnode behind a REAL RpcServer (the in-process
    pool hides redirect/socket behavior — memory: drive new distributed
    paths over real HTTP)."""
    sn = ShardNode(0, data_dir=str(tmp_path / "sn"))
    srv = rpc.RpcServer(sn, service="shardnode").start()
    try:
        cli = rpc.Client(srv.addr)
        cli.call("create_shard", {"shard_id": 7, "start": "", "end": ""})
        cli.call("kv_put", {"shard_id": 7, "key": "http"}, b"payload")
        _, v = cli.call("kv_get", {"shard_id": 7, "key": "http"})
        assert v == b"payload"
        meta, _ = cli.call("list_shards", {})
        assert meta["shards"][0]["items"] == 1
    finally:
        srv.stop()
        sn.stop()
    # process restart analog
    sn2 = ShardNode(0, data_dir=str(tmp_path / "sn"))
    srv2 = rpc.RpcServer(sn2, service="shardnode").start()
    try:
        cli = rpc.Client(srv2.addr)
        _, v = cli.call("kv_get", {"shard_id": 7, "key": "http"})
        assert v == b"payload"
    finally:
        srv2.stop()
        sn2.stop()
