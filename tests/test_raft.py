"""Raft: election, replication, leader failover, log convergence after
partitions, persistence — the correctness core the metadata planes rely
on (modeled on the reference's raft paper-conformance suite)."""

import threading
import time

import pytest

from cubefs_tpu.parallel import raft
from cubefs_tpu.utils.rpc import NodePool


class Member:
    """One process-local raft member with its applied-entry record."""

    def __init__(self, name, members, pool, tmp=None):
        self.applied = []
        self.routes = {}
        self.node = raft.RaftNode(
            "g1", name, members, self.applied.append, pool,
            data_dir=tmp and str(tmp / name),
        )
        raft.register_routes(self.routes, self.node)


class FlakyPool(NodePool):
    """NodePool with per-address blackholing (network partitions)."""

    def __init__(self):
        super().__init__()
        self.down: set[str] = set()

    def _wrap(self, addr, client):
        outer = self

        class Wrapped:
            def call(self, method, args=None, body=b"", timeout=30.0):
                if addr in outer.down:
                    from cubefs_tpu.utils.rpc import ServiceUnavailable
                    raise ServiceUnavailable(503, f"{addr} partitioned")
                return client.call(method, args, body, timeout)

        return Wrapped()

    def get(self, addr):
        return self._wrap(addr, super().get(addr))

    def get_direct(self, addr):
        # raft's point-to-point transport rides get_direct: partitions
        # must blackhole it too
        return self._wrap(addr, super().get_direct(addr))


def make_cluster(n=3, tmp=None, pool=None):
    pool = pool or NodePool()
    names = [f"r{i}" for i in range(n)]
    members = {}
    for name in names:
        m = Member(name, names, pool, tmp)
        members[name] = m
        pool.bind(name, _Routes(m.routes))
    for m in members.values():
        m.node.start()
    return members, pool


class _Routes:
    def __init__(self, routes):
        for k, v in routes.items():
            setattr(self, f"rpc_{k}", v)


def wait_leader(members, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        leaders = [m for m in members.values() if m.node.status()["role"] == "leader"]
        if len(leaders) == 1:
            return leaders[0]
        time.sleep(0.02)
    raise AssertionError(
        f"no single leader: {[m.node.status() for m in members.values()]}"
    )


def wait_applied(members, n, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(len(m.applied) >= n for m in members.values()):
            return
        time.sleep(0.02)
    raise AssertionError({k: len(m.applied) for k, m in members.items()})


def stop_all(members):
    for m in members.values():
        m.node.stop()


def test_elects_single_leader_and_replicates():
    members, _ = make_cluster(3)
    try:
        leader = wait_leader(members)
        for i in range(5):
            leader.node.propose({"n": i})
        wait_applied(members, 5)
        for m in members.values():
            assert m.applied == [{"n": i} for i in range(5)]
    finally:
        stop_all(members)


def test_follower_rejects_propose_with_redirect():
    members, _ = make_cluster(3)
    try:
        leader = wait_leader(members)
        follower = next(m for m in members.values() if m is not leader)
        # the redirect hint comes with the leader's first heartbeat: on
        # a loaded machine the election can be seen before it lands
        deadline = time.time() + 5.0
        while follower.node.leader is None and time.time() < deadline:
            time.sleep(0.02)
        with pytest.raises(raft.NotLeaderError) as ei:
            follower.node.propose({"x": 1})
        assert ei.value.leader == leader.node.me
    finally:
        stop_all(members)


def test_leader_failover_preserves_log():
    pool = FlakyPool()
    members, _ = make_cluster(3, pool=pool)
    try:
        leader = wait_leader(members)
        leader.node.propose({"v": "committed"})
        wait_applied(members, 1)
        # partition the leader away; remaining two elect a new leader
        pool.down.add(leader.node.me)
        leader.node.stop()
        rest = {k: m for k, m in members.items() if m is not leader}
        new_leader = wait_leader(rest, timeout=8.0)
        assert new_leader is not leader
        new_leader.node.propose({"v": "after-failover"})
        wait_applied(rest, 2)
        for m in rest.values():
            assert m.applied == [{"v": "committed"}, {"v": "after-failover"}]
    finally:
        stop_all(members)


def test_partitioned_minority_cannot_commit():
    pool = FlakyPool()
    members, _ = make_cluster(3, pool=pool)
    try:
        leader = wait_leader(members)
        others = [m for m in members.values() if m is not leader]
        # cut the leader off from both followers
        pool.down.update(m.node.me for m in others)
        with pytest.raises((TimeoutError, raft.NotLeaderError)):
            leader.node.propose({"lost": True}, timeout=0.6)
        # heal; cluster converges on ONE log (the uncommitted entry may
        # survive or be truncated depending on the new leader)
        pool.down.clear()
        new_leader = wait_leader(members, timeout=8.0)
        new_leader.node.propose({"final": True})
        deadline = time.time() + 5
        while time.time() < deadline:
            logs = [tuple(map(str, m.applied)) for m in members.values()]
            if len(set(logs)) == 1 and any("final" in s for s in logs[0]):
                break
            time.sleep(0.05)
        logs = [tuple(map(str, m.applied)) for m in members.values()]
        assert len(set(logs)) == 1
    finally:
        stop_all(members)


def test_restart_recovers_log(tmp_path):
    members, pool = make_cluster(3, tmp=tmp_path)
    try:
        leader = wait_leader(members)
        for i in range(3):
            leader.node.propose({"i": i})
        wait_applied(members, 3)
    finally:
        stop_all(members)
    time.sleep(0.1)
    # restart all members from their wals
    members2, _ = make_cluster(3, tmp=tmp_path)
    try:
        leader = wait_leader(members2)
        # replayed log re-applies on commit advance
        leader.node.propose({"i": 99})
        wait_applied(members2, 4)
        for m in members2.values():
            assert m.applied[:3] == [{"i": i} for i in range(3)]
    finally:
        stop_all(members2)


def test_single_node_group_commits_immediately():
    members, _ = make_cluster(1)
    try:
        leader = wait_leader(members)
        leader.node.propose({"solo": True})
        assert members["r0"].applied == [{"solo": True}]
    finally:
        stop_all(members)


def test_log_compaction_and_snapshot_install(tmp_path):
    """Auto-compaction via the FSM snapshot hook + a lagging member
    catching up through InstallSnapshot instead of replay."""
    pool = FlakyPool()
    state = {name: [] for name in ("r0", "r1", "r2")}

    class SnapMember(Member):
        def __init__(self, name, members, pool, tmp):
            self.applied = state[name]
            self.routes = {}
            self.node = raft.RaftNode(
                "g1", name, members, self.applied.append, pool,
                data_dir=str(tmp / name),
                snapshot_fn=lambda: repr(self.applied).encode(),
                restore_fn=lambda b: self.applied.__init__(eval(b.decode())),
            )
            self.node.COMPACT_THRESHOLD = 20
            raft.register_routes(self.routes, self.node)

    names = ["r0", "r1", "r2"]
    members = {}
    for n in names:
        m = SnapMember(n, names, pool, tmp_path)
        members[n] = m
        pool.bind(n, _Routes(m.routes))
    for m in members.values():
        m.node.start()
    try:
        leader = wait_leader(members)
        # partition one follower away, then write enough to force compaction
        lag = next(m for m in members.values() if m is not leader)
        pool.down.add(lag.node.me)
        for i in range(60):
            leader.node.propose({"i": i})
        deadline = time.time() + 8
        while time.time() < deadline and leader.node.status()["log_base"] == 0:
            time.sleep(0.05)
        assert leader.node.status()["log_base"] > 0, leader.node.status()
        # heal: the lagging member must catch up (snapshot + tail entries)
        pool.down.clear()
        deadline = time.time() + 8
        while time.time() < deadline:
            if [e for e in lag.applied] == [e for e in members[leader.node.me].applied]:
                break
            time.sleep(0.05)
        assert lag.applied == members[leader.node.me].applied
        assert len(lag.applied) == 60
    finally:
        stop_all(members)


def _solo_with_snapshots(tmp_path, state):
    """Single-node group whose FSM is an applied list, with snapshot
    hooks wired (compaction machinery active)."""
    pool = NodePool()

    class M(Member):
        def __init__(self):
            self.applied = state
            self.routes = {}
            self.node = raft.RaftNode(
                "g1", "r0", ["r0"], self.applied.append, pool,
                data_dir=str(tmp_path / "r0"),
                snapshot_fn=lambda: repr(self.applied).encode(),
                restore_fn=lambda b: self.applied.__init__(eval(b.decode())),
            )
            raft.register_routes(self.routes, self.node)

    m = M()
    pool.bind("r0", _Routes(m.routes))
    m.node.start()
    return m


def test_wal_survives_snapshot_crash_window(tmp_path):
    """Crash between snapshot+meta persistence (new log_base) and the WAL
    rewrite must not replay old-base entries at wrong absolute indices:
    WAL records carry their absolute index, so load() skips the covered
    prefix and keeps the acknowledged tail."""
    import json as _json

    state = []
    m = _solo_with_snapshots(tmp_path, state)
    try:
        wait_leader({"r0": m})
        for i in range(8):
            m.node.propose({"i": i})
    finally:
        m.node.stop()
    time.sleep(0.1)

    d = tmp_path / "r0"
    # simulate the crash window: snapshot + meta say log_base=N (first 5
    # applied entries compacted), but the WAL was never rewritten.
    wal = [_json.loads(ln) for ln in open(d / "raft.jsonl") if ln.strip()]
    cut = wal[4]["idx"]  # compact through the 5th record
    snap_term = wal[4]["term"]
    covered = [rec["entry"] for rec in wal[:5] if not rec["entry"].get("__raft_noop__")]
    (d / "snapshot.json").write_text(_json.dumps({
        "index": cut, "term": snap_term,
        "data": __import__("base64").b64encode(repr(covered).encode()).decode(),
    }))
    meta = _json.loads((d / "meta.json").read_text())
    meta["log_base"], meta["log_base_term"] = cut, snap_term
    (d / "meta.json").write_text(_json.dumps(meta))

    state2 = []
    m2 = _solo_with_snapshots(tmp_path, state2)
    try:
        wait_leader({"r0": m2})
        assert m2.node.status()["log_base"] == cut
        m2.node.propose({"i": 99})
        # every pre-crash entry exactly once, at the right position
        assert state2 == covered + [
            rec["entry"] for rec in wal[5:] if not rec["entry"].get("__raft_noop__")
        ] + [{"i": 99}]
    finally:
        m2.node.stop()


def test_wal_torn_tail_dropped(tmp_path):
    """A torn (half-written) trailing WAL record was never acknowledged;
    reload keeps the intact prefix and drops the tail."""
    state = []
    m = _solo_with_snapshots(tmp_path, state)
    try:
        wait_leader({"r0": m})
        for i in range(4):
            m.node.propose({"i": i})
    finally:
        m.node.stop()
    time.sleep(0.1)

    wal_path = tmp_path / "r0" / "raft.jsonl"
    with open(wal_path, "a") as f:
        f.write('{"idx": 999, "term": 1, "ent')  # torn write

    state2 = []
    m2 = _solo_with_snapshots(tmp_path, state2)
    try:
        wait_leader({"r0": m2})
        m2.node.propose({"i": 4})
        assert state2 == [{"i": i} for i in range(5)]
    finally:
        m2.node.stop()
    time.sleep(0.1)

    # the post-crash entry {"i": 4} was acknowledged AFTER the torn tail:
    # the reload must have rewritten the WAL so a further restart keeps it
    state3 = []
    m3 = _solo_with_snapshots(tmp_path, state3)
    try:
        wait_leader({"r0": m3})
        m3.node.propose({"i": 5})
        assert state3 == [{"i": i} for i in range(6)]
    finally:
        m3.node.stop()


def test_direct_client_never_follows_leader_redirects():
    """Raft transport rides NodePool.get_direct: a 421 must surface as
    an error, never reroute the message — the shared default client's
    learned-leader cache once hijacked raft appends addressed to a
    follower back to the leader (self-heartbeat -> spurious step-down
    livelock on HTTP topologies)."""
    from cubefs_tpu.utils import rpc

    class Svc:
        def rpc_ping(self, args, body):
            raise rpc.RpcError(421, "leader=127.0.0.1:1")

    srv = rpc.RpcServer(Svc(), service="t").start()
    try:
        pool = NodePool()
        direct = pool.get_direct(srv.addr)
        with pytest.raises(rpc.RpcError) as ei:
            direct.call("ping", timeout=5.0)
        assert ei.value.code == 421  # surfaced, not followed
        # poisoning the default client's leader cache must not affect
        # the direct client (separate cache, separate instance)
        default = pool.get(srv.addr)
        default._leader = "127.0.0.1:1"
        assert pool.get_direct(srv.addr) is direct
    finally:
        srv.stop()


def test_http_raft_survives_poisoned_sdk_leader_cache():
    """End-to-end regression for the livelock: a 2-node raft over REAL
    HTTP where the SDK client for the follower has 'learned' the leader
    address. Replication must still commit (raft traffic bypasses the
    redirect cache)."""
    from cubefs_tpu.utils import rpc

    pool = NodePool()
    applied_a, applied_b = [], []
    routes_a, routes_b = {}, {}

    class SvcA:
        extra_routes = routes_a

    class SvcB:
        extra_routes = routes_b

    srv_a = rpc.RpcServer(SvcA(), service="a").start()
    srv_b = rpc.RpcServer(SvcB(), service="b").start()
    members = [srv_a.addr, srv_b.addr]
    node_a = raft.RaftNode("g9", srv_a.addr, members, applied_a.append, pool)
    node_b = raft.RaftNode("g9", srv_b.addr, members, applied_b.append, pool)
    raft.register_routes(routes_a, node_a)
    raft.register_routes(routes_b, node_b)
    node_a.start()
    node_b.start()
    try:
        deadline = time.time() + 10
        leader = None
        while time.time() < deadline and leader is None:
            for n in (node_a, node_b):
                if n.status()["role"] == "leader":
                    leader = n
            time.sleep(0.05)
        assert leader is not None, "no leader elected over HTTP"
        follower_addr = (srv_b.addr if leader is node_a else srv_a.addr)
        # the poison: an SDK-style 421 learned earlier on this address
        pool.get(follower_addr)._leader = leader.me
        for i in range(3):
            leader.propose({"seq": i})
        follower_applied = applied_b if leader is node_a else applied_a
        deadline = time.time() + 5
        while time.time() < deadline and len(follower_applied) < 3:
            time.sleep(0.05)
        assert [e.get("seq") for e in follower_applied
                if "seq" in e] == [0, 1, 2]
    finally:
        node_a.stop()
        node_b.stop()
        srv_a.stop()
        srv_b.stop()


def test_role_listener_fires_on_change_only():
    """handle_append runs _notify_role on EVERY heartbeat; a listener
    must hear each (role, leader) state once, not 20x/s — re-firing an
    exclusive-locking listener per heartbeat is the native-read-plane
    stall regression. A listener attached late must still hear the
    current state on the next heartbeat."""
    members, _ = make_cluster(3)
    try:
        leader = wait_leader(members)
        follower = next(m for m in members.values() if m is not leader)
        calls = []
        follower.node.role_listener = lambda r, l: calls.append((r, l))
        time.sleep(12 * raft.RaftNode.HEARTBEAT)
        assert calls == [("follower", leader.node.me)]
    finally:
        stop_all(members)


def test_concurrent_proposes_group_commit(tmp_path):
    """The proposal batcher: many concurrent propose() callers all
    succeed with their own results, entries apply in log order, and the
    drain count stays well below the proposal count (one replication
    round carries many entries). Also covers the per-index waiter path
    replacing the shared notify_all herd."""
    from cubefs_tpu.utils import metrics

    members, _ = make_cluster(2, tmp=tmp_path)
    try:
        leader = wait_leader(members)
        gid = leader.node.group_id
        p0 = metrics.raft_proposals.value(group=gid)
        b0 = metrics.raft_proposal_batches.value(group=gid)
        n_threads, per_thread = 12, 8
        results = {}
        gate = threading.Barrier(n_threads)

        def worker(t):
            gate.wait(timeout=10)
            for i in range(per_thread):
                results[(t, i)] = leader.node.propose(
                    {"seq": t * 1000 + i}, timeout=10.0)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        n = n_threads * per_thread
        assert len(results) == n
        # apply_fn here is list.append -> returns None; every propose
        # resolved (no exception) and the leader applied all entries
        seqs = sorted(e["seq"] for e in leader.applied if "seq" in e)
        assert seqs == sorted(t * 1000 + i for t in range(n_threads)
                              for i in range(per_thread))
        proposals = metrics.raft_proposals.value(group=gid) - p0
        drains = metrics.raft_proposal_batches.value(group=gid) - b0
        assert proposals == n
        assert drains < n, "no batching happened under contention"
    finally:
        stop_all(members)


def test_propose_timeout_cleans_up_waiter():
    """A timed-out proposer withdraws its waiter; the entry may still
    commit later without anyone to wake (no leak, no crash)."""
    members, pool = make_cluster(3, pool=FlakyPool())
    try:
        leader = wait_leader(members)
        for m in members.values():
            if m is not leader:
                pool.down.add(m.node.me)
        with pytest.raises(TimeoutError):
            leader.node.propose({"seq": 1}, timeout=0.3)
        assert not leader.node._waiters, "timed-out waiter leaked"
        pool.down.clear()
        leader2 = wait_leader(members)
        leader2.node.propose({"seq": 2}, timeout=5.0)
    finally:
        stop_all(members)


# ---------------- pipelined replication (CUBEFS_RAFT_PIPELINE) ----------------

def test_pipelined_appends_overlap_and_commit_in_order(monkeypatch):
    """With a window > 1 the leader ships optimistic appends (the
    pipelined counter moves, the in-flight histogram records widths)
    while commit/apply order stays exactly the propose order."""
    from cubefs_tpu.utils import metrics

    monkeypatch.setenv("CUBEFS_RAFT_PIPELINE", "4")
    monkeypatch.setenv("CUBEFS_RAFT_MUX", "1")
    members, _ = make_cluster(3)
    try:
        leader = wait_leader(members)
        gid = leader.node.group_id
        a0 = metrics.raft_pipelined_appends.value(group=gid)
        ths = []
        for i in range(30):
            t = threading.Thread(
                target=leader.node.propose, args=({"n": i},),
                kwargs={"timeout": 5.0})
            t.start()
            ths.append(t)
        for t in ths:
            t.join(timeout=10.0)
        wait_applied(members, 30)
        seen = [e["n"] for e in leader.applied]
        for m in members.values():
            assert [e["n"] for e in m.applied] == seen  # one total order
        assert sorted(seen) == list(range(30))
        assert metrics.raft_pipelined_appends.value(group=gid) > a0
        assert not leader.node._waiters
    finally:
        stop_all(members)


def test_pipeline_door_off_restores_legacy_path(monkeypatch):
    """CUBEFS_RAFT_PIPELINE=0: per-peer replication threads, no
    pipelined dispatches — and the cluster still replicates."""
    from cubefs_tpu.utils import metrics

    monkeypatch.setenv("CUBEFS_RAFT_PIPELINE", "0")
    members, _ = make_cluster(3)
    try:
        leader = wait_leader(members)
        gid = leader.node.group_id
        a0 = metrics.raft_pipelined_appends.value(group=gid)
        assert leader.node._pipeline == 0
        for i in range(5):
            leader.node.propose({"n": i}, timeout=5.0)
        wait_applied(members, 5)
        assert metrics.raft_pipelined_appends.value(group=gid) == a0
    finally:
        stop_all(members)
