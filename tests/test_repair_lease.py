"""A volume's pending unit repairs are leased together and decoded from
one read of its survivors (PR 38): the scheduler hands the siblings out
with the first task, the worker leaves every unit of the lease out of
its reads from the start, fills one step array a chunk and runs one
decode step a unit over it — and a lease of one task makes the calls it
made before. CPU, small sizes, seeded; the plain reference is
cellbench/reference.py through `reference_stripe`."""

import json
import os
import sys
import threading

import numpy as np
import pytest

from cellbench import registry, run, spec
from cubefs_tpu.blob.worker import RepairWorker, units_per_read
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec.batcher import admit
from cubefs_tpu.ops import rs_kernel
from cubefs_tpu.utils import metrics, rpc
from cubefs_tpu.utils import trace as tracelib
from test_put_stripe_rows import reference_stripe
from test_repair_rungs import Seeing, fill, fleet, hist, lose, rebuilt

RS = [cmode.CodeMode.EC12P4, cmode.CodeMode.EC6P6, cmode.CodeMode.EC3P3]
BIDS = 20


def recorded(c) -> list[tuple]:
    """Every call the node clients carry from now on, in order:
    (method, disk, chunk, bid, "ok" or the refusal's code)."""
    calls: list[tuple] = []

    def wrap(call):
        def wrapped(method, args=None, body=b"", timeout=30.0):
            what = (method, args.get("disk_id"), args.get("chunk_id"),
                    args.get("bid"))
            try:
                out = call(method, args, body, timeout)
            except rpc.RpcError as e:
                calls.append(what + (e.code,))
                raise
            calls.append(what + ("ok",))
            return out
        return wrapped

    for node in c.nodes:
        client = c.pool.get(f"node{node.node_id}")
        client.call = wrap(client.call)
    return calls


def sched_calls(c) -> list[tuple[str, dict]]:
    """(method, args) of every call the worker makes to the scheduler."""
    seen: list[tuple[str, dict]] = []
    real = c.worker.sched.call

    def call(method, args=None, body=b"", timeout=30.0):
        seen.append((method, dict(args)))
        return real(method, args, body, timeout)

    c.worker.sched.call = call
    return seen


def watched(c) -> tuple[Seeing, list[np.ndarray]]:
    """The worker on a codec that keeps what each engine call was
    handed, and the step arrays it took."""
    seeing, made = Seeing(), []
    c.worker.codec = admit("numpy-xor", seeing)
    c.worker._step_array = lambda shape: made.append(
        np.full(shape, 0xFF, dtype=np.uint8)) or made[-1]
    return seeing, made


def written_order(sizes: dict[int, int]) -> list[int]:
    """The bids as a unit's write-back carries them: group by group (a
    width rung each, in the order the listing first reaches them)."""
    by_rung: dict[int, list[int]] = {}
    for bid, size in sizes.items():
        by_rung.setdefault(rs_kernel.rung_width(size), []).append(bid)
    return [bid for group in by_rung.values() for bid in group]


def shard_sizes(c, vid, index) -> dict[int, int]:
    u = c.cm.get_volume(vid).units[index]
    meta, _ = c.pool.get(u.node_addr).call(
        "list_chunk", {"disk_id": u.disk_id, "chunk_id": u.chunk_id})
    return {bid: size for bid, size, _ in meta["shards"]}


def reads() -> tuple[float, float]:
    """(own, shared) of `cubefs_repair_task_reads_total` so far."""
    return (metrics.repair_task_reads.value(reads="own"),
            metrics.repair_task_reads.value(reads="shared"))


def reads_since(before) -> tuple[float, float]:
    return tuple(a - b for a, b in zip(reads(), before))


# ---------------- the scheduler's lease ----------------

def test_acquire_task_leases_a_volumes_pending_unit_repairs_together(
        tmp_path):
    c = fleet(tmp_path)
    a = fill(c, cmode.CodeMode.EC6P6, seed=13, count=3)[0][1].slices[0].vid
    b = fill(c, cmode.CodeMode.EC3P3, seed=14, count=3)[0][1].slices[0].vid
    ids = {}
    for v, index in ((b, 1), (a, 0)):
        ids[v, index] = c.sched.manual_migrate(v, index)
    # a pending task of another kind, in the middle of the queue
    c.sched.tasks["swap"] = {
        "task_id": "swap", "type": "shard_repair", "state": "pending",
        "lease_until": 0.0, "worker": None, "attempts": 0}
    for index in (7, 3, 9):
        ids[a, index] = c.sched.manual_migrate(a, index)
    # one of the volume's repairs is another worker's, one is done
    held, done = c.sched.tasks[ids[a, 3]], c.sched.tasks[ids[a, 9]]
    held.update(state="leased", worker="other", attempts=1,
                lease_until=1e18)
    done.update(state="done", worker="other", attempts=1)

    def key(x):
        return x["type"], x.get("vid"), x.get("unit_index")

    first = c.sched.acquire_task("w")
    assert key(first["task"]) == ("unit_repair", b, 1)
    assert first["siblings"] == []  # nothing else of its volume
    lease = c.sched.acquire_task("w")
    assert key(lease["task"]) == ("unit_repair", a, 0)
    assert [key(x) for x in lease["siblings"]] == [("unit_repair", a, 7)]
    for x in [lease["task"], *lease["siblings"]]:
        mine = c.sched.tasks[x["task_id"]]
        assert (mine["state"], mine["worker"], mine["attempts"]) == (
            "leased", "w", 1)
        assert mine["lease_until"] > 0 and x == mine
    assert (held["worker"], held["attempts"], done["state"]) == (
        "other", 1, "done")
    assert c.sched.tasks["swap"]["state"] == "pending"
    swap = c.sched.acquire_task("w")
    assert swap["task"]["task_id"] == "swap" and swap["siblings"] == []
    assert c.sched.acquire_task("w") is None

    # each task of a lease is renewed, completed and failed alone
    sibling = c.sched.tasks[ids[a, 7]]
    assert c.sched.renew_task(ids[a, 0], "w")
    c.sched.fail_task(ids[a, 0], "w", "its own error")
    assert c.sched.tasks[ids[a, 0]]["state"] == "pending"
    assert sibling["state"] == "leased" and "last_error" not in sibling
    # an expired sibling lease queues that task again, alone: the
    # volume's other repair, failed a moment ago, is pending and comes
    # with it, the one another worker still holds does not
    sibling["lease_until"] = 0.0
    again = c.sched.acquire_task("w2")
    assert [x["task_id"] for x in [again["task"], *again["siblings"]]] == [
        ids[a, 0], ids[a, 7]]
    assert [x["attempts"] for x in [again["task"], *again["siblings"]]] == [
        2, 2]
    assert held["worker"] == "other"
    sibling["lease_until"] = 0.0
    alone = c.sched.acquire_task("w3")
    assert alone["task"]["task_id"] == ids[a, 7] and not alone["siblings"]
    assert alone["task"]["attempts"] == 3
    assert c.sched.tasks[ids[a, 0]]["worker"] == "w2"

    # over the wire: the same shape, and an empty lease when none is left
    client = rpc.Client(c.sched)
    meta, _ = client.call("acquire_task", {"worker_id": "w4"})
    assert meta == {"task": None, "siblings": []}
    held["lease_until"] = 0.0
    meta, _ = client.call("acquire_task", {"worker_id": "w4"})
    assert meta["task"]["task_id"] == ids[a, 3] and meta["siblings"] == []


# ---------------- one read for the units of a lease ----------------

def lost_cases():
    """Two lost units of each plain RS mode, data+data and data+parity,
    and as many as one read serves of the modes that serve more."""
    for mode in RS:
        t = cmode.tactic(mode)
        yield pytest.param(mode, [0, 2], id=f"{mode.name}-data+data")
        yield pytest.param(mode, [1, t.n + 1], id=f"{mode.name}-data+parity")
        if units_per_read(t) > 2:
            wide = [0, 2, t.n + 1, 4, t.total - 1][:units_per_read(t)]
            yield pytest.param(mode, wide, id=f"{mode.name}-{len(wide)}-lost")


@pytest.mark.parametrize("mode,bads", lost_cases())
def test_the_lost_units_of_a_volume_are_rebuilt_from_one_read_of_the_survivors(
        tmp_path, mode, bads):
    t = cmode.tactic(mode)
    c = fleet(tmp_path)
    seeing, made = watched(c)
    objects = fill(c, mode, seed=7, count=BIDS)
    vid = objects[0][1].slices[0].vid
    k = len(bads)
    sizes = shard_sizes(c, vid, 0 if 0 not in bads else 1)
    units = c.cm.get_volume(vid).units
    old = [lose(c, vid, bad) for bad in bads]
    before = reads()
    tasks0, steps0 = hist(metrics.repair_steps_per_task)
    tracelib.reset_collector()
    calls, asked = recorded(c), sched_calls(c)

    assert c.worker.run_once() is True
    assert c.worker.run_once() is False  # one lease served them all
    assert c.worker.completed == k and c.worker.failed == 0
    assert {x["state"] for x in c.sched.tasks.values()} == {"done"}
    task_of = {x["unit_index"]: x for x in c.sched.tasks.values()}

    # one question to the scheduler leased them all
    me = {"worker_id": c.worker.worker_id}
    assert [m for m, _ in asked] == (
        ["acquire_task"] + ["complete_task"] * k + ["acquire_task"])
    assert all(a == me for m, a in asked if m == "acquire_task")

    # each survivor of a bid read once — the calls of ONE task, not of
    # k: the first n + 1 units that are not of the lease, in index
    # order, none refused
    survivors = [i for i in range(t.n + t.m) if i not in bads][:t.n + 1]
    first = units[survivors[0]]
    want = [("list_chunk", first.disk_id, first.chunk_id, None, "ok")]
    want += [("get_shard", units[i].disk_id, units[i].chunk_id, bid, "ok")
             for bid in sizes for i in survivors]
    want += [("put_shard", task_of[bad]["dest_disk"],
              task_of[bad]["dest_chunk"], bid, "ok")
             for bad in bads for bid in written_order(sizes)]
    assert calls == want
    assert sum(x[0] == "get_shard" for x in calls) == BIDS * (t.n + 1)

    # one step array a chunk — never two alive —, one two-row decode
    # step a lost unit over it
    rungs = {rs_kernel.rung_width(s) for s in sizes.values()}
    assert len(made) == len(rungs)
    assert len(seeing.seen) == k * len(made)
    for i, arr in enumerate(made):
        steps = seeing.seen[k * i:k * i + k]
        assert all(a is arr for _, a in steps)
        assert all(rows.shape == (rs_kernel.REPAIR_ROWS, t.n)
                   for rows, _ in steps)
        assert len({rows.tobytes() for rows, _ in steps}) == k
    assert reads_since(before) == (1, k - 1)
    tasks, steps = hist(metrics.repair_steps_per_task)
    assert (tasks - tasks0, steps - steps0) == (k, k * len(made))
    root = [s for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert [s["tags"]["units"] for s in root] == [k]

    # each rebuilt, bit-identical, off the lost disks
    for bad, lost_unit in zip(bads, old):
        for data, loc in objects:
            unit, meta, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert unit.disk_id != lost_unit.disk_id
            assert got == reference_stripe(data, t)[bad].tobytes()
    for data, loc in objects[::7]:
        assert c.access.get(loc) == data


@pytest.mark.parametrize("refusing", [None, 3],
                         ids=["one-loss", "second-unit-refuses"])
def test_a_lease_of_one_task_makes_the_calls_it_made_before(
        tmp_path, refusing):
    """One queued task (and, in the second case, a unit that refuses
    reads and was not reported — the two-loss stripe of before this PR):
    the calls are the parent's, in the parent's order — one question to
    the scheduler, the listing, n + 1 reads a bid in index order past
    the lost unit, one refused call to learn of the other, one step a
    chunk, the write-backs, one completion."""
    mode, t = cmode.CodeMode.EC6P6, cmode.tactic(cmode.CodeMode.EC6P6)
    c = fleet(tmp_path)
    seeing, made = watched(c)
    objects = fill(c, mode, seed=8, count=BIDS)
    vid = objects[0][1].slices[0].vid
    bad = 1
    sizes = shard_sizes(c, vid, 0)
    units = c.cm.get_volume(vid).units
    if refusing is not None:
        lose(c, vid, refusing, report=False)
    lose(c, vid, bad)
    before = reads()
    calls, asked = recorded(c), sched_calls(c)
    assert c.worker.run_once() and c.worker.completed == 1
    task = next(iter(c.sched.tasks.values()))

    me = {"worker_id": c.worker.worker_id}
    assert asked == [
        ("acquire_task", me),
        ("complete_task", {**me, "task_id": task["task_id"]})]
    order = [i for i in range(t.n + t.m) if i != bad]
    alive = [i for i in order if i != refusing]
    want = [("list_chunk", units[0].disk_id, units[0].chunk_id, None, "ok")]
    for k, bid in enumerate(sizes):
        for i in (order if k == 0 else alive):
            if i == refusing:
                want.append(("get_shard", units[i].disk_id,
                             units[i].chunk_id, bid, 503))
            elif i in alive[:t.n + 1]:
                want.append(("get_shard", units[i].disk_id,
                             units[i].chunk_id, bid, "ok"))
    want += [("put_shard", task["dest_disk"], task["dest_chunk"], bid, "ok")
             for bid in written_order(sizes)]
    assert calls == want
    rungs = {rs_kernel.rung_width(s) for s in sizes.values()}
    assert len(made) == len(seeing.seen) == len(rungs)
    assert all(a is m for (_, a), m in zip(seeing.seen, made))
    assert reads_since(before) == (1, 0)


# ---------------- failure stays per unit ----------------

def test_a_corrupted_survivor_refuses_both_writebacks(tmp_path):
    mode = cmode.CodeMode.EC6P6
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=9, count=12)
    loc = objects[5][1]
    vid, bid = loc.slices[0].vid, loc.slices[0].min_bid
    u = c.cm.get_volume(vid).units[7]  # the extra survivor of (0, 8)
    node = c.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    node.put_shard(u.disk_id, u.chunk_id, bid,
                   good[:-1] + bytes([good[-1] ^ 1]))
    lose(c, vid, 0)
    lose(c, vid, 8)
    before = reads()
    assert c.worker.run_once()
    assert c.worker.failed == 2 and c.worker.completed == 0
    assert reads_since(before) == (0, 0)
    for task in c.sched.tasks.values():
        assert task["state"] == "pending" and task["attempts"] == 1
        assert "disagrees with extra survivor 7" in task["last_error"]
        with pytest.raises(rpc.RpcError, match="no such chunk"):
            c.pool.get(task["dest_addr"]).call(
                "list_chunk", {"disk_id": task["dest_disk"],
                               "chunk_id": task["dest_chunk"]})


@pytest.mark.parametrize("mode,bads,refused", [
    (cmode.CodeMode.EC3P3, (0, 4), 4), (cmode.CodeMode.EC12P4, (1, 6, 14), 6)],
    ids=["second-of-two", "middle-of-three"])
def test_a_writeback_failure_fails_its_task_alone(tmp_path, mode, bads,
                                                  refused):
    """One unit's destination refuses: that task fails and is queued
    again with its error, the others complete and their units move; the
    next lease rebuilds that unit alone, reading the others where they
    now are."""
    t = cmode.tactic(mode)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=10, count=12)
    vid = objects[0][1].slices[0].vid
    old = {bad: lose(c, vid, bad) for bad in bads}
    task_of = {x["unit_index"]: x for x in c.sched.tasks.values()}
    failing = task_of[refused]
    client = c.pool.get(failing["dest_addr"])
    real = client.call

    def call(method, args=None, body=b"", timeout=30.0):
        if (method == "put_shard"
                and args["chunk_id"] == failing["dest_chunk"]):
            raise rpc.RpcError(500, "destination disk is full")
        return real(method, args, body, timeout)

    client.call = call
    before = reads()
    assert c.worker.run_once()
    client.call = real
    assert (c.worker.completed, c.worker.failed) == (len(bads) - 1, 1)
    own, shared = reads_since(before)
    assert own + shared == len(bads) - 1 and own == (refused != bads[0])
    again = c.sched.tasks[failing["task_id"]]
    assert again["state"] == "pending" and again["attempts"] == 1
    assert "disk is full" in again["last_error"]
    units = c.cm.get_volume(vid).units
    for bad in bads:
        moved = units[bad].disk_id != old[bad].disk_id
        assert moved == (bad != refused)
        assert (task_of[bad]["state"] == "done") == (bad != refused)

    before = reads()
    assert c.worker.run_once() and c.worker.completed == len(bads)
    assert reads_since(before) == (1, 0)
    for bad in bads:
        for data, loc in objects:
            unit, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert unit.disk_id != old[bad].disk_id
            assert got == reference_stripe(data, t)[bad].tobytes()


def test_a_completion_that_raises_fails_its_task_alone(tmp_path):
    """The scheduler refuses to record the first unit's move (a cluster
    manager that is not the leader): that task is failed and queued
    again, its sibling completes, and the worker's loop goes on."""
    mode, t = cmode.CodeMode.EC6P6, cmode.tactic(cmode.CodeMode.EC6P6)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=17, count=8)
    vid = objects[0][1].slices[0].vid
    old = [lose(c, vid, 2), lose(c, vid, 7)]
    first, second = sorted(c.sched.tasks.values(),
                           key=lambda x: x["unit_index"])
    real = c.worker.sched.call

    def call(method, args=None, body=b"", timeout=30.0):
        if (method, args.get("task_id")) == ("complete_task",
                                             first["task_id"]):
            raise rpc.RpcError(421, "not the leader")
        return real(method, args, body, timeout)

    c.worker.sched.call = call
    assert c.worker.run_once() is True
    c.worker.sched.call = real
    assert (c.worker.completed, c.worker.failed) == (1, 1)
    assert second["state"] == "done"
    assert first["state"] == "pending" and first["attempts"] == 1
    assert "not the leader" in first["last_error"]
    units = c.cm.get_volume(vid).units
    assert units[7].disk_id != old[1].disk_id
    assert units[2].disk_id == old[0].disk_id
    assert c.worker.run_once() and c.worker.completed == 2
    for bad in (2, 7):
        for data, loc in objects:
            _, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert got == reference_stripe(data, t)[bad].tobytes()


def test_a_read_that_fails_fails_every_task_of_the_lease(tmp_path):
    """Two lost and reported, two more refusing: 2 of EC3P3's 3 are
    left, so what the two tasks share fails both, each is queued again
    with the error, and nothing is written."""
    mode = cmode.CodeMode.EC3P3
    c = fleet(tmp_path)
    vid = fill(c, mode, seed=19, count=4)[0][1].slices[0].vid
    for index in (2, 3):
        lose(c, vid, index, report=False)
    for index in (0, 5):
        lose(c, vid, index)
    calls = recorded(c)
    tracelib.reset_collector()
    assert c.worker.run_once() is True
    assert (c.worker.completed, c.worker.failed) == (0, 2)
    for task in c.sched.tasks.values():
        assert task["state"] == "pending" and task["attempts"] == 1
        assert "2/3 survivors" in task["last_error"]
    assert not [x for x in calls if x[0] == "put_shard"]
    root = [s["tags"] for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert [x["units"] for x in root] == [2]
    assert "2/3 survivors" in root[0]["error"]


# ---------------- volumes whose units each read their own ----------------

@pytest.mark.parametrize("mode,bads", [
    (cmode.CodeMode.EC4P4L2, (1, 3)), (cmode.CodeMode.EC4P4MSR, (1, 6))],
    ids=["EC4P4L2", "EC4P4MSR"])
def test_an_lrc_and_an_msr_volume_run_their_tasks_apart(tmp_path, mode,
                                                        bads):
    """A local stripe and a sub-shard repair read for one unit: the two
    tasks come in one lease and run one after the other, each through
    the path it took before, each its own read. (The MSR units are an
    operator's moves: with two units lost a sub-shard repair has too few
    helpers and falls back, as it did before.)"""
    t = cmode.tactic(mode)
    assert units_per_read(t) == 1
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=11, count=10)
    vid = objects[0][1].slices[0].vid
    if t.is_msr():
        old = [c.cm.get_volume(vid).units[bad] for bad in bads]
        for bad in bads:
            c.sched.manual_migrate(vid, bad)
    else:
        old = [lose(c, vid, bad) for bad in bads]
    before = reads()
    pulled0 = metrics.repair_subshard_reads.value()
    tracelib.reset_collector()
    assert c.worker.run_once() and c.worker.completed == 2
    assert not c.worker.run_once() and c.worker.failed == 0
    assert reads_since(before) == (2, 0)
    root = [s["tags"] for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert [x["units"] for x in root] == [1, 1]
    if t.is_msr():  # the sub-shard path served both, not the fallback
        assert metrics.repair_subshard_reads.value() > pulled0
        assert all("msr_fallback" not in x for x in root)
    for bad, lost_unit in zip(bads, old):
        for data, loc in objects:
            unit, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert (unit.disk_id, unit.chunk_id) != (lost_unit.disk_id,
                                                     lost_unit.chunk_id)
            assert got == reference_stripe(data, t)[bad].tobytes()


@pytest.mark.parametrize("mode,width", [
    (cmode.CodeMode.EC12P4, 3), (cmode.CodeMode.EC6P6, 5),
    (cmode.CodeMode.EC3P3, 2), (cmode.CodeMode.EC4P4L2, 1),
    (cmode.CodeMode.EC4P4MSR, 1)], ids=lambda v: getattr(v, "name", v))
def test_a_shared_read_leaves_every_unit_its_checking_survivor(mode, width):
    t = cmode.tactic(mode)
    assert units_per_read(t) == width
    if width > 1:
        assert t.n + t.m - width >= t.n + 1


def test_three_lost_units_of_ec3p3_are_a_read_of_two_and_one_alone(tmp_path):
    """n + 1 units must be left to read: one lease holds the three
    tasks, two share a read, the third runs alone (with no unit left to
    check with, as it ran before)."""
    mode, t = cmode.CodeMode.EC3P3, cmode.tactic(cmode.CodeMode.EC3P3)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=12, count=8)
    vid = objects[0][1].slices[0].vid
    for bad in (0, 2, 5):
        lose(c, vid, bad)
    before = reads()
    tracelib.reset_collector()
    assert c.worker.run_once() and not c.worker.run_once()
    assert c.worker.completed == 3 and c.worker.failed == 0
    assert reads_since(before) == (2, 1)
    root = [s["tags"]["units"] for s in tracelib.finished_spans()
            if s["op"] == "worker.repair"]
    assert root == [2, 1]
    for bad in (0, 2, 5):
        for data, loc in objects:
            _, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert got == reference_stripe(data, t)[bad].tobytes()


def test_workers_that_race_for_leases_finish_every_task_once(tmp_path):
    """Three workers on one scheduler and three volumes of two moving
    units: a volume's two tasks go to one worker under one lock, no task
    is leased twice, and every unit is rebuilt exactly once."""
    modes = [cmode.CodeMode.EC6P6, cmode.CodeMode.EC3P3,
             cmode.CodeMode.EC12P4]
    c = fleet(tmp_path)
    filled = {m: fill(c, m, seed=20 + i, count=6)
              for i, m in enumerate(modes)}
    for m, objects in filled.items():
        for bad in (0, cmode.tactic(m).n):
            c.sched.manual_migrate(objects[0][1].slices[0].vid, bad)
    workers = [RepairWorker(rpc.Client(c.sched), c.cm_client, c.pool)
               for _ in range(3)]

    def drain(w):
        while w.run_once():
            pass

    threads = [threading.Thread(target=drain, args=(w,)) for w in workers]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert sum(w.completed for w in workers) == 6
    assert sum(w.failed for w in workers) == 0
    assert all(x["state"] == "done" and x["attempts"] == 1
               for x in c.sched.tasks.values())
    by_vid: dict[int, set] = {}
    for x in c.sched.tasks.values():
        by_vid.setdefault(x["vid"], set()).add(x["worker"])
    assert all(len(who) == 1 for who in by_vid.values())
    for m, objects in filled.items():
        t = cmode.tactic(m)
        for bad in (0, t.n):
            for data, loc in objects:
                _, _, got = rebuilt(c, loc.slices[0].vid, bad,
                                    loc.slices[0].min_bid)
                assert got == reference_stripe(data, t)[bad].tobytes()


# ---------------- what the benchmark reads ----------------

def test_the_counter_and_its_layer_entry_resolve(tmp_path):
    bench = spec.load_benchmark()
    entries = [m for m in bench["per_layer"]
               if m["name"].startswith("repair.shared_read_share")]
    assert entries == [{
        "name": "repair.shared_read_share-2disk", "unit": "%",
        "better": "higher", "source": "program_counter", "layer": "repair",
        "moves": "repair_rate", "workloads": ["disk-repair-2disk"]}]
    assert entries[0] in spec.metric_entries(bench, "disk-repair-2disk",
                                             "per_layer")
    assert entries[0] not in spec.metric_entries(bench, "disk-repair",
                                                 "per_layer")
    sp = spec.metric_spec("per_layer", "repair.shared_read_share-2disk")
    assert sp == {"reader": "counter_share",
                  "params": {"metric": "cubefs_repair_task_reads_total",
                             "labels": {"reads": "shared"}}}
    assert json.dumps(sp)  # a data file: nothing but JSON

    reader = spec.reader(sp["reader"])
    cell = run.Cell({}, {}, 1, 4.0, False)
    cell.registry = {("cubefs_repair_steps_per_task_count",
                      frozenset()): 3.0}  # a program without the counter
    assert reader.read(cell, **sp["params"]) is None
    c = fleet(tmp_path)
    vid = fill(c, cmode.CodeMode.EC6P6, seed=15,
               count=4)[0][1].slices[0].vid
    other = fill(c, cmode.CodeMode.EC3P3, seed=16,
                 count=4)[0][1].slices[0].vid
    # queued as an operator's moves: siblings with healthy sources
    # share a read under the same rule
    for v, index in ((vid, 2), (vid, 9), (other, 1), (vid, 4)):
        c.sched.manual_migrate(v, index)
    before = registry.snapshot()
    c.drain_worker()
    cell.registry = registry.delta(before, registry.snapshot())
    assert c.worker.completed == 4
    assert reader.read(cell, **sp["params"]) == 50.0  # 2 own, 2 shared


def test_two_disk_cell_reads_the_survivors_of_a_two_loss_volume_once(
        monkeypatch):
    """The tiny `disk-repair-2disk` cell: the two tasks of a volume
    that lost two units come in one lease, the second is decoded from
    the first's read of the survivors, and the cell's new per-layer
    metric says how often — while every check of the cell still holds."""
    from cubefs_tpu.codec import batcher

    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    traffic = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "cellbench", "traffic", "disk-repair-2disk.json")
    result = run.run_cell("disk-repair-2disk", 14, 4.0, True,
                          device_checks=False, traffic_path=traffic)
    detail = result["detail"]
    assert result["correct"] is True, detail
    checks, worker = detail["checks"], detail["notes"]["worker"]
    assert checks["faults"] == [] and worker["failed"] == 0
    assert checks["two_loss_stripes_checked"] >= 1
    two_lost = detail["notes"]["backlog"]["volumes_two_lost"]
    assert two_lost >= 1 and worker["completed"] == checks["tasks_done"]
    # a `run_once` is a lease: fewer of them than tasks
    assert worker["tasks_run"] < checks["tasks_done"]
    m = result["metrics"]
    share = m["repair.shared_read_share-2disk"]
    assert share["unit"] == "%" and 0 < share["value"] <= 50
    if worker["backlog_drained"]:
        assert share["value"] == pytest.approx(
            100 * two_lost / checks["tasks_done"])
    assert m["dispatch.compiles_in_window-2disk"]["value"] == 0
    assert m["dispatch.device_step_share-2disk"]["value"] == 100
    # the untraced line carries end-to-end metrics alone
    spec_names = {x["name"] for x in spec.metric_entries(
        spec.load_benchmark(), "disk-repair-2disk", "end_to_end")}
    assert "repair.shared_read_share-2disk" not in spec_names
