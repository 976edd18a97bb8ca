"""Repair backlog: fill volumes, lose a disk, and run the RepairWorker
loop on the tasks the scheduler queues. Parameters (traffic file):

  object_bytes   size of every filled object
  payload_pool   distinct seeded payloads
  fill_objects   objects PUT in set-up (through the proxy allocator)
  fill_clients   client threads of the fill
  ramp_tasks     tasks the worker runs in set-up, before the window
  break          "most_units": the disk that holds the most volume units
  verify         {"shards": n, "gets": n} sample sizes after the window

Set-up fills, warms the encode shapes of the fill and then — from the
backlog itself — every (repair matrix, step shape) the tasks will use:
the matrix by the worker's own survivor rule and row function, zeros of
the step's shape through ``worker.codec.matrix_apply``, which also pays
each matrix's Pallas gate. Then it breaks the disk and reports it to the
scheduler. The window runs ``worker.run_once()`` until it ends; the task
in flight at the end finishes and does not count: a task writes its
shards back in one burst at its end, and a burst cut by the window's end
would be a part of a task over the whole of its time. One operation =
one rebuilt shard written back (kind
``repair_shard``); it is timed at the node client the worker writes
through, the only place the benchmark can see a write-back.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common


class State:
    def __init__(self):
        self.pool: list[bytes] = []
        self.objects: list[tuple[int, object]] = []  # (payload, Location)
        self.disk = -1
        self.tasks: list[dict] = []  # the backlog as queued
        self.writebacks: list[tuple] = []
        self.recording = False


def _recorded(call, st: State):
    """Record every put_shard the node clients carry while recording."""
    def wrapped(method, args=None, body=b"", timeout=30.0):
        if method != "put_shard" or not st.recording:
            return call(method, args, body, timeout)
        t0 = time.perf_counter()
        try:
            out = call(method, args, body, timeout)
            ok = True
            return out
        except Exception:
            ok = False
            raise
        finally:
            st.writebacks.append(("repair_shard", t0, time.perf_counter(),
                                  len(body), ok))

    return wrapped


def _survivors(t, bad: int) -> tuple[list[int], list[int]]:
    """(solving shards, wanted outputs) the worker picks for one lost
    unit of a healthy RS stripe: the first n + 1 survivors in index
    order, the last of them rebuilt beside the lost one as the check."""
    subs = [i for i in range(t.n + t.m) if i != bad][:t.n + 1]
    return subs[:t.n], sorted({bad, subs[t.n]})


def _warm_repairs(cell, st: State) -> list[tuple]:
    from cubefs_tpu.codec import codemode as cm
    from cubefs_tpu.ops import rs_kernel

    dep = cell.dep
    shapes: dict[tuple, None] = {}
    for task in st.tasks:
        vol = dep.cm.get_volume(task["vid"])
        t = cm.tactic(vol.codemode)
        bad = int(task["unit_index"])
        other = next(u for u in vol.units if u.index != bad)
        meta, _ = dep.unit_call(other, "list_chunk")
        by_size: dict[int, int] = {}
        for _, size, _ in meta["shards"]:
            by_size[size] = by_size.get(size, 0) + 1
        for size, count in by_size.items():
            full, rest = divmod(count, dep.worker.batch_stripes)
            for b in ([dep.worker.batch_stripes] if full else []) + (
                    [rest] if rest else []):
                shapes[(vol.codemode, bad, b, size)] = None
    for mode, bad, b, size in shapes:
        t = cm.tactic(mode)
        solve, wanted = _survivors(t, bad)
        rows = rs_kernel.reconstruct_rows(t.n, t.n + t.m, solve, wanted)
        dep.worker.codec.matrix_apply(
            rows, np.zeros((b, t.n, size), dtype=np.uint8))
    return sorted(shapes)


def setup(cell) -> None:
    from cubefs_tpu.blob.proxy import ProxyAllocator

    tr, dep = cell.traffic, cell.dep
    st = cell.state = State()
    size = int(tr["object_bytes"])
    t0 = time.perf_counter()
    st.pool = common.payload_pool(cell.seed, 0, int(tr["payload_pool"]), size)
    clients = int(tr["fill_clients"])
    common.warm_encode(dep, [size], clients)
    t1 = time.perf_counter()

    def put(i):
        return i % len(st.pool), dep.access.put(st.pool[i % len(st.pool)])

    # the proxy allocator rotates its volume when one is full, and two
    # PUTs that find it full together each open a new one: the first PUT
    # of every volume's worth goes alone, so every run fills the same
    # volumes with the same number of blobs
    _, per_put, _ = common.put_shape(dep, size)
    per_volume = max(1, ProxyAllocator.VOLUME_REUSE // per_put)
    with ThreadPoolExecutor(clients) as ex:
        for first in range(0, int(tr["fill_objects"]), per_volume):
            last = min(first + per_volume, int(tr["fill_objects"]))
            st.objects.append(put(first))
            st.objects += list(ex.map(put, range(first + 1, last)))
    t2 = time.perf_counter()

    if tr.get("break", "most_units") != "most_units":
        raise ValueError(f"unknown break rule {tr['break']!r}")
    units = {d: len(dep.cm.volumes_on_disk(d)) for d in dep.cm.disks}
    st.disk = max(sorted(units), key=lambda d: units[d])
    dep.wrap_node_calls(lambda call: _recorded(call, st))
    dep.node_of_disk(st.disk).break_disk(st.disk)
    queued = dep.sched.mark_disk_broken(st.disk)
    st.tasks = [dict(t) for t in dep.sched.tasks.values()
                if t.get("src_disk") == st.disk]
    if queued != units[st.disk] or len(st.tasks) != queued:
        raise RuntimeError(f"disk {st.disk} held {units[st.disk]} units, "
                           f"{queued} tasks queued, {len(st.tasks)} found")
    t3 = time.perf_counter()
    warmed = _warm_repairs(cell, st)
    t4 = time.perf_counter()
    for _ in range(int(tr.get("ramp_tasks", 0))):
        dep.worker.run_once()
    t5 = time.perf_counter()
    cell.notes["backlog"] = {
        "disk": st.disk, "tasks": len(st.tasks),
        "unit_indexes": sorted(int(t["unit_index"]) for t in st.tasks),
        "warmed": [[m, bad, b, s] for m, bad, b, s in warmed]}
    cell.notes["setup_parts_s"] = {"payloads_warm_encode": t1 - t0,
                                   "fill": t2 - t1, "break_plan": t3 - t2,
                                   "warm_repair": t4 - t3, "ramp": t5 - t4}


def run(cell) -> None:
    st, dep = cell.state, cell.dep
    span = cell.spans.span if cell.spans is not None else None
    st.recording = True
    ran = drained = 0
    while time.perf_counter() < cell.t1:
        mark = len(st.writebacks)
        if span:
            with span("worker.run_once"):
                got = dep.worker.run_once()
        else:
            got = dep.worker.run_once()
        if not got:
            drained = 1
            break
        ran += 1
        if time.perf_counter() > cell.t1:  # whole tasks only; failures stay
            st.writebacks[mark:] = [w for w in st.writebacks[mark:]
                                    if not w[4]]
    st.recording = False
    cell.ops = list(st.writebacks)
    cell.notes["worker"] = {"tasks_run": ran, "backlog_drained": drained,
                            "completed": dep.worker.completed,
                            "failed": dep.worker.failed}


def verify(cell) -> tuple[bool, dict]:
    """A sample of rebuilt shards against the reference stripe of the
    payload that was PUT there, and GETs of objects in repaired volumes."""
    from .. import reference

    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults: list[str] = []
    if dep.worker.failed:
        faults.append(f"{dep.worker.failed} repair task runs failed")
    done = [t for t in st.tasks
            if dep.sched.tasks[t["task_id"]]["state"] == "done"]
    by_vid: dict[int, list] = {}
    for p, loc in st.objects:
        by_vid.setdefault(loc.slices[0].vid, []).append((p, loc))
    n_shards = n_gets = 0
    for i in rng.permutation(len(done))[:int(want.get("shards", 4))]:
        task = done[int(i)]
        bad = int(task["unit_index"])
        unit = dep.cm.get_volume(task["vid"]).units[bad]
        if unit.disk_id == st.disk:
            faults.append(f"vid {task['vid']} unit {bad} is still on the "
                          f"broken disk after its task completed")
            continue
        p, loc = by_vid[task["vid"]][int(rng.integers(
            0, len(by_vid[task["vid"]])))]
        _, t = common.codemode_of(cell.config, loc.codemode)
        sl = loc.slices[0]
        k = int(rng.integers(0, sl.count))
        blob = st.pool[p][k * sl.blob_size:(k + 1) * sl.blob_size]
        ref = reference.stripe(blob, t["n"], t["m"], t["min_shard"])
        meta, got = dep.unit_call(unit, "get_shard", sl.min_bid + k)
        n_shards += 1
        if got != ref[bad].tobytes():
            faults.append(f"vid {task['vid']} unit {bad} bid "
                          f"{sl.min_bid + k}: the rebuilt shard differs "
                          f"from the reference stripe's")
        if reference.crc32(got) != meta["crc"]:
            faults.append(f"vid {task['vid']} unit {bad} bid "
                          f"{sl.min_bid + k}: stored crc is not zlib's")
        if n_gets < int(want.get("gets", 1)):
            n_gets += 1
            if dep.access.get(loc) != st.pool[p]:
                faults.append(f"GET of an object in repaired volume "
                              f"{task['vid']} differs from what was PUT")
    if not done:
        faults.append("no repair task completed")
    return not faults, {"tasks_done": len(done), "tasks": len(st.tasks),
                        "rebuilt_shards_checked": n_shards,
                        "gets": n_gets, "faults": faults[:10]}
