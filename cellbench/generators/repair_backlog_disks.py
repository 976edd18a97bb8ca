"""Repair backlog after several disks are lost: ``repair_backlog``'s
fill, window and recording, with stripes that miss more than one unit.
Parameters (traffic file), beside ``repair_backlog``'s ``object_bytes``,
``payload_pool``, ``fill_objects``, ``fill_clients``, ``ramp_tasks``:

  broken_disks   how many disks are lost (2)
  break          "most_units_pair": the disk that holds the most volume
                 units, then the disk that shares the most volumes with
                 it (ties to the lower disk id), so that as many stripes
                 as the placement allows miss two units
  verify         {"shards": n, "gets": n} sample sizes after the window

Set-up fills, breaks the disks and reports them to the scheduler, then
warms one zero step per step *shape* the backlog will run — (codemode,
rows, stripes, shard size), a zero coefficient matrix through
``worker.codec.matrix_apply`` — and never a repair matrix by name: which
matrix a task brings depends on which of its volume's two tasks runs
first, and nearly every task of the window brings one the process has
not seen. A program that compiles per matrix compiles in the window, and
the run is then not ``correct``. The window is ``repair_backlog.run``:
``worker.run_once()`` on the backlog, whole tasks only, one operation =
one rebuilt shard written back (kind ``repair_shard``).

One task rebuilds one unit, as upstream schedules it; the scheduler
leases the two tasks of a volume that lost two units together, and the
worker decodes both units from one read of the survivors (one
``run_once`` of the window is such a lease).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import common, repair_backlog

run = repair_backlog.run


def _pick_disks(dep, count: int) -> list[int]:
    """The ``most_units_pair`` rule, continued for ``count`` disks."""
    vols = {d: {vid for vid, _ in dep.cm.volumes_on_disk(d)}
            for d in sorted(dep.cm.disks)}
    units = {d: len(dep.cm.volumes_on_disk(d)) for d in vols}
    picked = [max(vols, key=lambda d: units[d])]
    while len(picked) < count:
        lost = set().union(*(vols[d] for d in picked))
        picked.append(max((d for d in vols if d not in picked),
                          key=lambda d: len(vols[d] & lost)))
    return picked


def _warm_shapes(cell, st) -> list[tuple]:
    """One zero step per (codemode, rows, stripes, shard size) of the
    backlog: ``rows`` is 2 (the lost unit and the checking survivor)
    while a stripe has n + 1 readable units left, else 1."""
    from cubefs_tpu.codec import codemode as cm

    dep = cell.dep
    lost = set(st.disks)
    shapes: dict[tuple, None] = {}
    for task in st.tasks:
        vol = dep.cm.get_volume(task["vid"])
        t = cm.tactic(vol.codemode)
        alive = [u for u in vol.units[:t.n + t.m] if u.disk_id not in lost]
        rows = 2 if len(alive) > t.n else 1
        meta, _ = dep.unit_call(alive[0], "list_chunk")
        by_size: dict[int, int] = {}
        for _, size, _ in meta["shards"]:
            by_size[size] = by_size.get(size, 0) + 1
        for size, count in by_size.items():
            full, rest = divmod(count, dep.worker.batch_stripes)
            for b in ([dep.worker.batch_stripes] if full else []) + (
                    [rest] if rest else []):
                shapes[(vol.codemode, rows, b, size)] = None
    for mode, rows, b, size in shapes:
        n = cm.tactic(mode).n
        dep.worker.codec.matrix_apply(
            np.zeros((rows, n), dtype=np.uint8),
            np.zeros((b, n, size), dtype=np.uint8))
    return sorted(shapes)


def setup(cell) -> None:
    from cubefs_tpu.blob.proxy import ProxyAllocator

    tr, dep = cell.traffic, cell.dep
    st = cell.state = repair_backlog.State()
    size = int(tr["object_bytes"])
    t0 = time.perf_counter()
    st.pool = common.payload_pool(cell.seed, 0, int(tr["payload_pool"]), size)
    clients = int(tr["fill_clients"])
    common.warm_encode(dep, [size], clients)
    t1 = time.perf_counter()

    def put(i):
        return i % len(st.pool), dep.access.put(st.pool[i % len(st.pool)])

    # the first PUT of every volume's worth goes alone (repair_backlog)
    _, per_put, _ = common.put_shape(dep, size)
    per_volume = max(1, ProxyAllocator.VOLUME_REUSE // per_put)
    with ThreadPoolExecutor(clients) as ex:
        for first in range(0, int(tr["fill_objects"]), per_volume):
            last = min(first + per_volume, int(tr["fill_objects"]))
            st.objects.append(put(first))
            st.objects += list(ex.map(put, range(first + 1, last)))
    t2 = time.perf_counter()

    if tr.get("break") != "most_units_pair":
        raise ValueError(f"unknown break rule {tr.get('break')!r}")
    st.disks = _pick_disks(dep, int(tr["broken_disks"]))
    st.disk = st.disks[0]
    held = {d: len(dep.cm.volumes_on_disk(d)) for d in st.disks}
    dep.wrap_node_calls(lambda call: repair_backlog._recorded(call, st))
    for d in st.disks:
        dep.node_of_disk(d).break_disk(d)
    queued = sum(dep.sched.mark_disk_broken(d) for d in st.disks)
    st.tasks = [dict(t) for t in dep.sched.tasks.values()
                if t.get("src_disk") in st.disks]
    if queued != sum(held.values()) or len(st.tasks) != queued:
        raise RuntimeError(f"disks {st.disks} held {held} units, {queued} "
                           f"tasks queued, {len(st.tasks)} found")
    by_vid: dict[int, int] = {}
    for t in st.tasks:
        by_vid[t["vid"]] = by_vid.get(t["vid"], 0) + 1
    t3 = time.perf_counter()
    warmed = _warm_shapes(cell, st)
    t4 = time.perf_counter()
    for _ in range(int(tr.get("ramp_tasks", 0))):
        dep.worker.run_once()
    t5 = time.perf_counter()
    cell.notes["backlog"] = {
        "disks": st.disks, "units_on_disk": held, "tasks": len(st.tasks),
        "volumes_two_lost": sum(1 for n in by_vid.values() if n > 1),
        "unit_indexes": sorted([t["vid"], int(t["unit_index"])]
                               for t in st.tasks),
        "warmed_shapes": [list(s) for s in warmed]}
    cell.notes["setup_parts_s"] = {"payloads_warm_encode": t1 - t0,
                                   "fill": t2 - t1, "break_plan": t3 - t2,
                                   "warm_repair": t4 - t3, "ramp": t5 - t4}


def verify(cell) -> tuple[bool, dict]:
    """Rebuilt shards against the reference stripe's row — both lost
    units of a two-loss stripe where both tasks are done — and GETs of
    objects in volumes after all their repairs."""
    from .. import reference

    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults: list[str] = []
    if dep.worker.failed:
        faults.append(f"{dep.worker.failed} repair task runs failed")
    done: dict[int, list[int]] = {}
    open_vids = set()
    for t in st.tasks:
        if dep.sched.tasks[t["task_id"]]["state"] == "done":
            done.setdefault(t["vid"], []).append(int(t["unit_index"]))
        else:
            open_vids.add(t["vid"])
    objects: dict[int, list] = {}
    for p, loc in st.objects:
        objects.setdefault(loc.slices[0].vid, []).append((p, loc))
    # volumes whose two lost units are both rebuilt come first
    vids = [sorted(done)[int(i)] for i in rng.permutation(len(done))]
    vids.sort(key=lambda v: -len(done[v]))
    n_shards = n_gets = n_pairs = 0
    for vid in vids:
        if n_shards >= int(want.get("shards", 4)):
            break
        vol = dep.cm.get_volume(vid)
        p, loc = objects[vid][int(rng.integers(0, len(objects[vid])))]
        _, t = common.codemode_of(cell.config, loc.codemode)
        sl = loc.slices[0]
        k = int(rng.integers(0, sl.count))
        blob = st.pool[p][k * sl.blob_size:(k + 1) * sl.blob_size]
        ref = reference.stripe(blob, t["n"], t["m"], t["min_shard"])
        n_pairs += len(done[vid]) > 1
        for bad in sorted(done[vid]):
            unit = vol.units[bad]
            if unit.disk_id in st.disks:
                faults.append(f"vid {vid} unit {bad} is still on a broken "
                              f"disk after its task completed")
                continue
            meta, got = dep.unit_call(unit, "get_shard", sl.min_bid + k)
            n_shards += 1
            if got != ref[bad].tobytes():
                faults.append(f"vid {vid} unit {bad} bid {sl.min_bid + k}: "
                              f"the rebuilt shard differs from the "
                              f"reference stripe's")
            if reference.crc32(got) != meta["crc"]:
                faults.append(f"vid {vid} unit {bad} bid {sl.min_bid + k}: "
                              f"stored crc is not zlib's")
        if vid not in open_vids and n_gets < int(want.get("gets", 1)):
            n_gets += 1
            if dep.access.get(loc) != st.pool[p]:
                faults.append(f"GET of an object in volume {vid}, after "
                              f"all its repairs, differs from what was PUT")
    if not done:
        faults.append("no repair task completed")
    return not faults, {"tasks_done": sum(len(v) for v in done.values()),
                        "tasks": len(st.tasks),
                        "rebuilt_shards_checked": n_shards,
                        "two_loss_stripes_checked": n_pairs,
                        "gets": n_gets, "faults": faults[:10]}
