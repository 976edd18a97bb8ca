"""Repair backlog of a fleet of any-size objects: fill the volumes with
``closed_loop_sizes``' sizes (warp's ``--obj.randsize``), lose a disk,
and run the RepairWorker loop on the tasks the scheduler queues — one a
lost unit, over every size-class codemode at once, each volume a mix of
the sizes its objects had. ``repair_backlog``'s window and recording.
Parameters (traffic file):

  sizes            as ``closed_loop_sizes``: one byte count a stratum,
                   drawn from ``--seed``
  fill_objects     doublings x strata: every size is PUT once
  fill_order_seed  the order of the fill and each object's payload are
                   drawn from THIS number, not from ``--seed``: every
                   seed fills the same volumes with the same strata
                   (sizes differ only inside a stratum) and so breaks
                   the same disk with the same task list
  fill_clients     client threads of the fill
  payload_pool     seeded buffers of ``max_bytes``; a PUT sends a prefix
  broken_disks     1
  break            "most_units": the disk that holds the most volume
                   units, ties to the lower disk id
  ramp_tasks       tasks the worker runs in set-up, before the window
  verify           {"shards": n, "gets": n} sample sizes after the window

Set-up warms through two calls and names no shape and no matrix: the
worker's ``ready(max_bytes)`` and the front door's — what a deployment
does once at start-up from its policies and its largest object. A
program that lacks the worker's door cannot run the cell and fails
there, at once. The fill is planned from the file alone: the proxy
allocator gives a codemode's PUTs one volume until ``VOLUME_REUSE``
blobs are in it, so the objects of each volume follow from the order;
the volumes are filled one after another in the order of their first
object, that first PUT alone (it opens the volume) and the rest by
``fill_clients`` threads, and set-up raises if any planned volume's
objects did not land in one volume of their own. After the window:
rebuilt shards of every codemode (the shortest object's and a
several-blob object's last blob among them) against
``cellbench/reference.py`` at their exact size and length, stored CRCs
against zlib, a GET a codemode from a repaired volume; and the run is
not ``correct`` if a codec program was built after the ready doors.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import reference, registry
from . import closed_loop_sizes, common, repair_backlog

PROGRAMS = closed_loop_sizes.PROGRAMS
run = repair_backlog.run


class State(repair_backlog.State):
    def __init__(self):
        super().__init__()
        self.sizes: np.ndarray = np.zeros(0, dtype=np.int64)  # by stratum
        self.payload: np.ndarray = np.zeros(0, dtype=np.int64)
        self.done: dict[int, object] = {}  # stratum -> Location
        self.after_ready: dict = {}  # the registry when the doors closed


def _shape(dep, size: int) -> tuple[int, int]:
    """(codemode, blobs) of a PUT of ``size`` bytes."""
    from cubefs_tpu.codec import codemode as cm

    cfg = dep.access.cfg
    return (int(cm.select_codemode(cfg.policies, size)),
            -(-size // cfg.blob_size))


def class_sizes(dep, seed: int, spec: dict) -> tuple[np.ndarray, list]:
    """(one byte count a stratum, its (codemode, blobs)): ``draw_sizes``
    of this seed. A stratum whose lower edge is a size-class boundary
    (256 KiB, 4 MiB, a whole blob) draws that very byte count about one
    seed in a thousand, which is the class below: it is taken one byte
    larger, so a stratum is of one codemode and blob count whatever the
    seed — the middle of the stratum's."""
    sizes = closed_loop_sizes.draw_sizes(seed, spec)
    lo, hi = int(spec["min_bytes"]), int(spec["max_bytes"])
    n = len(sizes)
    middle = 2.0 ** (np.log2(lo) + (np.arange(n) + 0.5)
                     * (np.log2(hi / lo) / n))
    shapes = [_shape(dep, int(m)) for m in middle]
    for k, shape in enumerate(shapes):
        if _shape(dep, int(sizes[k])) != shape:
            sizes[k] += 1
            if _shape(dep, int(sizes[k])) != shape:
                raise RuntimeError(f"stratum {k} spans two size classes")
    return sizes, shapes


def plan_volumes(order, shapes: list, reuse: int) -> list[tuple[int, list]]:
    """[(codemode, objects)] a volume, in the order of the volumes'
    first objects: what ``ProxyAllocator`` makes of PUTs in ``order``,
    one codemode's volume rotating when the next PUT's blobs would pass
    ``reuse``."""
    volumes: list[tuple[int, list]] = []
    current: dict[int, tuple[list, int]] = {}  # mode -> (objects, blobs)
    for k in order:
        mode, blobs = shapes[int(k)]
        objects, used = current.get(mode, (None, 0))
        if objects is None or used + blobs > reuse:
            objects, used = [], 0
            volumes.append((mode, objects))
        objects.append(int(k))
        current[mode] = (objects, used + blobs)
    return volumes


def _data(st: State, k: int) -> memoryview:
    return memoryview(st.pool[int(st.payload[k])])[:int(st.sizes[k])]


def setup(cell) -> None:
    from cubefs_tpu.blob.proxy import ProxyAllocator

    tr, dep = cell.traffic, cell.dep
    st = cell.state = State()
    spec = tr["sizes"]
    largest = int(spec["max_bytes"])
    t0 = time.perf_counter()
    before = registry.snapshot()
    steps = {"worker": int(dep.worker.ready(largest)),
             "access": int(dep.access.ready(largest))}
    st.after_ready = registry.snapshot()
    built = registry.delta(before, st.after_ready)
    t1 = time.perf_counter()

    st.sizes, shapes = class_sizes(dep, cell.seed, spec)
    if int(tr["fill_objects"]) != len(st.sizes):
        raise ValueError(f"fill_objects {tr['fill_objects']} is not "
                         f"doublings x strata = {len(st.sizes)}")
    rng = np.random.default_rng([int(tr["fill_order_seed"]), 6])
    order = rng.permutation(len(st.sizes))
    st.payload = rng.integers(0, int(tr["payload_pool"]), len(st.sizes))
    st.pool = common.payload_pool(cell.seed, 0, int(tr["payload_pool"]),
                                  largest)
    volumes = plan_volumes(order, shapes, ProxyAllocator.VOLUME_REUSE)
    t2 = time.perf_counter()

    def put(k):
        st.done[k] = dep.access.put(_data(st, k))

    with ThreadPoolExecutor(int(tr["fill_clients"])) as ex:
        for _, objects in volumes:
            put(objects[0])
            list(ex.map(put, objects[1:]))
    vids = [{st.done[k].slices[0].vid for k in objects}
            for _, objects in volumes]
    if any(len(v) != 1 for v in vids) or len(set().union(*vids)) != len(vids):
        raise RuntimeError("the fill did not land as planned: the objects "
                           "of a planned volume are in volumes "
                           f"{[sorted(v) for v in vids if len(v) != 1][:3]}")
    st.objects = [(k, st.done[k]) for k in sorted(st.done)]
    t3 = time.perf_counter()

    if tr.get("break") != "most_units" or int(tr["broken_disks"]) != 1:
        raise ValueError(f"unknown break rule {tr.get('break')!r} x "
                         f"{tr.get('broken_disks')}")
    units = {d: len(dep.cm.volumes_on_disk(d)) for d in dep.cm.disks}
    st.disk = max(sorted(units), key=lambda d: units[d])
    dep.wrap_node_calls(lambda call: repair_backlog._recorded(call, st))
    dep.node_of_disk(st.disk).break_disk(st.disk)
    queued = dep.sched.mark_disk_broken(st.disk)
    st.tasks = [dict(t) for t in dep.sched.tasks.values()
                if t.get("src_disk") == st.disk]
    if queued != units[st.disk] or len(st.tasks) != queued:
        raise RuntimeError(f"disk {st.disk} held {units[st.disk]} units, "
                           f"{queued} tasks queued, {len(st.tasks)} found")
    t4 = time.perf_counter()
    for _ in range(int(tr.get("ramp_tasks", 0))):
        dep.worker.run_once()
    t5 = time.perf_counter()

    by_mode: dict[str, int] = {}
    for mode, _ in volumes:
        name, _ = common.codemode_of(cell.config, mode)
        by_mode[name] = by_mode.get(name, 0) + 1
    modes = {v: m for (m, _), vs in zip(volumes, vids) for v in vs}
    cell.notes["ready"] = {
        "steps": steps,
        "programs_built": {dict(lb).get("kernel", ""): int(v)
                           for (name, lb), v in built.items()
                           if name == PROGRAMS and v}}
    cell.notes["fill"] = {"objects": len(st.done),
                          "bytes": int(st.sizes.sum()),
                          "volumes": by_mode}
    cell.notes["backlog"] = {
        "disk": st.disk, "tasks": len(st.tasks),
        "units": sorted([common.codemode_of(cell.config,
                                            modes[t["vid"]])[0],
                         t["vid"], int(t["unit_index"])]
                        for t in st.tasks)}
    cell.notes["setup_parts_s"] = {
        "ready": t1 - t0, "payloads_plan": t2 - t1, "fill": t3 - t2,
        "break_plan": t4 - t3, "ramp": t5 - t4}


def _blob(cell, k: int, loc, b: int) -> bytes:
    """Blob ``b`` of object ``k`` as the port stores it: every blob of a
    PUT at the first blob's shard size, a short last one zero-padded to
    a whole blob (closed_loop_sizes._stripe_faults)."""
    sl = loc.slices[0]
    data = bytes(_data(cell.state, k))
    if sl.count > 1:
        data = data.ljust(sl.count * sl.blob_size, b"\0")
    return data[b * sl.blob_size:(b + 1) * sl.blob_size]


def verify(cell) -> tuple[bool, dict]:
    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults: list[str] = []
    if dep.worker.failed:
        faults.append(f"{dep.worker.failed} repair task runs failed")
    built = registry.total(
        registry.delta(st.after_ready, registry.snapshot()), PROGRAMS)
    if built:
        faults.append(f"{int(built)} codec programs were built after the "
                      f"ready doors")
    done = [t for t in st.tasks
            if dep.sched.tasks[t["task_id"]]["state"] == "done"]
    if not done:
        faults.append("no repair task completed")
    in_vid: dict[int, list] = {}
    for k, loc in st.objects:
        in_vid.setdefault(loc.slices[0].vid, []).append((k, loc))
    by_mode: dict[int, list] = {}  # codemode -> [(task, object, Location)]
    for i in rng.permutation(len(done)):
        task = done[int(i)]
        for k, loc in in_vid[task["vid"]]:
            by_mode.setdefault(loc.codemode, []).append((task, k, loc))
    per = max(1, int(want.get("shards", 12)) // max(1, len(by_mode)))
    n_shards = n_gets = n_last_blobs = 0
    shortest = None
    for mode, cands in sorted(by_mode.items()):
        name, t = common.codemode_of(cell.config, mode)
        # the shortest object of the codemode's repaired volumes, a
        # several-blob object's last blob, then whatever the seed draws
        picks = [min(cands, key=lambda c: st.sizes[c[1]])]
        picks += [c for c in cands if c[2].slices[0].count > 1][:1]
        for j in rng.permutation(len(cands)):
            if cands[int(j)] not in picks:
                picks.append(cands[int(j)])
        for task, k, loc in picks[:per]:
            bad = int(task["unit_index"])
            unit = dep.cm.get_volume(task["vid"]).units[bad]
            if unit.disk_id == st.disk:
                faults.append(f"vid {task['vid']} unit {bad} is still on "
                              f"the broken disk after its task completed")
                continue
            sl = loc.slices[0]
            b = sl.count - 1
            n_last_blobs += sl.count > 1
            ref = reference.stripe(_blob(cell, k, loc, b), t["n"], t["m"],
                                   t["min_shard"])[bad]
            meta, got = dep.unit_call(unit, "get_shard", sl.min_bid + b)
            n_shards += 1
            shortest = min(len(got), shortest or len(got))
            where = f"{name} vid {task['vid']} unit {bad} bid {sl.min_bid + b}"
            if len(got) != ref.shape[0]:
                faults.append(f"{where}: the rebuilt shard holds "
                              f"{len(got)} B, the reference stripe's "
                              f"{ref.shape[0]}")
            elif got != ref.tobytes():
                faults.append(f"{where}: the rebuilt shard differs from "
                              f"the reference stripe's")
            if reference.crc32(got) != meta["crc"]:
                faults.append(f"{where}: stored crc is not zlib's")
        if n_gets < int(want.get("gets", 3)):
            _, k, loc = picks[0]
            n_gets += 1
            try:
                same = dep.access.get(loc) == _data(st, k)
            except Exception as e:  # a GET that fails is a fault too
                same = False
                cell.notes.setdefault("errors", []).append(repr(e)[:200])
            if not same:
                faults.append(f"GET of a {name} object in a repaired "
                              f"volume differs from what was PUT")
    return not faults, {"tasks_done": len(done), "tasks": len(st.tasks),
                        "rebuilt_shards_checked": n_shards,
                        "codemodes_checked": len(by_mode),
                        "shortest_shard_checked": shortest,
                        "last_blobs_of_several_checked": int(n_last_blobs),
                        "programs_built_after_ready": int(built),
                        "gets": n_gets, "faults": faults[:10]}
