"""Repair backlog of a two-AZ LRC fleet (the `lrc-disk-repair` cell):
``closed_loop_lrc``'s AZ labels and policy on the fleet, then
``repair_backlog``'s fill, break, window and recording. Parameters
(traffic file): ``repair_backlog``'s ``object_bytes``, ``payload_pool``,
``fill_objects``, ``fill_clients``, ``ramp_tasks``, ``break``
("most_units") and ``verify`` ({"shards": n, "gets": n}).

Set-up is the two ready doors and nothing else: the worker's
``ready(object_bytes, policies)`` first — a worker whose door builds no
repair program of the file's codemode cannot run the cell and fails
there, at once — then ``closed_loop_lrc.setup`` (every disk and node
labelled with its AZ, the file's policy on the access handler, checked to
pick the file's codemode, the front door's ``ready`` and the seeded
payloads), called with the fill's sizes and clients. No shape and no
matrix is named. Then the fill (the first PUT of every volume alone, so
every run fills the same volumes), the disk with the most units lost and
reported, ``ramp_tasks`` tasks.

After the window: rebuilt shards — a global parity unit's, a local
parity unit's where the lost disk held one, then whatever the seed
draws — against ``cellbench/reference_lrc.py`` at their exact size, their
stored CRCs against zlib, GETs from repaired volumes and the whole
stored stripe of each GET's blob (all n + m + l units, CRCs, put
quorum: ``closed_loop_lrc.check_object``), every repaired
volume's local stripes each in one AZ of its own (so every rebuilt unit
landed in its AZ); and from the program's counters over the window: no
byte read across AZs, every unit rebuilt from its local stripe, every
rebuilt shard checked (none ``how="none"``), and no codec program built
after the ready doors. ``run.py`` adds the rest of ``correct``: every
step served by the configuration's engine, nothing compiled in the
window.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import reference, reference_lrc, registry
from . import closed_loop_lrc, closed_loop_sizes, common, repair_backlog

PROGRAMS = closed_loop_sizes.PROGRAMS
run = repair_backlog.run


class State(repair_backlog.State):
    def __init__(self):
        super().__init__()
        self.after_ready: dict = {}  # the registry when the doors closed


def _labels_policy_and_front_door(cell, size: int) -> list[bytes]:
    """``closed_loop_lrc.setup`` for PUTs of ``size`` bytes from the
    fill's clients; returns its seeded payloads."""
    tr = cell.traffic
    cell.traffic = {"ops": {"put": 1.0},
                    "sizes": [{"bytes": size, "weight": 1.0}],
                    "payload_pool": tr["payload_pool"],
                    "clients": tr["fill_clients"]}
    try:
        closed_loop_lrc.setup(cell)
    finally:
        cell.traffic = tr
    return cell.state.pools[0]


def setup(cell) -> None:
    from cubefs_tpu.blob.proxy import ProxyAllocator
    from cubefs_tpu.codec import codemode as cm

    tr, dep, config = cell.traffic, cell.dep, cell.config
    size = int(tr["object_bytes"])
    t0 = time.perf_counter()
    before = registry.snapshot()
    policies = [cm.Policy(**p) for p in config["policies"]]
    worker_steps = int(dep.worker.ready(size, policies,
                                        dep.access.cfg.blob_size))
    if not worker_steps:
        raise RuntimeError(f"the worker's ready door builds no repair "
                           f"program of {sorted(config['codemodes'])}")
    pool = _labels_policy_and_front_door(cell, size)
    st = cell.state = State()
    st.pool = pool
    st.after_ready = registry.snapshot()
    built = registry.delta(before, st.after_ready)
    t1 = time.perf_counter()

    def put(i):
        return i % len(st.pool), dep.access.put(st.pool[i % len(st.pool)])

    _, per_put, _ = common.put_shape(dep, size)
    per_volume = max(1, ProxyAllocator.VOLUME_REUSE // per_put)
    with ThreadPoolExecutor(int(tr["fill_clients"])) as ex:
        for first in range(0, int(tr["fill_objects"]), per_volume):
            last = min(first + per_volume, int(tr["fill_objects"]))
            st.objects.append(put(first))
            st.objects += list(ex.map(put, range(first + 1, last)))
    t2 = time.perf_counter()

    if tr.get("break", "most_units") != "most_units":
        raise ValueError(f"unknown break rule {tr['break']!r}")
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
    t3 = time.perf_counter()
    for _ in range(int(tr.get("ramp_tasks", 0))):
        dep.worker.run_once()
    t4 = time.perf_counter()
    cell.notes["ready"] = {
        "steps": {"worker": worker_steps,
                  "access": int(cell.notes.pop("ready_steps"))},
        "programs_built": {dict(lb).get("kernel", ""): int(v)
                           for (name, lb), v in built.items()
                           if name == PROGRAMS and v}}
    cell.notes["backlog"] = {
        "disk": st.disk, "az": dep.cm.disks[st.disk].az,
        "tasks": len(st.tasks),
        "unit_indexes": sorted(int(t["unit_index"]) for t in st.tasks)}
    cell.notes["setup_parts_s"] = {"ready_labels_payloads": t1 - t0,
                                   "fill": t2 - t1, "break_plan": t3 - t2,
                                   "ramp": t4 - t3}


def _picks(cell, done: list, rng) -> list:
    """The tasks whose rebuilt shards are compared: a global parity
    unit's, a local parity unit's where there is one, then the seed's
    draw."""
    _, t = common.codemode_of(cell.config, cell.dep.cm.get_volume(
        done[0]["vid"]).codemode)
    glob, local = t["n"] + t["m"], t["n"] + t["m"] + t["l"]
    order = [done[int(i)] for i in rng.permutation(len(done))]
    first = [next((x for x in order if lo <= int(x["unit_index"]) < hi),
                  None) for lo, hi in ((t["n"], glob), (glob, local))]
    first = [x for x in first if x is not None]
    return first + [x for x in order if x not in first]


def _window_faults(cell) -> tuple[list[str], dict]:
    """What the program's counters say of the window's repairs."""
    w = cell.registry
    pulled = {s: registry.total(w, "cubefs_repair_bytes_pulled_total",
                                scope=s) for s in ("az_local", "cross_az")}
    sources = {s: registry.total(w, "cubefs_repair_sources_total", source=s)
               for s in ("local", "global")}
    checks = {h: registry.total(w, "cubefs_repair_checks_total", how=h)
              for h in ("survivor", "derived", "none")}
    faults = []
    if pulled["cross_az"] or not pulled["az_local"]:
        faults.append(f"survivor bytes read in the window by scope: "
                      f"{pulled}; want every byte from the lost unit's AZ")
    if sources["global"] or not sources["local"]:
        faults.append(f"units repaired in the window by source: {sources}; "
                      f"want every one from its local stripe")
    if checks["none"] or not sum(checks.values()):
        faults.append(f"rebuilt shards by check in the window: {checks}; "
                      f"want every one checked before its write-back")
    return faults, {"bytes_pulled": pulled, "sources": sources,
                    "checks": checks}


def verify(cell) -> tuple[bool, dict]:
    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults, counted = _window_faults(cell)
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
        return False, {"tasks": len(st.tasks), "faults": faults[:10],
                       **counted}
    by_vid: dict[int, list] = {}
    for p, loc in st.objects:
        by_vid.setdefault(loc.slices[0].vid, []).append((p, loc))
    homes = {}
    for task in done:
        vid = task["vid"]
        if vid in homes:
            continue
        _, t = common.codemode_of(cell.config, dep.cm.get_volume(vid).codemode)
        homes[vid] = closed_loop_lrc.stripe_azs(dep, dep.cm.get_volume(vid), t)
        if (any(len(h) != 1 for h in homes[vid])
                or len({h[0] for h in homes[vid]}) != len(homes[vid])):
            faults.append(f"vid {vid}: after its repair the local stripes "
                          f"lie in AZs {homes[vid]}, want one AZ each and "
                          f"each its own")
    n_shards = n_gets = 0
    kinds = []
    for task in _picks(cell, done, rng)[:int(want.get("shards", 4))]:
        vid, bad = task["vid"], int(task["unit_index"])
        unit = dep.cm.get_volume(vid).units[bad]
        if unit.disk_id == st.disk:
            faults.append(f"vid {vid} unit {bad} is still on the broken "
                          f"disk after its task completed")
            continue
        p, loc = by_vid[vid][int(rng.integers(0, len(by_vid[vid])))]
        name, t = common.codemode_of(cell.config, loc.codemode)
        sl = loc.slices[0]
        k = int(rng.integers(0, sl.count))
        blob = st.pool[p][k * sl.blob_size:(k + 1) * sl.blob_size]
        ref = reference_lrc.stripe(blob, t["n"], t["m"], t["l"],
                                   t["az_count"], t["min_shard"])[bad]
        meta, got = dep.unit_call(unit, "get_shard", sl.min_bid + k)
        n_shards += 1
        kinds.append(bad)
        where = f"{name} vid {vid} unit {bad} bid {sl.min_bid + k}"
        if len(got) != ref.shape[0]:
            faults.append(f"{where}: the rebuilt shard holds {len(got)} B, "
                          f"the reference stripe's {ref.shape[0]}")
        elif got != ref.tobytes():
            faults.append(f"{where}: the rebuilt shard differs from the "
                          f"reference stripe's")
        if reference.crc32(got) != meta["crc"]:
            faults.append(f"{where}: stored crc is not zlib's")
        if n_gets < int(want.get("gets", 1)):
            # the whole stored stripe of the blob after its repair, both
            # AZs' parity among it (``closed_loop_lrc.check_object``)
            n_gets += 1
            if dep.access.get(loc) != st.pool[p]:
                faults.append(f"GET of an object in repaired volume {vid} "
                              f"differs from what was PUT")
            faults += closed_loop_lrc.check_object(cell, st.pool[p], loc,
                                                   k)[0]
    return not faults, {"tasks_done": len(done), "tasks": len(st.tasks),
                        "rebuilt_shards_checked": n_shards,
                        "units_checked": kinds, "gets": n_gets,
                        "local_stripe_azs": list(homes.values()),
                        "programs_built_after_ready": int(built),
                        "faults": faults[:10], **counted}
