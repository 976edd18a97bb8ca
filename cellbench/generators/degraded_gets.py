"""Degraded GETs: fill volumes, lose a disk, and GET the objects that
lost a data unit with it, while no repair runs (the hours between a
disk's loss and its rebuild reaching the volumes). Parameters (traffic
file):

  object_bytes   size of every filled object (and the door's largest)
  payload_pool   distinct seeded payloads
  fill_objects   objects PUT in set-up (through the proxy allocator)
  fill_clients   client threads of the fill
  break          "most_units": the disk that holds the most volume units
  clients        closed-loop GET clients in the window, no think time
  verify         {"units": n, "stripes": n} sample sizes after the window

Set-up: ``access.ready(object_bytes)`` — the deployment's door, the only
warm-up: every program a PUT or a degraded GET of up to that size can
ask for — then the fill (the first PUT of each volume alone, as in
``repair_backlog``), then the disk broken and reported to the scheduler,
which queues its repair tasks; no worker leases one. The GET keys are
the objects whose volume's unit on the lost disk is a data unit (index
< n), drawn uniformly in one seeded order that the clients take from one
count: every blob of every GET is decoded (``global_reconstruct``), and
the decodes of concurrent GETs that lost the same unit meet in the
batcher. One operation = one whole-object GET (kind ``get``), compared
with its payload as it returns.

``verify``: every GET of the window returned its payload; ``units``
decoded data units (at least one per lost index met) equal
``cellbench/reference_decode.py``'s decode of survivors read off the
disks at their full size, and the reference stripe's; the window's
``cubefs_reconstruct_total{path="global"}`` covers every blob its GETs
read; no program was built in the window; ``stripes`` objects' stored
units are the reference stripe's; no repair task ran.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext

import numpy as np

from .. import reference, reference_decode, registry
from . import common

SCHEDULE = 1 << 16  # keys drawn in set-up; cycled if a window outlasts them
PROGRAMS = "cubefs_codec_programs_total"
RECONSTRUCTS = "cubefs_reconstruct_total"
STRIPES = "cubefs_codec_batch_stripes_per_step"


class State:
    def __init__(self):
        self.pool: list[bytes] = []
        self.objects: list[tuple[int, object]] = []  # (payload, Location)
        self.disk = -1
        self.tasks: list[str] = []  # the backlog's task ids
        self.targets: list[tuple[int, object, int]] = []  # + lost index
        self.keys: np.ndarray | None = None


def _fill(cell, st: State, size: int) -> None:
    from cubefs_tpu.blob.proxy import ProxyAllocator

    tr, dep = cell.traffic, cell.dep

    def put(i):
        return i % len(st.pool), dep.access.put(st.pool[i % len(st.pool)])

    # the proxy allocator rotates its volume when one is full, and two
    # PUTs that find it full together each open a new one: the first PUT
    # of every volume's worth goes alone, so every run fills the same
    # volumes with the same number of blobs
    _, per_put, _ = common.put_shape(dep, size)
    per_volume = max(1, ProxyAllocator.VOLUME_REUSE // per_put)
    total = int(tr["fill_objects"])
    with ThreadPoolExecutor(int(tr["fill_clients"])) as ex:
        for first in range(0, total, per_volume):
            last = min(first + per_volume, total)
            st.objects.append(put(first))
            st.objects += list(ex.map(put, range(first + 1, last)))


def _break(cell, st: State) -> None:
    dep = cell.dep
    if cell.traffic.get("break", "most_units") != "most_units":
        raise ValueError(f"unknown break rule {cell.traffic['break']!r}")
    units = {d: len(dep.cm.volumes_on_disk(d)) for d in dep.cm.disks}
    st.disk = max(sorted(units), key=lambda d: units[d])
    dep.node_of_disk(st.disk).break_disk(st.disk)
    queued = dep.sched.mark_disk_broken(st.disk)
    st.tasks = [tid for tid, t in dep.sched.tasks.items()
                if t.get("src_disk") == st.disk]
    if queued != units[st.disk] or len(st.tasks) != queued:
        raise RuntimeError(f"disk {st.disk} held {units[st.disk]} units, "
                           f"{queued} tasks queued, {len(st.tasks)} found")


def _lost_index(dep, disk: int, vid: int) -> int | None:
    return next((u.index for u in dep.cm.get_volume(vid).units
                 if u.disk_id == disk), None)


def setup(cell) -> None:
    tr, dep = cell.traffic, cell.dep
    st = cell.state = State()
    size = int(tr["object_bytes"])
    t0 = time.perf_counter()
    st.pool = common.payload_pool(cell.seed, 0, int(tr["payload_pool"]), size)
    t1 = time.perf_counter()
    before = registry.snapshot()
    steps = dep.access.ready(size)
    built = registry.total(registry.delta(before, registry.snapshot()),
                           PROGRAMS)
    t2 = time.perf_counter()
    _fill(cell, st, size)
    t3 = time.perf_counter()
    _break(cell, st)
    lost: dict[int, int | None] = {}
    for p, loc in st.objects:
        vid = loc.slices[0].vid
        if vid not in lost:
            lost[vid] = _lost_index(dep, st.disk, vid)
        _, t = common.codemode_of(cell.config, loc.codemode)
        if lost[vid] is not None and lost[vid] < t["n"]:
            st.targets.append((p, loc, lost[vid]))
    if not st.targets:
        raise RuntimeError(f"disk {st.disk} holds no data unit of a filled "
                           f"object: no GET would decode")
    st.keys = np.random.default_rng([cell.seed, 6]).integers(
        0, len(st.targets), SCHEDULE)
    cell.notes["ready"] = {"steps": int(steps), "programs_built": int(built)}
    cell.notes["backlog"] = {
        "disk": st.disk, "tasks": len(st.tasks),
        "lost_units": sorted(i for i in lost.values() if i is not None),
        "objects": len(st.targets),
        "blobs": sum(loc.slices[0].count for _, loc, _ in st.targets)}
    cell.notes["setup_parts_s"] = {"payloads": t1 - t0, "ready": t2 - t1,
                                   "fill": t3 - t2,
                                   "break": time.perf_counter() - t3}


def run(cell) -> None:
    st, dep = cell.state, cell.dep
    span = cell.spans.span if cell.spans is not None else None
    drawn = itertools.count()  # next() is one bytecode: atomic under the GIL
    logs: list[list] = [[] for _ in range(int(cell.traffic["clients"]))]

    def client(log: list) -> None:
        while time.perf_counter() < cell.t1:
            p, loc, _ = st.targets[int(st.keys[next(drawn) % SCHEDULE])]
            t0 = time.perf_counter()
            try:
                with span("client.get") if span else nullcontext():
                    got = dep.access.get(loc)
            except Exception as e:
                got = None
                cell.notes.setdefault("errors", []).append(repr(e)[:200])
            log.append(("get", t0, time.perf_counter(), loc.size,
                        got == st.pool[p]))
            del got

    threads = [threading.Thread(target=client, args=(log,),
                                name=f"cellbench-client-{c}")
               for c, log in enumerate(logs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cell.ops = [o for log in logs for o in log]
    took = sorted(o[2] - o[1] for o in cell.ops)
    if took:
        cell.notes["get_s"] = {
            "first_end": min(o[2] for o in cell.ops) - cell.t0,
            "median": took[len(took) // 2], "max": took[-1]}


def _check_units(cell, p: int, loc, lost: int, k: int) -> list[str]:
    """Blob ``k`` of one object: the lost unit no longer reads; the
    reference decode of a seeded choice of n survivors read off the
    disks equals the reference stripe's data units; and the program's
    GET holds the same bytes where the lost unit was."""
    from cubefs_tpu.utils import rpc

    dep = cell.dep
    name, t = common.codemode_of(cell.config, loc.codemode)
    n, m = t["n"], t["m"]
    sl = loc.slices[0]
    bid = sl.min_bid + k
    units = dep.cm.get_volume(sl.vid).units
    faults = []
    survivors = {}
    for u in units:
        try:
            _, body = dep.unit_call(u, "get_shard", bid)
        except rpc.RpcError:  # the lost disk's unit
            continue
        if u.index == lost:
            faults.append(f"{name} bid {bid}: lost unit {lost} still reads")
        survivors[u.index] = body
    rng = np.random.default_rng([cell.seed, 7, bid])
    pick = sorted(int(i) for i in rng.choice(sorted(survivors), n,
                                             replace=False))
    decoded = reference_decode.decode({i: survivors[i] for i in pick}, n, m)
    blob = cell.state.pool[p][k * sl.blob_size:(k + 1) * sl.blob_size]
    # a PUT's blobs all take its first blob's shard size
    want = reference.stripe(blob.ljust(min(loc.size, sl.blob_size), b"\0"),
                            n, m, t["min_shard"])
    if not np.array_equal(decoded, want[:n]):
        faults.append(f"{name} bid {bid}: the reference decode of units "
                      f"{pick} differs from the reference stripe")
    s = want.shape[1]
    got = dep.access.get(loc)
    a = k * sl.blob_size + lost * s
    piece = got[a:min(a + s, k * sl.blob_size + len(blob))]
    if piece != decoded[lost, :len(piece)].tobytes():
        faults.append(f"{name} bid {bid}: the GET's unit {lost} differs "
                      f"from the reference decode's")
    return faults


def verify(cell) -> tuple[bool, dict]:
    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults: list[str] = []
    gets = cell.window_ops("get")
    wrong = sum(1 for o in cell.ops if not o[4])
    if wrong:
        faults.append(f"{wrong} GETs did not return the payload that was "
                      f"PUT")
    blobs = sum(-(-o[3] // dep.access.cfg.blob_size) for o in gets)
    decoded = registry.total(cell.registry, RECONSTRUCTS, path="global")
    if decoded < blobs:
        faults.append(f"{int(decoded)} global reconstructs for the "
                      f"{blobs} blobs the window's GETs read")
    built = registry.total(cell.registry, PROGRAMS)
    if built:
        faults.append(f"{int(built)} codec programs built in the window")
    # at least one sampled unit per lost index met, then round the indexes
    by_lost: dict[int, list[int]] = {}
    for j, (_, _, lost) in enumerate(st.targets):
        by_lost.setdefault(lost, []).append(j)
    order = [rng.permutation(js) for _, js in sorted(by_lost.items())]
    n_units = int(want.get("units", 8))
    picks = [int(js[i % len(js)]) for i in range(n_units)
             for js in order][:max(n_units, len(order))]
    for j in picks:
        p, loc, lost = st.targets[j]
        k = int(rng.integers(0, loc.slices[0].count))
        faults += _check_units(cell, p, loc, lost, k)
    n_stripes = min(int(want.get("stripes", 2)), len(st.objects))
    for i in rng.permutation(len(st.objects))[:n_stripes]:
        p, loc = st.objects[int(i)]
        k = int(rng.integers(0, loc.slices[0].count))
        faults += common.check_object(cell, st.pool[p], loc, k)
    ran = [tid for tid in st.tasks
           if dep.sched.tasks[tid]["state"] != "pending"]
    if ran:
        faults.append(f"{len(ran)} repair tasks left pending state in the "
                      f"window")
    # the window's decode steps and the stripes each carried: what the
    # batcher made of the GETs' concurrent decodes
    steps = registry.total(cell.registry, STRIPES + "_count", op="apply")
    stripes = registry.total(cell.registry, STRIPES + "_sum", op="apply")
    return not faults, {"gets_compared": len(cell.ops),
                        "gets_in_window": len(gets),
                        "blobs_in_window": blobs,
                        "global_reconstructs": int(decoded),
                        "units_checked": len(picks),
                        "lost_indexes_checked": len(order),
                        "stripes_checked": int(n_stripes),
                        "decode_steps": int(steps),
                        "decode_stripes_per_step":
                            stripes / steps if steps else None,
                        "faults": faults[:10]}
