"""Closed loop over objects of any size: warp's ``--obj.randsize``
(log2-distributed sizes, the same number of objects in every doubling
up to ``--obj.size``) as a fixed amount of work. Parameters (traffic
file):

  clients       number of client threads, each waiting for its reply
  sizes         {"min_bytes", "max_bytes", "doublings", "strata"}: the
                range [min, max) is cut into ``doublings`` x ``strata``
                strata equal in log-size and one byte count is drawn in
                each from the seed — so every seed brings sizes no
                process has seen and the same histogram to within a
                stratum (warp draws independently; stratified, the byte
                weight is the file's and not the seed's)
  max_ops       doublings x strata: every size is PUT once, in one
                seeded order the clients draw from one shared counter,
                so a run is the same work whatever the interleaving and
                ends with the last reply
  payload_pool  seeded buffers of ``max_bytes``; a PUT sends a prefix
  verify        {"readback_per_doubling": n, "stripes_per_codemode": n}

Set-up warms through one call and names no shape: the front door's
``ready(max_bytes)`` — what a deployment does once at start-up from the
codemodes its policies serve and its largest object. A program that
lacks that door cannot run the cell and fails here, at once. After the
window: sampled PUTs of every doubling are read back, stored stripes of
every codemode (a two-blob object among them) are compared shard by
shard with ``cellbench/reference.py``, and the run is not ``correct`` if
a codec program was built after ``ready``. A PUT the window closed on is
late, not wrong (``puts_late`` on the detail line): it is waited for, a
failure of it counts in ``failed``, and the rate is what the window saw
- its bytes over the time to its last acknowledgement - so a run the
host stalled reads slow and stays ``correct``.
"""

from __future__ import annotations

import itertools
import threading
import time
from contextlib import nullcontext

import numpy as np

from .. import registry
from . import common

PROGRAMS = "cubefs_codec_programs_total"


class State:
    def __init__(self):
        self.pool: list[bytes] = []
        self.sizes: np.ndarray = np.zeros(0, dtype=np.int64)  # by stratum
        self.order: np.ndarray = np.zeros(0, dtype=np.int64)  # PUT order
        self.payload: np.ndarray = np.zeros(0, dtype=np.int64)  # by stratum
        self.done: dict[int, object] = {}  # stratum -> Location


def draw_sizes(seed: int, sizes: dict) -> np.ndarray:
    """One byte count per stratum, ascending by stratum."""
    lo, hi = int(sizes["min_bytes"]), int(sizes["max_bytes"])
    n = int(sizes["doublings"]) * int(sizes["strata"])
    u = np.random.default_rng([seed, 5]).random(n)
    log_size = np.log2(lo) + (np.arange(n) + u) * (np.log2(hi / lo) / n)
    return np.clip(np.floor(2.0 ** log_size), lo, hi - 1).astype(np.int64)


def setup(cell) -> None:
    tr, dep = cell.traffic, cell.dep
    st = cell.state = State()
    spec = tr["sizes"]
    t0 = time.perf_counter()
    before = registry.snapshot()
    steps = dep.access.ready(int(spec["max_bytes"]))
    built = registry.delta(before, registry.snapshot())
    t1 = time.perf_counter()
    st.sizes = draw_sizes(cell.seed, spec)
    if int(tr["max_ops"]) != len(st.sizes):
        raise ValueError(f"max_ops {tr['max_ops']} is not doublings x "
                         f"strata = {len(st.sizes)}")
    rng = np.random.default_rng([cell.seed, 6])
    st.order = rng.permutation(len(st.sizes))
    st.payload = rng.integers(0, int(tr["payload_pool"]), len(st.sizes))
    st.pool = common.payload_pool(cell.seed, 0, int(tr["payload_pool"]),
                                  int(spec["max_bytes"]))
    cell.notes["ready"] = {
        "steps": int(steps),
        "programs_built": {dict(lb).get("kernel", ""): int(v)
                           for (name, lb), v in built.items()
                           if name == PROGRAMS and v}}
    cell.notes["offered_bytes"] = int(st.sizes.sum())
    cell.notes["setup_parts_s"] = {"ready": t1 - t0,
                                   "payloads": time.perf_counter() - t1}


def _data(st: State, k: int) -> memoryview:
    return memoryview(st.pool[int(st.payload[k])])[:int(st.sizes[k])]


def run(cell) -> None:
    st, dep = cell.state, cell.dep
    clients = int(cell.traffic["clients"])
    logs: list[list] = [[] for _ in range(clients)]
    span = cell.spans.span if cell.spans is not None else None
    drawn = itertools.count()  # next() is one bytecode: atomic under the GIL

    def client(c: int) -> None:
        log = logs[c]
        while time.perf_counter() < cell.t1:
            i = next(drawn)
            if i >= len(st.order):
                break
            k = int(st.order[i])
            data = _data(st, k)
            t0 = time.perf_counter()
            try:
                with span("client.put") if span else nullcontext():
                    loc = dep.access.put(data)
            except Exception as e:
                loc = None
                cell.notes.setdefault("errors", []).append(repr(e)[:200])
            t1 = time.perf_counter()
            log.append(("put", t0, t1, len(data), loc is not None))
            if loc is not None and t1 <= cell.t1:
                st.done[k] = loc  # one stratum, one writer

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"cellbench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cell.ops = [o for log in logs for o in log]


def _stripe_faults(cell, k: int, loc) -> list[str]:
    """Every blob of object ``k`` against the reference stripe. The port
    stores each blob of a PUT at the first blob's shard size: a short
    last blob is compared as its bytes zero-padded to a whole blob."""
    st = cell.state
    blobs = loc.slices[0].count
    data = bytes(_data(st, k))
    if blobs > 1:
        data = data.ljust(blobs * loc.slices[0].blob_size, b"\0")
    return [f for b in range(blobs)
            for f in common.check_object(cell, data, loc, b)]


def verify(cell) -> tuple[bool, dict]:
    st, dep, tr = cell.state, cell.dep, cell.traffic
    want = tr.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    faults: list[str] = []
    built = registry.total(cell.registry, PROGRAMS)
    if built:
        faults.append(f"{int(built)} codec programs were built after "
                      f"ready, inside the window")

    strata = int(tr["sizes"]["strata"])
    per = int(want.get("readback_per_doubling", 8))
    n_read = 0
    for d in range(int(tr["sizes"]["doublings"])):
        have = [k for k in range(d * strata, (d + 1) * strata)
                if k in st.done]
        for k in rng.permutation(have)[:per]:
            n_read += 1
            if dep.access.get(st.done[int(k)]) != _data(st, int(k)):
                faults.append(f"read back of the PUT of {st.sizes[k]} B "
                              f"differs from what was PUT")

    by_mode: dict[int, list[int]] = {}
    for k in rng.permutation(sorted(st.done)):
        by_mode.setdefault(st.done[int(k)].codemode, []).append(int(k))
    per = int(want.get("stripes_per_codemode", 4))
    checked, two_blob = 0, 0
    for mode, ks in sorted(by_mode.items()):
        # one several-blob object, where the codemode stored one
        first = [k for k in ks if st.done[k].slices[0].count > 1][:1]
        for k in (first + [k for k in ks if k not in first])[:per]:
            checked += 1
            two_blob += st.done[k].slices[0].count > 1
            faults += _stripe_faults(cell, k, st.done[k])
    return not faults, {"read_back": n_read, "objects_checked": checked,
                        "several_blob_objects_checked": int(two_blob),
                        "codemodes_checked": len(by_mode),
                        "puts_in_window": len(st.done),
                        "puts_late": len(st.order) - len(st.done),
                        "programs_built_in_window": int(built),
                        "faults": faults[:10]}
