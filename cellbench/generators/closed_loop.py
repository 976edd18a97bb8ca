"""Closed loop: ``clients`` callers, each waiting for its reply before
the next request (what an SDK caller does). Parameters (traffic file):

  clients          number of client threads
  max_ops          (optional) operations offered in the window in all; the
                   clients draw them from one count, so all stay busy to
                   the end, and the run ends with the last reply if that
                   comes before the window does: a fixed amount of work
  ops              {"put": share, "get": share}
  sizes            [{"bytes": n, "weight": w}, ...] object size classes
  payload_pool     distinct seeded payloads per size class
  prefill_objects  objects PUT in set-up (the GET key space)
  get_keys         {"dist": "zipf", "theta": 0.99} over the prefilled objects
  verify           {"readback": n, "stripes": n} sample sizes after the window

Every client draws its schedule (operation, size class, payload, key)
from the seed before the window. A GET is compared with its pooled
payload as it returns; PUTs acknowledged in the window are sampled
after it: read back, and stored stripes compared with the reference.
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext

import numpy as np

from . import common

SCHEDULE = 1 << 14  # draws per client; cycled if a window outlasts them


class State:
    def __init__(self):
        self.pools: list[list[bytes]] = []
        self.objects: list[tuple[int, int, object]] = []  # prefilled
        self.new: list[tuple[int, int, object]] = []  # PUT in the window
        self.schedules: list[dict] = []


def _zipf_cdf(n: int, theta: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    return np.cumsum(w / w.sum())


def setup(cell) -> None:
    tr, dep = cell.traffic, cell.dep
    st = cell.state = State()
    sizes = [int(s["bytes"]) for s in tr["sizes"]]
    weights = np.array([float(s["weight"]) for s in tr["sizes"]])
    weights = weights / weights.sum()
    clients = int(tr["clients"])
    t0 = time.perf_counter()
    st.pools = [common.payload_pool(cell.seed, k, int(tr["payload_pool"]), sz)
                for k, sz in enumerate(sizes)]
    t1 = time.perf_counter()
    cell.notes["warmed_shapes"] = common.warm_encode(dep, sizes, clients)
    t2 = time.perf_counter()

    rng = np.random.default_rng([cell.seed, 2])
    n_pre = int(tr.get("prefill_objects", 0))
    if n_pre:
        klass = rng.choice(len(sizes), size=n_pre, p=weights)

        def put(i):
            k, p = int(klass[i]), i % len(st.pools[int(klass[i])])
            return k, p, dep.access.put(st.pools[k][p])

        with ThreadPoolExecutor(clients) as ex:
            st.objects = list(ex.map(put, range(n_pre)))
    t3 = time.perf_counter()

    put_share = float(tr["ops"].get("put", 0.0))
    if put_share < 1.0 and not st.objects:
        raise ValueError("GETs need prefill_objects")
    keys = tr.get("get_keys") or {}
    cdf = (_zipf_cdf(len(st.objects), float(keys.get("theta", 0.99)))
           if st.objects else None)
    order = rng.permutation(len(st.objects)) if st.objects else None
    for c in range(clients):
        r = np.random.default_rng([cell.seed, 3, c])
        sched = {"put": r.random(SCHEDULE) < put_share,
                 "klass": r.choice(len(sizes), size=SCHEDULE, p=weights),
                 "pool": r.integers(0, int(tr["payload_pool"]), SCHEDULE)}
        if cdf is not None:
            sched["key"] = order[np.searchsorted(cdf, r.random(SCHEDULE))]
        st.schedules.append(sched)
    cell.notes["setup_parts_s"] = {"payloads": t1 - t0, "warm": t2 - t1,
                                   "prefill": t3 - t2}


def run(cell) -> None:
    st, dep = cell.state, cell.dep
    logs: list[list] = [[] for _ in st.schedules]
    news: list[list] = [[] for _ in st.schedules]
    span = cell.spans.span if cell.spans is not None else None
    budget = int(cell.traffic.get("max_ops", 0)) or None
    drawn = itertools.count()  # next() is one bytecode: atomic under the GIL

    def timed(kind: str, call):
        """(reply or None, start, end) of one client operation."""
        t0 = time.perf_counter()
        try:
            with span(f"client.{kind}") if span else nullcontext():
                out = call()
        except Exception as e:
            out = None
            cell.notes.setdefault("errors", []).append(repr(e)[:200])
        return out, t0, time.perf_counter()

    def client(c: int) -> None:
        sched, log, new = st.schedules[c], logs[c], news[c]
        i = 0
        while time.perf_counter() < cell.t1:
            if budget is not None and next(drawn) >= budget:
                break
            j = i % SCHEDULE
            i += 1
            if sched["put"][j]:
                k, p = int(sched["klass"][j]), int(sched["pool"][j])
                data = st.pools[k][p]
                loc, t0, t1 = timed("put", lambda: dep.access.put(data))
                log.append(("put", t0, t1, len(data), loc is not None))
                if loc is not None:
                    new.append((k, p, loc, t1))
            else:
                k, p, loc = st.objects[int(sched["key"][j])]
                got, t0, t1 = timed("get", lambda: dep.access.get(loc))
                log.append(("get", t0, t1, loc.size, got == st.pools[k][p]))

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"cellbench-client-{c}")
               for c in range(len(st.schedules))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cell.ops = [o for log in logs for o in log]
    st.new = [(k, p, loc) for new in news for k, p, loc, t1 in new
              if t1 <= cell.t1]


def verify(cell) -> tuple[bool, dict]:
    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    written = st.new or st.objects
    faults: list[str] = []
    pick = rng.permutation(len(written))
    n_read = min(int(want.get("readback", 8)), len(written))
    for i in pick[:n_read]:
        k, p, loc = written[int(i)]
        if dep.access.get(loc) != st.pools[k][p]:
            faults.append(f"read back of a PUT (class {k}, payload {p}) "
                          f"differs from what was PUT")
    n_stripes = min(int(want.get("stripes", 2)), len(written))
    for i in pick[:n_stripes]:
        k, p, loc = written[int(i)]
        blob = int(rng.integers(0, loc.slices[0].count))
        faults += common.check_object(cell, st.pools[k][p], loc, blob)
    wrong_gets = sum(1 for o in cell.ops if o[0] == "get" and not o[4])
    if wrong_gets:
        faults.append(f"{wrong_gets} GETs in the window did not return "
                      f"the payload that was PUT")
    return not faults, {"read_back": n_read, "stripes_checked": n_stripes,
                        "gets_compared": sum(1 for o in cell.ops
                                             if o[0] == "get"),
                        "puts_in_window": len(st.new),
                        "faults": faults[:10]}
