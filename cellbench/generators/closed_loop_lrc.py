"""Closed loop over a two-AZ LRC deployment (the `ingest-lrc` cell):
``closed_loop``'s clients and run, PUTs only, on a fleet whose set-up
first gives every disk its AZ, holds the access handler to the
configuration file's codemode policy and builds every program through
the handler's own ready door. Parameters (traffic file): ``closed_loop``'s
``clients``, ``ops`` (put only), ``sizes``, ``payload_pool``, ``max_ops``
and ``verify``.

Set-up, before any volume exists:
  * node k of N is AZ ``az<k * azs // N>`` (``deployment.azs`` of the
    configuration file): every disk relabelled in ClusterMgr and the
    BlobNode's own ``az`` set, so its heartbeat carries the same label
    (upstream reads it from each blobnode's ``idc``);
  * the access handler's ``cfg.policies`` are the file's ``policies``
    (upstream: the cluster's code-mode policy), checked to pick the
    file's codemode for every size the traffic PUTs;
  * ``access.ready(largest size)``: every rung's program of that
    codemode's step.

``verify`` reads sampled PUTs back and compares sampled stored blobs
shard by shard — all n + m + l of them, both AZs' local parity among
them — with ``cellbench/reference_lrc.py``; checks every stored CRC
with zlib, the put quorum, and that each local stripe's units lie in one
AZ and the stripes in different AZs, by the cluster's disk table.
"""

from __future__ import annotations

import numpy as np

from .. import reference, reference_lrc
from . import closed_loop, common

run = closed_loop.run


def setup(cell) -> None:
    from cubefs_tpu.codec import codemode as cm

    tr, dep, config = cell.traffic, cell.dep, cell.config
    if float(tr["ops"].get("put", 0.0)) != 1.0:
        raise ValueError("closed_loop_lrc PUTs only")
    azs = int(config["deployment"]["azs"])
    nodes = list(dep.nodes.values())
    for k, node in enumerate(nodes):
        node.az = f"az{k * azs // len(nodes)}"
        for disk_id in node.disk_ids:
            dep.cm.relabel_disk(disk_id, node.az)
        node.send_heartbeat()
    policy = [cm.Policy(**p) for p in config["policies"]]
    dep.access.cfg.policies = list(policy)
    sizes = [int(s["bytes"]) for s in tr["sizes"]]
    for size in sizes:
        mode = cm.select_codemode(dep.access.cfg.policies, size)
        if mode.name not in config["codemodes"]:
            raise RuntimeError(f"the access handler picks {mode.name} for "
                               f"{size} B, not the file's codemode")
    cell.notes["ready_steps"] = dep.access.ready(max(sizes))

    st = cell.state = closed_loop.State()
    st.pools = [common.payload_pool(cell.seed, k, int(tr["payload_pool"]), sz)
                for k, sz in enumerate(sizes)]
    weights = np.array([float(s["weight"]) for s in tr["sizes"]])
    for c in range(int(tr["clients"])):
        r = np.random.default_rng([cell.seed, 3, c])
        st.schedules.append({
            "put": np.ones(closed_loop.SCHEDULE, dtype=bool),
            "klass": r.choice(len(sizes), size=closed_loop.SCHEDULE,
                              p=weights / weights.sum()),
            "pool": r.integers(0, int(tr["payload_pool"]),
                               closed_loop.SCHEDULE)})


def stripe_azs(dep, vol, t: dict) -> list[list[str]]:
    """The AZs, by the cluster's disk table, of each local stripe's units."""
    az_of = {d: info.az for d, info in dep.cm.disks.items()}
    return [sorted({az_of[vol.units[i].disk_id] for i in units})
            for units in reference_lrc.az_layout(t["n"], t["m"], t["l"],
                                                 t["az_count"])]


def check_object(cell, data: bytes, loc, blob_index: int
                 ) -> tuple[list[str], list[list[str]]]:
    """``common.check_object`` over all n + m + l units against
    ``reference_lrc``, plus the placement of the local stripes. Returns
    (faults, the AZs of each local stripe)."""
    dep = cell.dep
    name, t = common.codemode_of(cell.config, loc.codemode)
    sl = loc.slices[0]
    blob = data[blob_index * sl.blob_size:(blob_index + 1) * sl.blob_size]
    want = reference_lrc.stripe(blob, t["n"], t["m"], t["l"], t["az_count"],
                                t["min_shard"])
    bid = sl.min_bid + blob_index
    vol = dep.cm.get_volume(sl.vid)
    faults, held = [], 0
    for u in vol.units:
        try:
            meta, got = dep.unit_call(u, "get_shard", bid)
        except Exception:
            continue  # a unit may miss a bid; the quorum below may not
        held += 1
        if got != want[u.index].tobytes():
            faults.append(f"{name} bid {bid} unit {u.index}: stored bytes "
                          f"differ from the reference stripe")
        if reference.crc32(got) != meta["crc"]:
            faults.append(f"{name} bid {bid} unit {u.index}: stored crc "
                          f"{meta['crc']} is not zlib's")
    if held < t["put_quorum"]:
        faults.append(f"{name} bid {bid}: on {held} units, put quorum is "
                      f"{t['put_quorum']}")
    homes = stripe_azs(dep, vol, t)
    if (any(len(h) != 1 for h in homes)
            or len({h[0] for h in homes}) != len(homes)):
        faults.append(f"{name} vid {sl.vid}: local stripes lie in AZs "
                      f"{homes}, want one AZ each and each its own")
    return faults, homes


def verify(cell) -> tuple[bool, dict]:
    st, dep = cell.state, cell.dep
    want = cell.traffic.get("verify", {})
    rng = np.random.default_rng([cell.seed, 4])
    written = st.new
    faults: list[str] = []
    if not written:
        faults.append("no PUT was acknowledged inside the window")
    pick = rng.permutation(len(written))
    n_read = min(int(want.get("readback", 8)), len(written))
    for i in pick[:n_read]:
        k, p, loc = written[int(i)]
        if dep.access.get(loc) != st.pools[k][p]:
            faults.append(f"read back of a PUT (class {k}, payload {p}) "
                          f"differs from what was PUT")
    n_stripes = min(int(want.get("stripes", 2)), len(written))
    homes = []
    for i in pick[:n_stripes]:
        k, p, loc = written[int(i)]
        blob = int(rng.integers(0, loc.slices[0].count))
        got, az = check_object(cell, st.pools[k][p], loc, blob)
        faults += got
        homes.append(az)
    return not faults, {"read_back": n_read, "stripes_checked": n_stripes,
                        "puts_in_window": len(written),
                        "local_stripe_azs": homes,
                        "faults": faults[:10]}
