"""What the generators share: seeded payloads, warming the encoder's
shapes, and the comparison of stored stripes with the reference."""

from __future__ import annotations

import numpy as np

from .. import reference


def payload_pool(seed: int, klass: int, count: int, size: int) -> list[bytes]:
    return [np.random.default_rng([seed, 1, klass, i]).bytes(size)
            for i in range(count)]


def codemode_of(config: dict, mode_id: int) -> tuple[str, dict]:
    for name, t in config["codemodes"].items():
        if int(t["id"]) == int(mode_id):
            return name, t
    raise KeyError(f"codemode {mode_id} is not in the configuration file")


def put_shape(dep, size: int) -> tuple[object, int, int]:
    """(encoder, stripes per PUT, shard size) the front door will use
    for an object of ``size`` bytes."""
    from cubefs_tpu.codec import codemode as cm

    cfg = dep.access.cfg
    enc = dep.access._encoder(int(cm.select_codemode(cfg.policies, size)))
    blobs = -(-size // cfg.blob_size)
    return enc, blobs, enc.shard_size(min(size, cfg.blob_size))


def step_sizes(dep, enc, per_put: int, shard: int, clients: int) -> list[int]:
    """Every number of stripes a drained encode step can have when up to
    ``clients`` PUTs of ``per_put`` stripes wait together: the batcher
    joins whole submissions up to its byte bound and never splits one."""
    from cubefs_tpu.codec import batcher

    b = batcher.DEFAULT
    cap = min(b.max_batch, max(1, b.max_step_bytes // (enc.t.n * shard)))
    return sorted({per_put} | {per_put * k for k in range(1, clients + 1)
                               if per_put * k <= cap})


def warm_encode(dep, sizes: list[int], clients: int) -> list[tuple]:
    """Push zeros of every step shape of these object sizes through the
    cell's own encoder (and so through the batcher and the engine)."""
    shapes = []
    for size in sizes:
        enc, per_put, shard = put_shape(dep, size)
        for b in step_sizes(dep, enc, per_put, shard, clients):
            zeros = np.zeros((b, enc.t.n, shard), dtype=np.uint8)
            enc.engine.encode_parity(zeros, enc.t.m)
            shapes.append((b, enc.t.n, shard))
    return shapes


def check_object(cell, data: bytes, loc, blob_index: int) -> list[str]:
    """Compare one stored blob of an acknowledged PUT, shard by shard,
    with the reference stripe; every stored CRC with zlib; and count the
    units that hold the bid against the put quorum. Returns the faults."""
    dep = cell.dep
    name, t = codemode_of(cell.config, loc.codemode)
    sl = loc.slices[0]
    blob = data[blob_index * sl.blob_size:(blob_index + 1) * sl.blob_size]
    want = reference.stripe(blob, t["n"], t["m"], t["min_shard"])
    bid = sl.min_bid + blob_index
    faults, held = [], 0
    for u in dep.cm.get_volume(sl.vid).units:
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
    return faults
