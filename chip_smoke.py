"""chip_smoke.py — the served blob path, once, on the chip.

    python chip_smoke.py        (no arguments; needs one TPU, or four)

One process owns the chip and hosts every codec caller of a blob
deployment — ClusterMgr, 24 disks over 6 BlobNodes, AccessHandler,
Scheduler, RepairWorker and one CodecService sidecar — built from the
classes cmd.run_role builds, over the in-process transport (the only
topology in which access, worker and blobnodes share batcher.DEFAULT
and so one device queue). The deployment is the upstream default at its
own widths: AccessConfig() as shipped (8 MiB blobs, EC3P3 <= 256 KiB <
EC6P6 <= 4 MiB < EC12P4) with engine="tpu" on access, worker and
sidecar. Data comes from one seed.

It PUTs and GETs every size class plus one LRC and one MSR object,
checks a sample of stored stripes against the numpy table engine and
zlib, breaks a disk (degraded GET, scheduler -> worker repair, rebuilt
shards bit-identical), drives the sidecar RPCs and the fused CRC kernel,
PUTs and reads back seeded random sizes of every size class after the
front door's `ready` (no program may be built after it), and then
proves the device did the work: no engine quarantined, no
matrix refused by the Pallas gate, every large-class step on the fused
kernel (or, with several devices, dp steps holding data on every one).
The first failed check ends the run non-zero; nothing is downgraded to
a warning. Without a TPU it fails before printing any result.

Stdout: first line the device as JAX reports it; then the run's summary
as one JSON line (per-phase records, bytes, compile seconds, ending in
`"claim": null`; also written to chiprun_out/chip_smoke/summary.json);
last line the driver's result object, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Wall times in the summary are smoke wall times (compilation and host
work included), not benchmark numbers. tests/test_chip_bringup.py runs
the same phases at TINY sizes on CPU with only the device assertions
skipped.
"""

from __future__ import annotations

import hashlib
import importlib.metadata
import json
import logging
import os
import shutil
import sys
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 20260926


@dataclass(frozen=True)
class Sizes:
    """Object population and cluster shape. FULL is what the driver runs
    on the chip; TINY keeps every phase and every size class (the policy
    boundaries are AccessConfig's, so "large" still means > 4 MiB) at a
    scale the CPU test suite can afford."""

    nodes: int = 6
    disks_per_node: int = 4
    blob_size: int | None = None  # None = AccessConfig() as shipped
    put_threads: int = 4
    large: tuple[int, int] = (16, 64 << 20)  # (objects, bytes) -> EC12P4
    mid: tuple[int, int] = (64, 1 << 20)  # -> EC6P6
    small: tuple[int, int] = (256, 64 << 10)  # -> EC3P3
    special_bytes: int = 64 << 20  # one object each: EC6P10L2, EC6P6MSR
    ref_stripes: int = 2  # stripes per codemode checked against numpy
    sidecar_shard: int = 4 << 20  # RS(12+4) shard bytes over RPC
    crc_blocks: int = 1024
    crc_block_len: int = 128 << 10
    crc_tiles: tuple[int, ...] = (128, 256, 512)
    two_loss: tuple[int, int] = (8, 699051)  # (stripes, shard bytes) a step
    any_size: tuple[int, int] = (32, 16 << 20)  # (objects, largest bytes)
    repair_any_size: int = 24  # objects a codemode, in one volume
    repair_stripes: int = 64  # the worker's batch_stripes


FULL = Sizes()
TINY = Sizes(blob_size=1 << 20, put_threads=2,
             large=(2, (4 << 20) + 4099), mid=(2, (256 << 10) + 1001),
             small=(3, 10_007), special_bytes=300_007, ref_stripes=1,
             sidecar_shard=8192, crc_blocks=8, crc_block_len=8192,
             crc_tiles=(8,), two_loss=(2, 300),
             any_size=(6, (4 << 20) + 8192), repair_any_size=5,
             repair_stripes=8)

# what .gitignore lists: the only paths a run may create or change
_IGNORED_DIRS = {".git", ".jax_cache", "chiprun_out", "__pycache__",
                 ".pytest_cache", ".hypothesis", ".cache"}
_IGNORED_FILES = {"PROGRESS.jsonl", "COPYCHECK.json"}
_IGNORED_SUFFIXES = (".pyc", ".so", ".so.srchash")


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def payload(kind: int, idx: int, size: int) -> bytes:
    return np.random.default_rng([SEED, kind, idx]).bytes(size)


def tree_digest(root: str) -> dict[str, str]:
    """sha256 of every file git would commit under ``root``."""
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in _IGNORED_DIRS]
        for name in filenames:
            if name in _IGNORED_FILES or name.endswith(_IGNORED_SUFFIXES):
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit
    counts its retrieval time) and counts cache hits and misses, so a
    cold run and a warm run can be told apart from the summary."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Deployment:
    """The in-process blob cluster (tests/test_blob_e2e.py's shape) with
    every codec caller pinned to the device engine."""

    def __init__(self, workdir: str, sizes: Sizes):
        from cubefs_tpu.blob.access import (AccessConfig, AccessHandler,
                                            NodePool)
        from cubefs_tpu.blob.blobnode import BlobNode
        from cubefs_tpu.blob.clustermgr import ClusterMgr
        from cubefs_tpu.blob.mq import MessageQueue
        from cubefs_tpu.blob.scheduler import Scheduler
        from cubefs_tpu.blob.worker import RepairWorker
        from cubefs_tpu.codec.service import CodecService
        from cubefs_tpu.utils import rpc

        self.cm = ClusterMgr()
        self.cm_client = rpc.Client(self.cm)
        self.pool = NodePool()
        self.nodes: dict[str, BlobNode] = {}
        for n in range(sizes.nodes):
            addr = f"node{n}"
            node = BlobNode(
                node_id=n,
                disk_paths=[os.path.join(workdir, f"n{n}d{d}")
                            for d in range(sizes.disks_per_node)],
                cm_client=self.cm_client, addr=addr)
            node.register()
            node.send_heartbeat()
            self.pool.bind(addr, node)
            self.nodes[addr] = node
        self.repair_q = MessageQueue()
        self.delete_q = MessageQueue()
        cfg = AccessConfig(engine="tpu")
        if sizes.blob_size is not None:
            cfg.blob_size = sizes.blob_size
        self.access = AccessHandler(
            self.cm_client, self.pool, cfg,
            repair_queue=self.repair_q, delete_queue=self.delete_q)
        self.sched = Scheduler(self.cm, repair_queue=self.repair_q,
                               delete_queue=self.delete_q,
                               node_pool=self.pool)
        self.worker = RepairWorker(rpc.Client(self.sched), self.cm_client,
                                   self.pool, engine="tpu",
                                   batch_stripes=sizes.repair_stripes)
        self.sidecar = rpc.RpcServer(
            rpc.expose(CodecService(engine="tpu")), service="codec").start()

    def stop(self) -> None:
        self.sidecar.stop()
        self.access._pool.shutdown(wait=True)
        for node in self.nodes.values():
            node.stop()

    def unit_call(self, unit, method: str, bid: int | None = None):
        args = {"disk_id": unit.disk_id, "chunk_id": unit.chunk_id}
        if bid is not None:
            args["bid"] = bid
        return self.pool.get(unit.node_addr).call(method, args)


# ---------------------------------------------------------------- phases

def phase_build() -> dict:
    """Compile the native runtime here, from runtime/src — never serve
    from a .so that rode along in the copy."""
    from cubefs_tpu.runtime import build as rt_build

    t0 = time.time()
    so = rt_build.build()
    if os.path.getmtime(so) < t0 - 1:
        raise RuntimeError(f"{so} was not rebuilt by this run")
    rt_build.load()
    return {"ok": True, "build_wall_s": round(time.time() - t0, 2)}


def phase_device(device_checks: bool) -> dict:
    import jax

    from cubefs_tpu import ops

    devs = ops.require_tpu() if device_checks else jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"compile_cache={ops.COMPILE_CACHE_DIR}", flush=True)
    return dev


def phase_put(dep: Deployment, sizes: Sizes) -> tuple[dict, dict]:
    """PUT every size class from the client threads; returns the phase
    record and {(kind, idx): (size, Location)}."""
    from cubefs_tpu.codec.codemode import CodeMode

    classes = [  # (kind, label, forced codemode, expected, count, bytes)
        (0, "large", None, CodeMode.EC12P4, *sizes.large),
        (1, "mid", None, CodeMode.EC6P6, *sizes.mid),
        (2, "small", None, CodeMode.EC3P3, *sizes.small),
        (3, "lrc", CodeMode.EC6P10L2, CodeMode.EC6P10L2, 1,
         sizes.special_bytes),
        (4, "msr", CodeMode.EC6P6MSR, CodeMode.EC6P6MSR, 1,
         sizes.special_bytes),
    ]
    objects: dict = {}
    rec: dict = {"ok": True}

    def put_one(job):
        kind, idx, size, mode = job
        return (kind, idx), (size, dep.access.put(
            payload(kind, idx, size), codemode=mode))

    with ThreadPoolExecutor(sizes.put_threads) as clients:
        for kind, label, forced, expected, count, size in classes:
            t0 = time.perf_counter()
            done = list(clients.map(
                put_one, [(kind, i, size, forced) for i in range(count)]))
            for key, (sz, loc) in done:
                if loc.codemode != int(expected) or loc.size != sz:
                    raise RuntimeError(
                        f"{label} object {key}: stored as codemode "
                        f"{loc.codemode} size {loc.size}, want "
                        f"{expected.name} size {sz}")
                objects[key] = (sz, loc)
            rec[label] = {"codemode": expected.name, "objects": count,
                          "bytes": count * size,
                          "smoke_wall_s": round(
                              time.perf_counter() - t0, 3)}
            log(f"PUT {label}: {count} x {size} B as {expected.name} in "
                f"{rec[label]['smoke_wall_s']} s (smoke wall time)")
    return rec, objects


def phase_get(dep: Deployment, sizes: Sizes, objects: dict,
              keys=None) -> dict:
    """GET objects back and compare bytes with the seeded payload."""
    keys = sorted(objects) if keys is None else list(keys)

    def get_one(key):
        size, loc = objects[key]
        if dep.access.get(loc) != payload(key[0], key[1], size):
            raise RuntimeError(f"GET {key}: bytes differ from what was PUT")
        return size

    t0 = time.perf_counter()
    with ThreadPoolExecutor(sizes.put_threads) as clients:
        total = sum(clients.map(get_one, keys))
    return {"ok": True, "objects": len(keys), "bytes": total,
            "smoke_wall_s": round(time.perf_counter() - t0, 3)}


def reference_stripe(t, blob: bytes, shard_size: int) -> np.ndarray:
    """The full (total, S) stripe of one blob by the plain reference:
    NumpyEngine (table-driven GF(2^8)) over the codemode's geometry —
    no batcher, no XOR programs, no device."""
    from cubefs_tpu.codec.engine import get_engine
    from cubefs_tpu.ops import msr

    ref = get_engine("numpy")
    stripe = np.zeros((t.total, shard_size), dtype=np.uint8)
    buf = np.frombuffer(blob, dtype=np.uint8)
    stripe.reshape(-1)[:buf.size] = buf
    data = stripe[:t.n]
    if t.is_msr():
        sub = data.reshape(t.n * t.alpha, shard_size // t.alpha)
        stripe[t.n:] = ref.matrix_apply(
            msr.encode_rows(t.n, t.n + t.m, t.d), sub
        ).reshape(t.m, shard_size)
        return stripe
    stripe[t.n:t.n + t.m] = ref.encode_parity(data, t.m)
    for az in range(t.az_count if t.l else 0):
        idx, ln, lm = t.local_stripe_in_az(az)
        stripe[idx[ln:]] = ref.encode_parity(stripe[idx[:ln]], lm)
    return stripe


def phase_reference(dep: Deployment, sizes: Sizes, objects: dict) -> dict:
    """A sample of stored stripes from every codemode, shard by shard,
    against the numpy reference; every shard's stored CRC against zlib."""
    from cubefs_tpu.codec import codemode as cm

    checked: dict[str, int] = {}
    shards = 0
    for kind in sorted({k for k, _ in objects}):
        size, loc = objects[(kind, 0)]
        data = payload(kind, 0, size)
        t = cm.tactic(loc.codemode)
        enc = dep.access._encoder(loc.codemode)
        sl = loc.slices[0]
        vol = dep.cm.get_volume(sl.vid)
        for k in range(min(sizes.ref_stripes, sl.count)):
            blob = data[k * sl.blob_size:(k + 1) * sl.blob_size]
            want = reference_stripe(t, blob, enc.shard_size(len(blob)))
            for u in vol.units:
                meta, got = dep.unit_call(u, "get_shard", sl.min_bid + k)
                if got != want[u.index].tobytes():
                    raise RuntimeError(
                        f"{cm.CodeMode(loc.codemode).name} bid "
                        f"{sl.min_bid + k} shard {u.index}: stored bytes "
                        f"differ from the numpy reference")
                if zlib.crc32(got) != meta["crc"]:
                    raise RuntimeError(
                        f"bid {sl.min_bid + k} shard {u.index}: stored "
                        f"crc {meta['crc']} != zlib")
                shards += 1
            name = cm.CodeMode(loc.codemode).name
            checked[name] = checked.get(name, 0) + 1
    return {"ok": True, "stripes": checked, "shards": shards}


def _capture_disk(dep: Deployment, disk_id: int) -> dict:
    """{(vid, unit_index): {bid: shard bytes}} for every unit on a disk."""
    held = {}
    for vid, index in dep.cm.volumes_on_disk(disk_id):
        u = dep.cm.get_volume(vid).units[index]
        meta, _ = dep.unit_call(u, "list_chunk")
        held[(vid, index)] = {
            bid: dep.unit_call(u, "get_shard", bid)[1]
            for bid, _, _ in meta["shards"]}
    return held


def _second_disk(dep: Deployment, objects: dict, vid: int,
                 spare: tuple) -> int:
    """The disk under the first later unit of volume `vid` that is not
    unit 0's and holds nothing of the volume of object `spare` (the MSR
    one: with two units lost its sub-shard repair has too few helpers
    and would fall back, which this phase treats as a miss)."""
    units = dep.cm.get_volume(vid).units
    avoid = {u.disk_id for u in dep.cm.get_volume(
        objects[spare][1].slices[0].vid).units} | {units[0].disk_id}
    for u in units[1:]:
        if u.disk_id not in avoid:
            return u.disk_id
    raise RuntimeError(f"every disk of vid {vid} also holds a unit of "
                       f"the MSR volume: no second disk to lose")


def phase_break_repair(dep: Deployment, sizes: Sizes,
                       objects: dict) -> dict:
    """Break the disk under data shard 0 of an EC12P4, the LRC and the
    MSR volume (one disk where they share it, else one after another):
    degraded GET, scheduler -> worker repair, rebuilt shards compared
    with the copies captured before the break, healthy GET after. With
    the EC12P4 volume's disk a second disk under another of its units
    is lost too: the scheduler leases the volume's two tasks together
    and the worker rebuilds both units from one read of the survivors
    (`cubefs_repair_task_reads_total`: one `shared`)."""
    from cubefs_tpu.blob.types import DiskStatus
    from cubefs_tpu.utils import metrics

    targets = [(0, 0), (3, 0), (4, 0)]  # large, lrc, msr
    fallbacks0 = dict(metrics.repair_msr_fallbacks.samples())
    shared0 = metrics.repair_task_reads.value(reads="shared")
    rounds = []
    bytes_rebuilt = 0
    pending = list(targets)
    while pending:
        # the disk under shard 0 of the first pending target, plus any
        # other pending target with a DATA shard on that same disk
        pending0 = pending[0]
        vid0 = objects[pending0][1].slices[0].vid
        disk = dep.cm.get_volume(vid0).units[0].disk_id
        hit = []
        for key in pending:
            loc = objects[key][1]
            t = dep.access._encoder(loc.codemode).t
            vol = dep.cm.get_volume(loc.slices[0].vid)
            if any(u.disk_id == disk and u.index < t.n for u in vol.units):
                hit.append(key)
        pending = [k for k in pending if k not in hit]

        t0 = time.perf_counter()
        disks = [disk]
        if pending0 == targets[0]:
            disks.append(_second_disk(dep, objects, vid0, targets[2]))
        held = {}
        for d in disks:
            held.update(_capture_disk(dep, d))
            node = next(n for n in dep.nodes.values() if d in n.disk_ids)
            node.break_disk(d)

        recon0 = sum(v for _, v in metrics.reconstruct_reads.samples())
        phase_get(dep, sizes, objects, hit)
        degraded = sum(
            v for _, v in metrics.reconstruct_reads.samples()) - recon0
        if degraded < len(hit):
            raise RuntimeError(
                f"disk {disk}: {len(hit)} degraded GETs but only "
                f"{degraded} reconstruct reads counted")

        n_tasks = sum(dep.sched.mark_disk_broken(d) for d in disks)
        if n_tasks != len(held):
            raise RuntimeError(f"disks {disks}: {len(held)} units held, "
                               f"{n_tasks} repair tasks queued")
        for _ in range(n_tasks * (dep.sched.MAX_ATTEMPTS + 1)):
            if not dep.worker.run_once():
                break
        if dep.worker.failed:
            errs = sorted({t.get("last_error", "") for t in
                           dep.sched.tasks.values() if t.get("last_error")})
            raise RuntimeError(f"disk {disk}: {dep.worker.failed} repair "
                               f"task runs failed: {errs[:3]}")
        if any(dep.cm.disks[d].status != DiskStatus.REPAIRED
               for d in disks):
            raise RuntimeError(f"disks {disks} not REPAIRED after the drain")

        for (vid, index), shards in held.items():
            u = dep.cm.get_volume(vid).units[index]
            if u.disk_id in disks:
                raise RuntimeError(f"vid {vid} unit {index} still on "
                                   f"broken disk {u.disk_id}")
            for bid, want in shards.items():
                if dep.unit_call(u, "get_shard", bid)[1] != want:
                    raise RuntimeError(
                        f"vid {vid} unit {index} bid {bid}: rebuilt shard "
                        f"differs from the copy taken before the break")
                bytes_rebuilt += len(want)

        # healthy again: no unit left on the broken disk (above) and the
        # bytes come back. (Not "zero reconstruct reads": a hedged GET
        # may legitimately decode from parity when a data read is slow.)
        phase_get(dep, sizes, objects, hit)
        rounds.append({"disk": disk, "disks": disks, "units": len(held),
                       "targets": [list(k) for k in hit],
                       "smoke_wall_s": round(time.perf_counter() - t0, 3)})
        log(f"disks {disks}: {len(held)} units rebuilt bit-identical, "
            f"targets {hit}, {rounds[-1]['smoke_wall_s']} s "
            f"(smoke wall time)")
    fallbacks = {k: v for k, v in metrics.repair_msr_fallbacks.samples()
                 if v != fallbacks0.get(k, 0)}
    if fallbacks:
        raise RuntimeError(f"MSR sub-shard repair fell back to the "
                           f"conventional decode: {fallbacks}")
    shared = metrics.repair_task_reads.value(reads="shared") - shared0
    if shared < 1:
        raise RuntimeError(
            f"two units of vid {objects[targets[0]][1].slices[0].vid} "
            f"were lost together and no task was decoded from its "
            f"sibling's read of the survivors")
    return {"ok": True, "rounds": rounds, "bytes_rebuilt": bytes_rebuilt,
            "shared_reads": int(shared),
            "repair_decode_legs": {
                engine: v for (op, engine), v
                in metrics.codec_batch_steps.samples() if op == "apply"}}


def phase_sidecar(dep: Deployment, sizes: Sizes) -> dict:
    """BASELINE.json configs 2-4 over the sidecar's RPC socket, then the
    fused Pallas CRC kernel once at the same shape."""
    from cubefs_tpu.codec.engine import get_engine
    from cubefs_tpu.ops import pallas_crc
    from cubefs_tpu.utils import rpc

    cli = rpc.Client(dep.sidecar.addr)
    n, m, s = 12, 4, sizes.sidecar_shard
    rng = np.random.default_rng([SEED, 9])
    data = rng.integers(0, 256, (1, n, s), dtype=np.uint8)
    geom = {"n": n, "m": m, "shard_size": s, "batch": 1}
    t0 = time.perf_counter()

    meta, raw = cli.call("encode", geom, data.tobytes(), timeout=600)
    parity = np.frombuffer(raw, dtype=np.uint8).reshape(meta["shape"])
    if not np.array_equal(parity, get_engine("numpy").encode_parity(data, m)):
        raise RuntimeError("sidecar encode differs from the numpy reference")
    stripe = np.concatenate([data, parity], axis=1)

    bad = [1, 7]
    present = [i for i in range(n + m) if i not in bad]
    meta, raw = cli.call(
        "reconstruct",
        {"n": n, "total": n + m, "present": present, "wanted": bad,
         "shard_size": s, "batch": 1},
        np.ascontiguousarray(stripe[:, present[:n]]).tobytes(), timeout=600)
    rec = np.frombuffer(raw, dtype=np.uint8).reshape(meta["shape"])
    if not np.array_equal(rec, stripe[:, bad]):
        raise RuntimeError("sidecar reconstruct did not return the lost "
                           "shards")

    torn = stripe.copy()
    torn[0, n, 0] ^= 1
    meta, _ = cli.call("verify", dict(geom, batch=2),
                       np.concatenate([stripe, torn]).tobytes(), timeout=600)
    if meta["ok"] != [True, False]:
        raise RuntimeError(f"sidecar verify said {meta['ok']} for one good "
                           f"and one torn stripe")

    blocks = rng.integers(0, 256, (sizes.crc_blocks, sizes.crc_block_len),
                          dtype=np.uint8)
    want = np.array([zlib.crc32(b.tobytes()) for b in blocks], dtype="<u4")
    meta, raw = cli.call("crc32", {"block_len": sizes.crc_block_len},
                         blocks.tobytes(), timeout=600)
    if not np.array_equal(np.frombuffer(raw, dtype="<u4"), want):
        raise RuntimeError("sidecar crc32 differs from zlib")

    if not np.array_equal(
            np.asarray(pallas_crc.crc32_blocks_pallas(blocks)), want):
        raise RuntimeError("pallas_crc.crc32_blocks_pallas differs from zlib")
    for tb in sizes.crc_tiles:
        if not pallas_crc.verify_tile(sizes.crc_block_len, 1024, tb):
            raise RuntimeError(f"pallas_crc.verify_tile refused "
                               f"tile_blocks={tb}")
    return {"ok": True, "rs_bytes": int(stripe.nbytes),
            "crc_bytes": int(blocks.nbytes),
            "crc_tiles_verified": list(sizes.crc_tiles),
            "smoke_wall_s": round(time.perf_counter() - t0, 3)}


def phase_two_loss(dep: Deployment, sizes: Sizes, clock: CompileClock
                   ) -> dict:
    """Every repair matrix of EC12P4 with one or two units lost — the
    lost unit and, where there is one, the other lost unit: 16 + 240 —
    built by the worker's rule (``worker.solve_and_wanted``: survivors in
    index order past both, the first 12 solve, the 13th is rebuilt
    beside the lost one as the check), applied through ``worker.codec.matrix_apply`` to the
    survivors of one reference stripe batch (cellbench/reference.py:
    table GF(2^8), nothing of cubefs_tpu) and compared bit for bit with
    that stripe's rows. One program serves them all: nothing compiles
    after the first step."""
    from cellbench import reference
    from cubefs_tpu.blob.worker import solve_and_wanted
    from cubefs_tpu.ops import rs_kernel
    from cubefs_tpu.utils import metrics

    n, m = 12, 4
    b, s = sizes.two_loss
    t0 = time.perf_counter()
    rng = np.random.default_rng([SEED, 28])
    stripes = np.zeros((b, n + m, s), dtype=np.uint8)
    stripes[:, :n] = rng.integers(0, 256, (b, n, s), dtype=np.uint8)
    for k in range(b):
        stripes[k, n:] = reference.matmul(
            reference.encode_matrix(n, n + m)[n:], stripes[k, :n])
    misses = metrics.codec_matrix_cache.value(op="apply", result="miss")
    after_first = None
    distinct = set()
    pairs = [(bad, other) for bad in range(n + m)
             for other in [None] + [i for i in range(n + m) if i != bad]]
    for bad, other in pairs:
        solve, wanted = solve_and_wanted(
            [i for i in range(n + m) if i not in (bad, other)][:n + 1], n, bad)
        rows = rs_kernel.reconstruct_rows(n, n + m, solve, wanted)
        distinct.add(rows.tobytes())
        got = dep.worker.codec.matrix_apply(rows, stripes[:, solve])
        if not np.array_equal(got, stripes[:, wanted]):
            raise RuntimeError(
                f"EC12P4 repair matrix (lost {bad}, also lost {other}) at "
                f"({b}, {n}, {s}) differs from the reference stripe")
        if after_first is None:
            after_first = clock.compiles
    if clock.compiles != after_first:
        raise RuntimeError(
            f"{clock.compiles - after_first} programs compiled after the "
            f"first of {len(pairs)} repair matrices of one shape")
    return {"ok": True, "matrices": len(pairs),
            "distinct_matrices": len(distinct), "shape": [b, n, s],
            "compiles_after_first_step": 0,
            "matrix_cache_misses": int(metrics.codec_matrix_cache.value(
                op="apply", result="miss") - misses),
            "smoke_wall_s": round(time.perf_counter() - t0, 3)}


def phase_any_size(dep: Deployment, sizes: Sizes, clock: CompileClock
                   ) -> dict:
    """Objects of sizes nobody named: after the front door's `ready`
    (every rung of the step-shape ladder its policies can reach, built
    once), seeded random byte counts across the three size classes are
    PUT and read back — and not one program may be built after it."""
    from cubefs_tpu.codec import codemode as cm
    from cubefs_tpu.utils import metrics

    count, largest = sizes.any_size
    built = lambda: sum(v for _, v in metrics.codec_programs.samples())
    t0 = time.perf_counter()
    before = built()
    steps = dep.access.ready(largest)
    ready_s = time.perf_counter() - t0
    at_ready, compiles = built(), clock.compiles
    rng = np.random.default_rng([SEED, 34])
    # a third of the objects in each size class, log-uniform inside it
    bounds = [4096] + [p.max_size for p in dep.access.cfg.policies[:2]] \
        + [largest]
    modes = set()
    for i in range(count):
        lo, hi = bounds[i % 3], bounds[i % 3 + 1]
        size = int(np.exp(rng.uniform(np.log(lo + 1), np.log(hi))))
        data = payload(5, i, size)
        loc = dep.access.put(data)
        modes.add(cm.CodeMode(loc.codemode).name)
        if dep.access.get(loc) != data:
            raise RuntimeError(f"GET of a {size} B object differs from "
                               f"what was PUT")
    if built() != at_ready or clock.compiles != compiles:
        raise RuntimeError(
            f"{built() - at_ready} codec programs built and "
            f"{clock.compiles - compiles} programs compiled after ready, "
            f"by {count} PUTs and GETs of sizes up to {largest} B")
    if len(modes) != 3:
        raise RuntimeError(f"the sizes reached codemodes {sorted(modes)}, "
                           f"not all three size classes")
    return {"ok": True, "objects": count, "largest_bytes": largest,
            "codemodes": sorted(modes), "ready_steps": int(steps),
            "programs_built_at_ready": int(at_ready - before),
            "programs_built_after_ready": 0,
            "ready_wall_s": round(ready_s, 3),
            "smoke_wall_s": round(time.perf_counter() - t0, 3)}


def _check_rebuilt_unit(dep: Deployment, objects: list, t, unit,
                        bad: int) -> list[int]:
    """Every shard of rebuilt unit `bad` against the reference stripe's
    row of the blob that was PUT there, and its stored CRC against
    zlib's; returns the shards' lengths."""
    from cellbench import reference
    from cubefs_tpu.codec import codemode as cm

    lengths = []
    for data, o in objects:
        sl = o.slices[0]
        whole = data if sl.count == 1 else data.ljust(
            sl.count * sl.blob_size, b"\0")
        for b in range(sl.count):
            ref = reference.stripe(
                whole[b * sl.blob_size:(b + 1) * sl.blob_size],
                t.n, t.m, t.min_shard_size)[bad]
            meta, got = dep.unit_call(unit, "get_shard", sl.min_bid + b)
            if got != ref.tobytes():
                raise RuntimeError(
                    f"{cm.CodeMode(o.codemode).name} bid {sl.min_bid + b} "
                    f"unit {bad}: rebuilt shard of {len(got)} B differs "
                    f"from the reference stripe's {ref.shape[0]} B")
            if reference.crc32(got) != meta["crc"]:
                raise RuntimeError(f"bid {sl.min_bid + b} unit {bad}: "
                                   f"stored crc is not zlib's")
            lengths.append(len(got))
    return lengths


def phase_repair_any_size(dep: Deployment, sizes: Sizes, clock: CompileClock
                          ) -> dict:
    """A volume of objects of any sizes per size-class codemode, two
    units of each rebuilt (a data and a parity one): after the worker's
    `ready` (the repair steps its policies can reach, built once) the
    volume's two tasks come in one lease and share one read of the
    survivors, they group their bids by width rung, not one program is
    built, and every rebuilt shard is the reference stripe's row
    (cellbench/reference.py) at its exact size. The PUTs go through a
    proxy allocator, so a codemode's objects share a volume; the repairs
    are queued as an operator's (`manual_migrate`): the worker reads
    nothing of the units it rebuilds."""
    from cubefs_tpu.blob.access import AccessHandler
    from cubefs_tpu.blob.proxy import ProxyAllocator
    from cubefs_tpu.codec import codemode as cm
    from cubefs_tpu.utils import metrics, rpc

    largest = sizes.any_size[1]
    cfg = dep.access.cfg
    built = lambda: sum(v for _, v in metrics.codec_programs.samples())
    t0 = time.perf_counter()
    before = built()
    steps = dep.worker.ready(largest, cfg.policies, cfg.blob_size)
    ready_s = time.perf_counter() - t0
    dep.access.ready(largest)  # the fill's programs (phase any_size's)
    at_ready, compiles = built(), clock.compiles
    front = AccessHandler(
        dep.cm_client, dep.pool, cfg, repair_queue=dep.repair_q,
        delete_queue=dep.delete_q,
        proxy_client=rpc.Client(ProxyAllocator(dep.cm_client)))
    rng = np.random.default_rng([SEED, 36])
    bounds = [4096] + [p.max_size for p in cfg.policies[:2]] + [largest]
    tasks0 = dict(metrics.repair_steps_per_task.samples()).get(
        (), {"count": 0, "sum": 0.0})
    shared0 = metrics.repair_task_reads.value(reads="shared")
    checked, by_mode = 0, {}
    try:
        for klass in range(3):
            objects = []
            for i in range(sizes.repair_any_size):
                size = int(np.exp(rng.uniform(np.log(bounds[klass] + 1),
                                              np.log(bounds[klass + 1]))))
                data = payload(6, 100 * klass + i, size)
                objects.append((data, front.put(data)))
            loc = objects[0][1]
            t = cm.tactic(loc.codemode)
            vid = loc.slices[0].vid
            if {o.slices[0].vid for _, o in objects} != {vid}:
                raise RuntimeError(f"{sizes.repair_any_size} PUTs of one "
                                   f"size class are not in one volume")
            # data + parity, parity + data, last + data
            bads = ((1, t.n), (t.n + 1, 0), (t.n + t.m - 1, 3))[klass]
            old = [dep.cm.get_volume(vid).units[bad] for bad in bads]
            for bad in bads:
                dep.sched.manual_migrate(vid, bad)
            for _ in range(dep.sched.MAX_ATTEMPTS + 1):
                if not dep.worker.run_once():
                    break
            if dep.worker.failed:
                errs = sorted({x.get("last_error", "") for x in
                               dep.sched.tasks.values()
                               if x.get("last_error")})
                raise RuntimeError(f"vid {vid} units {bads}: repair task "
                                   f"runs failed: {errs[:3]}")
            widths = set()
            for bad, was in zip(bads, old):
                unit = dep.cm.get_volume(vid).units[bad]
                if (unit.disk_id, unit.chunk_id) == (was.disk_id,
                                                     was.chunk_id):
                    raise RuntimeError(
                        f"vid {vid} unit {bad} was not rebuilt")
                lengths = _check_rebuilt_unit(dep, objects, t, unit, bad)
                widths.update(lengths)
                checked += len(lengths)
            by_mode[cm.CodeMode(loc.codemode).name] = len(widths)
    finally:
        front._pool.shutdown(wait=True)
    if built() != at_ready or clock.compiles != compiles:
        raise RuntimeError(
            f"{built() - at_ready} codec programs built and "
            f"{clock.compiles - compiles} programs compiled after ready, "
            f"by the repair of three mixed-size volumes")
    shared = metrics.repair_task_reads.value(reads="shared") - shared0
    if shared != 3:
        raise RuntimeError(f"three volumes of two moving units each: "
                           f"{shared} tasks shared their sibling's read "
                           f"of the survivors, not one a volume")
    tasks = dict(metrics.repair_steps_per_task.samples())[()]
    return {"ok": True, "objects_per_codemode": sizes.repair_any_size,
            "distinct_shard_sizes": by_mode, "rebuilt_shards_checked": checked,
            "decode_steps": int(tasks["sum"] - tasks0["sum"]),
            "tasks": int(tasks["count"] - tasks0["count"]),
            "shared_reads": int(shared),
            "ready_steps": int(steps),
            "programs_built_at_ready": int(at_ready - before),
            "programs_built_after_ready": 0,
            "ready_wall_s": round(ready_s, 3),
            "smoke_wall_s": round(time.perf_counter() - t0, 3)}


def phase_device_proof(n_devices: int, device_checks: bool) -> dict:
    """Right answers are not enough: show where they were computed."""
    from cubefs_tpu.codec import engine
    from cubefs_tpu.ops import progcache, rs_kernel
    from cubefs_tpu.utils import metrics

    if engine._dead_engines:
        raise RuntimeError(f"codec engines quarantined during the run: "
                           f"{sorted(engine._dead_engines)} (cause logged "
                           f"by cubefs.codec)")
    if rs_kernel.pallas_refusals:
        raise RuntimeError(f"Pallas gate refused programs: "
                           f"{rs_kernel.pallas_refusals}")
    steps = {f"{k[0]}/{k[1]}": v
             for k, v in metrics.codec_batch_steps.samples()}
    device_steps = sum(v for k, v in metrics.codec_batch_steps.samples()
                       if k[1] == "tpu")
    if not device_steps:
        raise RuntimeError(f"no codec step was served by the device "
                           f"engine: {steps}")
    rec = {"ok": True, "steps_by_op_engine": steps,
           "pallas_gate_refusals": 0, "engines_quarantined": 0}

    # every program the device engines built for a served step, (B, C, S)
    # in: a large-class shape has to be a fused program's (the gate's
    # own programs are (C, tile) and a refusal has raised above)
    fused = sum(1 for _, _, _, shape, _, _ in
                progcache.SHARED.keys("pallas_gf") if len(shape) == 3)
    by_jnp = [(rows, cols, shape) for _, rows, cols, shape in
              progcache.SHARED.keys("rs_jit")
              if len(shape) == 3 and rs_kernel._pallas_profitable(shape[-1])]
    if device_checks and n_devices == 1 and by_jnp:
        raise RuntimeError(
            f"large-class shapes (rows, cols, input) {by_jnp} are served "
            f"by the jnp path, not the fused kernel")
    rec["large_class_geometries"] = fused + len(by_jnp)
    rec["served_by_fused_kernel"] = fused
    if device_checks and n_devices == 1 and not fused:
        raise RuntimeError("no large-class geometry reached the batcher")

    dp = {k[0]: v for k, v in metrics.codec_batch_dp_steps.samples()}
    rec["dp_steps_by_devices_holding_input"] = dp
    if device_checks and n_devices > 1 and not dp.get(str(n_devices)):
        raise RuntimeError(
            f"{n_devices} devices visible but no drained step placed its "
            f"input on all of them: dp steps {dp}")
    return rec


# ------------------------------------------------------------------ run

def run(sizes: Sizes, workdir: str, device_checks: bool) -> dict:
    """All phases in order; raises at the first miss. ``device_checks``
    False (CPU test suite) skips only what needs the chip's machine:
    require_tpu, the forced native rebuild and the fused-kernel / dp
    assertions."""
    phases: dict = {}
    t_start = time.perf_counter()
    if device_checks:
        phases["build"] = phase_build()
    device = phase_device(device_checks)
    clock = CompileClock()
    before = tree_digest(HERE)

    os.makedirs(workdir, exist_ok=True)
    dep = Deployment(workdir, sizes)
    try:
        phases["put"], objects = phase_put(dep, sizes)
        phases["get"] = phase_get(dep, sizes, objects)
        phases["reference"] = phase_reference(dep, sizes, objects)
        phases["break_repair"] = phase_break_repair(dep, sizes, objects)
        phases["sidecar"] = phase_sidecar(dep, sizes)
        phases["two_loss"] = phase_two_loss(dep, sizes, clock)
        phases["any_size"] = phase_any_size(dep, sizes, clock)
        phases["repair_any_size"] = phase_repair_any_size(dep, sizes, clock)
        phases["device_proof"] = phase_device_proof(
            device["count"], device_checks)
    finally:
        clock.close()
        dep.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    after = tree_digest(HERE)
    if after != before:
        changed = sorted(k for k in before.keys() | after.keys()
                         if before.get(k) != after.get(k))
        raise RuntimeError(f"the run changed tracked files: {changed[:10]}")
    phases["checkout_clean"] = {"ok": True, "files": len(after)}

    return {
        "ok": all(p["ok"] for p in phases.values()),
        "device": device,
        "seed": SEED,
        "phases": phases,
        "bytes_put": sum(v["bytes"] for v in phases["put"].values()
                         if isinstance(v, dict)),
        "bytes_rebuilt": phases["break_repair"]["bytes_rebuilt"],
        "compile": {"seconds": round(clock.seconds, 2),
                    "compiles": clock.compiles,
                    "persistent_cache_hits": clock.cache_hits,
                    "persistent_cache_misses": clock.cache_misses,
                    "cold": clock.cache_hits == 0},
        "smoke_wall_s": round(time.perf_counter() - t_start, 2),
        "claim": None,
    }


def result_line(summary: dict) -> str:
    """What the driver reads off the end of stdout: these keys and no
    others."""
    dev = summary["device"]
    return json.dumps({"ok": bool(summary["ok"]),
                       "device": {"platform": dev["platform"],
                                  "kind": dev["kind"],
                                  "count": dev["count"]}})


def main() -> int:
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    os.makedirs(OUT_DIR, exist_ok=True)
    summary = run(FULL, os.path.join(OUT_DIR, "disks"), device_checks=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    print(result_line(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
