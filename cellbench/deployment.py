"""The in-process blob deployment a cell runs against, built from its
configuration file (copied from chip_smoke.Deployment, which proved this
topology on the chip in PR 21; the smoke's sidecar is left out and a
ProxyAllocator is put in front of ClusterMgr, as upstream runs it).

One process owns the chip and hosts ClusterMgr, the BlobNodes, the
AccessHandler, the Scheduler and the RepairWorker over the in-process
transport: the only topology in which access, worker and blobnodes share
``batcher.DEFAULT`` and so one device queue.
"""

from __future__ import annotations

import os


class CompileClock:
    """Counts JAX's backend compilations and persistent-cache hits and
    misses (copy of chip_smoke.CompileClock). ``mark()`` returns the
    counts so far, so the caller can tell the window from set-up."""

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

    def mark(self) -> dict:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def hold_to_file(cfg, deploy: dict, codemodes: dict) -> None:
    """The configuration file is the deployment as it is run: the port's
    blob size, codemode tactics and size classes must be the file's."""
    from cubefs_tpu.codec import codemode as cm

    if cfg.blob_size != int(deploy["blob_size"]):
        raise RuntimeError(
            f"the configuration states blob_size {deploy['blob_size']} "
            f"but AccessConfig() ships {cfg.blob_size}")
    for name, want in codemodes.items():
        t = cm.tactic(name)
        got = {"id": int(cm.CodeMode[name]), "n": t.n, "m": t.m,
               "put_quorum": t.put_quorum, "min_shard": t.min_shard_size}
        got["max_object_bytes"] = next(
            (p.max_size for p in cfg.policies
             if p.enable and p.mode_name == name), None)
        stated = {k: want.get(k) for k in got}
        if got != stated:
            raise RuntimeError(f"the configuration states {name} as "
                               f"{stated} but the port ships {got}")


class Deployment:
    """ClusterMgr + nodes x disks BlobNodes + proxy allocator + access +
    scheduler + repair worker, every codec caller on ``engine``."""

    def __init__(self, workdir: str, deploy: dict, codemodes: dict):
        from cubefs_tpu.blob.access import (AccessConfig, AccessHandler,
                                            NodePool)
        from cubefs_tpu.blob.blobnode import BlobNode
        from cubefs_tpu.blob.clustermgr import ClusterMgr
        from cubefs_tpu.blob.mq import MessageQueue
        from cubefs_tpu.blob.proxy import ProxyAllocator
        from cubefs_tpu.blob.scheduler import Scheduler
        from cubefs_tpu.blob.worker import RepairWorker
        from cubefs_tpu.utils import rpc

        self.cm = ClusterMgr()
        self.cm_client = rpc.Client(self.cm)
        self.pool = NodePool()
        self.nodes: dict[str, BlobNode] = {}
        for n in range(int(deploy["nodes"])):
            addr = f"node{n}"
            node = BlobNode(
                node_id=n,
                disk_paths=[os.path.join(workdir, f"n{n}d{d}")
                            for d in range(int(deploy["disks_per_node"]))],
                cm_client=self.cm_client, addr=addr)
            node.register()
            node.send_heartbeat()
            self.pool.bind(addr, node)
            self.nodes[addr] = node
        self.repair_q = MessageQueue()
        self.delete_q = MessageQueue()
        cfg = AccessConfig(engine=deploy["engine"])
        hold_to_file(cfg, deploy, codemodes)
        proxy = None
        if deploy["allocator"] == "proxy":
            proxy = rpc.Client(ProxyAllocator(self.cm_client))
        self.access = AccessHandler(
            self.cm_client, self.pool, cfg, repair_queue=self.repair_q,
            delete_queue=self.delete_q, proxy_client=proxy)
        self.sched = Scheduler(self.cm, repair_queue=self.repair_q,
                               delete_queue=self.delete_q,
                               node_pool=self.pool)
        self.worker = RepairWorker(rpc.Client(self.sched), self.cm_client,
                                   self.pool, engine=deploy["engine"])

    def stop(self) -> None:
        self.sched.stop()
        self.access._pool.shutdown(wait=True)
        for node in self.nodes.values():
            node.stop()

    def unit_call(self, unit, method: str, bid: int | None = None):
        args = {"disk_id": unit.disk_id, "chunk_id": unit.chunk_id}
        if bid is not None:
            args["bid"] = bid
        return self.pool.get(unit.node_addr).call(method, args)

    def wrap_node_calls(self, wrap) -> None:
        """Replace every node client's ``call`` by ``wrap(call)``: the
        one seam at which the benchmark sees shard reads and writes."""
        for addr in self.nodes:
            client = self.pool.get(addr)
            client.call = wrap(client.call)

    def node_of_disk(self, disk_id: int):
        return next(n for n in self.nodes.values() if disk_id in n.disk_ids)
