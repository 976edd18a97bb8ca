"""Single-binary role launcher.

Role parity: cmd/cmd.go — one `cfs-server` binary dispatching on the
"role" key of a JSON config (cmd.go:184-231), here
`python -m cubefs_tpu.cmd -c config.json`. Each role builds its service
object(s), serves them with the RPC layer, registers with its control
plane, and blocks. Heartbeat loops run in daemon threads.

Config keys (JSON):
  role:        master | metanode | datanode | objectnode | fuseclient |
               clustermgr | blobnode | access | proxy | scheduler | codec |
               fsgateway | console | flashnode | flashgroupmanager
  listen_host / listen_port: bind address (port 0 = ephemeral)
  master_addr / clustermgr_addr / scheduler_addr: upstreams
  data_dirs / data_dir: storage paths
  vols: {bucket: vol_name} (objectnode)
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _audit_for(cfg):
    from .utils.auditlog import AuditLogger

    if cfg.get("audit_dir"):
        return AuditLogger(f"{cfg['audit_dir']}/{cfg['role']}.audit.log")
    return None


def _serve(routes, cfg, audit=None):
    from .utils import rpc

    if audit is None:
        audit = _audit_for(cfg)
    srv = rpc.RpcServer(
        routes, host=cfg.get("listen_host", "127.0.0.1"),
        port=int(cfg.get("listen_port", 0)),
        service=cfg["role"], audit=audit,
    ).start()
    print(f"[{cfg['role']}] listening on {srv.addr}", flush=True)
    return srv


def _claim_codec_device(role: str) -> None:
    """Initialise the JAX backend at boot, not inside the first request,
    and say which device this codec host got. With an explicit
    JAX_PLATFORMS (deploy.cluster sets one for the chip owner) a chip
    that is missing or held by another process raises here and the
    role dies with JAX's message instead of serving from CPU."""
    import jax

    devs = jax.devices()
    print(f"[{role}] codec device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)


def _heartbeat_loop(fn, interval=3.0):
    def loop():
        while True:
            try:
                fn()
            except Exception as e:
                print(f"heartbeat error: {e}", file=sys.stderr, flush=True)
            time.sleep(interval)

    threading.Thread(target=loop, daemon=True).start()


def run_role(cfg: dict):
    # NOTE: heavy imports (jax via the codec) stay inside the role
    # branches that need them — datanode/metanode/master boot fast.
    from .utils import rpc
    from .utils.rpc import NodePool

    role = cfg["role"]
    pool = NodePool()

    if role == "master":
        from .fs.master import Master

        svc = Master(pool, replicas=int(cfg.get("replicas", 3)),
                     allow_single_node=bool(cfg.get("allow_single_node", False)),
                     data_dir=cfg.get("data_dir"),
                     me=cfg.get("me"), peers=cfg.get("peers"))
        svc.start_quota_sweeper(float(cfg.get("quota_sweep_interval", 30.0)))
        return _serve(svc, cfg), svc

    if role == "metanode":
        from .fs.metanode import MetaNode

        svc = MetaNode(int(cfg.get("node_id", 0)), data_dir=cfg.get("data_dir"),
                       node_pool=pool)
        audit = _audit_for(cfg)
        srv = _serve(svc, cfg, audit=audit)  # live routing: per-partition raft handlers
        svc.addr = srv.addr
        # the binary meta plane (manager_op.go analog) listens beside HTTP
        # and shares the HTTP plane's audit log
        psrv = svc.serve_packets(host=cfg.get("listen_host", "127.0.0.1"),
                                 port=int(cfg.get("packet_port", 0)),
                                 audit=audit)
        print(f"[metanode] packet plane on {psrv.addr}", flush=True)
        # native C++ read plane (metaserve.cc) beside the Python planes
        raddr = svc.serve_native(host=cfg.get("listen_host", "127.0.0.1"),
                                 port=int(cfg.get("read_port", 0)))
        if raddr:
            print(f"[metanode] native read plane on {raddr}", flush=True)
        master = rpc.Client(cfg["master_addr"])
        zone = cfg.get("zone", "default")
        rack = cfg.get("rack")
        master.call("register", {"kind": "meta", "addr": srv.addr,
                                 "zone": zone, "rack": rack,
                                 "packet_addr": psrv.addr,
                                 "read_addr": raddr})
        _heartbeat_loop(lambda: master.call(
            "heartbeat", {"kind": "meta", "addr": srv.addr, "zone": zone,
                          "rack": rack,
                          "packet_addr": psrv.addr, "read_addr": raddr}))

        def _dp_view():
            meta, _ = master.call("dp_view", {})
            return {int(k): v for k, v in meta["dps"].items()}

        svc.set_dp_view(_dp_view)  # enables the deferred-deletion scan
        return srv, svc

    if role == "datanode":
        from .fs.datanode import DataNode

        # the node learns its own address only after the server binds
        svc = DataNode(int(cfg.get("node_id", 0)), cfg["data_dir"], "pending", pool,
                       qos=cfg.get("qos"),  # {"read_bps":..., "write_bps":...}
                       disks=cfg.get("disks"))  # multi-disk: list of dirs
        audit = _audit_for(cfg)
        srv = _serve(svc, cfg, audit=audit)  # live routing: per-dp raft handlers
        svc.addr = srv.addr
        # the binary packet plane (hot data path) listens beside HTTP
        # and shares the HTTP plane's audit log
        psrv = svc.serve_packets(host=cfg.get("listen_host", "127.0.0.1"),
                                 port=int(cfg.get("packet_port", 0)),
                                 audit=audit)
        print(f"[datanode] packet plane on {psrv.addr}", flush=True)
        # native C++ read plane (dataserve.cc) beside the Python planes
        raddr = svc.serve_native(host=cfg.get("listen_host", "127.0.0.1"),
                                 port=int(cfg.get("read_port", 0)))
        if raddr:
            print(f"[datanode] native read plane on {raddr}", flush=True)
        master = rpc.Client(cfg["master_addr"])
        zone = cfg.get("zone", "default")
        rack = cfg.get("rack")
        master.call("register", {"kind": "data", "addr": srv.addr,
                                 "zone": zone, "rack": rack,
                                 "packet_addr": psrv.addr,
                                 "read_addr": raddr,
                                 "disks": svc.disk_report()})
        # heartbeats carry the disk report: the master's disk manager
        # migrates partitions off any disk reported broken
        _heartbeat_loop(lambda: master.call(
            "heartbeat", {"kind": "data", "addr": srv.addr, "zone": zone,
                          "rack": rack,
                          "packet_addr": psrv.addr, "read_addr": raddr,
                          "disks": svc.disk_report()}))
        return srv, svc

    if role == "flashnode":
        from .fs.remotecache import FlashNode

        svc = FlashNode(capacity_bytes=int(cfg.get("capacity_bytes",
                                                   256 << 20)))
        srv = _serve(svc, cfg)
        if cfg.get("fgm_addr"):
            fgm = rpc.Client(cfg["fgm_addr"])
            _heartbeat_loop(lambda: fgm.call("flashnode_heartbeat",
                                             {"addr": srv.addr}))
        return srv, svc

    if role == "flashgroupmanager":
        from .fs.remotecache import FlashGroupManager

        svc = FlashGroupManager(data_dir=cfg.get("data_dir"),
                                me=cfg.get("me"), peers=cfg.get("peers"),
                                node_pool=pool)
        return _serve(svc, cfg), svc

    if role == "objectnode":
        from .fs.client import FileSystem
        from .fs.objectnode import ObjectNode

        master = rpc.Client(cfg["master_addr"])
        vols = {}
        for bucket, vol_name in cfg.get("vols", {}).items():
            view = master.call("client_view", {"name": vol_name})[0]["volume"]
            vols[bucket] = FileSystem(view, pool,
                                      master_addr=cfg["master_addr"])
        auth = None
        if cfg.get("users_from_master"):
            # the master's replicated user table is the identity source
            from .fs.s3auth import MasterUserStore, S3V4Authenticator

            auth = S3V4Authenticator(MasterUserStore(master),
                                     dict(cfg.get("vols", {})))
        elif cfg.get("users"):  # [{access_key, secret_key, grants:{vol:perm}}]
            from .fs.authnode import UserStore
            from .fs.s3auth import S3V4Authenticator

            store = UserStore()
            for u in cfg["users"]:
                store.users[u["access_key"]] = {
                    "user_id": u.get("user_id", u["access_key"]),
                    "sk": u["secret_key"],
                    "volumes": dict(u.get("grants", {})),
                }
            auth = S3V4Authenticator(store, dict(cfg.get("vols", {})))
        sinks = []
        if cfg.get("audit_webhook_url"):
            from .fs.s3audit import WebhookAuditSink

            sinks.append(WebhookAuditSink(cfg["audit_webhook_url"]))
        if cfg.get("audit_queue_dir"):
            from .blob.mq import MessageQueue
            from .fs.s3audit import QueueAuditSink

            sinks.append(QueueAuditSink(
                MessageQueue(cfg["audit_queue_dir"], topic="s3audit")))
        node = ObjectNode(vols, host=cfg.get("listen_host", "127.0.0.1"),
                          port=int(cfg.get("listen_port", 0)),
                          authenticator=auth, audit_sinks=sinks).start()
        print(f"[objectnode] S3 on {node.addr}", flush=True)
        return node, node

    if role == "fuseclient":
        from .fs.client import FileSystem
        from .fs.fuse import mount as fuse_mount

        master = rpc.Client(cfg["master_addr"])
        view = master.call("client_view", {"name": cfg["vol"]})[0]["volume"]
        m = fuse_mount(FileSystem(view, pool, master_addr=cfg["master_addr"]),
                       cfg["mountpoint"])
        print(f"[fuseclient] {cfg['vol']} mounted at {cfg['mountpoint']}",
              flush=True)
        return m, m

    if role == "clustermgr":
        from .blob.clustermgr import ClusterMgr

        # peers (incl. our own addr) enable raft replication; addresses
        # must be static (listen_port != 0) so the group can dial us
        svc = ClusterMgr(data_dir=cfg.get("data_dir"),
                         allow_colocated_units=bool(cfg.get("allow_colocated_units", False)),
                         me=cfg.get("me"), peers=cfg.get("peers"),
                         node_pool=pool)
        return _serve(svc, cfg), svc

    if role == "blobnode":
        from .blob.blobnode import BlobNode

        svc = BlobNode(int(cfg.get("node_id", 0)), cfg["data_dirs"],
                       rpc.Client(cfg["clustermgr_addr"]), addr="",
                       az=cfg.get("az", ""), rack=cfg.get("rack", ""))
        srv = _serve(rpc.expose(svc), cfg)
        svc.addr = srv.addr
        svc.register()
        svc.start_heartbeat()
        return srv, svc

    if role == "proxy":
        from .blob.proxy import ProxyAllocator

        svc = ProxyAllocator(rpc.Client(cfg["clustermgr_addr"]))
        return _serve(rpc.expose(svc), cfg), svc

    if role == "access":
        from .blob.access import AccessConfig, AccessHandler
        from .blob.mq import MessageQueue, QueueProducer

        _claim_codec_device(role)
        q_dir = cfg.get("queue_dir")
        mq_members = cfg.get("mq_members")  # replicated bus (Kafka role)
        if mq_members:
            rq = QueueProducer("repair", mq_members, pool,
                               int(cfg.get("mq_partitions", 2)))
            dq = QueueProducer("delete", mq_members, pool,
                               int(cfg.get("mq_partitions", 2)))
        else:
            rq = MessageQueue(q_dir, "repair") if q_dir else None
            dq = MessageQueue(q_dir, "delete") if q_dir else None
        svc = AccessHandler(
            rpc.Client(cfg["clustermgr_addr"]), pool,
            AccessConfig(blob_size=int(cfg.get("blob_size", 8 << 20)),
                         engine=cfg.get("ec_engine", "auto"),
                         client_az=cfg.get("az")),
            repair_queue=rq,
            delete_queue=dq,
            proxy_client=rpc.Client(cfg["proxy_addr"]) if cfg.get("proxy_addr") else None,
        )
        return _serve(rpc.expose(svc), cfg), svc

    if role == "codec":
        from .codec.service import CodecService

        _claim_codec_device(role)
        svc = CodecService(engine=cfg.get("ec_engine"))
        return _serve(rpc.expose(svc), cfg), svc

    if role == "scheduler":
        # The scheduler colocates with clustermgr state; in multi-process
        # deployments it owns its own ClusterMgr data dir (leader mode).
        from .blob.clustermgr import ClusterMgr
        from .blob.mq import MessageQueue
        from .blob.scheduler import Scheduler

        cm = ClusterMgr(data_dir=cfg.get("data_dir"))
        q_dir = cfg.get("queue_dir")
        mq_routes: dict = {}
        if cfg.get("mq_me") and cfg.get("mq_peers"):
            # replicated bus member (Kafka role): this scheduler hosts a
            # raft member of each topic; producers relay via mq_*_put
            from .blob.mq import ReplicatedQueue

            nparts = int(cfg.get("mq_partitions", 2))
            rq = ReplicatedQueue("repair", cfg["mq_me"], cfg["mq_peers"],
                                 pool, data_dir=cfg.get("mq_dir"),
                                 n_partitions=nparts)
            dq = ReplicatedQueue("delete", cfg["mq_me"], cfg["mq_peers"],
                                 pool, data_dir=cfg.get("mq_dir"),
                                 n_partitions=nparts)
            mq_routes = {**rq.extra_routes, **dq.extra_routes,
                         "mq_status": lambda a, b: {
                             "repair": rq.status(), "delete": dq.status()}}
        else:
            rq = MessageQueue(q_dir, "repair") if q_dir else None
            dq = MessageQueue(q_dir, "delete") if q_dir else None
        svc = Scheduler(
            cm,
            repair_queue=rq,
            delete_queue=dq,
            node_pool=pool,
            data_dir=cfg.get("task_dir"),
        )
        svc.start()
        routes = {**rpc.expose(svc), **mq_routes,
                  **{f"cm_{k}": v for k, v in rpc.expose(cm).items()}}
        return _serve(dict(routes, role=lambda a, b: {"role": "scheduler"}), cfg), svc

    if role == "fsgateway":
        from .fs.client import FileSystem
        from .fs.fsgateway import FsGateway

        master = rpc.Client(cfg["master_addr"])
        view = master.call("client_view", {"name": cfg["vol"]})[0]["volume"]
        fs = FileSystem(view, pool, master_addr=cfg["master_addr"])
        svc = FsGateway(fs)
        srv = _serve(rpc.expose(svc), cfg)
        print(f"[fsgateway] {cfg['vol']} on {srv.addr}", flush=True)
        return srv, svc

    if role == "console":
        from .fs.console import Console

        svc = Console(master_addr=cfg.get("master_addr"),
                      clustermgr_addr=cfg.get("clustermgr_addr"),
                      scheduler_addr=cfg.get("scheduler_addr"),
                      host=cfg.get("listen_host", "127.0.0.1"),
                      port=int(cfg.get("listen_port", 0))).start()
        print(f"[console] listening on {svc.addr}", flush=True)
        return svc, svc

    raise SystemExit(f"unknown role {role!r}")


def main(argv=None):
    import signal

    ap = argparse.ArgumentParser(prog="cubefs-tpu-server")
    ap.add_argument("-c", "--config", required=True, help="JSON config file")
    args = ap.parse_args(argv)
    cfg = json.load(open(args.config))
    srv, svc = run_role(cfg)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    # graceful shutdown: persist/close stores and raft state before exit
    print(f"[{cfg['role']}] shutting down", flush=True)
    for closer in ("stop", "fsm_stop", "unmount"):
        fn = getattr(svc, closer, None)
        if callable(fn):
            try:
                fn()
            except Exception:
                pass
    if hasattr(srv, "stop"):
        try:
            srv.stop()
        except Exception:
            pass


if __name__ == "__main__":
    main()
