"""Cluster launcher: the docker-compose analog.

Role parity: docker/docker-compose.yml (3 masters, N metanodes/datanodes,
objectnodes, monitoring) and blobstore/run_docker.sh — one topology JSON
spawns every role as a local process (master, metanodes, datanodes,
optional blob plane, objectnode, codec sidecar, fsgateway, console),
waits for liveness, creates the
initial volume, and writes a state file with all addresses.

  python -m cubefs_tpu.deploy.cluster --topo topo.json --workdir /tmp/c1

Topology JSON (all counts optional):
  {"metanodes": 3, "datanodes": 4, "blobnodes": 1, "disks_per_blobnode": 9,
   "objectnode": true, "access": true, "scheduler": false, "codec": false,
   "volume": {"name": "vol1", "mp_count": 3, "dp_count": 4},
   "blob_azs": 3}

blob_azs spreads blobnodes across failure domains round-robin: an int
yields AZ names az0..azN-1, a list supplies the names. Multi-AZ LRC
codemodes then place each local stripe inside one AZ
(cubefs_tpu/blob/topology.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def codec_host(topo: dict) -> str | None:
    """The one role of a topology that may initialise the accelerator:
    the codec sidecar where configured, else access. A chip belongs to
    one process at a time, and JAX hands a process that cannot get it
    the CPU without a word — so the launcher decides, not boot order."""
    if topo.get("codec"):
        return "codec"
    if topo.get("blobnodes") and topo.get("access", True):
        return "access"
    return None


def role_env(role: str, owner: str | None, environ) -> dict:
    """Explicit environment for one role process. The chip owner keeps
    the operator's JAX_PLATFORMS, or asks for the TPU by name when none
    is set: an explicit platform makes JAX fail at start-up instead of
    choosing CPU. Every other role is pinned to CPU."""
    env = dict(environ)
    if role == owner:
        env["JAX_PLATFORMS"] = environ.get("JAX_PLATFORMS") or "tpu"
    else:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Proc:
    def __init__(self, role: str, cfg: dict, workdir: str, env: dict):
        self.role = role
        path = os.path.join(workdir, f"{cfg.get('name', role)}.json")
        with open(path, "w") as f:
            json.dump(cfg, f)
        self.log_path = os.path.join(workdir, f"{cfg.get('name', role)}.log")
        self.log = open(self.log_path, "w")
        self.p = subprocess.Popen(
            [sys.executable, "-m", "cubefs_tpu.cmd", "-c", path],
            stdout=self.log, stderr=subprocess.STDOUT, env=env,
        )
        self.addr: str | None = None

    def wait_addr(self, timeout: float = 60.0) -> str:
        deadline = time.time() + timeout
        while time.time() < deadline:
            for line in open(self.log_path):
                if "listening on" in line or "S3 on" in line:
                    self.addr = line.strip().rsplit(" ", 1)[-1]
                    return self.addr
            if self.p.poll() is not None:
                raise RuntimeError(
                    f"{self.role} exited: {open(self.log_path).read()[-800:]}"
                )
            time.sleep(0.3)
        raise TimeoutError(f"{self.role} did not come up; log: {self.log_path}")


class Cluster:
    def __init__(self, topo: dict, workdir: str):
        self.topo = topo
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        self.procs: list[Proc] = []
        self.state: dict = {"roles": {}}

    def _spawn(self, role: str, cfg: dict) -> str:
        cfg["role"] = role
        p = Proc(role, cfg, self.workdir,
                 role_env(role, codec_host(self.topo), os.environ))
        self.procs.append(p)
        addr = p.wait_addr()
        self.state["roles"].setdefault(role, []).append(addr)
        return addr

    def up(self) -> dict:
        t = self.topo
        master = self._spawn("master", {
            "replicas": t.get("replicas", 3),
            "allow_single_node": t.get("datanodes", 4) < t.get("replicas", 3),
        })
        for i in range(t.get("metanodes", 3)):
            self._spawn("metanode", {
                "name": f"metanode{i}", "node_id": i, "master_addr": master,
                "data_dir": os.path.join(self.workdir, f"meta{i}")})
        for i in range(t.get("datanodes", 4)):
            self._spawn("datanode", {
                "name": f"datanode{i}", "node_id": i, "master_addr": master,
                "data_dir": os.path.join(self.workdir, f"data{i}")})
        from ..utils import rpc

        # nodes print "listening" before their register RPC lands; wait
        # until the master actually sees the full topology
        deadline = time.time() + 60
        want_meta, want_data = t.get("metanodes", 3), t.get("datanodes", 4)
        while time.time() < deadline:
            st = rpc.call(master, "stat")[0]
            if st["metanodes"] >= want_meta and st["datanodes"] >= want_data:
                break
            time.sleep(0.3)
        else:
            raise TimeoutError(f"nodes never registered: {st}")

        vol = t.get("volume", {"name": "vol1"})
        rpc.call(master, "create_volume", {
            "name": vol.get("name", "vol1"),
            "mp_count": vol.get("mp_count", 3),
            "dp_count": vol.get("dp_count", 4)})
        self.state["volume"] = vol.get("name", "vol1")

        cm = None
        if t.get("blobnodes"):
            cm = self._spawn("clustermgr", {
                "allow_colocated_units": t.get("blobnodes", 1) == 1,
                "data_dir": os.path.join(self.workdir, "cm")})
            azs = t.get("blob_azs")
            az_names = ([f"az{j}" for j in range(azs)]
                        if isinstance(azs, int) else list(azs or ()))
            for i in range(t["blobnodes"]):
                dirs = [os.path.join(self.workdir, f"bn{i}d{d}")
                        for d in range(t.get("disks_per_blobnode", 9))]
                bn_cfg = {"name": f"blobnode{i}", "node_id": i,
                          "clustermgr_addr": cm, "data_dirs": dirs}
                if az_names:
                    # round-robin AZ assignment; each node is its own rack
                    bn_cfg["az"] = az_names[i % len(az_names)]
                    bn_cfg["rack"] = f"{bn_cfg['az']}-r{i // len(az_names)}"
                self._spawn("blobnode", bn_cfg)
            if t.get("access", True):
                access_cfg = {"clustermgr_addr": cm,
                              "blob_size": t.get("blob_size", 8 << 20)}
                if az_names:
                    access_cfg["az"] = az_names[0]
                self._spawn("access", access_cfg)
        if t.get("objectnode"):
            self._spawn("objectnode", {
                "master_addr": master,
                "vols": {t.get("bucket", "bkt"): self.state["volume"]},
                "users": t.get("users", [])})
        if t.get("codec"):
            self._spawn("codec", {})
        if t.get("fsgateway"):
            self._spawn("fsgateway", {"master_addr": master,
                                      "vol": self.state["volume"]})
        if t.get("console"):
            console_cfg = {"master_addr": master}
            if cm is not None:
                console_cfg["clustermgr_addr"] = cm
            self._spawn("console", console_cfg)
        with open(os.path.join(self.workdir, "cluster.json"), "w") as f:
            json.dump(self.state, f, indent=2)
        return self.state

    def down(self) -> None:
        for p in self.procs:
            p.p.terminate()
        for p in self.procs:
            try:
                p.p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.p.kill()


def main(argv=None):
    ap = argparse.ArgumentParser(prog="cubefs-tpu-cluster")
    ap.add_argument("--topo", help="topology JSON file (defaults built in)")
    ap.add_argument("--workdir", required=True)
    args = ap.parse_args(argv)
    topo = json.load(open(args.topo)) if args.topo else {}
    c = Cluster(topo, args.workdir)
    state = c.up()
    print(json.dumps(state, indent=2), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        c.down()


if __name__ == "__main__":
    main()
