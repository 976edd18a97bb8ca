"""Aux subsystems: metrics registry/exposition, trace propagation across
RPC hops, audit logging with rotation, crc32block framing, proxy
allocator caching, dial prober, blob bench tool."""

import json
import os
import urllib.request

import numpy as np
import pytest

from cubefs_tpu.blob import dial as dialmod
from cubefs_tpu.blob.access import AccessConfig, AccessHandler
from cubefs_tpu.blob.blobnode import BlobNode
from cubefs_tpu.blob.clustermgr import ClusterMgr
from cubefs_tpu.blob.proxy import ProxyAllocator
from cubefs_tpu.codec import crc32block
from cubefs_tpu.utils import auditlog, metrics, rpc, trace
from cubefs_tpu.utils.rpc import NodePool


# ---------------- metrics ----------------
def test_counter_gauge_histogram_exposition():
    reg = metrics.Registry()
    c = reg.counter("test_ops_total", "ops", ("op",))
    c.inc(op="put")
    c.inc(2, op="put")
    g = reg.gauge("test_depth", "queue depth")
    g.set(7)
    h = reg.histogram("test_lat_seconds", "latency", ("op",))
    h.observe(0.003, op="get")
    h.observe(2.0, op="get")
    text = reg.render_text()
    assert 'test_ops_total{op="put"} 3.0' in text
    assert "test_depth 7.0" in text
    assert 'test_lat_seconds_bucket{op="get",le="0.005"} 1' in text
    assert 'test_lat_seconds_count{op="get"} 2' in text


def test_histogram_timer():
    reg = metrics.Registry()
    h = reg.histogram("t_seconds", "", ())
    with h.time():
        pass
    ((_, s),) = h.samples()
    assert s["count"] == 1 and s["sum"] >= 0


# ---------------- trace ----------------
def test_trace_propagates_across_rpc_hops():
    class Inner:
        def rpc_leaf(self, args, body):
            sp = trace.current()
            return {"trace_id": sp.trace_id, "parent": sp.parent_id}

    inner_srv = rpc.RpcServer(rpc.expose(Inner()), service="inner").start()

    class Outer:
        def rpc_entry(self, args, body):
            meta, _ = rpc.call(inner_srv.addr, "leaf")
            sp = trace.current()
            return {"outer_trace": sp.trace_id, "inner": meta}

    outer_srv = rpc.RpcServer(rpc.expose(Outer()), service="outer").start()
    try:
        meta, _ = rpc.call(outer_srv.addr, "entry")
        assert meta["inner"]["trace_id"] == meta["outer_trace"]
        assert meta["inner"]["parent"] is not None
        spans = trace.finished_spans(meta["outer_trace"])
        assert {s["op"] for s in spans} >= {"outer.entry", "inner.leaf"}
    finally:
        outer_srv.stop()
        inner_srv.stop()


def test_metrics_endpoint_served():
    class Svc:
        def rpc_ping(self, args, body):
            return {"pong": True}

    srv = rpc.RpcServer(rpc.expose(Svc()), service="s").start()
    try:
        rpc.call(srv.addr, "ping")
        with urllib.request.urlopen(f"http://{srv.addr}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "cubefs_rpc_requests_total" in text
    finally:
        srv.stop()


# ---------------- audit ----------------
def test_audit_log_rotation(tmp_path):
    path = str(tmp_path / "audit.log")
    log = auditlog.AuditLogger(path, max_bytes=500, keep=3)
    for i in range(40):
        log.record("svc", "op", 200, 0.001, detail=f"req {i}")
    log.close()
    assert os.path.exists(path + ".1")
    line = open(path + ".1").readline()
    rec = json.loads(line)
    assert rec["svc"] == "svc" and rec["code"] == 200


# ---------------- crc32block ----------------
def test_crc32block_roundtrip(rng):
    for n in (10, crc32block.BLOCK, crc32block.BLOCK + 1, 3 * crc32block.BLOCK + 17):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        frame = crc32block.encode(data)
        assert len(frame) == crc32block.encoded_size(n)
        assert crc32block.decoded_size(len(frame)) == n
        assert crc32block.decode(frame) == data


def test_crc32block_detects_corruption(rng):
    data = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    frame = bytearray(crc32block.encode(data))
    frame[70_000] ^= 1
    with pytest.raises(crc32block.CrcFrameError):
        crc32block.decode(bytes(frame))


def test_crc32block_layout_matches_reference(rng):
    """Byte layout pin (blobstore/common/crc32block/block.go:29-49): each
    unit is [crc32 LE][payload], unit size includes the CRC."""
    import zlib

    data = rng.integers(0, 256, 2000, dtype=np.uint8).tobytes()
    frame = crc32block.encode(data, block=1024)
    p = 1024 - 4
    assert frame[:4] == zlib.crc32(data[:p]).to_bytes(4, "little")
    assert frame[4 : 4 + p] == data[:p]
    assert frame[1024 : 1028] == zlib.crc32(data[p:]).to_bytes(4, "little")
    assert frame[1028:] == data[p:]


def test_crc32block_verify_batch(rng):
    block = 1024
    frames = []
    for _ in range(4):
        data = rng.integers(0, 256, 2 * (block - 4), dtype=np.uint8).tobytes()
        frames.append(np.frombuffer(crc32block.encode(data, block), dtype=np.uint8))
    arr = np.stack(frames)
    ok = crc32block.verify_batch(arr, block)
    assert ok.all()
    arr2 = arr.copy()
    arr2[1, 5] ^= 0xFF
    ok2 = crc32block.verify_batch(arr2, block)
    assert ok2[0] and not ok2[1]


# ---------------- proxy + dial over a mini blob cluster ----------------
@pytest.fixture
def mini_blob(tmp_path):
    cm = ClusterMgr(allow_colocated_units=True)
    cm_client = rpc.Client(cm)
    pool = NodePool()
    node = BlobNode(0, [str(tmp_path / f"d{i}") for i in range(9)], cm_client,
                    addr="n0")
    node.register()
    node.send_heartbeat()
    pool.bind("n0", node)
    return cm, cm_client, pool, node


def test_proxy_allocator_caches(mini_blob):
    cm, cm_client, pool, _ = mini_blob
    proxy = ProxyAllocator(cm_client)
    from cubefs_tpu.codec.codemode import CodeMode

    v1, b1 = proxy.alloc(CodeMode.EC6P3, 2)
    v2, b2 = proxy.alloc(CodeMode.EC6P3, 2)
    assert v1.vid == v2.vid  # volume reused from cache
    assert b2 == b1 + 2  # bids served from the leased range
    assert cm.stat()["volumes"] == 1
    proxy.invalidate_volume(CodeMode.EC6P3)
    v3, _ = proxy.alloc(CodeMode.EC6P3, 1)
    assert v3.vid != v1.vid


def test_access_through_proxy_and_dial(mini_blob, rng):
    cm, cm_client, pool, _ = mini_blob
    proxy = ProxyAllocator(cm_client)
    access = AccessHandler(cm_client, pool, AccessConfig(blob_size=32 << 10),
                           proxy_client=rpc.Client(proxy))
    payload = rng.integers(0, 256, 90_000, dtype=np.uint8).tobytes()
    loc = access.put(payload, codemode=13)  # EC6P3
    assert access.get(loc) == payload
    prober = dialmod.DialProber(rpc.Client(access), payload_size=8 << 10)
    assert prober.probe_once()
    assert prober.failures == 0


def test_blob_bench_tool(mini_blob):
    from cubefs_tpu.blob import bench_tool

    cm, cm_client, pool, _ = mini_blob
    access = AccessHandler(cm_client, pool, AccessConfig(blob_size=32 << 10))
    out = bench_tool.run(rpc.Client(access), size=8 << 10, count=4, concurrency=2)
    assert out["put_mbps"] > 0 and out["get_mbps"] > 0


def test_fs_bench_tool(tmp_path):
    from cubefs_tpu.tool import bench_fs

    fs, metas = bench_fs._inprocess_fs(str(tmp_path))
    try:
        out = bench_fs.run(fs, files=20, io_mb=2, threads=4)
        assert out["dir_create_ops"] > 0 and out["seq_read_mbps"] > 0
        assert out["small_file_create_tps"] > 0
    finally:
        for m in metas:
            m.stop()


def test_hedged_get_with_slow_data_shard(mini_blob, rng):
    """A stalling data-shard read must not stall the GET past the hedge
    window: parity backup requests fill in and decode recovers."""
    import time as _t
    cm, cm_client, pool, node = mini_blob
    access = AccessHandler(cm_client, pool, AccessConfig(blob_size=32 << 10))
    access.HEDGE_DELAY = 0.05
    payload = rng.integers(0, 256, 20_000, dtype=np.uint8).tobytes()
    loc = access.put(payload, codemode=13)  # EC6P3
    # wrap the node client: shard 0 reads stall 2s
    real = pool.get("n0")

    class SlowShard0:
        def call(self, method, args=None, body=b"", timeout=30.0):
            vol = cm.get_volume(loc.slices[0].vid)
            if (method == "get_shard"
                    and args.get("chunk_id") == vol.units[0].chunk_id):
                _t.sleep(2.0)
            return real.call(method, args, body, timeout)

    pool._clients["n0"] = SlowShard0()
    try:
        t0 = _t.time()
        assert access.get(loc) == payload
        assert _t.time() - t0 < 1.5  # hedged around the 2s stall
    finally:
        pool._clients["n0"] = real
