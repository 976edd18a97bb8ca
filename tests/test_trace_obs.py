"""End-to-end tail-latency observability: cross-service span stitching
over the real transports (HTTP RPC plane, binary packet plane), the
stage histogram / SLO tracker math, the CUBEFS_TRACE=0 A/B door, and
the collector's whole-trace eviction + determinism guarantees.

The stitching tests ride the same harnesses the e2e suites use: the
meta write goes client -> metanode (real-TCP packet plane) -> raft,
the blob put goes access -> blobnode over HTTP, and repair goes
worker -> blobnode over HTTP — each asserting ONE trace_id spans >= 3
hops and the reconstructed tree is renderable.
"""

import bisect
import json

import numpy as np
import pytest

from cubefs_tpu.blob.access import AccessConfig, AccessHandler, NodePool
from cubefs_tpu.blob.blobnode import BlobNode
from cubefs_tpu.blob.clustermgr import ClusterMgr
from cubefs_tpu.blob.mq import MessageQueue
from cubefs_tpu.blob.scheduler import Scheduler
from cubefs_tpu.blob.worker import RepairWorker
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.fs import metanode as mn
from cubefs_tpu.fs.metanode import MetaPartition
from cubefs_tpu.utils import metrics, rpc, slo
from cubefs_tpu.utils import trace as tracelib
from cubefs_tpu.utils.retry import MONOTONIC, FakeClock

from test_blob_e2e import Cluster
from test_fs_e2e import FsCluster


@pytest.fixture(autouse=True)
def _clean_tracer(monkeypatch):
    """Every test starts with an empty collector, the real clock, and
    the trace doors at their defaults (tracing on, full sampling, slow
    forensics off)."""
    for var in ("CUBEFS_TRACE", "CUBEFS_TRACE_SAMPLE", "CUBEFS_SLOW_MS"):
        monkeypatch.delenv(var, raising=False)
    tracelib.reset_collector()
    yield
    tracelib.set_clock(MONOTONIC)
    tracelib.reset_collector()


def _trace_ops(tid):
    return {s["op"] for s in tracelib.finished_spans(tid)}


def _depth(tree):
    return max((1 + _depth(n["children"]) for n in tree), default=0)


# ---------------------------------------------- cross-service stitching

def test_meta_write_stitches_client_metanode_raft(tmp_path):
    """client.submit -> metanode.meta_submit (packet plane, real TCP)
    -> submit coalescer -> raft propose: one trace_id, >= 3 hops."""
    c = FsCluster(tmp_path)
    try:
        tracelib.reset_collector()  # drop volume-creation noise
        c.fs.mkdir("/obs")
        roots = [s for s in tracelib.finished_spans()
                 if s["op"] == "client.submit" and s["parent_id"] is None]
        assert roots, "meta write produced no client-side root span"
        tid = roots[0]["trace_id"]
        ops = _trace_ops(tid)
        assert "metanode.meta_submit" in ops  # packet-server hop
        assert "stage:submit_coalesce" in ops  # batcher lander
        assert "stage:raft_propose" in ops    # consensus hop
        tree = tracelib.trace_tree(tid)
        assert _depth(tree) >= 3
        rendered = tracelib.render_tree(tree)
        assert "client.submit" in rendered
        assert "metanode.meta_submit" in rendered
    finally:
        c.stop()


class _HttpBlobCluster:
    """Blob plane with blobnodes served over REAL HTTP: NodePool has no
    in-process binding for the advertised addrs, so every shard RPC
    dials the wire and the X-Trace header does the stitching."""

    def __init__(self, tmp_path, n_nodes=4, disks_per_node=3):
        self.cm = ClusterMgr()
        self.cm_client = rpc.Client(self.cm)
        self.pool = NodePool()
        self.nodes, self.srvs = [], []
        for n in range(n_nodes):
            node = BlobNode(
                node_id=n,
                disk_paths=[str(tmp_path / f"hn{n}d{d}")
                            for d in range(disks_per_node)],
                cm_client=self.cm_client,
            )
            srv = rpc.RpcServer(rpc.expose(node), service="blobnode").start()
            node.addr = srv.addr
            node.register()
            node.send_heartbeat()
            self.nodes.append(node)
            self.srvs.append(srv)
        self.repair_q = MessageQueue()
        self.delete_q = MessageQueue()
        self.access = AccessHandler(
            self.cm_client, self.pool, AccessConfig(blob_size=64 << 10),
            repair_queue=self.repair_q, delete_queue=self.delete_q)

    def stop(self):
        for s in self.srvs:
            s.stop()


@pytest.fixture
def http_blob(tmp_path):
    c = _HttpBlobCluster(tmp_path)
    yield c
    c.stop()


def test_blob_put_stitches_access_blobnode_http(http_blob, rng):
    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    tracelib.reset_collector()
    loc = http_blob.access.put(data, codemode=cmode.CodeMode.EC6P3)

    roots = [s for s in tracelib.finished_spans()
             if s["op"] == "access.put" and s["parent_id"] is None]
    assert roots
    tid = roots[0]["trace_id"]
    ops = _trace_ops(tid)
    assert "stage:bid_alloc" in ops
    assert "stage:quorum_write" in ops
    assert "blobnode.put_shard" in ops  # HTTP server hop, stitched
    assert _depth(tracelib.trace_tree(tid)) >= 3

    # the GET leg stitches the same way
    tracelib.reset_collector()
    assert http_blob.access.get(loc) == data
    roots = [s for s in tracelib.finished_spans()
             if s["op"] == "access.get" and s["parent_id"] is None]
    assert roots
    ops = _trace_ops(roots[0]["trace_id"])
    assert "stage:read" in ops
    assert "blobnode.get_shard" in ops


def test_repair_stitches_worker_blobnode_http(http_blob, rng):
    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    loc = http_blob.access.put(data, codemode=cmode.CodeMode.EC6P3)
    vol = http_blob.cm.get_volume(loc.slices[0].vid)
    victim = vol.units[1]
    victim_node = next(n for n in http_blob.nodes
                       if n.addr == victim.node_addr)
    victim_node.break_disk(victim.disk_id)

    sched = Scheduler(http_blob.cm, repair_queue=http_blob.repair_q,
                      delete_queue=http_blob.delete_q,
                      node_pool=http_blob.pool)
    worker = RepairWorker(rpc.Client(sched), http_blob.cm_client,
                          http_blob.pool)
    assert sched.mark_disk_broken(victim.disk_id) >= 1
    tracelib.reset_collector()
    for _ in range(100):
        if not worker.run_once():
            break

    roots = [s for s in tracelib.finished_spans()
             if s["op"] == "worker.repair" and s["parent_id"] is None]
    assert roots, "repair produced no root span"
    tid = roots[0]["trace_id"]
    ops = _trace_ops(tid)
    assert "stage:survivor_reads" in ops
    assert "stage:decode" in ops
    assert "stage:writeback" in ops
    assert "blobnode.get_shard" in ops  # helper pulls over HTTP
    assert "blobnode.put_shard" in ops  # writeback over HTTP
    assert _depth(tracelib.trace_tree(tid)) >= 3
    assert http_blob.access.get(loc) == data


# --------------------------------------------------- quantile accuracy

def test_windowed_quantiles_track_numpy_percentile(rng):
    buckets = tuple(0.0005 * (1.12 ** i) for i in range(80))
    wh = slo.WindowedHistogram(buckets=buckets, clock=FakeClock(0.0))
    vals = rng.lognormal(mean=np.log(0.05), sigma=0.6, size=20_000)
    vals = np.clip(vals, buckets[0], buckets[-1] * 0.99)
    for v in vals:
        wh.observe(float(v))

    last = 0.0
    for q in (50.0, 95.0, 99.0, 99.9):
        true = float(np.percentile(vals, q))
        est = wh.quantile(q / 100.0)
        # interpolation error is bounded by the landing bucket's width
        # (geometric ratio 1.12 -> <= ~12% relative); leave headroom
        # for the one-sample rank-definition gap vs numpy
        assert abs(est - true) / true < 0.15, (q, est, true)
        i = bisect.bisect_left(buckets, true)
        lo = buckets[i - 1] if i > 0 else 0.0
        assert est >= lo * 0.999, (q, est, true)
        assert est >= last  # quantiles are monotone in q
        last = est


def test_slo_tracker_burn_rate_and_window_aging():
    reg = metrics.Registry()
    h = reg.histogram("t_stage_seconds", labels=("path", "stage"))
    clock = FakeClock(0.0)
    tr = slo.SloTracker(hist=h,
                        targets={"blob.put": slo.SloTarget(0.1, 0.9)},
                        clock=clock)
    for _ in range(90):
        h.observe(0.01, path="blob.put", stage="total")
    for _ in range(10):
        h.observe(0.5, path="blob.put", stage="total")
    # non-"total" stages never feed the tracker
    h.observe(9.0, path="blob.put", stage="quorum_write")

    snap = tr.snapshot()
    e = snap["blob.put"]
    assert e["count"] == 100
    # 10% of requests blow the 100ms target against a 10% error budget:
    # burning at exactly the objective
    assert e["burn_rate"] == pytest.approx(1.0)
    # p99 interpolates inside the (0.1, 0.5] bucket: rank 99 of 100,
    # 9 of the bucket's 10 samples below -> 0.1 + 0.4 * 0.9
    assert e["quantiles"]["p99"] == pytest.approx(0.46)
    assert e["quantiles"]["p50"] <= 0.01

    # sliding window: advance past window_s * windows and the samples
    # age out of the estimate entirely
    clock.advance(61.0)
    assert tr.snapshot()["blob.put"]["count"] == 0


# ------------------------------------------------- CUBEFS_TRACE=0 door

def _meta_records():
    recs = []
    for i in range(30):
        recs.append({"op": "mknod", "parent": mn.ROOT_INO, "name": f"f{i}",
                     "type": mn.FILE, "mode": 0o644, "ts": 1.0,
                     "op_id": f"obs-{i}"})
    for i in range(0, 30, 3):  # EEXIST losers: the error path must be
        recs.append({"op": "mknod", "parent": mn.ROOT_INO,  # replayable too
                     "name": f"f{i}", "type": mn.FILE, "mode": 0o644,
                     "ts": 2.0, "op_id": f"obs-dup-{i}"})
    return recs


def _apply_instrumented(records):
    mp = MetaPartition(1, 1, 1 << 20)
    for rec in records:
        with tracelib.path_span("meta.write", "client.submit"):
            with tracelib.stage("raft_apply"):
                try:
                    mp.apply(rec)
                except mn.MetaError:
                    pass  # deterministic loser (EEXIST), part of the FSM
    return mp.export_state()


def test_trace_door_off_means_zero_spans_and_identical_fsm(monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE", "1")
    state_on, apply_on = _apply_instrumented(_meta_records())
    assert len(tracelib.finished_spans()) >= 60  # root + stage per record

    tracelib.reset_collector()
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    state_off, apply_off = _apply_instrumented(_meta_records())
    assert tracelib.finished_spans() == []       # the door closes fully
    assert tracelib.known_trace_ids() == []
    # spans/stages are no-ops: bit-identical FSM either way
    assert state_on == state_off
    assert apply_on == apply_off

    # and no context leaks out for clients to propagate
    with tracelib.path_span("blob.put", "access.put") as sp:
        assert tracelib.current() is None
        assert sp.trace_id == ""


def test_sampled_out_roots_skip_collection(monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE_SAMPLE", "0.0")
    with tracelib.path_span("blob.put", "access.put"):
        with tracelib.stage("bid_alloc"):
            pass
    assert tracelib.finished_spans() == []
    # ...but the stage histogram still fed the SLO plane ("total" rides
    # outside the sampling decision)
    found = False
    for key, s in metrics.request_stage_seconds.samples():
        labels = dict(zip(metrics.request_stage_seconds.label_names, key))
        if labels.get("path") == "blob.put" and labels.get("stage") == "total":
            found = s["count"] >= 1
    assert found


# ------------------------------------------- collector + determinism

def test_eviction_drops_whole_traces_oldest_root_first(monkeypatch):
    monkeypatch.setattr(tracelib, "MAX_KEPT", 9)
    tids = []
    for i in range(5):
        with tracelib.path_span("blob.put", f"load{i}") as sp:
            tids.append(sp.trace_id)
            with tracelib.stage("bid_alloc"):
                pass
            with tracelib.stage("quorum_write"):
                pass
    kept = tracelib.known_trace_ids()
    assert tids[-1] in kept       # newest survives
    assert tids[0] not in kept    # oldest root evicted
    total = 0
    for tid in kept:
        spans = tracelib.finished_spans(tid)
        assert len(spans) == 3    # never a torn tree: all-or-nothing
        total += len(spans)
    assert total <= 9


def _deterministic_trace():
    tracelib.reset_collector()
    clock = FakeClock(100.0)
    tracelib.set_clock(clock)
    tracelib.seed_ids(0x0B5)
    with tracelib.path_span("blob.put", "access.put") as sp:
        sp.set_tag("svc", "access")
        with tracelib.stage("bid_alloc"):
            clock.advance(0.002)
        with tracelib.stage("quorum_write"):
            clock.advance(0.010)
        clock.advance(0.001)
    return tracelib.finished_spans()


def test_fakeclock_and_seeded_ids_reproduce_span_trees():
    a = _deterministic_trace()
    b = _deterministic_trace()
    assert a and a == b  # ids, timestamps, durations: all identical
    durs = {s["op"]: s["duration"] for s in a}
    assert durs["stage:bid_alloc"] == pytest.approx(0.002)
    assert durs["stage:quorum_write"] == pytest.approx(0.010)
    assert durs["access.put"] == pytest.approx(0.013)


# ------------------------------------------------ slow-request forensics

def test_slow_roots_capture_tree_to_jsonl(tmp_path, monkeypatch):
    monkeypatch.setenv("CUBEFS_SLOW_MS", "50")
    path = str(tmp_path / "slowtrace.jsonl")
    tracelib.configure_slow_log(path)
    try:
        clock = FakeClock(5.0)
        tracelib.set_clock(clock)
        with tracelib.path_span("blob.get", "access.get") as sp:
            tid = sp.trace_id
            with tracelib.stage("read"):
                clock.advance(0.2)  # 200ms >> 50ms threshold
        with tracelib.path_span("blob.get", "access.get"):
            clock.advance(0.001)  # fast request: not captured

        with open(path) as f:
            recs = [json.loads(line) for line in f]
        assert len(recs) == 1
        rec = recs[0]
        assert rec["trace_id"] == tid
        assert rec["path"] == "blob.get"
        assert rec["duration_ms"] == pytest.approx(200.0, rel=0.05)
        assert "read=" in rec["stages"]
        assert rec["tree"] and rec["tree"][0]["span"]["op"] == "access.get"

        slow = tracelib.slow_traces(top=5)
        assert slow and slow[0]["trace_id"] == tid
        assert tracelib.stage_summary(tid).startswith("read=")
    finally:
        log, tracelib._slow_log = tracelib._slow_log, None
        if log is not None:
            log.close()


# ------------------------------------- the served codec path, from inside

PUT_STAGES = ("stripe_fill", "encode_submit", "bid_alloc",
              "encode_admission", "quorum_write", "location_crc")


def _tpu_cluster(tmp_path, blob_size=1 << 20):
    """test_blob_e2e's in-process cluster with every codec caller on the
    device engine (JaxEngine, on the CPU backend here) through the
    process-wide batcher — the topology of the benchmark's deployments."""
    c = Cluster(tmp_path)
    c.access = AccessHandler(c.cm_client, c.pool,
                             AccessConfig(blob_size=blob_size, engine="tpu"))
    c.worker = RepairWorker(rpc.Client(c.sched), c.cm_client, c.pool,
                            engine="tpu")
    return c


def _stage_sums(path):
    return {k[1]: s["sum"]
            for k, s in metrics.request_stage_seconds.samples()
            if k[0] == path}


def _delta(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def test_put_is_covered_by_six_disjoint_stages(tmp_path, rng):
    """Every part of `_put` that takes time is inside a stage: the six
    run in sequence, so their sum is at most `total`, and at 4 MiB (the
    copies and the CRC are real work there) at least 80% of it."""
    c = _tpu_cluster(tmp_path)
    data = rng.integers(0, 256, 4 << 20, dtype=np.uint8).tobytes()
    c.access.put(data, codemode=cmode.CodeMode.EC6P3)  # compiles
    before = _stage_sums("blob.put")
    tracelib.reset_collector()
    loc = c.access.put(data, codemode=cmode.CodeMode.EC6P3)
    d = _delta(before, _stage_sums("blob.put"))
    assert set(PUT_STAGES) <= set(d), sorted(d)
    staged = sum(d[s] for s in PUT_STAGES)
    assert 0.8 * d["total"] <= staged <= d["total"], d
    # in sequence: each stage span starts after the one before ended
    root = next(s for s in tracelib.finished_spans()
                if s["op"] == "access.put")
    by_stage = {s["tags"].get("stage"): s
                for s in tracelib.finished_spans(root["trace_id"])
                if s["parent_id"] == root["span_id"]}
    order = [by_stage[s] for s in PUT_STAGES]
    for a, b in zip(order, order[1:]):
        assert a["start"] + a["duration"] <= b["start"]
    assert "encode_total_ms" in by_stage["encode_admission"]["tags"]
    assert c.access.get(loc) == data


def test_blobnode_times_one_put_and_one_get(tmp_path):
    """The blobnode's own view of a shard call (store + CRC verify), for
    the calls of a traced request: the door is the request's, read from
    the context, not from the environment once a shard."""
    def counts():
        return {k[0]: s["count"]
                for k, s in metrics.blobnode_shard_io.samples()}

    node = BlobNode(node_id=0, disk_paths=[])
    node.attach_local(3, str(tmp_path / "d0"))
    before = counts()
    with tracelib.path_span("blob.put", "test.put"):
        node.put_shard(3, 7, 1, b"x" * 4096)
        assert node.get_shard(3, 7, 1)[0] == b"x" * 4096
    assert _delta(before, counts()) == {"put": 1, "get": 1}
    node.put_shard(3, 7, 2, b"y" * 4096)  # no request span: not timed
    assert _delta(before, counts()) == {"put": 1, "get": 1}


def test_repair_counts_the_bytes_it_wrote_back(tmp_path, rng):
    """`cubefs_repair_bytes_rebuilt_total` rises by exactly the bytes
    the destination acknowledged, and `decode` is split into
    `decode_stack` + the engine step + `decode_verify`."""
    c = _tpu_cluster(tmp_path, blob_size=64 << 10)
    data = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
    loc = c.access.put(data, codemode=cmode.CodeMode.EC6P3)
    victim = c.cm.get_volume(loc.slices[0].vid).units[1]
    next(n for n in c.nodes
         if n.addr == victim.node_addr).break_disk(victim.disk_id)
    assert c.sched.mark_disk_broken(victim.disk_id) >= 1

    written = []
    real_call = rpc.Client.call

    def tapped(self, method, args=None, body=b"", *a, **kw):
        out = real_call(self, method, args, body, *a, **kw)
        if method == "put_shard":
            written.append(len(body))
        return out

    before = _stage_sums("blob.repair")
    rebuilt0 = metrics.repair_bytes_rebuilt.value()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(rpc.Client, "call", tapped)
        for _ in range(100):
            if not c.worker.run_once():
                break
    assert written and c.worker.failed == 0
    assert metrics.repair_bytes_rebuilt.value() - rebuilt0 == sum(written)
    d = _delta(before, _stage_sums("blob.repair"))
    assert {"decode", "decode_stack", "decode_verify"} <= set(d), sorted(d)
    assert d["decode_stack"] + d["decode_verify"] <= d["decode"]
    assert c.access.get(loc) == data


def _host_events(trace_dir, prefix):
    """{thread line: [(name, start_ns, end_ns)]} of the profile's host
    plane, for the events whose name starts with `prefix`."""
    import glob

    import jax.profiler

    path = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith(prefix)]
            if evs:
                out.setdefault(line.name, []).extend(evs)
    return out


def test_a_profile_shows_the_programs_spans_on_its_own_clock(tmp_path, rng):
    """While a profiler session runs, path spans, stages and the engine
    phases are in the profile's host plane, each stage inside its path
    span — one clock with the device trace, no second span store."""
    import jax.profiler

    from cubefs_tpu.codec import engine

    c = _tpu_cluster(tmp_path)
    data = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    c.access.put(data, codemode=cmode.CodeMode.EC6P3)  # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        # an engine takes apart one call in PHASE_EVERY_S: this one
        engine.get_engine("tpu")._phase_due = 0.0
        c.access.put(data, codemode=cmode.CodeMode.EC6P3)
    finally:
        jax.profiler.stop_trace()
    by_thread = _host_events(str(tmp_path / "prof"),
                             tracelib.PROFILE_PREFIX)
    names = {n for evs in by_thread.values() for n, _, _ in evs}
    assert {"cubefs:access.put", "cubefs:blob.put/quorum_write",
            "cubefs:blob.put/stripe_fill", "cubefs:blob.put/codec_step",
            "cubefs:codec.h2d", "cubefs:codec.wait"} <= names, sorted(names)
    # the PUT ran on this thread: its stages lie inside its path span
    mine = next(evs for evs in by_thread.values()
                if any(n == "cubefs:access.put" for n, _, _ in evs))
    _, lo, hi = next(e for e in mine if e[0] == "cubefs:access.put")
    stages = [e for e in mine if e[0].startswith("cubefs:blob.put/")]
    assert len(stages) >= len(PUT_STAGES)
    assert all(lo <= s and e <= hi for _, s, e in stages)
    h2d = next(e for e in mine if e[0] == "cubefs:codec.h2d")
    step = next(e for e in mine if e[0] == "cubefs:blob.put/codec_step")
    assert step[1] <= h2d[1] and h2d[2] <= step[2]


def test_span_layer_never_imports_jax_for_a_process_without_it():
    """fs-plane tools trace without the codec: the annotation binds only
    once JAX is already in the process."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from cubefs_tpu.utils import trace\n"
            "with trace.path_span('meta.write', 'client.mkdir'):\n"
            "    with trace.stage('raft_propose'):\n"
            "        pass\n"
            "with trace.stage('group_fsync', path='meta.write'):\n"
            "    pass\n"
            "assert trace.annotation('cubefs:x') is trace._NO_ANNOTATION\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "assert len(trace.finished_spans()) == 2\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_bound_series_and_one_count_a_bucket_read_back_cumulative():
    """An observation touches one bucket and a bound series skips the
    label lookup; what is read back — samples(), the exposition text —
    is cumulative as before, identical for both ways in."""
    reg = metrics.Registry()
    h = reg.histogram("t_seconds", "t", ("op",), buckets=(0.001, 0.01, 0.1))
    fast = h.bind(op="put")
    for v in (0.0005, 0.005, 0.005, 0.05, 7.0):  # 7.0: above every bound
        h.observe(v, op="get")
        fast.observe(v)
    got = dict(h.samples())
    assert got[("put",)] == got[("get",)]
    assert got[("put",)]["buckets"] == [1, 3, 4]
    assert got[("put",)]["count"] == 5
    assert got[("put",)]["sum"] == pytest.approx(7.0605)
    text = reg.render_text()
    assert 't_seconds_bucket{op="put",le="0.01"} 3' in text
    assert 't_seconds_bucket{op="put",le="+Inf"} 5' in text
    c = reg.counter("t_total", "t", ("kind",))
    inc = c.bind(kind="x")
    inc()
    inc(2)
    c.inc(kind="x")
    assert c.value(kind="x") == 4
