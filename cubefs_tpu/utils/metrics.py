"""Metrics: prometheus-style registry + text exposition.

Role parity: util/exporter (Prometheus registry + /metrics endpoint,
exporter.go:76,115) and the per-module metric files. Counters, gauges
and histograms register globally; any RPC server can mount
render_text() at /metrics. Pushgateway/Consul registration is a
deployment concern left to the operator (the reference gates it on
config too).
"""

from __future__ import annotations

import bisect
import itertools
import threading
import time


class _Metric:
    def __init__(self, name: str, help_: str, labels: tuple[str, ...]):
        self.name = name
        self.help = help_
        self.label_names = labels
        self._series: dict[tuple, object] = {}
        self._lock = threading.Lock()

    def _key(self, labels: dict) -> tuple:
        return tuple(str(labels.get(k, "")) for k in self.label_names)


class Counter(_Metric):
    TYPE = "counter"

    def inc(self, value: float = 1.0, **labels) -> None:
        k = self._key(labels)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + value

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(self._key(labels), 0.0)

    def bind(self, **labels):
        """`inc` of one label set with the label lookup done once, for
        call sites that run several times a request."""
        k, series, lock = self._key(labels), self._series, self._lock

        def inc(value: float = 1.0) -> None:
            with lock:
                series[k] = series.get(k, 0.0) + value

        return inc

    def samples(self):
        with self._lock:
            return [(k, v) for k, v in self._series.items()]


class Gauge(Counter):
    TYPE = "gauge"

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._series[self._key(labels)] = float(value)


class Histogram(_Metric):
    TYPE = "histogram"
    BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60)

    def __init__(self, name, help_, labels, buckets=None):
        super().__init__(name, help_, labels)
        # per-instance bounds: latency series keep the class default,
        # count-shaped series (entries per batch) need integer bounds
        self.BUCKETS = tuple(buckets) if buckets is not None else self.BUCKETS

    def observe(self, value: float, **labels) -> None:
        k = self._key(labels)
        with self._lock:
            s = self._series.get(k)
            if s is None:
                s = {"count": 0, "sum": 0.0, "buckets": [0] * len(self.BUCKETS)}
                self._series[k] = s
            s["count"] += 1
            s["sum"] += value
            i = bisect.bisect_left(self.BUCKETS, value)
            if i < len(self.BUCKETS):
                s["buckets"][i] += 1

    def bind(self, **labels) -> "_BoundSeries":
        """The series of one label set, resolved once: `observe(value)`
        on it skips the per-call label lookup — for call sites that run
        several times a request."""
        k = self._key(labels)
        with self._lock:
            s = self._series.get(k)
            if s is None:
                s = {"count": 0, "sum": 0.0, "buckets": [0] * len(self.BUCKETS)}
                self._series[k] = s
        return _BoundSeries(self, s)

    def observe_many(self, values, **labels) -> None:
        """Record a burst of samples under one lock acquisition — for
        hot paths that fan one event out to many members (e.g. per-
        submission waits of one drained codec step)."""
        if not values:
            return
        k = self._key(labels)
        with self._lock:
            s = self._series.get(k)
            if s is None:
                s = {"count": 0, "sum": 0.0, "buckets": [0] * len(self.BUCKETS)}
                self._series[k] = s
            s["count"] += len(values)
            s["sum"] += sum(values)
            bounds, buckets = self.BUCKETS, s["buckets"]
            for value in values:
                i = bisect.bisect_left(bounds, value)
                if i < len(buckets):
                    buckets[i] += 1

    def time(self, **labels):
        metric = self

        class _Timer:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                metric.observe(time.perf_counter() - self.t0, **labels)

        return _Timer()

    def samples(self):
        """[(label values, {"count", "sum", "buckets"})], the buckets
        cumulative as the exposition format has them (a series keeps
        one count a bucket, so an observation touches one)."""
        with self._lock:
            return [(k, dict(v, buckets=list(
                        itertools.accumulate(v["buckets"]))))
                    for k, v in self._series.items()]


class _BoundSeries:
    """Histogram.bind(): one series and its histogram's lock."""
    __slots__ = ("_series", "_buckets", "_bounds", "_lock")

    def __init__(self, hist: Histogram, series: dict):
        self._series = series
        self._buckets = series["buckets"]
        self._bounds = hist.BUCKETS
        self._lock = hist._lock

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self._bounds, value)
        with self._lock:
            s = self._series
            s["count"] += 1
            s["sum"] += value
            if i < len(self._buckets):
                self._buckets[i] += 1


class Registry:
    def __init__(self):
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()
        self._before_render: list = []

    def before_render(self, hook) -> None:
        """Call `hook()` at the start of every render_text(): for an
        account kept as "seconds since the last change of state", which
        is only exact in a scrape once settled up to the scrape."""
        with self._lock:
            self._before_render.append(hook)

    def _get(self, cls, name, help_, labels, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help_, tuple(labels), **kw)
                self._metrics[name] = m
            return m

    def counter(self, name, help_="", labels=()) -> Counter:
        return self._get(Counter, name, help_, labels)

    def gauge(self, name, help_="", labels=()) -> Gauge:
        return self._get(Gauge, name, help_, labels)

    def histogram(self, name, help_="", labels=(), buckets=None) -> Histogram:
        return self._get(Histogram, name, help_, labels, buckets=buckets)

    def render_text(self) -> str:
        """Prometheus exposition format."""
        out = []
        with self._lock:
            hooks = list(self._before_render)
        for hook in hooks:
            hook()
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            out.append(f"# HELP {m.name} {m.help}")
            out.append(f"# TYPE {m.name} {m.TYPE}")
            if isinstance(m, Histogram):
                for k, s in m.samples():
                    lbl = _labels(m.label_names, k)
                    for bound, cum in zip(m.BUCKETS, s["buckets"]):
                        le = _labels(m.label_names + ("le",), k + (str(bound),))
                        out.append(f"{m.name}_bucket{le} {cum}")
                    inf = _labels(m.label_names + ("le",), k + ("+Inf",))
                    out.append(f"{m.name}_bucket{inf} {s['count']}")
                    out.append(f"{m.name}_sum{lbl} {s['sum']}")
                    out.append(f"{m.name}_count{lbl} {s['count']}")
            else:
                for k, v in m.samples():
                    out.append(f"{m.name}{_labels(m.label_names, k)} {v}")
        return "\n".join(out) + "\n"


def _labels(names, values) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{v}"' for n, v in zip(names, values))
    return "{" + inner + "}"


DEFAULT = Registry()

# framework-wide series
rpc_requests = DEFAULT.counter("cubefs_rpc_requests_total",
                               "RPC requests served", ("method", "code"))
rpc_latency = DEFAULT.histogram("cubefs_rpc_latency_seconds",
                                "RPC handler latency", ("method",))
codec_bytes = DEFAULT.counter("cubefs_codec_bytes_total",
                              "bytes through the EC codec", ("op", "engine"))
repair_tasks = DEFAULT.counter("cubefs_repair_tasks_total",
                               "repair tasks", ("state",))
rpc_client_retries = DEFAULT.counter(
    "cubefs_rpc_client_retries_total",
    "client-side RPC retries taken through RetryPolicy", ("op", "reason"))
breaker_state = DEFAULT.gauge(
    "cubefs_breaker_state",
    "per-address circuit breaker state (0=closed, 1=half-open, 2=open)",
    ("addr",))
breaker_skips = DEFAULT.counter(
    "cubefs_breaker_skips_total",
    "calls skipped because the address's breaker was open", ("addr",))
faults_injected = DEFAULT.counter(
    "cubefs_faults_injected_total",
    "chaos faults injected by the installed FaultPlan", ("kind",))

# write-path group commit (raft proposal batching + meta submit coalescing)
raft_proposals = DEFAULT.counter(
    "cubefs_raft_proposals_total",
    "entries proposed through the leader group-commit batcher", ("group",))
raft_proposal_batches = DEFAULT.counter(
    "cubefs_raft_proposal_batches_total",
    "batcher drains: each is one log append + one WAL write + one "
    "replication round", ("group",))
raft_entries_per_batch = DEFAULT.histogram(
    "cubefs_raft_entries_per_batch",
    "entries carried per proposal-batcher drain", ("group",),
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
raft_wal_fsyncs = DEFAULT.counter(
    "cubefs_raft_wal_fsyncs_total",
    "actual fsync(2) calls on the raft WAL (group fsync shares one "
    "flush across concurrent acks)", ("group",))
raft_batch_apply_latency = DEFAULT.histogram(
    "cubefs_raft_batch_apply_seconds",
    "latency of applying one drained batch of committed entries before "
    "waking waiters", ("group",))
meta_batch_entries = DEFAULT.counter(
    "cubefs_meta_batch_entries_total",
    "__batch__ raft entries proposed by the metanode submit coalescer",
    ("pid",))
meta_batched_ops = DEFAULT.counter(
    "cubefs_meta_batched_ops_total",
    "mutations carried inside coalesced __batch__ entries", ("pid",))
meta_ops_per_batch = DEFAULT.histogram(
    "cubefs_meta_ops_per_batch_entry",
    "mutations carried per coalesced submit (1 = uncontended fast path)",
    ("pid",), buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))

# pipelined replication (CUBEFS_RAFT_PIPELINE) + the shared ReplMux
# sender plane + the fs client's cross-partition fan-out coalescer
raft_pipelined_appends = DEFAULT.counter(
    "cubefs_raft_pipelined_appends_total",
    "AppendEntries dispatched through the pipelined per-follower "
    "window (sent without waiting for the previous batch's ack)",
    ("group",))
raft_inflight_window = DEFAULT.histogram(
    "cubefs_raft_inflight_window",
    "in-flight appends per follower observed at dispatch — the "
    "replication pipeline depth actually used", ("group",),
    buckets=(1, 2, 3, 4, 6, 8, 12, 16))
raft_mux_jobs = DEFAULT.counter(
    "cubefs_raft_mux_jobs_total",
    "replication jobs shipped through the shared per-address ReplMux "
    "sender lanes (the multi-raft proposal mux)", ("kind",))
raft_mux_senders = DEFAULT.gauge(
    "cubefs_raft_mux_senders",
    "live sender worker threads in a ReplMux address lane", ("addr",))
meta_fanout_batches = DEFAULT.counter(
    "cubefs_meta_fanout_batches_total",
    "client-side cross-partition fan-out drains (one submit_batch RPC "
    "per drain)", ("pid",))
meta_fanout_ops = DEFAULT.counter(
    "cubefs_meta_fanout_ops_total",
    "mutations carried by client fan-out drains", ("pid",))
meta_fanout_inflight = DEFAULT.histogram(
    "cubefs_meta_fanout_partitions_inflight",
    "partitions with a batch in flight when a fan-out drain launches — "
    "the client-side K window actually used",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32))

# failure-domain topology (blob/topology.py): placement + rebalance
placement_az_skew = DEFAULT.gauge(
    "cubefs_placement_az_skew",
    "volume-unit count spread across AZs (max - min), set by the "
    "rebalance sweep's scoring pass")
placement_misplaced = DEFAULT.gauge(
    "cubefs_placement_misplaced_units",
    "volume units living outside their local stripe's home AZ; zero "
    "means every LRC stripe is physically AZ-local")
placement_colocated = DEFAULT.counter(
    "cubefs_placement_colocated_total",
    "volume allocations that degraded the failure-domain contract "
    "under allow_colocated_units", ("kind",))
rebalance_moves = DEFAULT.counter(
    "cubefs_rebalance_moves_total",
    "unit migrations queued by the rebalance sweep", ("reason",))
reconstruct_reads = DEFAULT.counter(
    "cubefs_reconstruct_total",
    "degraded-read reconstructions by stripe scope (local = intra-AZ "
    "LRC stripe, global = full-width RS)", ("path",))
# a PUT's data rows (blob/access.py): `reused` came from the process's
# kept arrays (utils/hostmem.KEPT: mapped pages), `fresh` from the
# allocator; one a PUT
access_stripe_buffers = DEFAULT.counter(
    "cubefs_access_stripe_buffers_total",
    "data-row arrays PUTs filled, by where the array came from "
    "(reused / fresh)", ("result",))
# a PUT's shard writes (blob/access.py): the data shards are submitted
# before the wait for the codec step, the parity shards after it;
# counted once a PUT, when the wait returns
access_shard_writes = DEFAULT.counter(
    "cubefs_access_shard_writes_total",
    "shard writes of PUTs by whether the write had ended when the PUT's "
    "encode did (under_encode) or not yet, parity writes among them "
    "(after_encode)", ("when",))

# batched codec admission (codec/batcher.py): device-sized steps
codec_batch_submissions = DEFAULT.counter(
    "cubefs_codec_batch_submissions_total",
    "stripes submitted through the codec admission surface", ("op",))
codec_batch_steps = DEFAULT.counter(
    "cubefs_codec_batch_steps_total",
    "drained device steps (each is ONE engine dispatch)",
    ("op", "engine"))
codec_batch_stripes = DEFAULT.histogram(
    "cubefs_codec_batch_stripes_per_step",
    "stripes coalesced per drained device step (1 = uncontended)",
    ("op",), buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024))
# a step runs at a rung of rs_kernel's ladder: `payload` is what its
# submissions brought (rows x each live stripe's own width), `pad` the
# zero columns and zero stripes up to the rung; widths counts the
# distinct payload widths that met in one step
codec_step_bytes = DEFAULT.counter(
    "cubefs_codec_step_bytes_total",
    "input bytes of drained device steps (payload / pad)",
    ("op", "kind"))
# a device result over malloc's mmap threshold (codec/engine.py:
# _to_host): `reused` lands in a buffer the process kept
# (utils/hostmem.KEPT: pages touched before), `fresh` in a new one; one
# a device call over the threshold, none under it
codec_result_buffers = DEFAULT.counter(
    "cubefs_codec_result_buffers_total",
    "device results over malloc's mmap threshold, by the host buffer "
    "they landed in (reused / fresh)", ("result",))
codec_batch_widths = DEFAULT.histogram(
    "cubefs_codec_batch_widths_per_step",
    "distinct payload widths coalesced per drained device step",
    ("op",), buckets=(1, 2, 3, 4, 6, 8))
codec_batch_wait = DEFAULT.histogram(
    "cubefs_codec_batch_wait_seconds",
    "submit-to-device-step admission wait", ("op",))
codec_batch_backpressure = DEFAULT.counter(
    "cubefs_codec_batch_backpressure_total",
    "submissions that blocked on the bounded pending queue", ("op",))
codec_batch_errors = DEFAULT.counter(
    "cubefs_codec_batch_errors_total",
    "per-submission errors fanned back by the drainer", ("op", "kind"))
codec_batch_dp_steps = DEFAULT.counter(
    "cubefs_codec_batch_dp_steps_total",
    "device steps sharded dp-wise across the mesh", ("dp",))
# where one drained step's host time goes: `gather` (the batcher's
# copy of its submissions into the rung-shaped array; 0 for a step that
# is one rung-shaped submission, so the mean is per step),
# then inside a device engine's call `matrix` (the step's bit matrix
# from the device-resident cache: a lookup, or on a miss one bit
# expansion and one upload), `h2d`, `launch` (Python dispatch until the
# not-yet-ready result is returned), `wait` (until it is ready), `d2h`
# — of the calls the engine takes apart, at most one in
# engine.PHASE_EVERY_S seconds
codec_engine_phase = DEFAULT.histogram(
    "cubefs_codec_engine_phase_seconds",
    "host seconds per phase of one drained codec step",
    ("engine", "op", "phase"),
    buckets=(0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1, 0.5, 2))
# the three places a request waits between the front door and the
# device, each with its own account (codec/batcher.py, blob/access.py).
# The engine seam: wall seconds since the batcher was made, by what the
# seam was doing — `busy` (at least one engine call in flight; calls of
# two geometry queues that overlap count once), `handoff` (no call in
# flight, a submission admitted and not yet resolved: parked with no
# drainer, or its drainer gathers, fans results back, lingers or waits
# for the GIL), `starved` (neither: every caller is outside the codec).
# Settled up to the instant of every render_text()
codec_engine_seconds = DEFAULT.counter(
    "cubefs_codec_engine_seconds_total",
    "wall seconds of the admission -> engine seam by state "
    "(busy / handoff / starved); the three sum to wall time", ("state",))
# the drainer's streak: one drain is a caller of result() that found its
# queue idle and ran it until it was empty
codec_drain_steps = DEFAULT.histogram(
    "cubefs_codec_drain_steps",
    "engine steps one drain ran before its queue was empty", ("op",),
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 32, 64))
codec_drain_seconds = DEFAULT.histogram(
    "cubefs_codec_drain_seconds",
    "seconds of one drain: `own` from becoming the drainer until its own "
    "submission was resolved, `others` from then until the queue was "
    "empty", ("op", "part"),
    buckets=(0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.03, 0.04, 0.05,
             0.065, 0.08, 0.1, 0.15, 0.25, 0.5, 1, 2.5))
codec_collects = DEFAULT.counter(
    "cubefs_codec_collects_total",
    "result() calls by how the submission came to be resolved: `ready` "
    "(before it was asked for), `waited` (by another caller's drain), "
    "`drained` (this caller drained the queue)", ("op", "how"))
# the front door's thread pool: submit to the task's first line
access_pool_wait = DEFAULT.histogram(
    "cubefs_access_pool_wait_seconds",
    "seconds a shard task waited for a thread of the access pool",
    ("op",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 1))

# shared compiled-program cache (ops/progcache.py): one process-wide
# capped LRU behind the msr product-matrix rows, the jitted rs_kernel
# closures and the scheduled XOR programs (ops/xorprog.py) — the bound
# that keeps long-lived repair processes from growing one cache entry
# per unique coefficient matrix forever. `cubefs-cli metrics codec`
# renders the hit ratio.
codec_program_cache = DEFAULT.counter(
    "cubefs_codec_program_cache_total",
    "compiled-program cache traffic by kernel family and event "
    "(hit / miss / evict)", ("family", "event"))
codec_program_cache_entries = DEFAULT.gauge(
    "cubefs_codec_program_cache_entries",
    "entries resident in the shared compiled-program cache")
# the GF apply takes its bit matrix as an operand (ops/rs_kernel.py):
# one program per shape, the matrices behind a bounded device-resident
# cache. A miss is one bit expansion + one upload = one matrix this
# process had not seen (or had evicted); programs count what was built,
# by kernel (`gf256_apply` fused, `bits` jnp); the gate counts the
# Pallas programs it blessed or refused.
codec_matrix_cache = DEFAULT.counter(
    "cubefs_codec_matrix_cache_total",
    "device-resident bit-matrix cache lookups (hit / miss)",
    ("op", "result"))
codec_programs = DEFAULT.counter(
    "cubefs_codec_programs_total",
    "GF apply programs built, one per shape", ("kernel",))
codec_pallas_gate = DEFAULT.counter(
    "cubefs_codec_pallas_gate_total",
    "fused-kernel programs through the miscompile gate "
    "(blessed / refused)", ("result",))
# LRC parity (codec/encoder.py: LrcEncoder): one a blob encoded, by how
# its local parity was made — `in_step`, in the one admitted step with
# the global rows (since PR 42), or `separate`, in steps of their own
# after it (how the port made it until PR 42; no path does since)
codec_lrc_local = DEFAULT.counter(
    "cubefs_codec_lrc_local_total",
    "LRC blobs encoded, by how their local parity was made "
    "(in_step / separate)", ("how",))

# repair-bandwidth observability (blob/worker.py): what a single-shard
# repair actually pulls over the network, split by failure-domain scope
# — the numbers the MSR sub-shard protocol (CUBEFS_CODEC_MSR) exists to
# shrink. `cubefs-cli metrics repair` renders these.
repair_bytes_pulled = DEFAULT.counter(
    "cubefs_repair_bytes_pulled_total",
    "bytes downloaded from survivors by repair (full shards on the "
    "conventional path, beta-sized helper symbols on the MSR path)",
    ("scope",))  # az_local | cross_az
repair_bytes_rebuilt = DEFAULT.counter(
    "cubefs_repair_bytes_rebuilt_total",
    "rebuilt shard bytes whose write-back the destination acknowledged")
# the conventional decode groups a task's bids by (width rung, survivor
# set): one device step a group and batch_stripes bids, whatever sizes
# the bids have
repair_steps_per_task = DEFAULT.histogram(
    "cubefs_repair_steps_per_task",
    "decode steps of one finished unit-repair task",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 64, 128))
# a repair worker's leases (blob/worker.py:run_once), by what the host's
# heap does with freed pages once the lease came: `kept` where the
# allocator took hostmem.keep_freed_heap's thresholds, `dynamic` where it
# did not (not glibc's); one a lease
repair_leases = DEFAULT.counter(
    "cubefs_repair_leases_total",
    "leases a repair worker ran, by the host heap's policy for freed "
    "pages (kept / dynamic)", ("heap",))
# the unit repairs of one volume leased together are decoded from one
# read of its survivors: `own` is the task whose lease made the read,
# `shared` each further task decoded from it
repair_task_reads = DEFAULT.counter(
    "cubefs_repair_task_reads_total",
    "finished unit-repair tasks, by whose read of the survivors they "
    "were decoded from (own / shared)", ("reads",))
# a decode step's array (blob/worker.py:_step_array): `reused` is a view
# of a buffer the process kept (utils/hostmem.KEPT: pages touched
# before), `fresh` a new buffer; one a step
repair_step_arrays = DEFAULT.counter(
    "cubefs_repair_step_arrays_total",
    "arrays decode steps of repairs filled, by where the array came "
    "from (reused / fresh)", ("result",))
repair_widths_per_step = DEFAULT.histogram(
    "cubefs_repair_widths_per_step",
    "distinct shard sizes among the bids of one decode step",
    buckets=(1, 2, 4, 8, 16, 32, 64))
# where a repaired unit's survivors came from (blob/worker.py): its AZ's
# local stripe (an LRC unit rebuilt inside its AZ) or the global stripe
# (every Reed-Solomon unit, an LRC unit whose local stripe could not be
# read); one a unit whose write-back ended
repair_sources = DEFAULT.counter(
    "cubefs_repair_sources_total",
    "repaired units, by the stripe whose read served them "
    "(local / global)", ("source",))
# how a rebuilt shard was checked before its write-back: against an
# extra survivor rebuilt beside it, against a second derivation of it
# through the global code (a local stripe that leaves no extra
# survivor), or not at all; one a rebuilt shard
repair_checks = DEFAULT.counter(
    "cubefs_repair_checks_total",
    "rebuilt shards, by how they were checked before the write-back "
    "(survivor / derived / none)", ("how",))
repair_subshard_reads = DEFAULT.counter(
    "cubefs_repair_subshard_reads_total",
    "beta-sized helper symbols served through read_subshard (one per "
    "bid per helper)")
repair_msr_fallbacks = DEFAULT.counter(
    "cubefs_repair_msr_fallback_total",
    "MSR repairs that fell back to the conventional k-shard decode",
    ("reason",))

# shard I/O as the blobnode itself sees it (blob/blobnode.py: store
# call + CRC verify, the `dt` disk health already takes) — against the
# node client's view, the difference is transport and the caller's loop
blobnode_shard_io = DEFAULT.histogram(
    "cubefs_blobnode_shard_io_seconds",
    "seconds inside BlobNode.put_shard / get_shard", ("op",),
    buckets=(0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1, 0.5, 2))

# end-to-end request observability (utils/trace.py + utils/slo.py):
# one shared per-stage histogram across every instrumented hot path,
# plus the SLO tail estimator's exported gauges. `path` is the request
# family (blob.put, blob.get, blob.repair, meta.write); `stage` is the
# hop inside it (encode_admission, quorum_write, group_fsync, ...).
request_stage_seconds = DEFAULT.histogram(
    "cubefs_request_stage_seconds",
    "per-stage latency of instrumented hot-path requests",
    ("path", "stage"),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60))
slo_latency_quantile = DEFAULT.gauge(
    "cubefs_slo_latency_quantile_seconds",
    "sliding-window latency quantile estimate per instrumented path",
    ("path", "quantile"))
slo_burn_rate = DEFAULT.gauge(
    "cubefs_slo_burn_rate",
    "error-budget burn rate per path: fraction of windowed requests "
    "over the SLO target divided by the budget (1-objective); 1.0 "
    "burns the budget exactly at the objective rate",
    ("path",))
slo_budget_remaining = DEFAULT.gauge(
    "cubefs_slo_error_budget_remaining",
    "fraction of the window's error budget still unspent (1 = no "
    "violations, 0 = budget exhausted)",
    ("path",))
slow_traces = DEFAULT.counter(
    "cubefs_slow_traces_total",
    "root spans that exceeded CUBEFS_SLOW_MS and were captured to the "
    "slow-trace forensics log", ("path",))

# AZ-local hot-read tier (fs/remotecache.py CachedReader) + fs-plane
# topology (fs/topology.py). `cubefs-cli metrics read-path` renders the
# readcache series; the misplaced gauge is the fs sweep's 0-contract.
readcache_serves = DEFAULT.counter(
    "cubefs_readcache_serves_total",
    "reads answered by the flash tier, by the serving group's AZ "
    "locality relative to the client", ("scope",))  # az_local | cross_az
readcache_fills = DEFAULT.counter(
    "cubefs_readcache_fills_total",
    "miss-path outcomes: `populated` pushed the block to a flashnode, "
    "`skipped_cold` failed the hotness admission bar (streaming scans "
    "must not flush the hot set), `failed` found no writable flashnode, "
    "`suppressed` deferred the fill during a QoS brownout",
    ("outcome",))
readcache_singleflight = DEFAULT.counter(
    "cubefs_readcache_singleflight_total",
    "concurrent misses of one block collapsed onto another caller's "
    "in-flight datanode read (thundering-herd suppression)")
readcache_invalidations = DEFAULT.counter(
    "cubefs_readcache_invalidations_total",
    "cached blocks evicted from the flash tier by write-path "
    "invalidation (overwrite / truncate / unlink)")
fs_placement_misplaced = DEFAULT.gauge(
    "cubefs_fs_placement_misplaced_replicas",
    "dp replicas colocated in an AZ beyond the one-per-AZ fair share; "
    "the rate-limited misplaced-replica sweep drives this to zero")

# elastic metadata plane (fs/split.py). `cubefs-cli metrics meta`
# renders these; the imbalance gauge is the meta balance sweep's
# 0-contract, mirroring the fs placement sweep above.
meta_partition_imbalance = DEFAULT.gauge(
    "cubefs_meta_partition_imbalance",
    "actionable metapartitions: hot/oversized ones the split engine "
    "would split plus cold adjacent pairs it would merge; the "
    "rate-limited balance sweep drives this to zero")
meta_range_migrations = DEFAULT.counter(
    "cubefs_meta_range_migrations_total",
    "completed live inode-range migrations, by kind", ("kind",))
meta_range_migration_aborts = DEFAULT.counter(
    "cubefs_meta_range_migration_aborts_total",
    "in-flight migrations aborted before COMMIT (poisoned delta tap, "
    "donor leadership change, crash recovery); aborts are clean — the "
    "range table never moved", ("reason",))
meta_range_redirects = DEFAULT.counter(
    "cubefs_meta_range_redirects_total",
    "requests bounced with the 453 range-moved routing code (frozen "
    "sub-range during handoff, or a stale client map after COMMIT)")

# token-bucket shaping (utils/ratelimit.py) — every shaped reservation
# is observable, whether the bucket itself sleeps or the QoS gate
# carries the wait as an admission delay.
ratelimit_waits = DEFAULT.counter(
    "cubefs_ratelimit_waits_total",
    "token-bucket reservations that had to wait for refill", ("limiter",))
ratelimit_wait_seconds = DEFAULT.histogram(
    "cubefs_ratelimit_wait_seconds",
    "per-reservation token-bucket wait (virtual-queue debt / rate)",
    ("limiter",))

# per-tenant QoS admission (utils/qos.py): the objectnode/S3 and blob
# access front doors. `cubefs-cli metrics qos` renders these. Tenant
# label cardinality is bounded by quota config (unconfigured tenants
# appear only while active).
qos_admitted = DEFAULT.counter(
    "cubefs_qos_admitted_total",
    "requests admitted through the QoS gate",
    ("path", "tenant", "priority"))
qos_shed = DEFAULT.counter(
    "cubefs_qos_shed_total",
    "requests shed (429) at admission: `over_quota` exhausted the "
    "tenant bucket, `queue_depth` hit the per-priority inflight bound, "
    "`brownout` was a low-priority class dropped while the path burns "
    "SLO budget", ("path", "tenant", "reason"))
qos_throttled = DEFAULT.counter(
    "cubefs_qos_throttled_total",
    "admissions shaped (delayed but not shed) by the tenant bucket",
    ("path", "tenant"))
qos_throttle_wait = DEFAULT.histogram(
    "cubefs_qos_throttle_wait_seconds",
    "admission shaping delay applied by the tenant bucket", ("path",))
qos_inflight = DEFAULT.gauge(
    "cubefs_qos_inflight",
    "requests currently inside the QoS gate, per path", ("path",))
qos_brownout = DEFAULT.gauge(
    "cubefs_qos_brownout_level",
    "burn-rate-driven degradation level per path: 0 healthy, 1 shed "
    "scrub + suppress flash fills + halve repair steps, 2 shed repair "
    "too and quarter repair steps", ("path",))

# cold-data lifecycle tiering (fs/tiering.py + fs/lcnode.py): the
# two-phase fs->blob migration FSM. `cubefs-cli metrics tiering`
# renders these.
tiering_transitions = DEFAULT.counter(
    "cubefs_tiering_transitions_total",
    "cold-tier migration attempts by outcome: `migrated` released the "
    "hot extents after a verified blob copy, `fenced` lost the race to "
    "a concurrent write/rename and rolled back, `resumed` finished a "
    "half-done migration found by rescan, `aborted` rolled one back, "
    "`verify_failed` rejected a corrupt blob copy before release, "
    "`error` died mid-flight (state machine resumes it)", ("outcome",))
tiering_bytes = DEFAULT.counter(
    "cubefs_tiering_bytes_total",
    "payload bytes moved across the fs<->blob bridge",
    ("direction",))  # cold (migrate) / hot (untier) / read (read-through)
tiering_cold_reads = DEFAULT.counter(
    "cubefs_tiering_cold_reads_total",
    "read-through requests served from the blob plane")
tiering_untiered = DEFAULT.counter(
    "cubefs_tiering_untiered_total",
    "re-heat promotions back to datanode extents by outcome",
    ("outcome",))
tiering_orphans_reaped = DEFAULT.counter(
    "cubefs_tiering_orphans_reaped_total",
    "unreachable blob copies deleted by the deferred blob-free reaper")
tiering_blob_freelist = DEFAULT.gauge(
    "cubefs_tiering_blob_freelist",
    "blob locations queued for deferred deletion (nonzero between a "
    "rollback/overwrite/unlink and the next reaper sweep)")
tiering_orphans_reconciled = DEFAULT.counter(
    "cubefs_tiering_orphans_reconciled_total",
    "leaked blob bids found by inventory reconciliation (the "
    "put->blob_written crash window) and enqueued for the reaper")
lc_scan_errors = DEFAULT.counter(
    "cubefs_lc_scan_errors_total",
    "lifecycle scan loop iterations that raised (loop stays alive)")

# silent-corruption defense (utils/fsm.py WAL framing, store-level
# verified reads with read-repair, utils/scrub.py sweeps, disk
# quarantine). `cubefs-cli metrics integrity` renders these.
integrity_corruptions_detected = DEFAULT.counter(
    "cubefs_integrity_corruptions_detected_total",
    "at-rest corruptions caught by a CRC check, by plane (fs/blob/wal) "
    "and source (`read` = foreground verified read, `scrub` = "
    "background sweep, `replay` = WAL replay)", ("plane", "source"))
integrity_corruptions_healed = DEFAULT.counter(
    "cubefs_integrity_corruptions_healed_total",
    "corrupt copies rewritten in place from a healthy replica (fs) or "
    "EC reconstruction (blob), by plane and source", ("plane", "source"))
integrity_repair_failures = DEFAULT.counter(
    "cubefs_integrity_repair_failures_total",
    "read-repair attempts that could not heal the bad copy (left for "
    "the scrubber / repair queue)", ("plane",))
wal_torn_tail = DEFAULT.counter(
    "cubefs_wal_torn_tail_total",
    "WAL replays that truncated a torn trailing record (the expected "
    "crash artifact; corrupt-MIDDLE records refuse replay instead)")
scrub_items = DEFAULT.counter(
    "cubefs_scrub_items_total",
    "scrubbed units by plane and outcome: `clean`, `corrupt` (detected "
    "and queued/healed), `skipped` (brownout or rate limit deferred)",
    ("plane", "outcome"))
scrub_last_full_pass = DEFAULT.gauge(
    "cubefs_scrub_last_full_pass_seconds",
    "wall seconds the most recent COMPLETED full scrub pass took, per "
    "plane (0 until a first pass completes)", ("plane",))
scrub_cursor = DEFAULT.gauge(
    "cubefs_scrub_cursor_position",
    "resumable sweep cursor position within the current pass",
    ("plane",))
disk_quarantined = DEFAULT.gauge(
    "cubefs_disk_quarantine_active",
    "disks currently quarantined (no new allocations; probe-based "
    "unquarantine pending)", ("node",))
disk_quarantine_transitions = DEFAULT.counter(
    "cubefs_disk_quarantine_transitions_total",
    "disk health state transitions: `quarantine` (io-error or latency "
    "outlier tripped), `probe_pass` (probe healed it back), "
    "`probe_fail` (probe kept it quarantined)", ("node", "event"))

# multiplexed streaming packet plane (utils/packet.py): frame/chunk
# traffic on both sides of the binary wire, mux session health, and the
# per-frame send-slot queue wait (how long a chunk waited for the
# shared connection). `cubefs-cli metrics wire` renders these.
pkt_frames = DEFAULT.counter(
    "cubefs_pkt_frames_total",
    "binary-plane frames moved, by direction (`tx`/`rx`) and side "
    "(`client`/`server`)", ("dir", "side"))
pkt_chunk_bytes = DEFAULT.counter(
    "cubefs_pkt_chunk_bytes_total",
    "binary-plane bytes moved (headers + args + payload chunks), by "
    "direction and side", ("dir", "side"))
pkt_mux_conns = DEFAULT.gauge(
    "cubefs_pkt_mux_conns",
    "live client-side mux connections (one shared socket per address)")
pkt_mux_streams = DEFAULT.gauge(
    "cubefs_pkt_mux_streams",
    "requests currently in flight across all mux connections (streams "
    "registered and not yet resolved)")
pkt_mux_queue_wait = DEFAULT.histogram(
    "cubefs_pkt_mux_queue_wait_seconds",
    "wait for the shared connection's per-frame send slot — how long "
    "one chunk queued behind other streams' frames",
    buckets=(0.00005, 0.0002, 0.001, 0.005, 0.02, 0.1, 0.5, 2))
pkt_stream_drops = DEFAULT.counter(
    "cubefs_pkt_stream_drops_total",
    "streams failed by a per-chunk CRC mismatch while the connection "
    "itself was kept (framing intact)", ("side",))

# cross-cluster geo-replication (utils/georepl.py + fs/georepl.py):
# per-partition WAL shipping, fenced promote/failback, follower-region
# read serving. `cubefs-cli metrics geo` renders these.
geo_lag = DEFAULT.gauge(
    "cubefs_geo_lag_seconds",
    "replication lag per shipped partition: ship-stamp age of the last "
    "record the follower applied (tenant-scoped RPO clock)",
    ("part", "tenant"))
geo_rpo_bytes = DEFAULT.gauge(
    "cubefs_geo_rpo_bytes",
    "bytes committed on the primary but not yet acknowledged by the "
    "follower — the data at risk if the region dies right now",
    ("part", "tenant"))
geo_shipped = DEFAULT.counter(
    "cubefs_geo_shipped_total",
    "records shipped to the peer region, per partition", ("part",))
geo_applied = DEFAULT.counter(
    "cubefs_geo_applied_total",
    "follower-side stream outcomes per partition: `applied`, "
    "`duplicate` (seq <= applied, idempotent skip), `gap` (backfill "
    "triggered), `corrupt` (framing/CRC rejected)", ("part", "outcome"))
geo_fencing_rejections = DEFAULT.counter(
    "cubefs_geo_fencing_rejections_total",
    "shipped records rejected for carrying a stale fencing epoch (a "
    "healed old primary replaying into a promoted follower)", ("part",))
geo_backfills = DEFAULT.counter(
    "cubefs_geo_backfills_total",
    "gap recoveries per partition by kind: `ring` (bounded backfill "
    "from the shipper's ring) or `bootstrap` (full snapshot transfer "
    "over the packet mux)", ("part", "kind"))
geo_state = DEFAULT.gauge(
    "cubefs_geo_state",
    "promote/failback state machine position per cluster: 0=PRIMARY "
    "1=FOLLOWING 2=FENCED 3=PROMOTED 4=FAILBACK_SYNC", ("cluster",))
geo_epoch = DEFAULT.gauge(
    "cubefs_geo_epoch",
    "current fencing epoch per cluster (monotonic; bumps on every "
    "promote so stale-primary appends are rejectable)", ("cluster",))
geo_redirects = DEFAULT.counter(
    "cubefs_geo_redirects_total",
    "mutations bounced off a follower region with GeoRedirect (the sdk "
    "retries them against the primary)", ("part",))
