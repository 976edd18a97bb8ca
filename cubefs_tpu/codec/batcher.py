"""Batched codec admission: coalesce concurrent submissions into
device-sized steps.

An engine call costs milliseconds of host time whatever it carries
(`engine.call_ms-small` 6.83 for ~18 us of device work; ledger, PR 29,
and PERF.md section 5), and the blob plane batches only *within* one PUT. This module is the admission
layer in between, and the only way blob-plane code reaches an engine:
every `encode_parity` / `matrix_apply` submission with compatible geometry
``(op, n, m, width rung)`` parks in a per-geometry queue — the rung of
`ops/rs_kernel.py`'s ladder that holds its shard size, so PUTs of
different sizes meet in one queue and a step's shape is one a program
was built for before the first request — and whichever
submitter finds the queue idle drains it as ONE engine call — the same
first-caller-drains pattern the raft proposal batcher uses for group
commit (parallel/raft.py): the step's duration itself is the batching
window, so an uncontended caller pays no added wait and batch width
tracks contention. What the benchmark's cells read
(`batcher.stripes_per_step`; ledger, PR 29): 8.0 where a 64 MiB PUT is
its own step (`ingest-large`; 32 B over `max_step_bytes`, so no second
PUT joins), 64.0 where a repair task is (`disk-repair`,
`disk-repair-2disk`), ~1.5 where eight clients PUT small objects
(`put-small`, the one cell that coalesces; PERF.md sections 4-5).

Per-submission results and errors fan back through private events (a
malformed submission mid-batch is rejected alone; its batch-mates
proceed). A bounded pending-stripe queue provides backpressure,
`max_batch` and `max_step_bytes` bound a step, `max_wait_ms` adds an
optional linger, and a drained step of the device engine is split
dp-wise across the device mesh (parallel/sharded_codec.py) when several
devices are visible.

Environment, read at construction: CUBEFS_CODEC_STEP_BYTES (the byte
bound of a step, 64 MiB; blob/scheduler.py sizes repair tasks by the
same variable) and CUBEFS_CODEC_DP=0 (no dp split).

Bit-identity: GF(2^8) math has no rounding, every engine is
bit-identical per stripe, and the dp split is along the independent
batch axis — a coalesced step's output equals each submission's own
call byte for byte (asserted in tests/test_codec_batch.py).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from collections.abc import Sequence

import numpy as np

from ..ops import progcache, rs_kernel
from ..utils import metrics
from ..utils import trace as tracelib
from .engine import (STEP_WIDTH, Engine, _dead_engines, _dispatch,
                     _drilled_dead, engine_for, get_engine)

_log = logging.getLogger("cubefs.codec")


class CodecAdmissionError(Exception):
    """Submission rejected or lost by the admission layer itself."""


class BackpressureError(CodecAdmissionError):
    """The bounded pending queue stayed full past the deadline."""


class CodecFuture:
    """One caller's stripes parked in a geometry queue. Resolved exactly
    once by the drainer — result or error — then its private event
    fires (no shared condition herd; the raft _ProposeWaiter shape).

    `submit_*_async` returns this handle so a caller can pipeline:
    submit several stripes, then collect. A collector whose queue has
    no drain in flight becomes the drainer itself (collector-drains,
    the async face of first-caller-drains) — there is no dedicated
    drainer thread to fall behind or die. One collector per future:
    the wake-up event is allocated lazily by that collector, because in
    pipelined use most futures are already resolved when collected and
    never need one (Event allocation and signalling are the admission
    layer's hottest per-submission costs)."""

    __slots__ = ("arr", "stripes", "widths", "width", "value", "exc",
                 "done", "event", "enq_t", "ref", "_batcher", "_key")

    def __init__(self, batcher: "BatchCodec", key: tuple, arr: np.ndarray,
                 widths: tuple):
        self.arr = arr
        self.stripes = int(arr.shape[0])
        # payload columns of each live stripe, the array's first
        # len(widths); the rest of it is pad. The rows handed back are
        # [:len(widths), :, :width]
        self.widths = widths
        self.width = max(widths)
        self.value = None
        self.exc: BaseException | None = None
        self.done = False
        self.event: threading.Event | None = None
        self.enq_t = time.perf_counter()
        # span handoff: the drainer runs in ONE submitter's context;
        # every other submitter's span survives only through this ref,
        # which the drain span records as a follows-from link
        self.ref = tracelib.capture()
        self._batcher = batcher
        self._key = key

    def resolve(self, value, exc: BaseException | None) -> None:
        self.value = value
        self.exc = exc
        self.arr = None  # nothing reads the rows after this
        # write order matters (Dekker with result()): done first, then
        # read the event slot — the GIL makes each step atomic and
        # sequentially consistent, so either the collector sees done or
        # we see its event
        self.done = True
        ev = self.event
        if ev is not None:
            ev.set()

    def result(self, timeout: float = 120.0) -> np.ndarray:
        """Block until resolved; return the stripes or raise the
        per-submission error. Drains the queue first if nobody is."""
        how = "ready"
        if not self.done:
            how = ("drained" if self._batcher._drain_if_idle(self._key, self)
                   else "waited")
            if not self.done:
                ev = self.event
                if ev is None:
                    ev = self.event = threading.Event()
                if not self.done and not ev.wait(timeout):
                    # the drainer still owns the submission and will
                    # resolve it; this caller just stops waiting
                    raise CodecAdmissionError(
                        f"{self._key[0]}: submission not drained within "
                        f"{timeout:.1f}s")
        if tracelib.enabled():
            _count_collect(self._key[0], how)
        if self.exc is not None:
            raise self.exc
        return self.value


class _GeometryQueue:
    """Pending submissions for one (op, engine, geometry) key. An apply
    key, and an LRC encode's, carries its matrix (a step has one
    matrix), and a worker meets hundreds of survivor sets: the queue
    lives in the map only while it holds submissions or a drain is in
    flight (``_drain`` drops it)."""

    __slots__ = ("subs", "busy", "coeff")

    def __init__(self, coeff: np.ndarray | None):
        self.subs: list[CodecFuture] = []
        self.busy = False
        self.coeff = coeff  # identical for every submission in the key


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class _Drain:
    """What one drain learns of itself, for the drainer's streak: the
    submission its caller came to collect, when the caller became the
    drainer, when that submission was resolved, the steps run."""

    __slots__ = ("own", "t0", "own_t", "steps")

    def __init__(self, own: CodecFuture, t0: float):
        self.own = own
        self.t0 = t0
        self.own_t = t0 if own.done else None
        self.steps = 0

    def step_ran(self, clock) -> None:
        """After a step's results (or its failure) were fanned back."""
        self.steps += 1
        if self.own_t is None and self.own.done:
            self.own_t = clock()


# a gathered step's array above this size is kept for the next one
SPARE_MIN_BYTES = 32 << 20
# what a drain and a gathered step's copy are called in a profile, in
# the style of codec/engine.py's _PHASE_SPANS
_DRAIN_SPAN = f"{tracelib.PROFILE_PREFIX}codec.drain"
_GATHER_SPAN = f"{tracelib.PROFILE_PREFIX}codec.gather"
_NO_SPAN = contextlib.nullcontext()
_SEAM_STATES = ("busy", "handoff", "starved")
_STEP_SERIES: dict[str, tuple] = {}
_DRAIN_SERIES: dict[str, tuple] = {}
_COLLECTS: dict[tuple[str, str], object] = {}


def _step_series(op: str) -> tuple:
    """(payload bytes, pad bytes, widths) of `op`'s steps, the label
    lookups done once: this runs once a step."""
    bound = _STEP_SERIES.get(op)
    if bound is None:
        bound = _STEP_SERIES[op] = (
            metrics.codec_step_bytes.bind(op=op, kind="payload"),
            metrics.codec_step_bytes.bind(op=op, kind="pad"),
            metrics.codec_batch_widths.bind(op=op))
    return bound


def _drain_series(op: str) -> tuple:
    """(steps a drain, its `own` seconds, its `others` seconds) of
    `op`, the label lookups done once: this runs once a drain."""
    bound = _DRAIN_SERIES.get(op)
    if bound is None:
        bound = _DRAIN_SERIES[op] = (
            metrics.codec_drain_steps.bind(op=op),
            metrics.codec_drain_seconds.bind(op=op, part="own"),
            metrics.codec_drain_seconds.bind(op=op, part="others"))
    return bound


def _count_collect(op: str, how: str) -> None:
    """One result() of `op`: `ready`, `waited` or `drained`."""
    inc = _COLLECTS.get((op, how))
    if inc is None:
        inc = _COLLECTS[(op, how)] = metrics.codec_collects.bind(
            op=op, how=how)
    inc()


class BatchCodec:
    """The submit surface. One instance per process is the norm
    (module-level DEFAULT below); tests construct private ones."""

    def __init__(self, max_batch: int = rs_kernel.STEP_BATCH,
                 max_wait_ms: float = 0.0,
                 max_pending: int = 4096,
                 max_step_bytes: int | None = None,
                 clock=time.perf_counter):
        # stripes per coalesced step: with max_step_bytes it bounds the
        # rungs a step can reach, so the programs a geometry can ask for
        self.max_batch = max_batch
        # drainer linger before the first swap (0: the step is the window)
        self.max_wait = max_wait_ms / 1e3
        # stripes parked across all queues before submitters block
        self.max_pending = max_pending
        # byte bound per device step: keeps 'auto' inside the measured
        # crossover sizes and bounds step working-set memory
        self.max_step_bytes = (max_step_bytes if max_step_bytes is not None
                               else _env_int("CUBEFS_CODEC_STEP_BYTES",
                                             64 << 20))
        self.dp_enabled = os.environ.get("CUBEFS_CODEC_DP", "1") != "0"
        self.dp_min_bytes = 1 << 20  # smallest step worth sharding
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: dict[tuple, _GeometryQueue] = {}
        self._pending = 0  # stripes parked across all queues
        self._n_busy = 0  # queues with a drain in flight
        self._dp_meshes: dict[int, object] = {}
        # (rows, width rung) -> stripes a coalesced step may hold
        self._caps: dict[tuple[int, int], int] = {}
        self._spare: np.ndarray | None = None  # see _gather
        # the engine seam's account, all of it under self._lock: what
        # decides the state, when it last changed, and the seconds of
        # each state not yet moved into metrics.codec_engine_seconds
        # (settle). `clock` times it and the drains; tests inject one.
        self._clock = clock
        self._calls = 0  # engine calls in flight
        self._open = 0  # submissions admitted and not yet resolved
        self._since = clock()
        self._seam = dict.fromkeys(_SEAM_STATES, 0.0)

    # ---------------- public submit surface ----------------
    def submit_encode(self, engine: str | None, data: np.ndarray,
                      n_parity: int, timeout: float = 120.0,
                      rows: np.ndarray | None = None,
                      local_rows: int = 0) -> np.ndarray:
        """(B, N, S) data -> (B, M, S) parity, coalesced with every
        concurrent submission of the same (N, M, engine, rows) whose S
        lies in the same width rung."""
        return self.submit_encode_async(
            engine, data, n_parity, timeout, rows=rows,
            local_rows=local_rows).result(timeout)

    def submit_apply(self, engine: str | None, coeff: np.ndarray,
                     shards: np.ndarray, timeout: float = 120.0
                     ) -> np.ndarray:
        """(R, C) GF matrix x (B, C, S) shards -> (B, R, S), coalesced
        with concurrent submissions sharing the identical matrix."""
        return self.submit_apply_async(
            engine, coeff, shards, timeout).result(timeout)

    def submit_encode_async(self, engine: str | None, data: np.ndarray,
                            n_parity: int, timeout: float = 120.0,
                            width: int | Sequence[int] | None = None,
                            rows: np.ndarray | None = None,
                            local_rows: int = 0) -> CodecFuture:
        """submit_encode that parks and returns immediately: collect
        with .result(). A caller pipelining K submissions before its
        first collect keeps K stripes continuously admitted — the
        sleep/wake cycle per stripe disappears and step width rises.

        ``width``: a caller that built ``data`` at its width rung
        (rs_kernel.rung_width, zeros past its shard size) says how many
        columns are payload: a step of that submission alone takes the
        array as it is, and the rows come back ``[:, :, :width]``. A
        sequence gives each stripe's own count (a repair step's bids
        have the sizes they have) and may be shorter than the array: the
        stripes past it are pad too (zero stripes up to a stripe rung),
        and the rows come back ``[:len(width), :, :max(width)]``.

        ``rows``: the (M, N) generator rows where they are not RS's
        systematic parity rows — an LRC codemode's global rows and, the
        last ``local_rows`` of them, its local rows composed through
        the global ones (rs_kernel.lrc_encode_rows): both levels in one
        step. A step's span carries ``local_rows``."""
        key, coeff, arr = self._prep_encode(engine, data, n_parity, rows,
                                            local_rows)
        return self._enqueue(key, coeff, arr, timeout, width)

    def submit_apply_async(self, engine: str | None, coeff: np.ndarray,
                           shards: np.ndarray, timeout: float = 120.0,
                           width: int | Sequence[int] | None = None
                           ) -> CodecFuture:
        """submit_apply that parks and returns immediately."""
        key, coeff, arr = self._prep_apply(engine, coeff, shards)
        return self._enqueue(key, coeff, arr, timeout, width)

    # ---------------- admission ----------------
    def _prep_encode(self, engine, data, n_parity, rows=None,
                     local_rows=0):
        data = np.asarray(data)
        if data.ndim != 3:
            raise ValueError(f"submit_encode takes (B, N, S), got "
                             f"{data.shape}")
        n, s = int(data.shape[1]), rs_kernel.rung_width(data.shape[2])
        key = ("encode", engine or "", n, int(n_parity), s)
        if rows is None:
            return key, None, data
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        if rows.shape != (int(n_parity), n):
            raise ValueError(f"submit_encode: {rows.shape} generator rows "
                             f"for {n_parity} parity rows of {n}")
        return key + (rows.tobytes(), int(local_rows)), rows, data

    def _prep_apply(self, engine, coeff, shards):
        shards = np.asarray(shards)
        if shards.ndim != 3:
            raise ValueError(f"submit_apply takes (B, C, S), got "
                             f"{shards.shape}")
        coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
        c, s = int(shards.shape[1]), rs_kernel.rung_width(shards.shape[2])
        return ("apply", engine or "", coeff.tobytes(), c, s), coeff, shards

    def _enqueue(self, key: tuple, coeff: np.ndarray | None,
                 arr: np.ndarray, timeout: float,
                 width: int | Sequence[int] | None = None) -> CodecFuture:
        b, s = int(arr.shape[0]), int(arr.shape[2])
        if width is None:
            widths = (s,) * b
        elif isinstance(width, (int, np.integer)):
            widths = (int(width),) * b
        else:
            widths = tuple(int(w) for w in width)
        if not (0 < len(widths) <= b and 0 <= min(widths)
                and max(widths) <= s):
            raise ValueError(f"{key[0]}: payload widths {widths[:4]}.. of "
                             f"{len(widths)} stripes do not fit {arr.shape}")
        sub = CodecFuture(self, key, arr, widths)
        with self._lock:
            # backpressure: block only while a drain in flight will
            # free space — the submitter who finds everything idle
            # becomes the drainer and must never park itself
            deadline = None
            while (self._pending + sub.stripes > self.max_pending
                   and self._n_busy > 0):
                op = key[0]
                if deadline is None:
                    metrics.codec_batch_backpressure.inc(op=op)
                    deadline = time.monotonic() + timeout
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    raise BackpressureError(
                        f"{op}: {self._pending} stripes pending > bound "
                        f"{self.max_pending} for {timeout:.1f}s")
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = _GeometryQueue(coeff)
            q.subs.append(sub)
            self._pending += sub.stripes
            self._seam_tick()
            self._open += 1
        return sub

    # ---------------- the engine seam's account ----------------
    def _seam_tick(self) -> None:
        """Book the seconds since the last change to the state the seam
        is in. The caller holds self._lock and changes `_calls` or
        `_open` next: a submission admitted, an engine call's start or
        end, the last resolve of a swap."""
        now = self._clock()
        state = ("busy" if self._calls else
                 "handoff" if self._open else "starved")
        self._seam[state] += now - self._since
        self._since = now

    def settle(self) -> None:
        """Move the account into cubefs_codec_engine_seconds_total{state},
        booked up to now (dropped instead while CUBEFS_TRACE=0). The
        registry calls this for DEFAULT before every render, so a delta
        between two scrapes is exact however long a state lasts."""
        with self._lock:
            self._seam_tick()
            seam, self._seam = self._seam, dict.fromkeys(_SEAM_STATES, 0.0)
        if tracelib.enabled():
            for state, seconds in seam.items():
                if seconds:
                    metrics.codec_engine_seconds.inc(seconds, state=state)

    def _drain_if_idle(self, key: tuple, own: CodecFuture) -> bool:
        """Become the drainer for `key` unless one is already running
        (collector-drains; called from CodecFuture.result, which hands
        in the submission it came for). True if this caller drained."""
        q = self._queues.get(key)
        # unlocked peek: a True `busy` is authoritative enough — the
        # running drainer only exits once the queue is empty, so any
        # parked submission it hasn't taken yet, it will. Skipping the
        # lock here keeps collectors off the drainer's neck.
        if q is not None and q.busy:
            return False
        with self._lock:
            q = self._queues.get(key)
            if q is None or q.busy or not q.subs:
                return False
            q.busy = True
            self._n_busy += 1
        on = tracelib.enabled()
        drain = _Drain(own, self._clock())
        with tracelib.annotation(_DRAIN_SPAN) if on else _NO_SPAN:
            if self.max_wait > 0:
                # optional linger: trade first-collector latency for
                # width when arrivals are sparse but steady
                time.sleep(self.max_wait)
            self._drain(key, q, drain)
        if on:
            self._record_drain(key[0], drain)
        return True

    def _record_drain(self, op: str, drain: _Drain) -> None:
        """The streak of a drain that ran to its end: how many steps,
        how long until the drainer's own submission was resolved, how
        long it then served the others; the last two also as tags of
        the drainer's span (a PUT's stage:encode_admission, the repair
        worker's stage:decode), which `cubefs-cli trace slow` shows."""
        end = self._clock()
        own_t = drain.own_t if drain.own_t is not None else end
        steps, own, others = _drain_series(op)
        steps.observe(drain.steps)
        own.observe(own_t - drain.t0)
        others.observe(end - own_t)
        span = tracelib.current()
        if span is not None:
            span.set_tag("drain_steps", drain.steps)
            span.set_tag("drain_others_ms", round((end - own_t) * 1e3, 3))

    def _drain(self, key: tuple, q: _GeometryQueue, drain: _Drain) -> None:
        """First-caller-drains loop: swap the queue out and land each
        swap as one (or a few, size-bounded) device steps. Submissions
        arriving during a step ride the next swap — the step duration
        is the batching window."""
        try:
            while True:
                with self._lock:
                    batch = q.subs
                    if not batch:
                        q.busy = False
                        self._n_busy -= 1
                        # empty and idle: the next submission of this
                        # key makes a new one (under this same lock)
                        if self._queues.get(key) is q:
                            del self._queues[key]
                        self._cond.notify_all()
                        return
                    q.subs = []
                total = sum(s.stripes for s in batch)
                n = len(batch)
                try:
                    self._run_steps(key, q.coeff, batch, total, drain)
                finally:
                    with self._lock:
                        self._pending -= total
                        self._seam_tick()
                        self._open -= n
                        self._cond.notify_all()
        except BaseException as e:
            # a dying drainer (MemoryError, interrupt) must not strand
            # the queue busy forever: fail whatever is still parked and
            # reopen the queue so later submissions can self-drain
            with self._lock:
                orphans = q.subs
                q.subs = []
                self._pending -= sum(s.stripes for s in orphans)
                self._seam_tick()
                self._open -= len(orphans)
                q.busy = False
                self._n_busy -= 1
                self._cond.notify_all()
            for sub in orphans:
                if not sub.done:
                    sub.resolve(None, CodecAdmissionError(
                        f"{key[0]}: drainer died: {e!r}"))
            raise

    def _run_steps(self, key: tuple, coeff: np.ndarray | None,
                   batch: list[CodecFuture], total: int,
                   drain: _Drain) -> None:
        """Validate, chunk, execute, and fan results back. Every
        submission is resolved exactly once, even when the device call
        fails or a batch-mate is malformed. One fused pass — this loop
        runs per submission at full admission rate."""
        op = key[0]
        # admitted-stripe accounting lands here, once per swap — per-
        # submission counter locks are measurable at this call rate
        metrics.codec_batch_submissions.inc(
            sum(len(sub.widths) for sub in batch), op=op)
        # the key carries the geometry: encode (.., n, m, rung[, rows,
        # local rows]), apply (.., coeff, c, rung); the cap is reckoned
        # on the rung, the width every stripe of the step goes up at
        geometry = (int(key[3]) if op == "apply" else int(key[2]),
                    int(key[4]))
        stripe_cap = self._caps.get(geometry)
        if stripe_cap is None:
            stripe_cap = self._caps[geometry] = rs_kernel.batch_cap(
                *geometry, self.max_step_bytes, self.max_batch)
        step: list[CodecFuture] = []
        try:
            stripes = 0
            # taken off the list as it goes: a future whose step has run
            # is its collector's alone, so the kept host buffer its
            # result views (utils/hostmem) is free once the collector is
            # done with it, not once this swap's last step has run
            batch.reverse()
            while batch:
                sub = batch[-1]
                # drain-time validation: key geometry comes from the
                # shape, so the remaining per-submission failure is
                # dtype — reject it alone (concatenate would silently
                # upcast the step)
                if sub.arr.dtype != np.uint8:
                    metrics.codec_batch_errors.inc(op=op, kind="dtype")
                    sub.resolve(None, CodecAdmissionError(
                        f"{op}: stripe dtype must be uint8, got "
                        f"{sub.arr.dtype}"))
                    batch.pop()
                    continue
                if step and stripes + sub.stripes > stripe_cap:
                    self._one_step(key, coeff, step, drain)
                    step, stripes = [], 0
                step.append(batch.pop())
                stripes += sub.stripes
            if step:
                self._one_step(key, coeff, step, drain)
        finally:
            # belt-and-braces: nobody waits forever
            for sub in step + batch:
                if not sub.done:
                    sub.resolve(None, CodecAdmissionError(
                        f"{op}: drain failed before this submission"))

    def _one_step(self, key: tuple, coeff: np.ndarray | None,
                  step: list[CodecFuture], drain: _Drain) -> None:
        """One engine call at the smallest rung that holds the step: a
        zeroed (B_rung, rows, S_rung) array with every submission's rows
        copied in at its own width — unless the step is one submission
        that is rung-shaped already (a PUT's data rows, a repair task's
        survivors), which goes up as it is."""
        op = key[0]
        on = tracelib.enabled()
        gather_t0 = time.perf_counter()
        first = step[0].arr
        cols = int(first.shape[1])
        rung_b, rung_s = rs_kernel.step_shape(
            cols, sum(sub.stripes for sub in step), int(key[4]))
        shape = (rung_b, cols, rung_s)
        gathered = len(step) > 1 or first.shape != shape
        arr = first
        if gathered:
            with tracelib.annotation(_GATHER_SPAN) if on else _NO_SPAN:
                arr = self._gather(step, shape)
        # what the submissions brought, live stripe by live stripe; the
        # rest of the rung-shaped array is pad
        live = [w for sub in step for w in sub.widths]
        n_stripes = len(live)
        payload = cols * sum(live)
        pad = arr.nbytes - payload
        wait_now = time.perf_counter()
        metrics.codec_batch_wait.observe_many(
            [wait_now - sub.enq_t for sub in step], op=op)
        # one drain-step span, follows-from every OTHER submitter's
        # captured context (the drainer's own span is the parent)
        span = tracelib.start_span(
            "stage:codec_step",
            links=[s.ref for s in step if s.ref is not None])
        span.set_tag("stage", "codec_step").set_tag("op", op)
        span.set_tag("stripes", n_stripes)
        span.set_tag("rung_b", shape[0]).set_tag("rung_s", shape[2])
        span.set_tag("pad_bytes", pad)
        if op == "encode":  # an LRC key carries its rows and this count
            span.set_tag("local_rows", key[6] if len(key) > 5 else 0)
        with span:
            with self._lock:
                self._seam_tick()
                self._calls += 1
            # the rows handed back stop at the widest submission: the
            # engine need not bring the columns past it back
            width = STEP_WIDTH.set(max(live))
            try:
                out, served = self._engine_call(key, coeff, arr)
            except BaseException as e:  # fan the step's failure back
                for sub in step:
                    sub.resolve(None, e)
                drain.step_ran(self._clock)
                return
            finally:
                STEP_WIDTH.reset(width)
                with self._lock:
                    self._seam_tick()
                    self._calls -= 1
            span.set_tag("engine", served)
        if gathered and arr.nbytes > SPARE_MIN_BYTES:
            self._spare = arr  # the engine has its own copy by now
        tracelib.observe_stage("codec_step", span.path,
                               time.perf_counter() - wait_now)
        if on:
            metrics.codec_engine_phase.observe(
                wait_now - gather_t0 if gathered else 0.0,
                engine=served, op=op, phase="gather")
            count_payload, count_pad, widths = _step_series(op)
            count_payload(payload)
            count_pad(pad)
            widths.observe(len(set(live)))
        metrics.codec_batch_stripes.observe(n_stripes, op=op)
        off = 0
        for sub in step:  # resolve inlined: this is the hottest loop
            sub.value = out[off:off + len(sub.widths), :, :sub.width]
            sub.arr = None  # read: the rows' kept buffer is the caller's
            sub.done = True  # write order: done before the event read
            ev = sub.event
            if ev is not None:
                ev.set()
            off += sub.stripes
        drain.step_ran(self._clock)

    def _gather(self, step: list[CodecFuture], shape: tuple) -> np.ndarray:
        """The step's rung-shaped array: every submission's rows at its
        own width, zeros round them. A large one is kept for the next
        step of its shape: each page of a fresh mapping is a fault at
        first touch (~0.9 s for a repair step's 554 MB on the chip's
        host, PERF.md section 6), whatever the memory bandwidth."""
        with self._lock:
            arr, self._spare = self._spare, None
        kept = arr is not None and arr.shape == shape
        if not kept:
            arr = np.zeros(shape, dtype=np.uint8)
        off = 0
        for sub in step:
            end, width = off + sub.stripes, sub.arr.shape[2]
            arr[off:end, :, :width] = sub.arr
            if kept:
                arr[off:end, :, width:] = 0
            off = end
        if kept:
            arr[off:] = 0
        return arr

    # ---------------- device step ----------------
    def _engine_call(self, key: tuple, coeff: np.ndarray | None,
                     arr: np.ndarray) -> tuple[np.ndarray, str]:
        """(the step's output, the engine that served it)."""
        op, label = key[0], key[1]
        name = label or os.environ.get("CUBEFS_TPU_EC_ENGINE", "tpu")
        if name == "auto":
            # the whole point of admission: the crossover policy sees
            # the COALESCED size, so concurrent tiny submissions ride
            # the engine measured best for the batch they became
            name = engine_for(int(arr.nbytes)).name
        if op == "encode":
            m = int(key[3])
            out = self._maybe_dp(name, coeff, arr, m)
            if out is None and coeff is None:
                out, name = _dispatch(name, "encode_parity", arr, m)
            elif out is None:  # an encode by generator rows of its own
                out, name = _dispatch(name, "matrix_apply", coeff, arr)
        else:
            out = self._maybe_dp(name, coeff, arr, None)
            if out is None:
                out, name = _dispatch(name, "matrix_apply", coeff, arr)
        # stamped AFTER dispatch with the leg that served the step: a
        # quarantined device engine must not keep counting as 'tpu'
        metrics.codec_batch_steps.inc(op=op, engine=name)
        return out, name

    def _maybe_dp(self, name: str, coeff: np.ndarray | None,
                  arr: np.ndarray, n_parity: int | None
                  ) -> np.ndarray | None:
        """Shard a drained step dp-wise over the visible devices (batch
        axis split 1/n per device, bit-identical). Returns None when not
        profitable/applicable."""
        if not self.dp_enabled or name != "tpu":
            return None
        if int(arr.nbytes) < self.dp_min_bytes or arr.shape[0] < 2:
            return None
        import jax

        devs = jax.devices()
        if len(devs) < 2:
            return None
        if name in _dead_engines or name in _drilled_dead():
            return None  # a lost device serves no step, sharded or not
        try:
            if coeff is None:
                from ..ops import gf256

                coeff = gf256.parity_matrix(int(arr.shape[1]),
                                            int(n_parity))
            from ..ops import rs_kernel

            coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
            dp = min(len(devs), int(arr.shape[0]))
            fn, sharding = self._dp_fn(
                coeff.shape[0], int(arr.shape[1]), dp)
            b = int(arr.shape[0])
            pad = (-b) % dp
            if pad:
                arr = np.concatenate(
                    [arr, np.zeros((pad,) + arr.shape[1:],
                                   dtype=np.uint8)], axis=0)
            x = jax.device_put(arr, sharding)
            out = np.asarray(fn(rs_kernel.device_bits(
                coeff, False, "encode" if n_parity else "apply"), x))
            # dp label = devices that actually hold a slice of the
            # step's input, not the width that was asked for
            metrics.codec_batch_dp_steps.inc(
                dp=len({s.device for s in x.addressable_shards}))
            return out[:b]
        except Exception:
            # a mesh/compile failure degrades to the single-device
            # engine path — a step never fails for a sharding miss —
            # but it is logged: a dp path that always fails would
            # otherwise look like a working single-device server
            _log.exception("dp-sharded codec step failed (%d stripes, "
                           "%d devices); serving it single-device",
                           int(arr.shape[0]), len(devs))
            return None

    def _dp_fn(self, rows: int, n_in: int, dp: int):
        """(jitted sharded apply, input sharding) for one matrix shape
        on a dp-wide mesh: the bit matrix is the program's operand, so
        it is built once per (rows, n_in, dp) — in the shared capped
        program cache — and serves every matrix of that shape."""
        def build():
            import jax

            from ..parallel import mesh as meshlib
            from ..parallel import sharded_codec

            mesh = self._dp_meshes.get(dp)
            if mesh is None:
                mesh = meshlib.make_mesh(
                    devices=jax.devices()[:dp],
                    dims={"dp": dp, "tp": 1, "sp": 1})
                self._dp_meshes[dp] = mesh
            metrics.codec_programs.inc(kernel="bits")
            return (jax.jit(sharded_codec.gf_apply_sharded(mesh, n_in)),
                    meshlib.stripe_sharding(mesh))

        return progcache.SHARED.get_or_build(
            "dp_jit", (rows, n_in, dp), build)


class AdmittedEngine:
    """Engine-protocol facade over the admission layer: the ONLY way
    blob-plane code reaches device math (lint family CFC). Accepts the
    same (..., C, S) shapes as a raw engine, flattening leading axes
    into the batch dimension for submission."""

    def __init__(self, batcher: BatchCodec, label: str | None):
        self.batcher = batcher
        self.label = label
        self.name = label or os.environ.get("CUBEFS_TPU_EC_ENGINE", "tpu")

    def encode_parity(self, data: np.ndarray, n_parity: int,
                      rows: np.ndarray | None = None,
                      local_rows: int = 0) -> np.ndarray:
        data = np.asarray(data)
        if data.ndim < 2:
            raise ValueError(f"shards must be (..., N, S), got {data.shape}")
        kw = {"rows": rows, "local_rows": local_rows}
        if data.ndim == 2:
            return self.batcher.submit_encode(
                self.label, data[None], n_parity, **kw)[0]
        if data.ndim == 3:
            return self.batcher.submit_encode(self.label, data, n_parity,
                                              **kw)
        lead = data.shape[:-2]
        out = self.batcher.submit_encode(
            self.label, data.reshape(-1, *data.shape[-2:]), n_parity, **kw)
        return out.reshape(*lead, *out.shape[-2:])

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray,
                     width: int | Sequence[int] | None = None
                     ) -> np.ndarray:
        """``width``: payload columns of a (B, C, S) array its caller
        built at the width rung, one count or one a live stripe
        (submit_encode_async's contract)."""
        shards = np.asarray(shards)
        if shards.ndim < 2:
            raise ValueError(
                f"shards must be (..., C, S), got {shards.shape}")
        if shards.ndim == 2:
            return self.batcher.submit_apply(
                self.label, coeff, shards[None])[0]
        if shards.ndim == 3:
            return self.batcher.submit_apply_async(
                self.label, coeff, shards, width=width).result()
        lead = shards.shape[:-2]
        out = self.batcher.submit_apply(
            self.label, coeff, shards.reshape(-1, *shards.shape[-2:]))
        return out.reshape(*lead, *out.shape[-2:])


DEFAULT = BatchCodec()
# the one process-wide account is DEFAULT's (looked up at each render:
# a test that swaps DEFAULT gets its own settled)
metrics.DEFAULT.before_render(lambda: DEFAULT.settle())


def admit(engine: str | None = None,
          batcher: BatchCodec | None = None) -> AdmittedEngine:
    """The admission surface: an Engine-shaped handle whose calls
    coalesce with every other admitted caller in the process. `engine`
    pins a named engine (same contract as get_engine); None follows
    CUBEFS_TPU_EC_ENGINE and 'auto' applies the measured size-class
    crossover to each DRAINED batch."""
    if engine is not None and engine != "auto":
        get_engine(engine)  # fail fast on unknown names, as before
    return AdmittedEngine(batcher or DEFAULT, engine)
