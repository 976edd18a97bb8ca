"""Repair worker: pulls unit-repair tasks and reconstructs on the TPU.

Role parity: blobstore/blobnode worker (loopAcquireTask at
worker_service.go:206; ShardRecover download-and-reconstruct at
worker_slice_recover.go:458,865; CRC cross-check at :45).

TPU-first redesign: instead of reconstructing blob-by-blob, a task's
blobs are grouped by the width rung of their shard size (ops/rs_kernel:
the ladder of step shapes) and recovered as BATCHED stripe stacks
(B_rung, n, S_rung) in one device call — every bid at its own size,
zeros past it — so a volume of objects of any sizes repairs in a
handful of device steps, at shapes whose programs `RepairWorker.ready`
built before the first task. A rebuilt shard is cut to its bid's size
before it is checked and written back: no stored shard carries pad.
The scheduler leases a volume's pending unit repairs together, and those
of a plain Reed-Solomon volume are decoded from ONE read of its
survivors (`units_per_read`): each step array is filled once, and one
decode step a lost unit runs over it. A step array is a view of a buffer
the process keeps (`_step_array`, `utils/hostmem.KEPT`): pages touched
once, not once a step.
A lost unit of an LRC volume is rebuilt from its AZ's local stripe
(upstream's recoverByLocalStripe), the global stripe the fallback; a
local stripe of one local parity leaves no survivor to check with, so
the step's second row derives the lost unit again through the global
code from the same reads, and the two must agree before the write-back.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import uuid
from collections import defaultdict

import numpy as np

from ..ops import rs_kernel
from ..codec import codemode as cm
from ..codec.batcher import admit
from ..utils import hostmem, metrics, rpc
from ..utils import trace as tracelib
from . import topology
from .types import VolumeInfo


def _msr_repair_enabled() -> bool:
    """CUBEFS_CODEC_MSR=0 pins MSR-coded volumes to the conventional
    k-full-shard repair path (the A/B door; reconstruction stays
    byte-identical either way, only the traffic shape changes)."""
    return os.environ.get("CUBEFS_CODEC_MSR", "1").lower() not in (
        "0", "false", "")


class MsrFallback(Exception):
    """Raised inside the MSR sub-shard path to hand the repair to the
    conventional decode — always BEFORE any writeback, so the fallback
    re-runs from scratch with no partial writes to undo."""

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        super().__init__(detail or reason)


def solve_and_wanted(subs: list[int], n_solve: int, bad_sub: int
                     ) -> tuple[list[int], list[int]]:
    """(solving survivors, rows to rebuild) for the survivors actually
    read, ascending, lost units already skipped: the first `n_solve`
    solve; where one more was read it is rebuilt beside the lost unit
    and compared with what was read — the pre-writeback check. Where
    none was, `RepairWorker._repair_rows` checks another way."""
    wanted = [bad_sub]
    if len(subs) > n_solve:
        wanted = sorted({bad_sub, subs[n_solve]})
    return list(subs[:n_solve]), wanted


def units_per_read(t: cm.Tactic) -> int:
    """How many unit repairs of one volume one read of its survivors
    serves. The read leaves out every unit it rebuilds from the start,
    so it is of the global stripe of a plain Reed-Solomon volume, and of
    as many units as leave n + 1 to read: each keeps the checking
    survivor it would have had alone. An LRC unit reads its own AZ's
    local stripe, which holds no unit past what the local code needs to
    solve it (its check is a second derivation through the global code),
    and an MSR unit its own helpers: one unit a read."""
    if t.l or t.is_msr():
        return 1
    return max(1, t.m - 1)


@dataclasses.dataclass
class _Unit:
    """One lost unit of a shared read: its task, its row in the solving
    code's shard space, where it goes, and what its decode steps gave."""
    task: dict
    sub: int
    dest: object
    writes: list = dataclasses.field(default_factory=list)
    steps: int = 0
    error: Exception | None = None


def repair_shard_sizes(t: cm.Tactic, lo: int, hi: int, blob_size: int
                       ) -> tuple[int, int]:
    """(least, largest) shard size of the blobs of objects of `lo`..`hi`
    bytes under tactic `t`: every blob of a PUT is stored at its first
    blob's shard size (blob/access.py)."""
    def size(length):
        return max(-(-min(length, blob_size) // t.n), t.min_shard_size)

    return size(lo), size(hi)


class RepairWorker:
    def __init__(self, scheduler_client: rpc.Client, cm_client: rpc.Client,
                 node_pool, engine: str | None = "auto",
                 worker_id: str | None = None, batch_stripes: int = 64):
        self.sched = scheduler_client
        self.cm = cm_client
        self.nodes = node_pool
        # 'auto' + admission: repair legs inherit the measured
        # crossover policy AND coalesce with concurrent PUT encodes
        # into shared device steps (codec/batcher.py)
        self.codec = admit(engine)
        self.worker_id = worker_id or uuid.uuid4().hex[:12]
        self.batch_stripes = batch_stripes
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.completed = 0
        self.failed = 0
        # how the last step's array came, `reused` or `fresh`
        self._came = "fresh"
        # what the process's heap does with freed pages, as the last
        # lease found it: `kept` or `dynamic` (hostmem.keep_freed_heap)
        self._heap = "dynamic"

    def ready(self, max_object_bytes: int, policies=None,
              blob_size: int | None = None) -> int:
        """What a worker host does once at start-up, from what it knows:
        the cluster's size-class policies and its largest object. Every
        program the repair of a Reed-Solomon or an LRC unit can ask the
        device for — any sizes in the volume, any lost unit, any
        survivor set — is built here: one zero step through the worker's
        own door at each shape of `rs_kernel.repair_steps`, REPAIR_ROWS
        rows by the codemode's n columns (the global stripe) and, for an
        LRC codemode, by its local stripe's ln columns too. Never
        implied by construction; returns the number of steps. MSR
        volumes are not listed: a sub-shard decode's programs are still
        built by its first step. What the host's heap keeps is set by
        the first lease (`run_once`), not here."""
        from .access import AccessConfig

        cfg = AccessConfig()
        policies = cfg.policies if policies is None else policies
        blob_size = cfg.blob_size if blob_size is None else blob_size
        steps = 0
        for p in policies:
            lo, hi = max(1, p.min_size), min(p.max_size, max_object_bytes)
            t = cm.tactic(cm.CodeMode[p.mode_name])
            if not p.enable or lo > hi or t.is_msr():
                continue
            for cols in sorted({t.n, t.local_stripe(0)[1] or t.n}):
                rows = np.zeros((rs_kernel.REPAIR_ROWS, cols),
                                dtype=np.uint8)
                for b, width in rs_kernel.repair_steps(
                        *repair_shard_sizes(t, lo, hi, blob_size),
                        self.batch_stripes):
                    self.codec.matrix_apply(
                        rows, np.zeros((b, cols, width), dtype=np.uint8))
                    steps += 1
        return steps

    # ---------------- loop ----------------
    def start(self, idle_wait: float = 0.5) -> None:
        def loop():
            while not self._stop.wait(0 if self.run_once() else idle_wait):
                pass

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def run_once(self) -> bool:
        """Acquire and execute one lease: a task and, where it repairs a
        unit of a volume, the volume's other pending unit repairs, which
        the scheduler leases with it. Each task is completed or failed
        alone; returns True if a lease was run. The first lease fixes
        the host's heap for the process (`hostmem.keep_freed_heap`):
        what a task frees stays mapped, and the next task's survivors
        land in pages the last one touched. A process whose worker never
        leases keeps glibc's own."""
        meta, _ = self.sched.call("acquire_task", {"worker_id": self.worker_id})
        if not meta.get("task"):
            return False
        self._heap = "kept" if hostmem.keep_freed_heap() else "dynamic"
        if tracelib.enabled():
            metrics.repair_leases.inc(heap=self._heap)
        tasks = [meta["task"], *meta.get("siblings", ())]
        errors = self.execute(tasks)
        for task in tasks:
            who = {"task_id": task["task_id"], "worker_id": self.worker_id}
            e = errors.get(task["task_id"])
            if e is None:
                try:
                    self.sched.call("complete_task", who)
                    self.completed += 1
                    metrics.repair_tasks.inc(state="completed")
                    continue
                except Exception as raised:  # the move was not recorded
                    e = raised
            self.sched.call("fail_task",
                            {**who, "error": f"{type(e).__name__}: {e}"})
            self.failed += 1
            metrics.repair_tasks.inc(state="failed")
        return True

    # ---------------- execution ----------------
    def execute(self, tasks: list[dict]) -> dict[str, Exception]:
        """Run the tasks of one lease; returns what failed, by task id."""
        # renew the leases on a timer for the whole execution: survivor
        # downloads for a large chunk can exceed one lease period long
        # before the first batch writes back
        renew_stop = threading.Event()

        def renew_loop():
            while not renew_stop.wait(10.0):
                for task in tasks:
                    try:
                        self.sched.call("renew_task",
                                        {"task_id": task["task_id"],
                                         "worker_id": self.worker_id})
                    except Exception:
                        pass

        renewer = threading.Thread(target=renew_loop, daemon=True)
        renewer.start()
        try:
            return self._execute(tasks)
        finally:
            renew_stop.set()

    def _execute(self, tasks: list[dict]) -> dict[str, Exception]:
        # as many tasks at a time as one read of the survivors serves:
        # one, for a task of another kind and for a volume whose units
        # each read their own (`units_per_read`)
        width = 1
        if tasks[0]["type"] == "unit_repair":
            width = units_per_read(cm.tactic(tasks[0]["codemode"]))
        failed: dict[str, Exception] = {}
        for i in range(0, len(tasks), width):
            sharing = tasks[i:i + width]
            try:
                with tracelib.path_span("blob.repair", "worker.repair") as sp:
                    sp.set_tag("svc", "worker").set_tag("task",
                                                       sharing[0]["type"])
                    sp.set_tag("units", len(sharing))
                    sp.set_tag("heap", self._heap)
                    errors = self._execute_traced(sharing, sp)
                    if errors:  # a unit's own failure does not raise
                        e = next(iter(errors.values()))
                        sp.set_tag("error", f"{type(e).__name__}: {e}")
            except Exception as e:  # what the tasks share failed
                errors = {task["task_id"]: e for task in sharing}
            failed.update(errors)
            if tracelib.enabled() and sharing[0]["type"] == "unit_repair":
                for j, task in enumerate(sharing):
                    if task["task_id"] not in errors:
                        metrics.repair_task_reads.inc(
                            reads="shared" if j else "own")
        return failed

    def _execute_traced(self, tasks: list[dict], sp
                        ) -> dict[str, Exception]:
        task = tasks[0]
        if task["type"] in ("shard_repair", "shard_migrate"):
            self._execute_shard_swap(task)
            return {}
        vol = VolumeInfo.from_dict(
            self.cm.call("get_volume", {"vid": task["vid"]})[0]["volume"]
        )
        t = cm.tactic(vol.codemode)
        bads = [int(x["unit_index"]) for x in tasks]

        # discover the blob population, bids and shard sizes, from a
        # surviving unit's chunk listing (one of the lost unit's AZ)
        bids = self._list_bids(vol, exclude=bads)
        dests = [self.nodes.get(x["dest_addr"]) for x in tasks]
        if not bids:
            return {}  # empty chunk: nothing to rebuild

        if t.is_msr() and _msr_repair_enabled():
            try:
                self._execute_msr(task, vol, t, bads[0],
                                  [b for b, _ in bids], dests[0])
                return {}
            except MsrFallback as e:
                # exactly-once degradation: the sub-shard path never
                # wrote anything (reads and verification both precede
                # writeback), so the conventional decode below rebuilds
                # from scratch
                metrics.repair_msr_fallbacks.inc(reason=e.reason)
                sp.set_tag("msr_fallback", e.reason)
        return self._execute_conventional(tasks, vol, t, bads, bids, dests,
                                          sp)

    def _execute_conventional(self, tasks: list[dict], vol: VolumeInfo,
                              t: cm.Tactic, bads: list[int],
                              bids: list[tuple[int, int]], dests: list, sp
                              ) -> dict[str, Exception]:
        # choose the read set: prefer the bad unit's local stripe peers
        # when an LRC local repair is possible (intra-AZ bandwidth). A
        # dark AZ (blackout) starves the local read set entirely — fall
        # back to the global stripe, which can also re-encode a lost
        # LOCAL PARITY through its stripe members (lrc_reconstruct_rows).
        # code_pos maps unit index -> index within the solving code's
        # shard space. Several units are of a plain RS volume
        # (`units_per_read`): the global stripe less every one of them.
        bad = bads[0]
        failed_azs = {vol.units[i].az for i in bads}
        local_idx, ln, lm = t.local_stripe(bad) if t.l else ([], 0, 0)
        sources = (["local", "global"] if local_idx and bad in local_idx
                   else ["global"])
        with tracelib.stage("survivor_reads"):
            for source in sources:
                if source == "local":
                    read_set = [i for i in local_idx if i != bad]
                    n_solve, total_code = ln, ln + lm
                    code_pos = {u: s for s, u in enumerate(local_idx)}
                    bad_subs = [code_pos[bad]]
                else:
                    read_set = [i for i in range(t.n + t.m) if i not in bads]
                    n_solve, total_code = t.n, t.n + t.m
                    code_pos = {u: u for u in read_set}
                    bad_subs = bads

                # per-bid survivor reads (one EXTRA when available: the
                # extra is reconstructed from the first n and compared,
                # the pre-writeback consistency check — a corrupted
                # download must not become the new truth). The ACTUALLY-
                # read survivor set selects the decode matrix, so per-
                # shard read failures mid-task are fine.
                want = min(n_solve + 1, len(read_set))
                # the groups, planned from the listing: a bid's step is
                # the width rung of its size; which of the rung's groups
                # it joins is decided by the survivors its reads return.
                # An MSR stripe's rows are cut into sub-shards, which
                # takes one size a step: those group by exact size.
                exact = t.is_msr() and bad_subs[0] < total_code
                by_key: dict[tuple, list] = defaultdict(list)
                # units found on a disk that does not serve (a lost disk
                # whose task is not among these) are skipped for the
                # rest of the read — survivors in index order past every
                # lost unit, the first n solve, the next one checks
                lost: set[int] = set()
                try:
                    for bid, size in bids:
                        subs, shards = self._read_survivors(
                            vol, read_set, code_pos, bid, need=n_solve,
                            want=want, failed_azs=failed_azs, lost=lost)
                        if any(len(shard) != size for shard in shards):
                            raise RuntimeError(
                                f"bid {bid}: survivors hold "
                                f"{sorted({len(x) for x in shards})} B, the "
                                f"chunk listing says {size}")
                        wide = size if exact else rs_kernel.rung_width(size)
                        by_key[(wide, tuple(subs))].append(
                            (bid, size, shards))
                except RuntimeError:
                    if source != sources[-1]:
                        continue  # local stripe unreadable: widen global
                    raise
                break
        sp.set_tag("source", source)

        units = [_Unit(task, sub, dest)
                 for task, sub, dest in zip(tasks, bad_subs, dests)]
        # the local stripe's unit indices, by position in the local code:
        # what a second derivation through the global code needs
        stripe = tuple(local_idx) if source == "local" else None
        with tracelib.stage("decode"):
            self._decode_groups(t, by_key, n_solve, total_code, units, exact,
                                stripe)
        for unit in units:
            if unit.error is None:
                try:
                    self._write_back(unit.task, unit.dest, unit.writes)
                except Exception as e:
                    unit.error = e
                    continue
                if tracelib.enabled():
                    metrics.repair_steps_per_task.observe(unit.steps)
                    metrics.repair_sources.inc(source=source)
        return {u.task["task_id"]: u.error for u in units
                if u.error is not None}

    def _write_back(self, task: dict, dest,
                    writes: list[tuple[int, bytes]]) -> None:
        with tracelib.stage("writeback"):
            for bid, shard in writes:
                dest.call(
                    "put_shard",
                    {"disk_id": task["dest_disk"],
                     "chunk_id": task["dest_chunk"], "bid": bid},
                    shard,
                )
                if tracelib.enabled():
                    metrics.repair_bytes_rebuilt.inc(len(shard))

    def _repair_rows(self, t, subs, n_solve, total_code, bad_sub,
                     stripe=None) -> tuple[np.ndarray, int, int | None, str]:
        """(the group's matrix, the lost unit's row in it, the row that
        checks it or None, how: `survivor` / `derived` / `none`) for the
        survivors `subs` as read. `survivor`: an extra survivor read is
        rebuilt beside the lost unit, to be compared with what was read.
        `derived`: a local stripe (`stripe`, its unit indices) read
        without one — it holds one local parity — gives the lost unit a
        second time through the global code, from the AZ's global units
        already read (`rs_kernel.lrc_checked_rows`), to be compared with
        the first. `none`: neither can be had; the lost unit's row twice,
        so the step still runs one of the programs `ready` built."""
        if stripe is not None and len(subs) == n_solve:
            rows = rs_kernel.lrc_checked_rows(
                t.n, t.n + t.m, tuple(map(tuple, t.ec_layout_by_az())),
                n_solve, stripe, tuple(subs), bad_sub)
            if rows is not None:
                return rows, 0, 1, "derived"
        solve_subs, wanted_out = solve_and_wanted(subs, n_solve, bad_sub)
        if bad_sub >= total_code:
            # global fallback for a LOCAL PARITY unit: its row lives
            # outside the global code space, so compose the local
            # encode row with the global solve
            rows = rs_kernel.lrc_reconstruct_rows(
                n_solve, total_code, t.ec_layout_by_az(),
                (t.n + t.m) // t.az_count, solve_subs, wanted_out
            )
        elif t.is_msr():
            # conventional decode of an MSR-coded stripe: k full
            # shards solved with the product-matrix generator over
            # the sub-shard space (this IS the CUBEFS_CODEC_MSR=0
            # control path and the helper-failure fallback)
            rows = rs_kernel.msr_reconstruct_rows(
                n_solve, total_code, t.d,
                tuple(solve_subs), tuple(wanted_out))
        else:
            rows = rs_kernel.reconstruct_rows(
                n_solve, total_code, solve_subs, wanted_out
            )
        if len(subs) > n_solve:
            return (rows, wanted_out.index(bad_sub),
                    wanted_out.index(subs[n_solve]), "survivor")
        return (np.repeat(rows, rs_kernel.REPAIR_ROWS, axis=0), 0, None,
                "none")

    def _step_array(self, shape: tuple) -> np.ndarray:
        """The array of one decode step, to be filled and zeroed by its
        caller: a C-contiguous view of a buffer the process keeps
        (`hostmem.KEPT`), so what it holds is an earlier step's
        survivors, an earlier PUT's rows or a result."""
        batch, self._came = hostmem.KEPT.take(shape)
        return batch

    def _decode_groups(self, t, by_key, n_solve, total_code,
                       units: list[_Unit], exact, stripe=None) -> None:
        """One step array per group and `batch_stripes` bids, and over
        it one device step a lost unit. A unit whose check fails keeps
        the error and takes no further step; the others go on. The
        array (`_step_array`) is this loop's from `_stack` until every
        unit's step over it has returned its rows to the host; nothing
        below keeps it."""
        for (wide, subs), group in by_key.items():
            plans = [(unit, *self._repair_rows(t, subs, n_solve, total_code,
                                               unit.sub, stripe))
                     for unit in units]
            for start in range(0, len(group), self.batch_stripes):
                live = [p for p in plans if p[0].error is None]
                if not live:
                    return
                chunk = group[start : start + self.batch_stripes]
                sizes = [size for _, size, _ in chunk]
                span = tracelib.start_span("stage:decode_step")
                with span:
                    batch = self._stack(t, wide, exact, n_solve, chunk,
                                        sizes, span)
                    span.set_tag("check", ",".join(sorted(
                        {p[4] for p in live})))
                    for unit, rows, out_pos, check, how in live:
                        try:
                            recovered = self._apply(t, rows, batch, sizes,
                                                    exact)
                            unit.steps += 1
                            self._check_and_cut(unit, recovered, chunk,
                                                out_pos, check, how, subs,
                                                n_solve)
                        except Exception as e:
                            unit.error = e
                    # the view goes before the next step asks for its
                    # array: while it lives its buffer is not handed out
                    del batch

    def _apply(self, t, rows, batch, sizes, exact) -> np.ndarray:
        """One decode step over a chunk's array."""
        if exact:
            b, n_solve, wide = batch.shape
            return self.codec.matrix_apply(
                rows, batch.reshape(b, n_solve * t.alpha, wide // t.alpha)
            ).reshape(b, -1, wide)
        return self.codec.matrix_apply(rows, batch, width=sizes)

    def _check_and_cut(self, unit: _Unit, recovered, chunk, out_pos,
                       check, how, subs, n_solve) -> None:
        with tracelib.stage("decode_verify"):
            for (bid, size, shards), rec in zip(chunk, recovered):
                # cut to the bid's own size first: what is checked and
                # written back never holds pad
                got = rec[out_pos, :size]
                if how == "survivor" and not np.array_equal(
                        rec[check, :size], np.frombuffer(
                            shards[n_solve], dtype=np.uint8)):
                    raise RuntimeError(
                        f"bid {bid}: reconstruction disagrees "
                        f"with extra survivor {subs[n_solve]} — "
                        f"refusing writeback (crc-conflict role)"
                    )
                if how == "derived" and not np.array_equal(
                        rec[check, :size], got):
                    raise RuntimeError(
                        f"bid {bid}: reconstruction disagrees with its "
                        f"derivation through the global code — refusing "
                        f"writeback (crc-conflict role)"
                    )
                unit.writes.append((bid, got.tobytes()))
        if tracelib.enabled():
            metrics.repair_checks.inc(len(chunk), how=how)

    def _stack(self, t, wide, exact, n_solve, chunk, sizes, span
               ) -> np.ndarray:
        """The array of one chunk of a group: survivors land once, each
        bid at its own size and zeros past it, in a shape `ready` built
        a program for — `rs_kernel.repair_step_shape`: the group's width
        rung, zero stripes up to a stripe rung — which the batcher
        passes whole. The array comes holding whatever its buffer last
        held (`_step_array`), so every byte of it is written here, a
        survivor's or a zero: that is the guarantee which keeps one
        task's shards out of the next task's step, not a habit."""
        if exact:
            # an MSR stripe, one exact size a step (the group's key):
            # the rows are cut into alpha sub-shards each, and the
            # batcher pads those
            if wide % t.alpha:
                raise RuntimeError(
                    f"shard size {wide} not divisible by "
                    f"alpha={t.alpha}: not MSR-encoded")
            shape = (len(chunk), n_solve, wide)
        else:
            rung_b, rung_s = rs_kernel.repair_step_shape(
                len(chunk), wide, self.batch_stripes)
            shape = (rung_b, n_solve, rung_s)
        with tracelib.stage("decode_stack"):
            batch = self._step_array(shape)
            # every zero first, in address order, then the survivors:
            # on the chip's host a (64, 12, 720896) array of fresh pages
            # fills in ~510 ms this way and ~580 ms with each bid's pad
            # zeroed after its rows (PERF.md section 6, PR 36)
            for b, size in enumerate(sizes):
                batch[b, :, size:] = 0
            batch[len(chunk):] = 0
            for b, (_, size, shards) in enumerate(chunk):
                for r, shard in enumerate(shards[:n_solve]):
                    batch[b, r, :size] = np.frombuffer(shard,
                                                       dtype=np.uint8)
        pad = batch.nbytes - n_solve * sum(sizes)
        span.set_tag("stage", "decode_step").set_tag("bids", len(chunk))
        span.set_tag("rung_b", shape[0]).set_tag("rung_s", shape[2])
        span.set_tag("array", self._came)
        span.set_tag("widths", len(set(sizes))).set_tag("pad_bytes", pad)
        if tracelib.enabled():
            metrics.repair_widths_per_step.observe(len(set(sizes)))
            metrics.repair_step_arrays.inc(result=self._came)
        return batch

    def _execute_msr(self, task: dict, vol: VolumeInfo, t: cm.Tactic,
                     bad: int, bids: list[int], dest) -> None:
        """Sub-shard repair of one failed MSR unit: pull a single
        beta-sized helper symbol per bid from each of d helpers
        (d*S/alpha bytes total vs the conventional k*S), solve the
        cached product-matrix repair rows, verify against an extra
        helper's symbol, THEN write back. Any miss before writeback
        raises MsrFallback — the conventional path owns the retry."""
        k, total, d, alpha = t.n, t.total, t.d, t.alpha
        with tracelib.stage("helper_election"):
            try:
                order = topology.pick_repair_helpers(vol.units, bad, d)
            except topology.NoAvailableDisks as e:
                raise MsrFallback("helpers_unavailable", str(e)) from None
            helpers = tuple(order[:d])
            extra = order[d] if len(order) > d else None
            coeff = rs_kernel.msr_helper_rows(k, total, d, bad)[0].tolist()
        failed_az = vol.units[bad].az

        # ONE read_subshard RPC per helper, batched over every bid; all
        # network reads land before any math or writeback, so a helper
        # dying mid-repair costs nothing but the fallback
        per_bid: dict[int, dict[int, bytes]] = {b: {} for b in bids}
        with tracelib.stage("beta_pulls"):
            for h in helpers + ((extra,) if extra is not None else ()):
                u = vol.units[h]
                try:
                    meta, raw = self.nodes.get(u.node_addr).call(
                        "read_subshard",
                        {"disk_id": u.disk_id, "chunk_id": u.chunk_id,
                         "bids": bids, "coeff": coeff})
                    sizes = meta["sizes"]
                    if len(sizes) != len(bids):
                        raise rpc.RpcError(409, f"{len(sizes)} sizes for "
                                                f"{len(bids)} bids")
                except rpc.RpcError as e:
                    if h == extra:
                        extra = None  # verification extra is best-effort
                        continue
                    raise MsrFallback(
                        "helper_read", f"helper unit {h}: {e}") from None
                scope = ("az_local" if u.az == failed_az else "cross_az")
                metrics.repair_bytes_pulled.inc(len(raw), scope=scope)
                off = 0
                for bid, beta in zip(bids, sizes):
                    per_bid[bid][h] = raw[off:off + beta]
                    off += beta

        # repair math + the extra-helper prediction are ONE fused device
        # step, so the "verify" stage covers both
        writes: list[tuple[int, bytes]] = []
        with tracelib.stage("verify"):
            rows = rs_kernel.msr_repair_rows(k, total, d, bad, helpers)
            if extra is not None:
                # verification rides the SAME device step: one stacked
                # (alpha+1, d) matrix predicts the extra helper's symbol
                # alongside the repair — a corrupt download breaks the
                # prediction before it can become the new truth
                rows = np.concatenate(
                    [rows, rs_kernel.msr_verify_rows(
                        k, total, d, bad, helpers, extra)])
            groups: dict[int, list[int]] = defaultdict(list)
            for bid in bids:
                sym = per_bid[bid]
                beta = len(sym[helpers[0]])
                if any(len(sym[h]) != beta for h in helpers):
                    raise MsrFallback(
                        "helper_read",
                        f"bid {bid}: helper symbol widths differ")
                groups[beta].append(bid)

            for beta, group in groups.items():
                for start in range(0, len(group), self.batch_stripes):
                    chunk = group[start:start + self.batch_stripes]
                    with tracelib.stage("decode_stack"):
                        batch = np.stack([
                            np.stack([np.frombuffer(per_bid[b][h],
                                                    dtype=np.uint8)
                                      for h in helpers])
                            for b in chunk
                        ])  # (B, d, beta)
                    out = self.codec.matrix_apply(rows, batch)
                    for i, b in enumerate(chunk):
                        if extra is not None:
                            expect = np.frombuffer(
                                per_bid[b].get(extra, b""), dtype=np.uint8)
                            if (expect.size != beta
                                    or not np.array_equal(out[i, alpha],
                                                          expect)):
                                raise MsrFallback(
                                    "verify",
                                    f"bid {b}: repair disagrees with extra "
                                    f"helper {extra}'s symbol")
                        writes.append(
                            (b, out[i, :alpha].reshape(-1).tobytes()))
        self._write_back(task, dest, writes)

    def _execute_shard_swap(self, task: dict) -> None:
        """shard_repair / shard_migrate execution (shard_disk_repairer
        role): swap one replica of a shard's raft group. Raft moves the
        data — the new member starts empty and the leader catches it up
        (appends or InstallSnapshot); this choreography is idempotent,
        so a lease expiry mid-way just re-runs it.

        Order matters: the NEW member must exist before survivors
        repoint at it, or the shrunk group could elect without it."""
        new_addrs = task["new_addrs"]
        dest = self.nodes.get(task["dest_addr"])
        dest.call("create_shard", {
            "shard_id": task["shard_id"], "start": task["start"],
            "end": task["end"], "peers": new_addrs})
        # re-issue the peer list on the destination too: a retried task
        # may find the shard pre-created with a stale set
        dest.call("update_shard_peers", {
            "shard_id": task["shard_id"], "peers": new_addrs})
        for addr in new_addrs:
            if addr == task["dest_addr"]:
                continue
            self.nodes.get(addr).call("update_shard_peers", {
                "shard_id": task["shard_id"], "peers": new_addrs})
        # the old replica (if it still answers) leaves the group; best
        # effort — a dead node is the usual reason we're here
        try:
            self.nodes.get(task["src_addr"]).call("update_shard_peers", {
                "shard_id": task["shard_id"],
                "peers": [a for a in new_addrs]})
        except Exception:
            pass

    def _list_bids(self, vol: VolumeInfo, exclude: list[int]
                   ) -> list[tuple[int, int]]:
        """(bid, shard size) of every blob of the volume, from the
        chunk listing of the first unit that gives one, the lost unit's
        AZ first: a repair inside an AZ asks nothing of another."""
        home = vol.units[exclude[0]].az
        for u in sorted(vol.units, key=lambda u: u.az != home):
            if u.index in exclude:
                continue
            try:
                meta, _ = self.nodes.get(u.node_addr).call(
                    "list_chunk", {"disk_id": u.disk_id, "chunk_id": u.chunk_id}
                )
                return [(b, size) for b, size, _ in meta["shards"]]
            except rpc.RpcError:
                continue
        raise RuntimeError(f"vid {vol.vid}: no unit listable")

    def _read_survivors(
        self, vol: VolumeInfo, read_set: list[int], code_pos: dict[int, int],
        bid: int, need: int, want: int | None = None,
        *, failed_azs: set[str], lost: set[int],
    ) -> tuple[list[int], list[bytes]]:
        """Read up to `want` survivors for bid (at least `need`, which is
        fatal to miss; the extras enable pre-writeback verification).
        Returns (code-space indices actually read, payloads), ascending.
        A unit whose disk answers 503 (broken, not serving) is added to
        `lost` (the caller's set, one a read), and the units in `lost`
        are not asked."""
        want = want or need
        subs: list[int] = []
        shards: list[bytes] = []
        for idx in read_set:
            if len(shards) == want:
                break
            if idx in lost:
                continue
            u = vol.units[idx]
            try:
                _, payload = self.nodes.get(u.node_addr).call(
                    "get_shard",
                    {"disk_id": u.disk_id, "chunk_id": u.chunk_id, "bid": bid},
                )
            except rpc.RpcError as e:
                if e.code == 503:
                    lost.add(idx)
                continue
            metrics.repair_bytes_pulled.inc(
                len(payload),
                scope="az_local" if u.az in failed_azs else "cross_az")
            subs.append(code_pos[idx])
            shards.append(payload)
        if len(shards) < need:
            raise RuntimeError(f"bid {bid}: only {len(shards)}/{need} survivors")
        order = np.argsort(subs)
        return [subs[i] for i in order], [shards[i] for i in order]
