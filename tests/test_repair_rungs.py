"""Repair by width rung (PR 36): the repair worker groups a task's bids
by the width rung of their shard size, so a volume of objects of any
sizes is rebuilt in a handful of device steps, at shapes whose programs
`RepairWorker.ready` built before the first task — and every rebuilt
shard is cut to its bid's own size before it is checked and written
back. CPU, small sizes (they reach few rungs), seeded; the plain
reference is cellbench/reference.py (table GF(2^8) + zlib)."""

import numpy as np
import pytest

from cellbench import reference
from cellbench.deployment import CompileClock
from cubefs_tpu.blob.access import AccessConfig, AccessHandler
from cubefs_tpu.blob.proxy import ProxyAllocator
from cubefs_tpu.blob.worker import RepairWorker, repair_shard_sizes
from cubefs_tpu.codec import batcher as batcher_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec.batcher import BatchCodec, admit
from cubefs_tpu.codec.engine import get_engine
from cubefs_tpu.ops import gf256, pallas_gf, rs_kernel
from cubefs_tpu.utils import metrics, rpc
from cubefs_tpu.utils import trace as tracelib
from test_blob_e2e import Cluster
from test_put_stripe_rows import reference_stripe

TILE = pallas_gf.DEFAULT_TILE
NUMPY = get_engine("numpy")
BLOB = 1 << 20  # shards up to 11 tiles of EC3P3, 3 of EC12P4
BIDS = 56  # one volume's worth and under ProxyAllocator.VOLUME_REUSE
MODES = [cmode.CodeMode.EC3P3, cmode.CodeMode.EC6P6, cmode.CodeMode.EC12P4,
         cmode.CodeMode.EC4P4L2, cmode.CodeMode.EC4P4MSR]


class Seeing(BatchCodec):
    """Keeps what every engine call was handed."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.seen: list[tuple[np.ndarray, np.ndarray]] = []

    def _engine_call(self, key, coeff, arr):
        self.seen.append((coeff, arr))
        return super()._engine_call(key, coeff, arr)


def fleet(tmp_path, engine="auto", blob=BLOB, **worker_kw) -> Cluster:
    """A cluster whose PUTs share volumes (the proxy allocator, as a
    deployment runs it) and whose step arrays last held 0xFF."""
    c = Cluster(tmp_path, n_nodes=6, disks_per_node=4)
    c.cm.allow_colocated_units = True
    c.access = AccessHandler(
        c.cm_client, c.pool, AccessConfig(blob_size=blob),
        repair_queue=c.repair_q, delete_queue=c.delete_q,
        proxy_client=rpc.Client(ProxyAllocator(c.cm_client)))
    c.worker = RepairWorker(rpc.Client(c.sched), c.cm_client, c.pool,
                            engine=engine, **worker_kw)
    c.worker._step_array = lambda shape: np.full(shape, 0xFF, dtype=np.uint8)
    return c


def fill(c: Cluster, mode, seed, count=BIDS, lo=1, hi=BLOB) -> list:
    """`count` one-blob objects of seeded log-uniform sizes in one
    volume: [(payload, Location)]."""
    r = np.random.default_rng([int(mode), seed])
    out = []
    for _ in range(count):
        size = int(np.exp(r.uniform(np.log(lo), np.log(hi))))
        data = r.integers(0, 256, size, dtype=np.uint8).tobytes()
        out.append((data, c.access.put(data, codemode=mode)))
    assert len({loc.slices[0].vid for _, loc in out}) == 1
    return out


def lose(c: Cluster, vid: int, index: int, report=True):
    """Break the disk under unit `index`; with `report` the scheduler
    queues its repair, without it the unit just refuses reads."""
    unit = c.cm.get_volume(vid).units[index]
    c.node_of(unit.node_addr).break_disk(unit.disk_id)
    if report:
        assert c.sched.mark_disk_broken(unit.disk_id) == 1
    return unit


def hist(h, labels=()) -> tuple[int, float]:
    """(count, sum) of one series of a histogram."""
    got = dict(h.samples()).get(labels, {"count": 0, "sum": 0.0})
    return got["count"], got["sum"]


def hist_of_apply() -> tuple[int, float]:
    """(steps, stripes) of the batcher's apply steps so far."""
    return hist(metrics.codec_batch_stripes, ("apply",))


def rebuilt(c: Cluster, vid: int, index: int, bid: int):
    unit = c.cm.get_volume(vid).units[index]
    meta, got = c.pool.get(unit.node_addr).call(
        "get_shard", {"disk_id": unit.disk_id, "chunk_id": unit.chunk_id,
                      "bid": bid})
    return unit, meta, got


# ---------------- the rebuilt shards ----------------

@pytest.mark.parametrize("second", [False, True], ids=["one-loss", "two-loss"])
@pytest.mark.parametrize("which", ["data", "parity", "last"])
@pytest.mark.parametrize("mode", MODES, ids=[m.name for m in MODES])
def test_a_volume_of_any_sizes_is_rebuilt_as_the_reference_stores_it(
        tmp_path, monkeypatch, mode, which, second):
    """56 bids of seeded sizes, one unit lost (and a second one refusing
    reads: two-loss stripes): every rebuilt shard is the reference
    stripe's row, byte for byte and in length, its stored CRC zlib's;
    and the task took at most one decode step for each width rung and
    survivor set present."""
    monkeypatch.setenv("CUBEFS_CODEC_MSR", "0")  # MSR: conventional path
    t = cmode.tactic(mode)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=1)
    vid = objects[0][1].slices[0].vid
    bad = {"data": 1, "parity": t.n + 1, "last": t.total - 1}[which]
    if second:
        lose(c, vid, (bad + 2) % t.total, report=False)
    old = lose(c, vid, bad)
    tasks0, steps0 = hist(metrics.repair_steps_per_task)
    steps_w0, widths0 = hist(metrics.repair_widths_per_step)
    c.drain_worker()
    assert c.worker.completed == 1 and c.worker.failed == 0

    sizes = set()
    for data, loc in objects:
        want = reference_stripe(data, t)[bad]
        unit, meta, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
        assert unit.disk_id != old.disk_id
        assert len(got) == want.shape[0] and got == want.tobytes()
        assert meta["crc"] == reference.crc32(got)
        sizes.add(len(got))
    tasks, steps = hist(metrics.repair_steps_per_task)
    assert tasks - tasks0 == 1
    # an MSR stripe's sub-shard reshape takes one size a step; every
    # other codemode one width rung. One survivor set a task here: the
    # refusing unit refuses every bid.
    groups = (len(sizes) if t.is_msr()
              else len({rs_kernel.rung_width(s) for s in sizes}))
    assert 1 <= steps - steps0 <= groups
    steps_w, widths = hist(metrics.repair_widths_per_step)
    assert steps_w - steps_w0 == steps - steps0
    assert widths - widths0 == len(sizes)  # each size in one step
    for data, loc in objects[::9]:
        assert c.access.get(loc) == data


def test_a_survivor_wrong_in_its_last_byte_refuses_writeback(tmp_path, rng):
    """CRC-consistent and wrong only in the last byte of a shard that
    ends short of its rung: the check compares the bid's whole size, the
    task fails and nothing is written back."""
    mode, t = cmode.CodeMode.EC6P6, cmode.tactic(cmode.CodeMode.EC6P6)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=2, count=12)
    loc = objects[5][1]
    vid, bid = loc.slices[0].vid, loc.slices[0].min_bid
    u = c.cm.get_volume(vid).units[3]
    node = c.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    assert len(good) % TILE  # ends inside its rung
    node.put_shard(u.disk_id, u.chunk_id, bid,
                   good[:-1] + bytes([good[-1] ^ 1]))
    lose(c, vid, 0)
    assert c.worker.run_once() and c.worker.failed == 1
    task = next(iter(c.sched.tasks.values()))
    assert "disagrees" in task.get("last_error", "")
    with pytest.raises(rpc.RpcError, match="no such chunk"):
        c.pool.get(task["dest_addr"]).call(
            "list_chunk", {"disk_id": task["dest_disk"],
                           "chunk_id": task["dest_chunk"]})


def test_a_survivor_of_another_length_than_the_listing_fails_the_task(
        tmp_path, rng):
    mode = cmode.CodeMode.EC6P6
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=3, count=4)
    loc = objects[1][1]
    vid, bid = loc.slices[0].vid, loc.slices[0].min_bid
    u = c.cm.get_volume(vid).units[4]
    node = c.node_of(u.node_addr)
    good, _ = node.get_shard(u.disk_id, u.chunk_id, bid)
    node.put_shard(u.disk_id, u.chunk_id, bid, good + b"\0")
    lose(c, vid, 0)
    assert c.worker.run_once() and c.worker.failed == 1
    task = next(iter(c.sched.tasks.values()))
    assert "chunk listing says" in task.get("last_error", "")


# ---------------- the steps ----------------

def test_a_volume_of_one_size_is_the_one_step_it_was(tmp_path, rng):
    """64 bids of one size: one (64, n, rung) array of two rows' matrix,
    handed to the engine as the worker built it."""
    mode, t = cmode.CodeMode.EC12P4, cmode.tactic(cmode.CodeMode.EC12P4)
    c = fleet(tmp_path)
    seeing = Seeing()
    c.worker.codec = admit("numpy-xor", seeing)
    made = []
    c.worker._step_array = lambda shape: made.append(
        np.full(shape, 0xFF, dtype=np.uint8)) or made[-1]
    size = 12 * 40_000 + 7
    for _ in range(64):
        loc = c.access.put(rng.integers(0, 256, size, dtype=np.uint8
                                        ).tobytes(), codemode=mode)
    _, stripes0 = hist_of_apply()
    payload0 = metrics.codec_step_bytes.value(op="apply", kind="payload")
    pad0 = metrics.codec_step_bytes.value(op="apply", kind="pad")
    lose(c, loc.slices[0].vid, 4)
    c.drain_worker()
    assert c.worker.completed == 1
    shard = -(-size // 12)
    assert [(m.shape, a.shape) for m, a in seeing.seen] == [
        ((2, 12), (64, 12, rs_kernel.rung_width(shard)))]
    assert seeing.seen[0][1] is made[0] and len(made) == 1
    steps, stripes = hist_of_apply()
    assert stripes - stripes0 == 64
    assert metrics.codec_step_bytes.value(
        op="apply", kind="payload") - payload0 == 64 * 12 * shard
    assert metrics.codec_step_bytes.value(
        op="apply", kind="pad") - pad0 == made[0].nbytes - 64 * 12 * shard


def test_a_mixed_volumes_steps_carry_their_bids_at_their_own_sizes(
        tmp_path, rng):
    """What the batcher is handed and what it accounts: each step one
    array at (stripe rung, n, width rung), live stripes first, each at
    its own size with zeros past it, zero stripes after; payload bytes
    are the bids' own, the rest is pad; the step's span says so."""
    mode, t = cmode.CodeMode.EC6P6, cmode.tactic(cmode.CodeMode.EC6P6)
    c = fleet(tmp_path)
    seeing = Seeing()
    c.worker.codec = admit("numpy-xor", seeing)
    objects = fill(c, mode, seed=4, count=40, lo=50_000)
    sizes = sorted(reference.shard_size(len(d), t.n, t.min_shard_size)
                   for d, _ in objects)
    payload0 = metrics.codec_step_bytes.value(op="apply", kind="payload")
    pad0 = metrics.codec_step_bytes.value(op="apply", kind="pad")
    steps0, stripes0 = hist_of_apply()
    tracelib.reset_collector()
    lose(c, objects[0][1].slices[0].vid, 7)
    c.drain_worker()
    assert c.worker.completed == 1
    by_rung: dict[int, list[int]] = {}
    for s in sizes:
        by_rung.setdefault(rs_kernel.rung_width(s), []).append(s)
    assert len(by_rung) >= 4
    shapes = sorted(a.shape for _, a in seeing.seen)
    assert shapes == sorted(
        (rs_kernel.repair_step_shape(len(g), w, 64)[0], t.n, w)
        for w, g in by_rung.items())
    for coeff, arr in seeing.seen:
        assert coeff.shape == (rs_kernel.REPAIR_ROWS, t.n)
        live = sorted(by_rung[arr.shape[2]])
        ends = sorted(int(np.flatnonzero(arr[b].any(axis=0)).max()) + 1
                      for b in range(len(live)))
        assert all(e <= s for e, s in zip(ends, live))
        for b in range(len(live)):
            assert not arr[b, :, max(live):].any()
        assert not arr[len(live):].any()  # the zero stripes
    payload = t.n * sum(sizes)
    assert metrics.codec_step_bytes.value(
        op="apply", kind="payload") - payload0 == payload
    assert metrics.codec_step_bytes.value(
        op="apply", kind="pad") - pad0 == sum(
            a.nbytes for _, a in seeing.seen) - payload
    steps, stripes = hist_of_apply()
    assert steps - steps0 == len(by_rung) and stripes - stripes0 == 40
    spans = [s for s in tracelib.finished_spans()
             if s["op"] == "stage:decode_step"]
    assert len(spans) == len(by_rung)
    for s in spans:
        group = by_rung[s["tags"]["rung_s"]]
        assert s["tags"]["bids"] == len(group)
        assert s["tags"]["widths"] == len(set(group))
        assert s["tags"]["pad_bytes"] == (
            s["tags"]["rung_b"] * t.n * s["tags"]["rung_s"]
            - t.n * sum(group))


def test_a_refused_read_mid_task_moves_those_bids_to_another_group(
        tmp_path, rng):
    """A unit that starts refusing after some bids were read: the bids
    read before and after have different survivor sets, so a rung holds
    two groups, and every shard is still the reference's."""
    mode, t = cmode.CodeMode.EC6P6, cmode.tactic(cmode.CodeMode.EC6P6)
    c = fleet(tmp_path)
    objects = fill(c, mode, seed=5, count=24, hi=150_000)
    vid = objects[0][1].slices[0].vid
    flaky = c.cm.get_volume(vid).units[2]
    client = c.pool.get(flaky.node_addr)
    real, reads = client.call, []

    def call(method, args=None, body=b"", timeout=30.0):
        if (method == "get_shard" and args["disk_id"] == flaky.disk_id):
            reads.append(args["bid"])
            if len(reads) > 10:
                raise rpc.RpcError(503, "disk stopped serving")
        return real(method, args, body, timeout)

    client.call = call
    _, steps0 = hist(metrics.repair_steps_per_task)
    lose(c, vid, 0)
    c.drain_worker()
    client.call = real
    assert c.worker.completed == 1 and c.worker.failed == 0
    _, steps = hist(metrics.repair_steps_per_task)
    rungs = {rs_kernel.rung_width(reference.shard_size(
        len(d), t.n, t.min_shard_size)) for d, _ in objects}
    assert len(rungs) < steps - steps0 <= 2 * len(rungs)
    for data, loc in objects:
        _, meta, got = rebuilt(c, vid, 0, loc.slices[0].min_bid)
        assert got == reference_stripe(data, t)[0].tobytes()


# ---------------- ready: no program after it ----------------

def test_after_ready_a_task_of_any_sizes_builds_no_program(
        tmp_path, rng, monkeypatch):
    """`RepairWorker.ready(largest object)` from the cluster's policies,
    then tasks over sizes never seen, any lost unit, a second unit
    refusing reads and none left to check with: no codec program is
    built and JAX compiles nothing."""
    monkeypatch.setattr(batcher_mod.DEFAULT, "dp_enabled", False)
    blob = 150_000  # shards of 2048..15000 B of EC10P4: one width rung
    policies = [cmode.Policy("EC10P4", 0, 1 << 62)]
    t = cmode.tactic(cmode.CodeMode.EC10P4)
    c = fleet(tmp_path, engine="tpu", blob=blob, batch_stripes=16)
    c.access.cfg.policies = policies
    built = lambda: sum(v for _, v in metrics.codec_programs.samples())
    before = built()
    steps = c.worker.ready(3 * blob, policies, blob)
    # stripe rungs 8, 12 and 16 at the one width rung, two rows by ten
    assert steps == 3 and built() - before <= 3
    after = built()
    assert c.worker.ready(3 * blob, policies, blob) == 3 and built() == after

    objects = fill(c, cmode.CodeMode.EC10P4, seed=6, count=20, hi=blob)
    vid = objects[0][1].slices[0].vid
    clock = CompileClock()
    before = built()
    try:
        # unit 12 lost: survivors 0..10, the last one checks; then with
        # units 0, 1 and 3 refusing too: ten left, none to check with
        for bad, refusing in ((12, ()), (2, (0, 1, 3))):
            for idx in refusing:
                lose(c, vid, idx, report=False)
            done = c.worker.completed
            lose(c, vid, bad)
            c.drain_worker()
            assert c.worker.completed == done + 1 and c.worker.failed == 0
            for data, loc in objects:
                _, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
                assert got == reference_stripe(data, t)[bad].tobytes()
        assert built() == before and clock.mark()["compiles"] == 0
    finally:
        clock.close()


# ---------------- the enumeration ----------------

CONFIG = (("EC3P3", 1, 256 << 10), ("EC6P6", (256 << 10) + 1, 4 << 20),
          ("EC12P4", (4 << 20) + 1, 16 << 20))  # repair-tpu-1az-randsize


def test_the_three_codemodes_repair_programs_number_under_a_hundred():
    count = 0
    for mode, lo, hi in CONFIG:
        lo_s, hi_s = repair_shard_sizes(cmode.tactic(mode), lo, hi, 8 << 20)
        count += len(rs_kernel.repair_steps(lo_s, hi_s, 64))
    assert count == 90


@pytest.mark.parametrize("mode,lo,hi", CONFIG)
@pytest.mark.parametrize("batch_stripes", [64, 50, 8, 5])
def test_the_enumeration_holds_every_step_the_worker_can_ask(
        mode, lo, hi, batch_stripes):
    """Finite, sorted, and closed over seeded groups; and every shape in
    it is one the batcher passes whole (a rung of `step_shape`)."""
    t = cmode.tactic(mode)
    n = t.n
    lo_s, hi_s = repair_shard_sizes(t, lo, hi, 8 << 20)
    shapes = rs_kernel.repair_steps(lo_s, hi_s, batch_stripes)
    assert shapes == sorted(set(shapes), key=lambda bs: (bs[1], bs[0]))
    assert len(shapes) <= 5 * 11
    for b, s in shapes:
        assert rs_kernel.step_shape(n, b, s) == (b, s)
    rng = np.random.default_rng([n, batch_stripes])
    for _ in range(400):
        size = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        shard = max(-(-min(size, 8 << 20) // n), 2048)
        b = int(rng.integers(1, batch_stripes + 1))
        shape = rs_kernel.repair_step_shape(b, shard, batch_stripes)
        assert shape in shapes and shape[0] >= b and shape[1] >= shard
        if b > rs_kernel.STEP_BATCH:
            assert shape[0] < 2 * b or shape[0] == shapes[-1][0]


# ---------------- per-stripe payload widths in the batcher ----------------

def test_one_submission_may_carry_stripes_of_different_widths(rng):
    seeing = Seeing()
    eng = admit("numpy", seeing)
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:2]
    sizes = [5_000, 1, 32_768, 17]
    wide = np.zeros((8, 6, 32_768), dtype=np.uint8)
    for b, s in enumerate(sizes):
        wide[b, :, :s] = rng.integers(0, 256, (6, s), dtype=np.uint8)
    payload0 = metrics.codec_step_bytes.value(op="apply", kind="payload")
    pad0 = metrics.codec_step_bytes.value(op="apply", kind="pad")
    out = eng.matrix_apply(rows, wide, width=sizes)
    assert seeing.seen[0][1] is wide  # rung-shaped: whole, uncopied
    assert out.shape == (4, 2, 32_768)
    for b, s in enumerate(sizes):
        assert np.array_equal(out[b, :, :s],
                              NUMPY.matrix_apply(rows, wide[b, :, :s]))
    assert metrics.codec_step_bytes.value(
        op="apply", kind="payload") - payload0 == 6 * sum(sizes)
    assert metrics.codec_step_bytes.value(
        op="apply", kind="pad") - pad0 == wide.nbytes - 6 * sum(sizes)
    # five live stripes of an eight-stripe array are no rung: gathered
    out = eng.matrix_apply(rows, wide[:5], width=sizes)
    assert seeing.seen[1][1].shape == (8, 6, 32_768)
    assert not np.shares_memory(seeing.seen[1][1], wide)
    assert out.shape == (4, 2, 32_768)
    assert np.array_equal(out[2], NUMPY.matrix_apply(rows, wide[2]))


@pytest.mark.parametrize("width", [[], [1, 2, 3], [40_000], [-1], 40_000])
def test_payload_widths_that_do_not_fit_the_array_are_refused(width):
    eng = admit("numpy", BatchCodec())
    rows = np.ones((2, 6), dtype=np.uint8)
    with pytest.raises(ValueError, match="do not fit"):
        eng.matrix_apply(rows, np.zeros((2, 6, 32_768), dtype=np.uint8),
                         width=width)
