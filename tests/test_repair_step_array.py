"""A repair step's array is kept: every decode step's array is a
view of a buffer the process keeps (`hostmem.KEPT`) — made by a step
that fits no buffer nothing holds, handed to the next step that fits it
once the last view is gone, kept while the worker is idle — and written
in full by `_stack`, so what the buffer last held reaches neither the
device's live columns nor a stored shard. What a task asks of the nodes,
the scheduler and the engine is the parent's, call for call. CPU, small
sizes, seeded; the plain reference is cellbench/reference.py through
`reference_stripe`."""

import time
import weakref

import numpy as np
import pytest

from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec.batcher import BatchCodec, admit
from cubefs_tpu.ops import rs_kernel
from cubefs_tpu.utils import hostmem, metrics
from cubefs_tpu.utils import trace as tracelib
from test_put_stripe_rows import holders, reference_stripe
from test_put_stripe_rows import scribble as scribble_kept
from test_repair_lease import recorded, sched_calls, shard_sizes
from test_repair_rungs import fill, fleet, lose, rebuilt

EC12P4, EC6P6, EC3P3 = (cmode.CodeMode.EC12P4, cmode.CodeMode.EC6P6,
                        cmode.CodeMode.EC3P3)
ENGINES = ["tpu", "cpp", "numpy-xor", "numpy"]


class Copying(BatchCodec):
    """Keeps a copy of what every engine call was handed, and the id of
    the array itself: a step's array is a view of a buffer that is
    filled again, so what it held is gone by the time a test looks —
    and a reference to it would keep the buffer from the next step."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.seen: list[tuple[np.ndarray, np.ndarray, int]] = []

    def _engine_call(self, key, coeff, arr):
        self.seen.append((coeff.copy(), arr.copy(), id(arr)))
        return super()._engine_call(key, coeff, arr)


def keeper(tmp_path, engine="auto", copying=None):
    """`fleet`, but the worker takes its step arrays as it ships: the
    fleet's own hands out a fresh 0xFF-filled one a step."""
    c = fleet(tmp_path, engine=engine)
    del c.worker._step_array
    if copying is not None:
        c.worker.codec = admit("numpy-xor" if engine == "auto" else engine,
                               copying)
    return c


def new_volume(c) -> None:
    """The next PUT of any codemode opens a volume of its own."""
    c.access.proxy._target._vols.clear()


def fill_one_size(c, mode, seed, count, size) -> list:
    r = np.random.default_rng([int(mode), seed, size])
    out = []
    for _ in range(count):
        data = r.integers(0, 256, size, dtype=np.uint8).tobytes()
        out.append((data, c.access.put(data, codemode=mode)))
    assert len({loc.slices[0].vid for _, loc in out}) == 1
    return out


def scribble(c) -> None:
    scribble_kept(hostmem.KEPT)


def held_buffers() -> int:
    """Kept buffers something holds, once the garbage is collected."""
    kept = hostmem.KEPT
    addresses = [buf.ctypes.data for buf in kept._kept]
    return sum(holders(kept, a) > 0 for a in addresses)


def drain_scribbling(c, max_leases=50) -> int:
    """Run the backlog to its end with every kept buffer overwritten
    with 0xFF after every lease; the number of leases run."""
    for ran in range(max_leases):
        if not c.worker.run_once():
            return ran
        assert hostmem.KEPT._kept  # kept after the lease
        scribble(c)
    raise AssertionError("worker did not drain")


def assert_rebuilt(c, objects, mode, bads) -> None:
    """Every shard of the units `bads`, where they now are: the
    reference stripe's row, byte for byte and in length — no pad."""
    t = cmode.tactic(mode)
    vid = objects[0][1].slices[0].vid
    for bad in bads:
        for data, loc in objects:
            want = reference_stripe(data, t)[bad]
            _, _, got = rebuilt(c, vid, bad, loc.slices[0].min_bid)
            assert len(got) == want.shape[0] and got == want.tobytes()


def assert_written_in_full(arr, sizes) -> None:
    """A step's array as the engine got it, after a buffer that held
    0xFF throughout: live stripes first, each ends at one of the
    chunk's sizes, zeros past it and in every stripe after."""
    live = [b for b in range(arr.shape[0]) if arr[b].any()]
    assert live == list(range(len(sizes)))
    ends = [int(np.flatnonzero(arr[b].any(axis=0)).max()) + 1 for b in live]
    assert sorted(ends) == sorted(sizes)


def arrays() -> tuple[float, float]:
    """(reused, fresh) of `cubefs_repair_step_arrays_total` so far."""
    return (metrics.repair_step_arrays.value(result="reused"),
            metrics.repair_step_arrays.value(result="fresh"))


def arrays_since(before) -> tuple[float, float]:
    return tuple(a - b for a, b in zip(arrays(), before))


def step_tags() -> list[dict]:
    return [s["tags"] for s in tracelib.finished_spans()
            if s["op"] == "stage:decode_step"]


def watch_buffers(monkeypatch, worker) -> list[tuple]:
    """[(weak reference to a buffer the worker made, how many of the
    earlier ones were alive when numpy was asked for it)], in order."""
    made: list[tuple] = []
    inside = []
    real_empty, real_take = np.empty, worker._step_array

    def empty(*args, **kw):
        alive = sum(ref() is not None for ref, _ in made)
        arr = real_empty(*args, **kw)
        if inside:
            made.append((weakref.ref(arr), alive))
        return arr

    def take(shape):
        inside.append(shape)
        try:
            return real_take(shape)
        finally:
            inside.pop()

    monkeypatch.setattr(np, "empty", empty)
    worker._step_array = take
    return made


# ---------------- (a) what the buffer held reaches nothing ----------------

@pytest.mark.parametrize("engine", ENGINES)
def test_a_backlog_of_one_size_volumes_is_rebuilt_over_one_scribbled_buffer(
        tmp_path, engine):
    """Three EC12P4 volumes of one object size, one moving unit each,
    the buffer overwritten between tasks: every shard bit-identical and
    exactly its size, every step the same view, its pad zero when the
    engine got it — on every engine leg the tests can run."""
    copying = Copying()
    c = keeper(tmp_path, engine=engine, copying=copying)
    size, count = 12 * 30_000 + 5, 10
    shard = -(-size // 12)
    volumes = []
    for seed, bad in ((1, 0), (2, 7), (3, 13)):
        new_volume(c)
        objects = fill_one_size(c, EC12P4, seed, count, size)
        c.sched.manual_migrate(objects[0][1].slices[0].vid, bad)
        volumes.append((objects, bad))
    before = arrays()
    assert drain_scribbling(c) == 3
    assert c.worker.completed == 3 and c.worker.failed == 0
    assert held_buffers() == 0  # the backlog ended: nothing holds one
    for objects, bad in volumes:
        assert_rebuilt(c, objects, EC12P4, [bad])
    rung_b, rung_s = rs_kernel.repair_step_shape(
        count, rs_kernel.rung_width(shard), 64)
    assert [a.shape for _, a, _ in copying.seen] == [(rung_b, 12, rung_s)] * 3
    for _, held, _ in copying.seen:
        assert_written_in_full(held, [shard] * count)
    assert arrays_since(before) == (2, 1)


@pytest.mark.parametrize("order", [(EC3P3, EC6P6, EC12P4, EC3P3),
                                   (EC12P4, EC6P6, EC3P3, EC12P4)],
                         ids=["growing", "shrinking"])
def test_mixed_size_volumes_share_the_buffer_larger_after_smaller_and_back(
        tmp_path, order):
    """Volumes of seeded log-uniform sizes over the three RS codemodes
    in one backlog: steps of many shapes follow one another over kept
    buffers that held 0xFF — a larger step makes one, a smaller one is
    a prefix view — and every shard comes out as the reference's."""
    copying = Copying()
    c = keeper(tmp_path, copying=copying)
    volumes = []
    for seed, mode in enumerate(order):
        new_volume(c)
        objects = fill(c, mode, seed=30 + seed, count=24, lo=30_000)
        bad = (1, cmode.tactic(mode).n + 1)[seed % 2]
        c.sched.manual_migrate(objects[0][1].slices[0].vid, bad)
        volumes.append((objects, mode, bad))
    before = arrays()
    assert drain_scribbling(c) == len(order)
    assert c.worker.completed == len(order) and c.worker.failed == 0
    for objects, mode, bad in volumes:
        assert_rebuilt(c, objects, mode, [bad])
        assert c.access.get(objects[3][1]) == objects[3][0]
    # each step's array: its group's bids at their own sizes, the rest 0
    steps = iter(copying.seen)
    nbytes = []
    for objects, mode, bad in volumes:
        sizes = shard_sizes(c, objects[0][1].slices[0].vid, 0 if bad else 1)
        by_rung: dict[int, list[int]] = {}
        for s in sizes.values():
            by_rung.setdefault(rs_kernel.rung_width(s), []).append(s)
        for wide, group in by_rung.items():
            _, held, _ = next(steps)
            assert held.shape == (
                rs_kernel.repair_step_shape(len(group), wide, 64)[0],
                cmode.tactic(mode).n, wide)
            assert_written_in_full(held, group)
            nbytes.append(held.nbytes)
    assert next(steps, None) is None
    # `fresh` exactly where a step outgrew every step before it
    grew = sum(n > max(nbytes[:i], default=0) for i, n in enumerate(nbytes))
    assert 1 <= grew < len(nbytes)
    assert arrays_since(before) == (len(nbytes) - grew, grew)
    assert any(a > b for a, b in zip(nbytes, nbytes[1:]))  # and shrank


@pytest.mark.parametrize("mode,bads", [(EC12P4, (1, 13)), (EC6P6, (0, 4, 9))],
                         ids=["EC12P4-two", "EC6P6-three"])
def test_the_units_of_a_lease_are_decoded_over_one_view(tmp_path, mode,
                                                        bads):
    t = cmode.tactic(mode)
    copying = Copying()
    c = keeper(tmp_path, copying=copying)
    objects = fill(c, mode, seed=41, count=20, lo=5_000)
    vid = objects[0][1].slices[0].vid
    for bad in bads:
        lose(c, vid, bad)
    scribbled = []
    real = c.worker._step_array
    c.worker._step_array = lambda shape: scribbled.append(
        scribble(c)) or real(shape)  # 0xFF before every step, too
    assert c.worker.run_once() and not c.worker.run_once()
    assert c.worker.completed == len(bads) and c.worker.failed == 0
    assert_rebuilt(c, objects, mode, bads)
    k = len(bads)
    assert len(copying.seen) == k * len(scribbled)
    for i in range(0, len(copying.seen), k):
        rows, held, arr = copying.seen[i]
        for rows_j, held_j, arr_j in copying.seen[i + 1:i + k]:
            assert arr_j == arr  # one array, not one a unit
            assert np.array_equal(held_j, held)  # nothing wrote between
            assert rows_j.tobytes() != rows.tobytes()
        assert rows.shape == (rs_kernel.REPAIR_ROWS, t.n)


@pytest.mark.parametrize("engine", ENGINES)
def test_a_steps_result_is_its_own_memory_on_every_engine_leg(tmp_path,
                                                              engine):
    """What `_apply` returns shares nothing with the array it was
    handed, and no kept buffer has a holder once the lease has
    returned: neither the batcher nor an engine keeps a submitted step
    array (a CPU `jnp.asarray` may alias host memory)."""
    c = keeper(tmp_path, engine=engine)
    objects = fill(c, EC6P6, seed=43, count=12, lo=20_000)
    vid = objects[0][1].slices[0].vid
    lose(c, vid, 2)
    lose(c, vid, 8)
    real = c.worker._apply
    checked = []

    def apply(t, rows, batch, sizes, exact):
        out = real(t, rows, batch, sizes, exact)
        assert not np.shares_memory(out, batch.base)
        want, held = np.array(out), batch.copy()
        batch[...] = 0xFF  # the next fill, early
        assert np.array_equal(out, want)
        batch[...] = held
        checked.append(out.shape)
        return out

    c.worker._apply = apply
    assert c.worker.run_once() and c.worker.completed == 2
    assert len(checked) >= 2
    # jax on the CPU shares a host array's memory and drops it with
    # its own garbage (`held_buffers` collects it); on the device it
    # copies
    assert hostmem.KEPT._kept and held_buffers() == 0
    assert_rebuilt(c, objects, EC6P6, [2, 8])


# ---------------- (b) the counter, the tag, one array alive ----------------

def test_first_step_fresh_then_reused_and_a_larger_step_fresh_once(
        tmp_path, monkeypatch):
    c = keeper(tmp_path)
    made = watch_buffers(monkeypatch, c.worker)
    small = 6 * 20_000
    shapes = []
    for seed, size in enumerate((small, small, 4 * small, small, 4 * small)):
        new_volume(c)
        objects = fill_one_size(c, EC6P6, seed, 9, size)
        c.sched.manual_migrate(objects[0][1].slices[0].vid, seed)
        shapes.append((16, 6, rs_kernel.rung_width(size // 6)))
    before = arrays()
    tracelib.reset_collector()
    held = []  # (buffers made so far, the kept sizes) after each lease
    while c.worker.run_once():
        held.append((len(made), sorted(b.size for b in hostmem.KEPT._kept)))
    assert c.worker.completed == 5
    tags = step_tags()
    assert [(x["rung_b"], 6, x["rung_s"]) for x in tags] == shapes
    assert [x["array"] for x in tags] == [
        "fresh", "reused", "fresh", "reused", "reused"]
    assert arrays_since(before) == (3, 2)
    # two buffers in all, the second made while the first was kept, and
    # both kept when idle: the smaller steps after the larger one are
    # views of the smaller buffer (the smallest that fits)
    assert [alive for _, alive in made] == [0, 1]
    first, second = (16 * 6 * shapes[i][2] for i in (0, 2))
    assert held == [(1, [first]), (1, [first]), (2, [first, second]),
                    (2, [first, second]), (2, [first, second])]
    assert all(ref() is not None for ref, _ in made)


def test_a_view_is_the_head_of_the_buffer_and_c_contiguous(tmp_path, kept):
    c = keeper(tmp_path)
    w = c.worker
    a = w._step_array((8, 6, 32768))
    buf = weakref.ref(a.base)  # not a reference: that would hold it
    assert buf().ndim == 1 and buf().dtype == np.uint8
    assert buf().size == a.size
    assert a.flags.c_contiguous and a.dtype == np.uint8
    assert a.ctypes.data == buf().ctypes.data
    del a
    b = w._step_array((8, 3, 32768))  # fits: the same buffer's head
    assert b.base is buf() and b.shape == (8, 3, 32768)
    assert b.flags.c_contiguous and b.ctypes.data == buf().ctypes.data
    b[...] = 7
    assert (buf()[:b.size] == 7).all()
    del b
    big = w._step_array((16, 6, 32768))  # does not: a new one, the old kept
    assert big.base.size == big.size and big.base is not buf()
    assert sorted(x.size for x in kept._kept) == [buf().size, big.size]


def test_the_door_closes_the_counter_and_the_tag(tmp_path, kept, monkeypatch):
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    c = keeper(tmp_path)
    objects = fill(c, EC3P3, seed=44, count=6)
    c.sched.manual_migrate(objects[0][1].slices[0].vid, 1)
    before = arrays()
    tracelib.reset_collector()
    assert c.worker.run_once() and c.worker.completed == 1
    assert arrays_since(before) == (0, 0) and step_tags() == []
    assert kept._kept  # kept all the same
    assert_rebuilt(c, objects, EC3P3, [1])


# ---------------- (c) an idle worker holds no view ----------------

def test_an_idle_workers_buffer_serves_the_next_backlogs_first_step(
        tmp_path, kept):
    c = keeper(tmp_path)
    assert c.worker.run_once() is False and kept._kept == []
    volumes = []
    for seed in (1, 2, 3):
        new_volume(c)
        volumes.append(fill_one_size(c, EC3P3, seed, 5, 3 * 9_000))
    for objects in volumes[:2]:
        c.sched.manual_migrate(objects[0][1].slices[0].vid, 2)
    before = arrays()
    assert c.worker.run_once() and c.worker.run_once()
    assert arrays_since(before) == (1, 1) and len(kept._kept) == 1
    first = kept._kept[0].ctypes.data
    assert c.worker.run_once() is False
    assert holders(kept, first) == 0  # idle: no view left
    c.sched.manual_migrate(volumes[2][0][1].slices[0].vid, 2)
    seen, take = [], c.worker._step_array

    def seam(shape):
        arr = take(shape)
        seen.append(arr.ctypes.data)
        return arr

    c.worker._step_array = seam
    assert c.worker.run_once()
    assert arrays_since(before) == (2, 1) and seen == [first]
    for objects in volumes:
        assert_rebuilt(c, objects, EC3P3, [2])


def test_the_workers_own_loop_holds_no_view_when_idle_or_stopped(
        tmp_path, kept):
    c = keeper(tmp_path)
    objects = fill(c, EC6P6, seed=45, count=8)
    c.sched.manual_migrate(objects[0][1].slices[0].vid, 3)
    c.worker.start(idle_wait=0.01)
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and c.worker.completed < 1:
            time.sleep(0.01)
        time.sleep(0.05)  # idle: the loop asks for work and finds none
        assert c.worker.completed == 1 and held_buffers() == 0
    finally:
        c.worker.stop()
        c.worker._thread.join(timeout=30)
    assert kept._kept and held_buffers() == 0
    assert_rebuilt(c, objects, EC6P6, [3])


# ---------------- (d) a task's calls are the parent's ----------------

def backlog_of_three(c):
    for seed, mode in enumerate((EC3P3, EC12P4, EC6P6)):
        new_volume(c)
        objects = fill(c, mode, seed=50 + seed, count=16, lo=3_000)
        c.sched.manual_migrate(objects[0][1].slices[0].vid, seed + 1)


def one_loss(c, refusing=None, mode=EC6P6):
    vid = fill(c, mode, seed=8, count=20)[0][1].slices[0].vid
    if refusing is not None:
        lose(c, vid, refusing, report=False)
    lose(c, vid, 1)


def lease_of(c, mode, bads):
    vid = fill(c, mode, seed=7, count=20)[0][1].slices[0].vid
    for bad in bads:
        lose(c, vid, bad)


SCENARIOS = {
    "one-loss": lambda c: one_loss(c),
    "second-unit-refuses": lambda c: one_loss(c, refusing=3),
    "lease-of-two-EC12P4": lambda c: lease_of(c, EC12P4, (1, 13)),
    "lease-of-five-EC6P6": lambda c: lease_of(c, EC6P6, (0, 2, 7, 4, 11)),
    "three-lost-EC3P3": lambda c: lease_of(c, EC3P3, (0, 2, 5)),
    "backlog-of-three-codemodes": backlog_of_three,
    "lrc-local": lambda c: one_loss(c, mode=cmode.CodeMode.EC4P4L2),
    "msr-conventional": lambda c: one_loss(c, mode=cmode.CodeMode.EC4P4MSR),
}


def run_recorded(path, scenario, parent: bool):
    """What one drain of `scenario` asked of the nodes, the scheduler
    and the engine, with task and worker ids taken out of the record."""
    path.mkdir()
    copying = Copying()
    c = keeper(path, copying=copying)
    take, taken = c.worker._step_array, []
    if parent:  # the parent's `_step_array`, word for word
        take = lambda shape: np.empty(shape, dtype=np.uint8)
    c.worker._step_array = lambda shape: taken.append(shape) or take(shape)
    SCENARIOS[scenario](c)
    calls, asked = recorded(c), sched_calls(c)
    c.drain_worker()
    names = {}
    for method, args in asked:
        names.setdefault(args.get("task_id"), len(names))
    asked = [(method, names[args.get("task_id")],
              sorted(k for k in args if k not in ("task_id", "worker_id")))
             for method, args in asked]
    steps = [(rows.tobytes(), rows.shape, held.shape, held.tobytes())
             for rows, held, _ in copying.seen]
    stored = {
        (x["vid"], x["unit_index"]): [
            rebuilt(c, x["vid"], x["unit_index"], bid)[2]
            for bid in shard_sizes(c, x["vid"], x["unit_index"])]
        for x in c.sched.tasks.values()}
    return (calls, asked, steps, stored, taken,
            (c.worker.completed, c.worker.failed)), c


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_a_tasks_calls_matrices_and_step_arrays_are_the_parents(
        tmp_path, monkeypatch, scenario):
    """Two fleets filled alike, one with the parent's `_step_array` (a
    new `np.empty` a step), one with the kept buffer: the node calls in
    their order, the questions to the scheduler, each step's matrix and
    its array — shape and every byte, pad included — and what is stored
    afterwards are the same."""
    monkeypatch.setenv("CUBEFS_CODEC_MSR", "0")  # MSR: conventional path
    parent, _ = run_recorded(tmp_path / "parent", scenario, parent=True)
    before = arrays()
    kept, c = run_recorded(tmp_path / "kept", scenario, parent=False)
    for got, want in zip(kept, parent):
        assert got == want
    calls, asked, steps, stored, taken, (completed, failed) = kept
    assert completed == len(c.sched.tasks) and failed == 0
    assert steps and sum(x[0] == "put_shard" for x in calls) > 0
    reused, fresh = arrays_since(before)
    assert fresh >= 1 and reused + fresh == len(taken) <= len(steps)


# ---------------- (e) the MSR conventional decode ----------------

def test_an_msr_conventional_decode_takes_its_exact_size_array_from_the_seam(
        tmp_path, monkeypatch):
    monkeypatch.setenv("CUBEFS_CODEC_MSR", "0")
    mode = cmode.CodeMode.EC4P4MSR
    t = cmode.tactic(mode)
    copying = Copying()
    c = keeper(tmp_path, copying=copying)
    objects = fill(c, mode, seed=46, count=10, lo=4_000)
    vid = objects[0][1].slices[0].vid
    sizes = shard_sizes(c, vid, 0)
    asked, handed, at = [], [], []
    take, apply = c.worker._step_array, c.worker.codec.matrix_apply

    def seam(shape):
        asked.append(shape)
        arr = take(shape)
        at.append(arr.ctypes.data)
        return arr

    def matrix_apply(rows, shards, width=None):
        handed.append((shards.shape, shards.ctypes.data == at[-1]))
        return apply(rows, shards, width=width)

    c.worker._step_array = seam
    c.worker.codec.matrix_apply = matrix_apply
    lose(c, vid, 1)
    before = arrays()
    tracelib.reset_collector()
    assert c.worker.run_once() and c.worker.completed == 1
    by_size: dict[int, int] = {}
    for size in sizes.values():
        by_size[size] = by_size.get(size, 0) + 1
    assert asked == [(count, t.n, size) for size, count in by_size.items()]
    # the codec got that array, cut into alpha sub-shards a row (the
    # batcher pads those to a rung in an array of its own)
    assert handed == [((count, t.n * t.alpha, size // t.alpha), True)
                      for size, count in by_size.items()]
    assert len(copying.seen) == len(asked)
    assert sum(arrays_since(before)) == len(asked)
    assert [x["array"] for x in step_tags()][0] == "fresh"
    assert_rebuilt(c, objects, mode, [1])
