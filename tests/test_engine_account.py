"""The three places a request waits keep their own account (PR 39): the
engine seam (`cubefs_codec_engine_seconds_total{state}`), the drainer's
streak (`cubefs_codec_drain_steps`, `cubefs_codec_drain_seconds`,
`cubefs_codec_collects_total`) and the access pool's queue
(`cubefs_access_pool_wait_seconds`). A stub engine on an injected clock:
every second below is scripted, none is measured. Threads are joined
with a timeout and a gate nobody opened fails the test."""

import threading
import time

import numpy as np
import pytest

from cellbench import registry
from cubefs_tpu.codec import batcher
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import metrics
from cubefs_tpu.utils import trace as tracelib
from test_put_stripe_rows import BLOB, cluster  # noqa: F401 (a fixture)

WAIT_S = 30.0
SEAM = "cubefs_codec_engine_seconds_total"
NEW_SERIES = (SEAM, "cubefs_codec_drain_steps", "cubefs_codec_drain_seconds",
              "cubefs_codec_collects_total",
              "cubefs_access_pool_wait_seconds")


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class Stub(batcher.BatchCodec):
    """A batcher whose engine is a script: `calls[n]` runs inside the
    engine call of the queue whose submissions have n rows (it advances
    the clock, parks more submissions, waits for the test), and a
    gathered step's copy takes `gather_s` on the clock."""

    def __init__(self, clock):
        super().__init__(clock=clock)
        self.clock, self.calls, self.gather_s, self.steps = clock, {}, 0.0, 0

    def _engine_call(self, key, coeff, arr):
        self.steps += 1
        self.calls.get(int(arr.shape[1]), lambda: None)()
        return np.zeros((arr.shape[0], int(key[3]), arr.shape[2]),
                        dtype=np.uint8), "stub"

    def _gather(self, step, shape):
        self.clock.advance(self.gather_s)
        return super()._gather(step, shape)

    def park(self, n: int, stripes: int = 1):
        """One submission of `stripes` stripes to the queue of n rows."""
        return self.submit_encode_async(
            "stub", np.zeros((stripes, n, 64), dtype=np.uint8), 2)


@pytest.fixture
def stub(monkeypatch):
    """The process's batcher for the test, so a render settles its
    account and no other; the registry is read through its text."""
    monkeypatch.delenv("CUBEFS_TRACE", raising=False)
    b = Stub(Clock())
    monkeypatch.setattr(batcher, "DEFAULT", b)
    return b


def rendered() -> registry.Series:
    return registry.parse(metrics.DEFAULT.render_text())


def seam(before: registry.Series) -> dict[str, float]:
    d = registry.delta(before, rendered())
    return {s: round(registry.total(d, SEAM, state=s), 9)
            for s in ("busy", "handoff", "starved")}


def in_thread(fn):
    th = threading.Thread(target=fn)
    th.start()
    return th


def join(th):
    th.join(WAIT_S)
    assert not th.is_alive()


def wait_for(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


# the script, stage by stage: what each adds to (busy, handoff, starved)
def _idle(b):
    b.clock.advance(5.0)  # nobody has submitted


def _parked(b):
    b.pending = [b.park(4), b.park(4)]
    b.clock.advance(2.0)  # admitted, no drainer yet


def _gathered_step(b):
    b.gather_s = 0.5  # two submissions: the step is a copy of both
    b.calls[4] = lambda: b.clock.advance(3.0)
    for sub in b.pending:
        assert sub.result(WAIT_S).shape == (1, 2, 64)
    assert b.steps == 1


def _long_starved(b):
    b.clock.advance(40.0)  # every caller is outside the codec


def _overlapping_calls(b):
    """Two geometry queues, each drained by its own caller; the second
    call starts and ends inside the first: busy counts the union."""
    inside, release = threading.Event(), threading.Event()

    def held():
        inside.set()
        assert release.wait(WAIT_S), "the test never let the call go"

    b.calls[4], b.calls[6] = held, lambda: b.clock.advance(2.0)
    b.gather_s = 0.0
    first, second = b.park(4), b.park(6)
    th = in_thread(lambda: first.result(WAIT_S))
    assert inside.wait(WAIT_S)
    b.clock.advance(1.0)  # the first call alone
    second.result(WAIT_S)  # +2.0 with both in flight
    b.clock.advance(1.5)  # the first alone again, `second` resolved
    release.set()
    join(th)
    assert first.done and b.steps == 3


def _idle_again(b):
    b.clock.advance(7.0)


SCRIPT = [
    (_idle, (0.0, 0.0, 5.0)),
    (_parked, (0.0, 2.0, 0.0)),
    (_gathered_step, (3.0, 0.5, 0.0)),
    (_long_starved, (0.0, 0.0, 40.0)),
    (_overlapping_calls, (4.5, 0.0, 0.0)),
    (_idle_again, (0.0, 0.0, 7.0)),
]


@pytest.mark.parametrize("upto", range(1, len(SCRIPT) + 1),
                         ids=[f.__name__.lstrip("_") for f, _ in SCRIPT])
def test_the_seam_books_every_scripted_second_to_one_state(stub, upto):
    """After each stage the three states hold exactly the seconds the
    script spent in them, and sum to the time since the batcher was
    made — read from two renders, with no call to the account between:
    the render settles it."""
    before, t_made = rendered(), stub.clock()
    want = np.zeros(3)
    for stage, adds in SCRIPT[:upto]:
        stage(stub)
        want += adds
    got = seam(before)
    assert got == dict(zip(("busy", "handoff", "starved"), want.tolist()))
    assert sum(got.values()) == pytest.approx(stub.clock() - t_made, abs=1e-9)


def test_a_render_inside_a_long_starved_stretch_already_counts_it(stub):
    before = rendered()
    stub.clock.advance(1800.0)
    assert seam(before) == {"busy": 0.0, "handoff": 0.0, "starved": 1800.0}
    mid = rendered()  # and books nothing twice
    stub.clock.advance(2.0)
    assert seam(mid) == {"busy": 0.0, "handoff": 0.0, "starved": 2.0}
    assert seam(before)["starved"] == 1802.0


def test_a_failed_engine_call_ends_busy_and_resolves_the_seam(stub):
    def fall_over():
        stub.clock.advance(1.0)
        raise RuntimeError("step fell over")

    before, stub.calls[4] = rendered(), fall_over
    sub = stub.park(4, 8)
    with pytest.raises(RuntimeError, match="fell over"):
        sub.result(WAIT_S)
    stub.clock.advance(3.0)
    assert seam(before) == {"busy": 1.0, "handoff": 0.0, "starved": 3.0}


def _streak(d: registry.Series) -> dict:
    tot = lambda name, **lb: registry.total(d, name, **lb)  # noqa: E731
    return {
        "drains": tot("cubefs_codec_drain_steps_count", op="encode"),
        "steps": tot("cubefs_codec_drain_steps_sum", op="encode"),
        "own": tot("cubefs_codec_drain_seconds_sum", op="encode", part="own"),
        "others": tot("cubefs_codec_drain_seconds_sum", op="encode",
                      part="others"),
        **{how: tot("cubefs_codec_collects_total", op="encode", how=how)
           for how in ("ready", "waited", "drained")}}


@pytest.mark.parametrize("k", [1, 2, 3, 5])
def test_a_drain_of_k_steps_whose_own_submission_is_the_first(stub, k):
    """The caller pipelines two submissions and collects the first: it
    drains. Under the first step another caller parks one and waits for
    it; under each later step but the last one more is parked and
    collected after the drain. One drain of k steps, one second a step:
    `own` is the first step's, `others` the k - 1 after it, and the
    collects are drained 1 / waited 1 / ready the rest."""
    late, waiter = [], []

    def one_step():
        stub.clock.advance(1.0)
        if stub.steps == 1 and k >= 2:
            def collect():
                sub = stub.park(4)
                late.append(sub)
                sub.result(WAIT_S)

            waiter.append(in_thread(collect))
            # parked, and asleep on its event: the drainer owns it now
            wait_for(lambda: late and late[0].event is not None,
                     "the second caller to wait")
        elif 1 < stub.steps < k:
            late.append(stub.park(4))

    stub.calls[4] = one_step
    before = rendered()
    own, piped = stub.park(4), stub.park(4)
    with tracelib.path_span("blob.put", "access.put"):
        with tracelib.stage("encode_admission") as st:
            own.result(WAIT_S)
    for th in waiter:
        join(th)
    for sub in [piped] + late[1:]:
        assert sub.done
        sub.result(WAIT_S)
    got = _streak(registry.delta(before, rendered()))
    assert stub.steps == k
    assert got == {"drains": 1, "steps": k, "own": 1.0, "others": k - 1.0,
                   "drained": 1, "waited": min(k - 1, 1),
                   "ready": 1 + max(k - 2, 0)}
    # the drainer's span says so too: what `cubefs-cli trace slow` shows
    assert st.span.tags["drain_steps"] == k
    assert st.span.tags["drain_others_ms"] == (k - 1) * 1000.0


def _put(c, mode, blobs, rng):
    data = rng.integers(0, 256, (blobs - 1) * BLOB + 999,
                        dtype=np.uint8).tobytes()
    loc = c.access.put(data, codemode=mode)
    assert c.access.get(loc) == data
    return cmode.tactic(mode)


@pytest.mark.parametrize("mode, blobs", [
    (cmode.CodeMode.EC3P3, 1), (cmode.CodeMode.EC6P6, 1),
    (cmode.CodeMode.EC12P4, 1), (cmode.CodeMode.EC12P4, 3)],
    ids=["EC3P3", "EC6P6", "EC12P4", "EC12P4_three_blobs"])
def test_a_put_observes_one_pool_wait_a_shard_it_wrote(
        cluster, rng, monkeypatch, mode, blobs):
    monkeypatch.delenv("CUBEFS_TRACE", raising=False)
    before = rendered()
    t = _put(cluster, mode, blobs, rng)
    d = registry.delta(before, rendered())
    name = "cubefs_access_pool_wait_seconds"
    assert registry.total(d, name + "_count", op="put_shard") \
        == t.total * blobs
    # the GET read its n data shards a blob through the same pool
    assert registry.total(d, name + "_count", op="get_shard") == t.n * blobs
    assert 0 <= registry.total(d, name + "_sum") < WAIT_S


@pytest.mark.parametrize("series", NEW_SERIES)
def test_with_the_door_closed_no_new_series_moves(
        cluster, rng, monkeypatch, series):
    """CUBEFS_TRACE=0: a PUT and a GET through the front door, a drain
    of two steps on the process's batcher and seconds in every state of
    the seam leave each new series where it was."""
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    b = Stub(Clock())
    b.calls[4] = lambda: (b.clock.advance(1.0),
                          b.steps == 1 and b.park(4))
    before = rendered()
    _put(cluster, cmode.CodeMode.EC6P6, 2, rng)
    monkeypatch.setattr(batcher, "DEFAULT", b)
    b.clock.advance(5.0)
    own, piped = b.park(4), b.park(4)
    own.result(WAIT_S)
    piped.result(WAIT_S)
    b.clock.advance(5.0)
    assert b.steps == 2
    moved = {k: v for k, v in registry.delta(before, rendered()).items()
             if k[0].startswith(series) and v}
    assert moved == {}
    # the door, not a dead path: open, the same account is booked
    monkeypatch.delenv("CUBEFS_TRACE")
    b.clock.advance(4.0)
    assert seam(before)["starved"] == 4.0


def test_the_cli_digest_renders_the_three_accounts(cluster, rng, stub):
    """`cubefs-cli metrics codec`: the seam's seconds and shares, a
    drain's streak and the collects beside the step counts, the pool's
    mean wait — from the exposition text an operator's scrape returns."""
    from cubefs_tpu import cli

    view0 = cli._codec_view(metrics.DEFAULT.render_text())
    stub.calls[4] = lambda: stub.clock.advance(0.25)
    own, piped = stub.park(4), stub.park(4)
    own.result(WAIT_S)
    piped.result(WAIT_S)
    stub.clock.advance(0.75)
    _put(cluster, cmode.CodeMode.EC3P3, 1, rng)
    view = cli._codec_view(metrics.DEFAULT.render_text())
    grew = lambda *path: _at(view, path) - _at(view0, path)  # noqa: E731
    assert grew("engine_seam", "seconds", "busy") == pytest.approx(0.25)
    assert grew("engine_seam", "seconds", "starved") == pytest.approx(0.75)
    assert sum(view["engine_seam"]["share_pct"].values()) \
        == pytest.approx(100.0, abs=0.02)
    assert grew("encode", "drains", "drains") >= 1
    assert grew("encode", "drains", "collects", "drained") >= 1
    assert grew("encode", "drains", "collects", "ready") >= 1
    assert view["encode"]["drains"]["steps_per_drain_avg"] >= 1
    assert grew("access_pool", "put_shard", "tasks") == 6
    assert view["access_pool"]["put_shard"]["wait_avg_ms"] >= 0


def _at(view: dict, path: tuple) -> float:
    for key in path:
        view = view.get(key, {}) if isinstance(view, dict) else 0.0
    return view or 0.0
