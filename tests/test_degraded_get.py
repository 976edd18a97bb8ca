"""A degraded GET: units of a blob that no longer read are decoded in the
request (``AccessHandler._get_blob`` -> ``global_reconstruct`` ->
``Encoder.reconstruct_data`` -> the batcher -> the device engine), and
``AccessHandler.ready`` builds every decode step that GETs which lost
the same units meet in. CPU, small sizes, seeded; the decoded units are
held to ``cellbench/reference_decode.py``."""

import threading

import numpy as np
import pytest

from cellbench import reference, reference_decode
from cellbench.deployment import CompileClock
from cubefs_tpu.blob import access as access_mod
from cubefs_tpu.blob.access import AccessConfig
from cubefs_tpu.codec import batcher as batcher_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.ops import pallas_gf, rs_kernel
from cubefs_tpu.utils import metrics, rpc
from test_blob_e2e import Cluster

TILE = pallas_gf.DEFAULT_TILE
LOST = [(mode, k) for mode in ("EC3P3", "EC6P6", "EC12P4")
        for k in range(1, cmode.tactic(mode).m + 1)]


def _cluster(tmp_path, blob_size: int, mode: str, smallest: int = 0
             ) -> Cluster:
    """Every object of `smallest` bytes and up stored as `mode`."""
    c = Cluster(tmp_path, n_nodes=4, disks_per_node=4)
    c.cm.allow_colocated_units = True
    c.access.cfg = AccessConfig(
        blob_size=blob_size, engine="tpu",
        policies=[cmode.Policy(mode, smallest, 1 << 62)])
    return c


def _lose(monkeypatch, lost: set[int]) -> None:
    """Every read of a unit in `lost` fails, as a lost disk's does."""
    real = access_mod.AccessHandler._read_shard

    def failing(self, vol, idx, bid):
        if idx in lost:
            return idx, None, rpc.RpcError(503, "disk is broken")
        return real(self, vol, idx, bid)

    monkeypatch.setattr(access_mod.AccessHandler, "_read_shard", failing)


def _stored(c: Cluster, loc, k: int) -> dict[int, bytes]:
    """Every unit of blob `k` as the disks hold it, at its full size."""
    sl = loc.slices[0]
    out = {}
    for u in c.cm.get_volume(sl.vid).units:
        _, body = c.pool.get(u.node_addr).call(
            "get_shard", {"disk_id": u.disk_id, "chunk_id": u.chunk_id,
                          "bid": sl.min_bid + k})
        out[u.index] = body
    return out


def _global_reconstructs() -> float:
    return sum(v for key, v in metrics.reconstruct_reads.samples()
               if key == ("global",))


@pytest.mark.parametrize("mode,k", LOST, ids=[f"{m}-{k}" for m, k in LOST])
def test_a_get_with_units_lost_returns_the_payload_and_the_reference_decode(
        tmp_path, monkeypatch, mode, k):
    """k of a stripe's n + m units lost (data and parity): the GET
    returns the payload, every blob through the global decode, and its
    data units are the reference's decode of n survivors."""
    t = cmode.tactic(mode)
    n, m = t.n, t.m
    rng = np.random.default_rng([n, m, k])
    blob = 12 * 5000
    c = _cluster(tmp_path, blob, mode)
    data = rng.integers(0, 256, 3 * blob - 777, dtype=np.uint8).tobytes()
    loc = c.access.put(data)
    lost = {int(rng.integers(0, n))}
    parity = [int(i) for i in rng.permutation(range(n, n + m))]
    others = [int(i) for i in rng.permutation(n)] + parity
    while len(lost) < k:  # alternate: a parity unit, then a data unit
        pool = parity if len(lost) % 2 else others
        lost.add(next(i for i in pool if i not in lost))
    _lose(monkeypatch, lost)
    before = _global_reconstructs()
    assert c.access.get(loc) == data
    sl = loc.slices[0]
    assert _global_reconstructs() - before == sl.count
    for b in range(sl.count):
        units = _stored(c, loc, b)
        alive = sorted(set(units) - lost)
        pick = sorted(int(i) for i in rng.choice(alive, n, replace=False))
        decoded = reference_decode.decode({i: units[i] for i in pick}, n, m)
        # a PUT's blobs all take the first blob's shard size
        part = data[b * blob:(b + 1) * blob].ljust(blob, b"\0")
        assert np.array_equal(decoded, reference.stripe(
            part, n, m, t.min_shard_size)[:n])


def test_the_reference_decode_takes_exactly_n_units():
    stripe = reference.stripe(b"\x07" * 5000, 3, 3, 16)
    with pytest.raises(ValueError, match="need n = 3"):
        reference_decode.decode({0: stripe[0], 4: stripe[4]}, 3, 3)
    with pytest.raises(ValueError, match="need n = 3"):
        reference_decode.decode({0: stripe[0], 1: stripe[1], 6: stripe[2]},
                                3, 3)
    got = reference_decode.decode({i: stripe[i] for i in (1, 3, 5)}, 3, 3)
    assert np.array_equal(got, stripe[:3])


def _concurrent_gets(c: Cluster, loc, data: bytes, clients: int) -> None:
    errors = []

    def client():
        try:
            assert c.access.get(loc) == data
        except BaseException as e:  # re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive()
    assert not errors, errors


def _apply_steps() -> dict[str, float]:
    """Decode steps so far, by the stripes they held."""
    hist = metrics.codec_batch_stripes
    return {"count": sum(s["count"] for key, s in hist.samples()
                         if key == ("apply",)),
            "sum": sum(s["sum"] for key, s in hist.samples()
                       if key == ("apply",))}


def _built() -> float:
    return sum(v for _, v in metrics.codec_programs.samples())


@pytest.fixture
def joined(monkeypatch):
    """One chip, as a deployment runs, and a drainer that waits for the
    others before its step, so concurrent GETs' decodes meet in one."""
    monkeypatch.setattr(batcher_mod.DEFAULT, "dp_enabled", False)
    monkeypatch.setattr(batcher_mod.DEFAULT, "max_wait", 0.3)


# each test below a width rung that no other test of the suite runs at:
# the program cache is process-wide, and xdist runs files one after
# another in one process
@pytest.mark.parametrize("tiles", [5])
def test_after_ready_concurrent_degraded_gets_build_no_program(
        tmp_path, monkeypatch, joined, tiles):
    """`ready(largest object)`, then four clients GET one object whose
    data unit 3 is lost: their decodes meet in steps of several
    stripes, and neither `cubefs_codec_programs_total` nor JAX's
    compile count moves."""
    n = 12
    blob = n * (tiles * TILE - 1000)
    # objects of one blob and up: the door's steps are at one width rung
    c = _cluster(tmp_path, blob, "EC12P4", smallest=blob)
    data = np.random.default_rng(48).bytes(2 * blob)
    steps = c.access.ready(len(data))
    bounds = (batcher_mod.DEFAULT.max_step_bytes,
              batcher_mod.DEFAULT.max_batch)
    shard = blob // n
    encodes = rs_kernel.ladder(n, shard, shard, *bounds, 2)
    decodes = rs_kernel.ladder(n, shard, shard, *bounds)
    assert decodes == [(b, tiles * TILE) for b in (1, 2, 4, 8)]
    assert steps == len(encodes)
    loc = c.access.put(data)
    _lose(monkeypatch, {3})
    before, steps0 = _built(), _apply_steps()
    clock = CompileClock()
    try:
        _concurrent_gets(c, loc, data, clients=4)
    finally:
        clock.close()
    after = _apply_steps()
    # 2 blobs x 4 GETs decoded in fewer steps than decodes: they met
    assert after["sum"] - steps0["sum"] == 8
    assert after["count"] - steps0["count"] < 8
    assert _built() == before and clock.compiles == 0


@pytest.mark.parametrize("tiles", [6])
def test_without_ready_concurrent_degraded_gets_build_their_steps(
        tmp_path, monkeypatch, joined, tiles):
    """What the door is for: with no `ready`, the first GETs that meet
    in a step of several stripes build its program inside the request
    (the one-stripe decode came with the PUT's encode)."""
    n = 12
    blob = n * (tiles * TILE - 1000)
    c = _cluster(tmp_path, blob, "EC12P4")
    data = np.random.default_rng(49).bytes(2 * blob)
    loc = c.access.put(data)
    _lose(monkeypatch, {5})
    before = _built()
    _concurrent_gets(c, loc, data, clients=4)
    assert _built() > before
