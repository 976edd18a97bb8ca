"""An LRC codemode's parity is one apply of composed rows (PR 42): the
global RS rows and every AZ's local rows composed through them
(`rs_kernel.lrc_encode_rows`), one admitted step a PUT, and the rows
encode, verify and reconstruct all use. Checked against the benchmark's
plain reference (`cellbench/reference_lrc.py`), which computes the two
levels in turn and composes nothing."""

import numpy as np
import pytest

from cellbench import reference_lrc
from cubefs_tpu.blob.access import AccessConfig, fill_stripe_rows
from cubefs_tpu.codec import batcher
from cubefs_tpu.codec import codemode as cm
from cubefs_tpu.codec.batcher import AdmittedEngine, BatchCodec
from cubefs_tpu.codec.encoder import CodecConfig, new_encoder
from cubefs_tpu.ops import rs_kernel
from cubefs_tpu.utils import metrics

LRC = ["EC16P20L2", "EC6P10L2", "EC4P4L2", "EC6P3L3"]


class Counting(BatchCodec):
    """A private batcher that records every engine call's key."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.keys = []

    def _engine_call(self, key, coeff, arr):
        self.keys.append(key)
        return super()._engine_call(key, coeff, arr)


def _blob(mode: str, shard: int, seed: int) -> tuple[bytes, cm.Tactic]:
    """A seeded blob whose shard size is `shard` (a few bytes short of
    n whole shards, so the last data row has a zero tail)."""
    t = cm.tactic(mode)
    size = t.n * shard - 3
    return np.random.default_rng([seed, shard]).bytes(size), t


def _want(blob: bytes, t: cm.Tactic) -> np.ndarray:
    return reference_lrc.stripe(blob, t.n, t.m, t.l, t.az_count,
                                t.min_shard_size)


@pytest.mark.parametrize("shard", [2048, 40_000, 524_288])
@pytest.mark.parametrize("mode", LRC)
def test_lrc_parity_is_the_reference_row_for_row(mode, shard):
    blob, t = _blob(mode, shard, 42)
    want = _want(blob, t)
    enc = new_encoder(CodecConfig(mode=cm.CodeMode[mode], engine="numpy"))
    enc.engine = AdmittedEngine(Counting(), "numpy")
    assert enc.shard_size(len(blob)) == shard == want.shape[1]

    rows = np.empty((1, t.n, enc.row_width(shard)), dtype=np.uint8)
    fill_stripe_rows(rows, blob, len(blob), shard)
    parity = enc.encode_rows_async(rows, shard).wait()
    assert parity.shape == (1, t.m + t.l, shard)
    for r in range(t.m + t.l):
        assert np.array_equal(parity[0, r], want[t.n + r]), r
    # the step was ONE apply of the composed rows, tagged with its
    # local rows: n columns in, m + l rows out
    (key,) = enc.engine.batcher.keys
    assert key[0] == "encode" and key[2:4] == (t.n, t.m + t.l)
    assert key[6] == t.l

    stripe = np.zeros_like(want)
    stripe[: t.n] = want[: t.n]
    assert np.array_equal(enc.encode(stripe), want)
    assert enc.verify(want)
    bad = want.copy()
    bad[-1, 0] ^= 1  # the last AZ's local parity
    assert not enc.verify(bad)
    # a data shard, a global and a local parity lost: rebuilt from the
    # rest, the local parity by its rows of the same composed matrix
    lost = [1, t.n + 1, t.n + t.m]
    broken = want.copy()
    broken[lost] = 0
    assert np.array_equal(enc.reconstruct(broken, lost), want)


def test_the_composed_rows_are_the_two_levels_in_turn():
    """rs_kernel.lrc_encode_rows: the first m rows are RS's own parity
    rows, each local row is its local code's row over its stripe's
    members' rows of the systematic generator."""
    from cubefs_tpu.ops import gf256

    t = cm.tactic(cm.CodeMode.EC16P20L2)
    stripes, ln, lm = t.all_local_stripes()
    rows = rs_kernel.lrc_encode_rows(t.n, t.n + t.m, stripes, ln)
    assert rows.shape == (22, 16) and (ln, lm) == (18, 1)
    assert np.array_equal(rows[: t.m], gf256.parity_matrix(t.n, t.m))
    gen = gf256.encode_matrix(t.n, t.n + t.m)
    local = gf256.encode_matrix(ln, ln + lm)[ln:]
    for az, stripe in enumerate(stripes):
        want = gf256.gf_matmul(local, gen[stripe[:ln]])
        assert np.array_equal(rows[t.m + az], want[0]), az


@pytest.fixture
def lrc_fleet(tmp_path, monkeypatch):
    """10 nodes x 4 disks (40 >= 38 units), blobs of 64 KiB, two AZs
    labelled as the `ingest-lrc` cell labels them; the process batcher's
    engine calls recorded."""
    from test_blob_e2e import Cluster

    c = Cluster(tmp_path, n_nodes=10, disks_per_node=4)
    for k, node in enumerate(c.nodes):
        node.az = f"az{k // 5}"
        for d in node.disk_ids:
            c.cm.relabel_disk(d, node.az)
    c.access.cfg.engine = "numpy"
    keys = []
    call = batcher.DEFAULT._engine_call

    def recording(key, coeff, arr):
        keys.append(key)
        return call(key, coeff, arr)

    monkeypatch.setattr(batcher.DEFAULT, "_engine_call", recording)
    return c, keys


def test_an_lrc_put_of_eight_blobs_is_one_codec_step(lrc_fleet,
                                                     monkeypatch):
    """Eight blobs of EC16P20L2 are one submission, and a submission is
    never split, though the coalescing cap at this rung is 4 stripes
    (rs_kernel.batch_cap; as at a 64 MiB PUT's 18-tile rung, here by a
    smaller byte bound): ONE engine call of 22 rows a PUT, where the
    parent ran three (the global step, then a step of each AZ's local
    row). Every blob counts once as `in_step`."""
    c, keys = lrc_fleet
    blob = c.access.cfg.blob_size
    data = np.random.default_rng(5).bytes(8 * blob)
    t = cm.tactic(cm.CodeMode.EC16P20L2)
    width = rs_kernel.rung_width(-(-blob // t.n))
    monkeypatch.setattr(batcher.DEFAULT, "max_step_bytes",
                        4 * t.n * width)
    monkeypatch.setattr(batcher.DEFAULT, "_caps", {})
    assert rs_kernel.batch_cap(t.n, width,
                               batcher.DEFAULT.max_step_bytes) == 4
    before = metrics.codec_lrc_local.value(how="in_step")
    loc = c.access.put(data, codemode=cm.CodeMode.EC16P20L2)
    assert len(keys) == 1
    assert keys[0][0] == "encode" and keys[0][2:4] == (16, 22)
    assert metrics.codec_lrc_local.value(how="in_step") - before == 8
    assert metrics.codec_lrc_local.value(how="separate") == 0
    assert c.access.get(loc) == data
    # what is stored is the reference, both AZs' local parity included
    vol = c.cm.get_volume(loc.slices[0].vid)
    want = _want(data[3 * blob:4 * blob], t)
    for u in vol.units:
        _, got = c.pool.get(u.node_addr).call(
            "get_shard", {"disk_id": u.disk_id, "chunk_id": u.chunk_id,
                          "bid": loc.slices[0].min_bid + 3})
        assert got == want[u.index].tobytes(), u.index
    homes = [{c.cm.disks[vol.units[i].disk_id].az for i in s}
             for s in reference_lrc.az_layout(t.n, t.m, t.l, t.az_count)]
    assert sorted(h.pop() for h in homes if len(h) == 1) == ["az0", "az1"]


def test_an_ec12p4_put_submits_as_before(lrc_fleet):
    """The RS encode door is untouched: an EC12P4 PUT is one step of
    the systematic parity, keyed (op, engine, n, m, rung) with no rows."""
    c, keys = lrc_fleet
    data = np.random.default_rng(6).bytes(8 * c.access.cfg.blob_size)
    loc = c.access.put(data, codemode=cm.CodeMode.EC12P4)
    shard = -(-c.access.cfg.blob_size // 12)
    assert keys == [("encode", "numpy", 12, 4, rs_kernel.rung_width(shard))]
    assert c.access.get(loc) == data


def test_ready_builds_the_composed_step_at_every_rung(monkeypatch):
    """AccessHandler.ready, with EC16P20L2 in its policies, submits the
    composed step at every rung of the ladder and one (n, n) decode a
    width rung — what a degraded GET asks for."""
    from cubefs_tpu.blob.access import AccessHandler

    bc = Counting()
    monkeypatch.setattr(batcher, "DEFAULT", bc)
    cfg = AccessConfig(engine="numpy",
                       policies=[cm.Policy("EC16P20L2", 0, 1 << 62)])
    acc = AccessHandler(None, None, cfg)
    try:
        size = 1_600_000  # one blob, shards of 1-4 tiles
        steps = acc.ready(size)
    finally:
        acc._pool.shutdown(wait=True)
    encodes = [k for k in bc.keys if k[0] == "encode"]
    decodes = [k for k in bc.keys if k[0] == "apply"]
    shapes = rs_kernel.ladder(16, 2048, -(-size // 16),
                              bc.max_step_bytes, bc.max_batch, 1)
    assert steps == len(encodes) == len(shapes)
    assert len(decodes) == len({s for _, s in shapes})
    assert {k[2:4] for k in encodes} == {(16, 22)}
    assert {k[6] for k in encodes} == {2}
    assert sorted({k[4] for k in decodes}) == sorted({s for _, s in shapes})
    assert all(k[3] == 16 for k in decodes)
