"""No pad column of a device step reaches a shard. A large result's
column blocks that lie wholly past the step's payload width are never
brought back (`codec/engine.py:_to_host`): those columns of the kept
host array hold whatever they held before — here a poison byte, put in
every kept array as it is handed out. An EC16P20L2 PUT and the rebuild of a
unit it lost, at the tests' width (4 KiB shards in a 32 KiB rung) and at
`ingest-lrc`'s and `lrc-disk-repair`'s (8 MiB blobs: 524288 columns of a
589824-column rung), store and rebuild the plain reference's shards,
byte for byte. CPU, the device engine, every result over the lowered
threshold."""

import numpy as np
import pytest

from cellbench import reference, reference_lrc
from cubefs_tpu.blob.access import AccessConfig, AccessHandler
from cubefs_tpu.blob.proxy import ProxyAllocator
from cubefs_tpu.blob.worker import RepairWorker
from cubefs_tpu.codec import batcher as batcher_mod
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.codec import engine
from cubefs_tpu.utils import hostmem, rpc
from test_blob_topology import AZCluster

MODE = cmode.CodeMode.EC16P20L2
POISON = 0xA5


@pytest.fixture
def cuts(kept, monkeypatch):
    """Every device result through the kept arrays, each array poisoned
    as it is handed out (a PUT's rows and a repair's step arrays too:
    their callers write every byte); returns the (blocks brought back,
    blocks) of every large result."""
    monkeypatch.setattr(hostmem, "MALLOC_MMAP_MAX", 0)
    monkeypatch.setattr(batcher_mod.DEFAULT, "dp_enabled", False)
    take = kept.take

    def poisoned(shape):
        buf, came = take(shape)
        buf.fill(POISON)
        return buf, came

    monkeypatch.setattr(kept, "take", poisoned)
    seen = []
    to_host = engine._to_host

    def counted(y, width=None):
        _, bounds = engine._splitter(tuple(y.shape),
                                     tuple(y.format.layout.major_to_minor))
        seen.append((sum(width is None or a < width for a, _ in bounds),
                     len(bounds)))
        return to_host(y, width)

    monkeypatch.setattr(engine, "_to_host", counted)
    return seen


def fleet(tmp_path, blob: int) -> AZCluster:
    """Two AZs of 5 nodes x 4 disks, access and worker on the device
    engine."""
    c = AZCluster(tmp_path, azs=("az0", "az1"), nodes_per_az=5,
                  disks_per_node=4)
    c.access = AccessHandler(
        c.cm_client, c.pool, AccessConfig(blob_size=blob, engine="tpu"),
        repair_queue=c.repair_q, delete_queue=c.delete_q,
        proxy_client=rpc.Client(ProxyAllocator(c.cm_client)))
    c.worker = RepairWorker(rpc.Client(c.sched), c.cm_client, c.pool,
                            engine="tpu")
    return c


def stored(c, vid: int, index: int, bid: int) -> bytes:
    unit = c.cm.get_volume(vid).units[index]
    meta, got = c.pool.get(unit.node_addr).call(
        "get_shard", {"disk_id": unit.disk_id, "chunk_id": unit.chunk_id,
                      "bid": bid})
    assert meta["crc"] == reference.crc32(got)
    return got


@pytest.mark.parametrize("blob", [64 << 10, 8 << 20],
                         ids=["test_width", "lrc_cells_width"])
def test_no_pad_column_reaches_a_stored_or_a_rebuilt_shard(
        tmp_path, cuts, blob):
    t = cmode.tactic(MODE)
    c = fleet(tmp_path, blob)
    rng = np.random.default_rng(blob)
    data = rng.integers(0, 256, blob, dtype=np.uint8).tobytes()
    loc = c.access.put(data, codemode=MODE)
    vid, bid = loc.slices[0].vid, loc.slices[0].min_bid
    want = reference_lrc.stripe(data, t.n, t.m, t.l, t.az_count,
                                t.min_shard_size)
    assert want.shape[1] == blob // t.n
    for index in range(t.n + t.m + t.l):
        assert stored(c, vid, index, bid) == want[index].tobytes()
    # the PUT's step left whole blocks of its rung behind
    assert cuts and all(came < blocks for came, blocks in cuts)

    del cuts[:]
    lost = t.ec_layout_by_az()[0][0]  # a data unit of az0
    unit = c.cm.get_volume(vid).units[lost]
    c.nodes[unit.node_addr].break_disk(unit.disk_id)
    assert c.sched.mark_disk_broken(unit.disk_id) == 1
    c.drain_worker()
    assert (c.worker.completed, c.worker.failed) == (1, 0)
    assert stored(c, vid, lost, bid) == want[lost].tobytes()
    assert cuts and all(came < blocks for came, blocks in cuts)
    assert c.access.get(loc) == data
