"""A GET's payload is built from its blobs' pieces in one join:
``AccessHandler._get_blob`` hands back each blob's data units as exact
``bytes`` (the reads as they came back, a decoded unit's row, the unit
the payload ends inside cut, the units past it left out), ``_get`` joins
every piece once and checks the location's CRC over the bytes returned,
and ``cubefs_access_get_bytes_total{source}`` says how many of them were
decoded. CPU, small sizes, seeded."""

import zlib

import numpy as np
import pytest

from cubefs_tpu.blob.access import AccessConfig, payload_pieces
from cubefs_tpu.blob.types import VolumeInfo
from cubefs_tpu.codec import codemode as cmode
from cubefs_tpu.utils import metrics
from test_blob_e2e import Cluster
from test_blob_topology import LRC, AZCluster
from test_degraded_get import _lose

SHARD = 5000  # every blob of these objects is stored as units of 5000 B


def _blob_size(n: int) -> int:
    """A full blob ends 2 bytes before the end of its unit n - 1, inside
    it, as an 8 MiB blob of EC12P4 does (12 x 699,051 = 8 MiB + 4)."""
    return n * SHARD - 2


# (codemode, lost data units, blobs, bytes of the last blob)
CASES = {
    "EC12P4-healthy": ("EC12P4", (), 2, None),
    **{f"EC12P4-lost-{i}": ("EC12P4", (i,), 2, None) for i in range(12)},
    # the last blob ends inside unit 3: units 4-11 are no part of it
    "EC12P4-short-last-lost-3": ("EC12P4", (3,), 3, 3 * SHARD + 17),
    "EC12P4-short-last-lost-7": ("EC12P4", (7,), 3, 3 * SHARD + 17),
    "EC12P4-lost-0-11": ("EC12P4", (0, 11), 2, None),
    "EC3P3-healthy": ("EC3P3", (), 2, None),
    "EC3P3-lost-2": ("EC3P3", (2,), 2, 2 * SHARD + 1),
    "EC6P6-healthy": ("EC6P6", (), 2, None),
    "EC6P6-lost-1-4": ("EC6P6", (1, 4), 3, SHARD - 1),
}


def _decoded(lost, blob_lens: list[int]) -> int:
    """The payload bytes the lost units hold: each one's part of each
    blob, none of a blob that ends before it."""
    return sum(min(max(p - i * SHARD, 0), SHARD)
               for p in blob_lens for i in lost)


def _got_bytes() -> dict[str, float]:
    return {s: metrics.access_get_bytes.value(source=s)
            for s in ("read", "decoded")}


@pytest.mark.parametrize("name", list(CASES))
def test_a_get_returns_the_put_bytes_from_one_join_of_its_pieces(
        tmp_path, monkeypatch, name):
    """The GET returns the PUT's bytes with the location's CRC; every
    blob's pieces are exact `bytes` whose lengths sum to its payload;
    the counter's `decoded` is exactly the lost units' payload bytes."""
    mode, lost, blobs, last = CASES[name]
    n = cmode.tactic(mode).n
    blob = _blob_size(n)
    c = Cluster(tmp_path, n_nodes=4, disks_per_node=4)
    c.cm.allow_colocated_units = True
    c.access.cfg = AccessConfig(blob_size=blob, engine="tpu",
                                policies=[cmode.Policy(mode, 0, 1 << 62)])
    blob_lens = [blob] * (blobs - 1) + [last or blob]
    rng = np.random.default_rng(sorted(lost) + [n, blobs])
    data = rng.bytes(sum(blob_lens))
    loc = c.access.put(data)
    assert loc.crc == zlib.crc32(data)
    _lose(monkeypatch, set(lost))

    sl = loc.slices[0]
    assert sl.count == blobs
    enc = c.access._encoder(loc.codemode)
    vol = VolumeInfo.from_dict(
        c.cm_client.call("get_volume", {"vid": sl.vid})[0]["volume"])
    for k, p in enumerate(blob_lens):
        pieces, decoded = c.access._get_blob(enc, vol, sl.min_bid + k, p)
        assert all(type(piece) is bytes for piece in pieces)
        assert sum(map(len, pieces)) == p
        assert b"".join(pieces) == data[k * blob:k * blob + p]
        assert decoded == _decoded(lost, [p])

    before = _got_bytes()
    got = c.access.get(loc)
    after = _got_bytes()
    assert type(got) is bytes
    assert got == data and zlib.crc32(got) == loc.crc
    assert after["decoded"] - before["decoded"] == _decoded(lost, blob_lens)
    assert after["read"] - before["read"] == (
        len(data) - _decoded(lost, blob_lens))


def test_an_lrc_get_mended_by_its_local_stripe_joins_the_decoded_row(
        tmp_path, rng):
    """A data unit lost in an LRC volume is rebuilt inside its AZ; the
    GET returns the PUT's bytes and counts that unit's bytes decoded."""
    c = AZCluster(tmp_path, client_az="az-a")
    c.access.HEDGE_DELAY = 60.0  # no hedged parity before the local stripe
    n = cmode.tactic(LRC).n
    data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
    loc = c.access.put(data, codemode=LRC)
    vol = c.cm.get_volume(loc.slices[0].vid)
    u = vol.units[1]
    c.node_of(u.node_addr).break_disk(u.disk_id)
    local0 = metrics.reconstruct_reads.value(path="local")
    before = _got_bytes()
    got = c.access.get(loc)
    after = _got_bytes()
    assert got == data and zlib.crc32(got) == loc.crc
    assert metrics.reconstruct_reads.value(path="local") == local0 + 1
    shard = -(-len(data) // n)  # one blob: each unit ceil(50000 / n)
    assert after["decoded"] - before["decoded"] == shard
    assert after["read"] - before["read"] == len(data) - shard


def test_payload_pieces_pass_bytes_uncopied_and_convert_the_rest():
    """Whole `bytes` rows are the pieces themselves; the row the payload
    ends inside is cut; the rows past it are left out; a decoded
    ndarray row and a memoryview become exact `bytes`."""
    a, b = b"a" * 10, b"b" * 10
    c = np.full(10, ord("c"), dtype=np.uint8)
    d = memoryview(b"d" * 10)
    pieces, decoded = payload_pieces([a, b, c, d, b"e" * 10], {2}, 35)
    assert [type(p) for p in pieces] == [bytes] * 4
    assert pieces[0] is a and pieces[1] is b
    assert b"".join(pieces) == b"a" * 10 + b"b" * 10 + b"c" * 10 + b"d" * 5
    assert decoded == 10
    pieces, decoded = payload_pieces([a, c], (1,), 7)
    assert pieces == [b"a" * 7] and decoded == 0
