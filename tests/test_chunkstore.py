"""Native chunk-store engine: put/get/delete/list, crash-replay of the
index log, CRC verification on read (incl. deliberate on-disk bit-rot),
and the native CRC32 vs zlib."""

import os
import zlib

import numpy as np
import pytest

from cubefs_tpu.blob import chunkstore


@pytest.fixture
def store(tmp_path):
    with chunkstore.ChunkStore(str(tmp_path / "disk0")) as cs:
        yield cs


def _data_file(directory: str) -> str:
    return next(os.path.join(directory, f) for f in os.listdir(directory)
                if f.endswith(".data"))


def test_put_get_roundtrip(store, rng):
    store.create_chunk(1)
    data = rng.integers(0, 256, 100_000).astype(np.uint8).tobytes()
    crc = store.put_shard(1, 42, data)
    assert crc == zlib.crc32(data)
    got, got_crc = store.get_shard(1, 42)
    assert got == data and got_crc == crc


def test_overwrite_last_wins(store):
    store.create_chunk(1)
    store.put_shard(1, 7, b"old-bytes")
    store.put_shard(1, 7, b"new")
    assert store.get_shard(1, 7)[0] == b"new"
    assert store.shard_count(1) == 1


def test_delete_and_missing(store):
    store.create_chunk(2)
    store.put_shard(2, 1, b"x")
    store.delete_shard(2, 1)
    with pytest.raises(chunkstore.ShardNotFoundError):
        store.get_shard(2, 1)
    with pytest.raises(chunkstore.ShardNotFoundError):
        store.delete_shard(2, 99)


def test_list_shards(store):
    store.create_chunk(3)
    for bid in (5, 1, 9):
        store.put_shard(3, bid, bytes([bid]))
    listed = store.list_shards(3)
    assert [b for b, _, _ in listed] == [1, 5, 9]  # ordered


def test_reopen_replays_index(tmp_path, rng):
    d = str(tmp_path / "disk1")
    data = rng.integers(0, 256, 5000).astype(np.uint8).tobytes()
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(1)
        cs.put_shard(1, 10, data)
        cs.put_shard(1, 11, b"gone")
        cs.delete_shard(1, 11)
        cs.sync(1)
    with chunkstore.ChunkStore(d) as cs:
        assert cs.get_shard(1, 10)[0] == data
        with pytest.raises(chunkstore.ShardNotFoundError):
            cs.get_shard(1, 11)


def test_torn_index_tail_ignored(tmp_path):
    d = str(tmp_path / "disk2")
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(1)
        cs.put_shard(1, 1, b"keep")
    idx = next(
        os.path.join(d, f) for f in os.listdir(d) if f.endswith(".idx")
    )
    with open(idx, "ab") as f:
        f.write(b"\x13\x37" * 7)  # torn partial record
    with chunkstore.ChunkStore(d) as cs:
        assert cs.get_shard(1, 1)[0] == b"keep"


def test_bitrot_detected(tmp_path):
    d = str(tmp_path / "disk3")
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(1)
        cs.put_shard(1, 1, b"A" * 1024)
    data_file = _data_file(d)
    with open(data_file, "r+b") as f:
        f.seek(100)
        f.write(b"\x00")
    with chunkstore.ChunkStore(d) as cs:
        with pytest.raises(chunkstore.CrcMismatchError):
            cs.get_shard(1, 1)


@pytest.mark.parametrize("size", [1, 2048, 699_051, 17 << 20])
def test_roundtrip_any_shard_size(store, rng, size):
    """A read is sized from the index, so no shard is too long to come
    back: 17 MiB is over the 16 MiB buffer every read once allocated."""
    store.create_chunk(1)
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    assert store.put_shard(1, 5, data) == zlib.crc32(data)
    assert store.get_shard(1, 5) == (data, zlib.crc32(data))


@pytest.fixture
def native_gets(store, monkeypatch):
    """Records the buffer length of every native cs_get_shard call."""
    native = store._lib.cs_get_shard
    lens: list[int] = []

    def cs_get_shard(h, chunk_id, bid, buf, buf_len, out_crc):
        lens.append(buf_len)
        return native(h, chunk_id, bid, buf, buf_len, out_crc)

    monkeypatch.setattr(store._lib, "cs_get_shard", cs_get_shard)
    return lens


def test_read_buffer_is_the_shards_size(store, native_gets):
    store.create_chunk(1)
    store.put_shard(1, 1, b"s" * 2048)
    store.put_shard(1, 2, b"")
    assert store.get_shard(1, 1)[0] == b"s" * 2048
    assert store.get_shard(1, 2) == (b"", 0)
    assert native_gets == [2048, 0]


@pytest.mark.parametrize("raced", [False, True],
                         ids=["before-the-read", "between-size-and-read"])
def test_overwrite_with_longer_shard_reads_the_longer(
        store, native_gets, monkeypatch, raced):
    store.create_chunk(1)
    store.put_shard(1, 7, b"short")
    longer = b"a-longer-shard" * 100
    if raced:  # the put lands after the reader has learnt the old size
        native_size = store._lib.cs_shard_size
        stale = [native_size(store._h, 1, 7)]
        monkeypatch.setattr(
            store._lib, "cs_shard_size",
            lambda h, c, b: stale.pop() if stale else native_size(h, c, b))
    store.put_shard(1, 7, longer)
    assert store.get_shard(1, 7) == (longer, zlib.crc32(longer))
    # the short buffer was refused by the native call (-3) and retried
    assert native_gets == ([5, len(longer)] if raced else [len(longer)])


def test_missing_bid_and_missing_chunk(store):
    store.create_chunk(1)
    store.put_shard(1, 1, b"x")
    for chunk_id, bid in ((1, 2), (404, 1)):
        with pytest.raises(chunkstore.ShardNotFoundError):
            store.get_shard(chunk_id, bid)
        with pytest.raises(chunkstore.ShardNotFoundError):
            chunkstore.verified_get_shard(store, chunk_id, bid)


def test_flipped_byte_counts_through_verified_get_shard(tmp_path, rng):
    """The CRC comparison runs inside the native call on every read: a
    byte flipped in the data file is a CrcMismatchError and one count
    in cubefs_integrity_corruptions_detected_total."""
    from cubefs_tpu.utils import metrics

    d = str(tmp_path / "disk4")
    data = rng.integers(0, 256, 699_051, dtype=np.uint8).tobytes()
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(1)
        cs.put_shard(1, 1, data)
        assert chunkstore.verified_get_shard(cs, 1, 1)[0] == data
        with open(_data_file(d), "r+b") as f:
            f.seek(345_678)
            f.write(bytes([data[345_678] ^ 0x10]))
        seen = metrics.integrity_corruptions_detected.value(
            plane="blob", source="scrub")
        with pytest.raises(chunkstore.CrcMismatchError):
            chunkstore.verified_get_shard(cs, 1, 1, source="scrub")
        assert metrics.integrity_corruptions_detected.value(
            plane="blob", source="scrub") - seen == 1


def test_native_crc_matches_zlib(rng):
    for n in (0, 1, 7, 8, 63, 1024, 100_001):
        buf = rng.integers(0, 256, n).astype(np.uint8).tobytes()
        assert chunkstore.cpu_crc32(buf) == zlib.crc32(buf)


def test_compaction_reclaims_dead_space(store, rng):
    store.create_chunk(9)
    keep = {}
    for bid in range(6):
        data = rng.integers(0, 256, 5000, dtype=np.uint8).tobytes()
        store.put_shard(9, bid, data)
        keep[bid] = data
    for bid in (1, 3, 5):  # tombstone half
        store.delete_shard(9, bid)
        del keep[bid]
    store.put_shard(9, 0, b"overwritten")  # old copy becomes dead space
    keep[0] = b"overwritten"
    reclaimed = store.compact(9)
    assert reclaimed >= 3 * 5000  # at least the tombstoned bytes
    for bid, data in keep.items():
        assert store.get_shard(9, bid)[0] == data
    # writes after compaction still work and survive reopen
    store.put_shard(9, 99, b"post-compact")
    assert store.get_shard(9, 99)[0] == b"post-compact"


def test_compaction_survives_reopen(tmp_path, rng):
    d = str(tmp_path / "cdisk")
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(1)
        cs.put_shard(1, 1, b"alive")
        cs.put_shard(1, 2, b"dead")
        cs.delete_shard(1, 2)
        cs.compact(1)
        cs.put_shard(1, 3, b"after")
    with chunkstore.ChunkStore(d) as cs:
        assert cs.get_shard(1, 1)[0] == b"alive"
        assert cs.get_shard(1, 3)[0] == b"after"
        with pytest.raises(chunkstore.ShardNotFoundError):
            cs.get_shard(1, 2)


def test_stale_generation_files_swept_at_open(tmp_path, rng):
    """Crash windows around compaction can leave data files of OTHER
    generations (the replaced gen N-1, or an uncommitted gen N+1);
    reopening the chunk removes them all without touching live data."""
    d = str(tmp_path / "gdisk")
    with chunkstore.ChunkStore(d) as cs:
        cs.create_chunk(5)
        cs.put_shard(5, 1, b"live-payload")
        cs.delete_shard(5, 1)
        cs.put_shard(5, 2, b"keep")
        cs.compact(5)  # live generation is now 1
    # simulate crash-leftovers: replaced legacy gen-0 file and a stray
    # uncommitted next-generation file
    legacy = os.path.join(d, "chunk_%016x.data" % 5)
    stray = os.path.join(d, "chunk_%016x.g2.data" % 5)
    open(legacy, "wb").write(b"old generation leftover")
    open(stray, "wb").write(b"uncommitted next generation")
    with chunkstore.ChunkStore(d) as cs:
        assert cs.get_shard(5, 2)[0] == b"keep"
        assert not os.path.exists(legacy)
        assert not os.path.exists(stray)


def test_native_buffer_pool():
    """The tcmalloc/resourcepool role: size-classed slab pool with
    stats + release-free-memory ops surface (bufpool.cc)."""
    import ctypes
    import json as _json

    from cubefs_tpu.runtime import build as rt

    lib = ctypes.CDLL(rt.build())
    lib.bp_alloc.restype = ctypes.c_void_p
    lib.bp_alloc.argtypes = [ctypes.c_size_t]
    lib.bp_free.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.bp_release_free_memory.restype = ctypes.c_size_t
    lib.bp_stats_json.restype = ctypes.c_size_t
    lib.bp_stats_json.argtypes = [ctypes.c_char_p, ctypes.c_size_t]

    lib.bp_release_free_memory()  # clean slate across test ordering
    # miss -> free -> hit on the same class
    p1 = lib.bp_alloc(100_000)  # 128 KiB class
    assert p1
    lib.bp_free(p1, 100_000)
    p2 = lib.bp_alloc(120_000)  # same class: must be a cache hit
    assert p2 == p1
    lib.bp_free(p2, 120_000)

    out = ctypes.create_string_buffer(8192)
    n = lib.bp_stats_json(out, 8192)
    stats = _json.loads(out.value[:n])
    cls = next(c for c in stats["classes"] if c["size"] == 128 * 1024)
    assert cls["hits"] >= 1 and cls["cached"] >= 1
    assert stats["held_bytes"] >= 128 * 1024

    released = lib.bp_release_free_memory()
    assert released >= 128 * 1024
    n = lib.bp_stats_json(out, 8192)
    assert _json.loads(out.value[:n])["held_bytes"] == 0

    # oversize requests fall through to the system allocator
    big = lib.bp_alloc(32 << 20)
    assert big
    lib.bp_free(big, 32 << 20)
