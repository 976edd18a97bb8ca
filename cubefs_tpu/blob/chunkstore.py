"""Pythonic facade over the native chunk-store engine (ctypes).

The blobnode disk engine (reference: blobstore/blobnode/core chunk files
+ shard meta KV) as a C++ runtime component; this wrapper adds typed
errors and numpy-friendly buffers.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ..runtime import build as rt


class ChunkStoreError(Exception):
    pass


class CrcMismatchError(ChunkStoreError):
    pass


class ShardNotFoundError(ChunkStoreError):
    pass


class ChunkStore:
    def __init__(self, directory: str):
        self._lib = rt.load()
        self._h = self._lib.cs_open(directory.encode())
        if not self._h:
            raise ChunkStoreError(f"cannot open store at {directory}")
        self.directory = directory

    def _err(self) -> str:
        return (self._lib.cs_last_error(self._h) or b"").decode()

    def close(self) -> None:
        if self._h:
            self._lib.cs_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def create_chunk(self, chunk_id: int) -> None:
        if self._lib.cs_create_chunk(self._h, chunk_id) != 0:
            raise ChunkStoreError(self._err())

    def put_shard(self, chunk_id: int, bid: int, data: bytes | np.ndarray) -> int:
        buf = data.tobytes() if isinstance(data, np.ndarray) else bytes(data)
        crc = ctypes.c_uint32()
        rc = self._lib.cs_put_shard(
            self._h, chunk_id, bid, buf, len(buf), ctypes.byref(crc)
        )
        if rc != 0:
            raise ChunkStoreError(self._err())
        return crc.value

    def get_shard(self, chunk_id: int, bid: int) -> tuple[bytes, int]:
        crc = ctypes.c_uint32()
        rc = -3
        # -3: overwritten with a longer shard since the size was read;
        # read that one, never a truncated payload
        while rc == -3:
            size = self._lib.cs_shard_size(self._h, chunk_id, bid)
            if size < 0:
                raise ShardNotFoundError(self._err())
            buf = ctypes.create_string_buffer(size)
            rc = self._lib.cs_get_shard(
                self._h, chunk_id, bid, buf, size, ctypes.byref(crc)
            )
        if rc == -2:
            raise CrcMismatchError(self._err())
        if rc < 0:
            raise ShardNotFoundError(self._err())
        return ctypes.string_at(buf, rc), crc.value

    def delete_shard(self, chunk_id: int, bid: int) -> None:
        if self._lib.cs_delete_shard(self._h, chunk_id, bid) != 0:
            raise ShardNotFoundError(self._err())

    def list_shards(self, chunk_id: int, cap: int = 1 << 20) -> list[tuple[int, int, int]]:
        n = self._lib.cs_shard_count(self._h, chunk_id)
        if n < 0:
            raise ChunkStoreError(self._err())
        n = min(n, cap)
        bids = (ctypes.c_uint64 * n)()
        sizes = (ctypes.c_uint32 * n)()
        crcs = (ctypes.c_uint32 * n)()
        got = self._lib.cs_list_shards(self._h, chunk_id, bids, sizes, crcs, n)
        if got < 0:
            raise ChunkStoreError(self._err())
        return [(bids[i], sizes[i], crcs[i]) for i in range(got)]

    def shard_count(self, chunk_id: int) -> int:
        n = self._lib.cs_shard_count(self._h, chunk_id)
        if n < 0:
            raise ChunkStoreError(self._err())
        return n

    def compact(self, chunk_id: int) -> int:
        """Rewrite live shards into fresh files (reclaims tombstoned and
        overwritten space); returns bytes reclaimed."""
        got = self._lib.cs_compact_chunk(self._h, chunk_id)
        if got < 0:
            raise ChunkStoreError(self._err())
        return got

    def sync(self, chunk_id: int) -> None:
        if self._lib.cs_sync(self._h, chunk_id) != 0:
            raise ChunkStoreError(self._err())


def verified_get_shard(store: ChunkStore, chunk_id: int, bid: int, *,
                       node_addr: str | None = None, disk_id: int = 0,
                       source: str = "read") -> tuple[bytes, int]:
    """The ONE sanctioned at-rest shard read outside this module (lint
    family CFI): the native per-shard CRC check runs on every read,
    planted at-rest chaos faults surface the same way, and every
    mismatch lands in
    cubefs_integrity_corruptions_detected_total{plane="blob"} before the
    CrcMismatchError propagates to the 409 EC-reconstruction path."""
    from ..utils import faultinject, metrics

    if node_addr is not None:
        plan = faultinject.current()
        if plan is not None:
            unit = f"c{chunk_id}:b{bid}"
            kind = plan.at_rest_fault(node_addr, disk_id, unit)
            if kind is not None:
                metrics.integrity_corruptions_detected.inc(
                    plane="blob", source=source)
                raise CrcMismatchError(
                    f"shard {unit}: at-rest {kind}")
    try:
        return store.get_shard(chunk_id, bid)
    except CrcMismatchError:
        metrics.integrity_corruptions_detected.inc(
            plane="blob", source=source)
        raise


def cpu_crc32(data: bytes) -> int:
    """Native slicing-by-8 CRC32 — the CPU baseline for the TPU kernel."""
    return rt.load().cs_crc32(data, len(data))
