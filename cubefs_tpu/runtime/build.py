"""Build + load the native runtime (ctypes, no pybind11).

g++ compiles cubefs_tpu/runtime/src/*.cc into libcubefs_rt.so next to
this file. The .so is never committed (gitignored): it is always built
from the reviewed sources, and rebuilt whenever the content hash of the
sources (recorded beside the .so) changes — mtimes are useless after a
git clone, which does not preserve them.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_SO = os.path.join(_DIR, "libcubefs_rt.so")
_STAMP = _SO + ".srchash"
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _src_hash() -> str:
    h = hashlib.sha256()
    for f in sorted(os.listdir(_SRC)):
        if f.endswith((".cc", ".h")):
            h.update(f.encode() + b"\0")
            with open(os.path.join(_SRC, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _needs_build() -> bool:
    if not os.path.exists(_SO) or not os.path.exists(_STAMP):
        return True
    with open(_STAMP) as f:
        return f.read().strip() != _src_hash()


def build() -> str:
    # hash BEFORE compiling: if a source changes mid-compile, the stamp
    # reflects the pre-edit inputs and the next check rebuilds
    src_hash = _src_hash()
    srcs = [
        os.path.join(_SRC, f) for f in sorted(os.listdir(_SRC)) if f.endswith(".cc")
    ]
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", _SO, *srcs]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    with open(_STAMP, "w") as f:
        f.write(src_hash)
    return _SO


def load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            if _needs_build():
                build()
            lib = ctypes.CDLL(_SO)
            c = ctypes
            lib.cs_open.restype = c.c_void_p
            lib.cs_open.argtypes = [c.c_char_p]
            lib.cs_close.argtypes = [c.c_void_p]
            lib.cs_last_error.restype = c.c_char_p
            lib.cs_last_error.argtypes = [c.c_void_p]
            lib.cs_create_chunk.argtypes = [c.c_void_p, c.c_uint64]
            lib.cs_put_shard.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64,
                c.c_char_p, c.c_uint32, c.POINTER(c.c_uint32),
            ]
            lib.cs_shard_size.restype = c.c_int64
            lib.cs_shard_size.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
            lib.cs_get_shard.restype = c.c_int64
            lib.cs_get_shard.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64,
                c.c_void_p, c.c_uint32, c.POINTER(c.c_uint32),
            ]
            lib.cs_delete_shard.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
            lib.cs_list_shards.restype = c.c_int64
            lib.cs_list_shards.argtypes = [
                c.c_void_p, c.c_uint64,
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64,
            ]
            lib.cs_shard_count.restype = c.c_int64
            lib.cs_shard_count.argtypes = [c.c_void_p, c.c_uint64]
            lib.cs_sync.argtypes = [c.c_void_p, c.c_uint64]
            lib.cs_crc32.restype = c.c_uint32
            lib.cs_crc32.argtypes = [c.c_char_p, c.c_uint64]
            lib.cs_compact_chunk.restype = c.c_int64
            lib.cs_compact_chunk.argtypes = [c.c_void_p, c.c_uint64]
            # extent store (datanode engine)
            lib.es_open.restype = c.c_void_p
            lib.es_open.argtypes = [c.c_char_p]
            lib.es_close.argtypes = [c.c_void_p]
            lib.es_last_error.restype = c.c_char_p
            lib.es_last_error.argtypes = [c.c_void_p]
            lib.es_create.argtypes = [c.c_void_p, c.c_uint64]
            lib.es_write.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_char_p, c.c_uint64,
            ]
            lib.es_read.restype = c.c_int64
            lib.es_read.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_void_p, c.c_uint64,
            ]
            lib.es_size.restype = c.c_uint64
            lib.es_size.argtypes = [c.c_void_p, c.c_uint64]
            lib.es_block_crcs.restype = c.c_int64
            lib.es_block_crcs.argtypes = [c.c_void_p, c.c_uint64, c.c_void_p, c.c_int64]
            lib.es_delete.argtypes = [c.c_void_p, c.c_uint64]
            lib.es_sync.argtypes = [c.c_void_p, c.c_uint64]
            # native client (libcfs-analog C ABI over the RPC wire)
            lib.cfs_last_error.restype = c.c_char_p
            lib.cfs_last_meta.restype = c.c_char_p
            lib.cfs_blob_put.argtypes = [
                c.c_char_p, c.c_int, c.c_char_p, c.c_uint64, c.c_char_p, c.c_uint64]
            lib.cfs_blob_get.restype = c.c_int64
            lib.cfs_blob_get.argtypes = [
                c.c_char_p, c.c_int, c.c_char_p, c.c_void_p, c.c_uint64]
            lib.cfs_blob_delete.argtypes = [c.c_char_p, c.c_int, c.c_char_p]
            lib.cfs_codec_encode.argtypes = [
                c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_uint64, c.c_int,
                c.c_char_p, c.c_void_p]
            lib.cfs_codec_encode_shm.restype = c.c_int
            lib.cfs_codec_encode_shm.argtypes = [
                c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_uint64, c.c_int,
                c.c_void_p, c.c_void_p]
            lib.cfs_codec_crc32.argtypes = [
                c.c_char_p, c.c_int, c.c_uint64, c.c_char_p, c.c_uint64, c.c_void_p]
            # POSIX file surface over the FsGateway (libcfs analog)
            lib.cfs_mount.restype = c.c_void_p
            lib.cfs_mount.argtypes = [c.c_char_p, c.c_int]
            lib.cfs_unmount.argtypes = [c.c_void_p]
            lib.cfs_open.restype = c.c_int
            lib.cfs_open.argtypes = [c.c_void_p, c.c_char_p, c.c_int, c.c_int]
            lib.cfs_close.restype = c.c_int
            lib.cfs_close.argtypes = [c.c_void_p, c.c_int]
            lib.cfs_read.restype = c.c_int64
            lib.cfs_read.argtypes = [c.c_void_p, c.c_int, c.c_void_p,
                                     c.c_uint64]
            lib.cfs_pread.restype = c.c_int64
            lib.cfs_pread.argtypes = [c.c_void_p, c.c_int, c.c_void_p,
                                      c.c_uint64, c.c_uint64]
            lib.cfs_write.restype = c.c_int64
            lib.cfs_write.argtypes = [c.c_void_p, c.c_int, c.c_char_p,
                                      c.c_uint64]
            lib.cfs_pwrite.restype = c.c_int64
            lib.cfs_pwrite.argtypes = [c.c_void_p, c.c_int, c.c_char_p,
                                       c.c_uint64, c.c_uint64]
            lib.cfs_lseek.restype = c.c_int64
            lib.cfs_lseek.argtypes = [c.c_void_p, c.c_int, c.c_int64, c.c_int]
            lib.cfs_stat_path.restype = c.c_int
            lib.cfs_stat_path.argtypes = [
                c.c_void_p, c.c_char_p, c.POINTER(c.c_uint64),
                c.POINTER(c.c_uint32), c.POINTER(c.c_uint32),
                c.POINTER(c.c_uint64)]
            lib.cfs_mkdirs.restype = c.c_int
            lib.cfs_mkdirs.argtypes = [c.c_void_p, c.c_char_p]
            lib.cfs_readdir.restype = c.c_int64
            lib.cfs_readdir.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p,
                                        c.c_uint64]
            lib.cfs_unlink.restype = c.c_int
            lib.cfs_unlink.argtypes = [c.c_void_p, c.c_char_p]
            lib.cfs_rmdir.restype = c.c_int
            lib.cfs_rmdir.argtypes = [c.c_void_p, c.c_char_p]
            lib.cfs_rename.restype = c.c_int
            lib.cfs_rename.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
            lib.cfs_truncate.restype = c.c_int
            lib.cfs_truncate.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
            lib.cfs_flush.restype = c.c_int
            lib.cfs_flush.argtypes = [c.c_void_p, c.c_int]
            # ordered KV store (RocksDB-analog shard/state engine)
            lib.kv_open.restype = c.c_void_p
            lib.kv_open.argtypes = [c.c_char_p]
            lib.kv_close.argtypes = [c.c_void_p]
            lib.kv_put.restype = c.c_int
            lib.kv_put.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32,
                                   c.c_char_p, c.c_uint32]
            lib.kv_del.restype = c.c_int
            lib.kv_del.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32]
            lib.kv_get.restype = c.c_int64
            lib.kv_get.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32,
                                   c.c_void_p, c.c_uint32]
            lib.kv_count.restype = c.c_uint64
            lib.kv_count.argtypes = [c.c_void_p]
            lib.kv_scan.restype = c.c_int64
            lib.kv_scan.argtypes = [
                c.c_void_p, c.c_char_p, c.c_uint32, c.c_char_p, c.c_uint32,
                c.c_uint32, c.c_void_p, c.c_uint32,
                c.POINTER(c.c_uint32), c.POINTER(c.c_uint32)]
            lib.kv_median.restype = c.c_int64
            lib.kv_median.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32,
                                      c.c_char_p, c.c_uint32, c.c_void_p,
                                      c.c_uint32]
            lib.kv_batch.restype = c.c_int64
            lib.kv_batch.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
            lib.kv_compact.restype = c.c_int
            lib.kv_compact.argtypes = [c.c_void_p]
            lib.kv_clear.restype = c.c_int
            lib.kv_clear.argtypes = [c.c_void_p]
            lib.kv_wal_bytes.restype = c.c_uint64
            lib.kv_wal_bytes.argtypes = [c.c_void_p]
            lib.kv_snap_bytes.restype = c.c_uint64
            lib.kv_snap_bytes.argtypes = [c.c_void_p]
            # native metanode read plane (manager_op.go hot-loop analog)
            lib.ms_create.restype = c.c_void_p
            lib.ms_destroy.argtypes = [c.c_void_p]
            lib.ms_add_partition.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_uint64]
            lib.ms_drop_partition.argtypes = [c.c_void_p, c.c_uint64]
            lib.ms_set_serving.argtypes = [
                c.c_void_p, c.c_uint64, c.c_int, c.c_char_p]
            lib.ms_put_inode.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_char_p, c.c_uint32]
            lib.ms_del_inode.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
            lib.ms_ensure_dir.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
            lib.ms_del_dir.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
            lib.ms_put_dentry.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_char_p, c.c_uint32,
                c.c_uint64]
            lib.ms_del_dentry.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_char_p, c.c_uint32]
            lib.ms_clear.argtypes = [c.c_void_p, c.c_uint64]
            lib.ms_op_count.restype = c.c_uint64
            lib.ms_op_count.argtypes = [c.c_void_p]
            lib.ms_serve.restype = c.c_int
            lib.ms_serve.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
            lib.ms_stop.argtypes = [c.c_void_p]
            lib.ms_bench.restype = c.c_double
            lib.ms_bench.argtypes = [c.c_char_p, c.c_int, c.c_int,
                                     c.c_char_p, c.c_int, c.c_int]
            # native CPU GF(2^8) engine (klauspost AVX2 fallback role);
            # mat/in are raw numpy buffer pointers (zero-copy)
            lib.gf_apply.argtypes = [
                c.c_void_p, c.c_uint64, c.c_uint64, c.c_void_p,
                c.c_void_p, c.c_uint64, c.c_uint64]
            lib.gf_cpu_level.restype = c.c_int
            # shared native CRC32 (clmul folding; crc32cpu.cc)
            lib.rt_crc32.restype = c.c_uint32
            lib.rt_crc32.argtypes = [c.c_uint32, c.c_void_p, c.c_size_t]
            lib.rt_crc32_level.restype = c.c_int
            # native datanode read plane (dataserve.cc)
            lib.ds_create.restype = c.c_void_p
            lib.ds_destroy.argtypes = [c.c_void_p]
            lib.ds_add_partition.argtypes = [
                c.c_void_p, c.c_uint64, c.c_void_p, c.c_int]
            lib.ds_set_serving.argtypes = [c.c_void_p, c.c_uint64, c.c_int]
            lib.ds_drop_partition.argtypes = [c.c_void_p, c.c_uint64]
            lib.ds_set_down.argtypes = [c.c_void_p, c.c_int]
            lib.ds_op_count.restype = c.c_uint64
            lib.ds_op_count.argtypes = [c.c_void_p]
            lib.ds_take_failed.restype = c.c_int
            lib.ds_take_failed.argtypes = [c.c_void_p, c.c_void_p, c.c_int]
            lib.ds_serve.restype = c.c_int
            lib.ds_serve.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
            lib.ds_stop.argtypes = [c.c_void_p]
            _lib = lib
    return _lib
