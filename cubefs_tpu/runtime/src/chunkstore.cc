// Native chunk-store runtime: the blobnode disk engine.
//
// Role parity: reference blobstore/blobnode/core (chunk data files with
// crc32block framing at core/storage/datafile.go:304-379 + RocksDB shard
// meta). This implementation is TPU-framework-native: a C++ engine with a
// C ABI consumed via ctypes (no cgo), storing
//   <dir>/chunk_<id>.data   — append-only shard payloads
//   <dir>/chunk_<id>.idx    — append-only fixed-width index records
// Shard lookup state is rebuilt from the index log at open (last record
// wins, delete records tombstone). CRC32 (IEEE, slicing-by-8) is computed
// on write and verified on read — this is also the CPU baseline the TPU
// CRC kernel is compared against.
//
// Build: g++ -O3 -shared -fPIC -o libcubefs_rt.so chunkstore.cc

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <cerrno>
#include <string>
#include <unordered_map>
#include <map>
#include <mutex>
#include <vector>
#include <cstdlib>
#include <dirent.h>
#include <fcntl.h>
#include <unistd.h>
#include <sys/stat.h>

#include "bufpool.h"

namespace {

// ---------------- CRC32 (IEEE reflected), slicing-by-8 ----------------
// CRC32 delegates to the shared native kernel (crc32cpu.cc): CLMUL
// folding at ~13 GB/s with a table fallback, bit-identical with zlib.
extern "C" uint32_t rt_crc32(uint32_t crc, const uint8_t* p, size_t n);

uint32_t crc32_ieee(uint32_t crc, const uint8_t* p, size_t n) {
  return rt_crc32(crc, p, n);
}

// ---------------- index format ----------------
// v2 idx files start with a header carrying the DATA FILE GENERATION:
// compaction writes a new generation data file and commits it with ONE
// atomic idx rename — there is never a moment where a live idx points at
// half-swapped data. Legacy headerless files read as generation 0.
struct __attribute__((packed)) IdxHdr {
  uint64_t magic;  // kIdxMagic
  uint64_t gen;
};
constexpr uint64_t kIdxMagic = 0xCFC17A6Eull;

struct __attribute__((packed)) IdxRec {
  uint64_t bid;      // blob id
  uint64_t offset;   // offset in .data file
  uint32_t size;     // payload bytes
  uint32_t crc;      // payload crc32
  uint32_t flags;    // 1 = delete tombstone
  uint32_t rec_crc;  // crc of the preceding fields
};

struct ShardLoc {
  uint64_t offset;
  uint32_t size;
  uint32_t crc;
};

struct Chunk {
  int data_fd = -1;
  int idx_fd = -1;
  uint64_t data_end = 0;
  uint64_t gen = 0;  // data file generation (committed via the idx)
  std::map<uint64_t, ShardLoc> shards;  // ordered for list-scans
  std::mutex mu;
};

struct Store {
  std::string dir;
  std::unordered_map<uint64_t, Chunk*> chunks;
  std::mutex mu;
  char err[256] = {0};
};

thread_local char g_err[256];

void set_err(Store* s, const char* msg) {
  snprintf(s ? s->err : g_err, 256, "%s (errno=%d %s)", msg, errno,
           errno ? strerror(errno) : "");
}

std::string chunk_path(Store* s, uint64_t id, const char* ext) {
  char buf[64];
  snprintf(buf, sizeof buf, "/chunk_%016llx.%s", (unsigned long long)id, ext);
  return s->dir + buf;
}

std::string data_path(Store* s, uint64_t id, uint64_t gen) {
  if (gen == 0) return chunk_path(s, id, "data");  // legacy name
  char buf[80];
  snprintf(buf, sizeof buf, "/chunk_%016llx.g%llu.data",
           (unsigned long long)id, (unsigned long long)gen);
  return s->dir + buf;
}

void fsync_dir(Store* s) {
  int fd = ::open(s->dir.c_str(), O_RDONLY);
  if (fd >= 0) {
    fsync(fd);
    close(fd);
  }
}

// Remove every data file of this chunk whose generation is not the
// committed one: a crash between the compaction commit rename and the
// old-file unlink leaves gen N-1 behind; a crash before the rename
// leaves gen N+1 — scan rather than guess, so nothing leaks.
void gc_stale_generations(Store* s, uint64_t id, uint64_t live_gen) {
  DIR* d = opendir(s->dir.c_str());
  if (!d) return;
  char prefix[64];
  snprintf(prefix, sizeof prefix, "chunk_%016llx.g", (unsigned long long)id);
  size_t plen = strlen(prefix);
  struct dirent* e;
  while ((e = readdir(d)) != nullptr) {
    if (strncmp(e->d_name, prefix, plen) != 0) continue;
    char* end = nullptr;
    unsigned long long g = strtoull(e->d_name + plen, &end, 10);
    if (end == e->d_name + plen || strcmp(end, ".data") != 0) continue;
    if (g != live_gen) unlink((s->dir + "/" + e->d_name).c_str());
  }
  closedir(d);
  if (live_gen != 0) unlink(chunk_path(s, id, "data").c_str());  // legacy g0
}

bool load_chunk(Store* s, uint64_t id, Chunk* c) {
  std::string ip = chunk_path(s, id, "idx");
  c->idx_fd = ::open(ip.c_str(), O_RDWR | O_CREAT | O_APPEND, 0644);
  if (c->idx_fd < 0) {
    set_err(s, "open idx file");
    return false;
  }
  // the idx header names the data generation (single commit point)
  IdxHdr hdr;
  off_t pos = 0;
  c->gen = 0;
  if (pread(c->idx_fd, &hdr, sizeof hdr, 0) == (ssize_t)sizeof hdr &&
      hdr.magic == kIdxMagic) {
    c->gen = hdr.gen;
    pos = sizeof hdr;
  }
  std::string dp = data_path(s, id, c->gen);
  c->data_fd = ::open(dp.c_str(), O_RDWR | O_CREAT, 0644);
  if (c->data_fd < 0) {
    set_err(s, "open data file");
    return false;
  }
  struct stat st;
  fstat(c->data_fd, &st);
  c->data_end = (uint64_t)st.st_size;
  // replay index log; torn/corrupt tail records are ignored (crash safety)
  IdxRec r;
  while (pread(c->idx_fd, &r, sizeof r, pos) == (ssize_t)sizeof r) {
    uint32_t expect = crc32_ieee(0, (const uint8_t*)&r, sizeof r - 4);
    if (r.rec_crc != expect) break;
    if (r.flags & 1)
      c->shards.erase(r.bid);
    else
      c->shards[r.bid] = ShardLoc{r.offset, r.size, r.crc};
    pos += sizeof r;
  }
  // crashes around compaction can leave stray data files of any other
  // generation (uncommitted gen+1, or the replaced gen-1 if the crash
  // hit between commit rename and unlink): sweep them all
  gc_stale_generations(s, id, c->gen);
  return true;
}

bool append_idx(Store* s, Chunk* c, const IdxRec& rec) {
  IdxRec r = rec;
  r.rec_crc = crc32_ieee(0, (const uint8_t*)&r, sizeof r - 4);
  if (write(c->idx_fd, &r, sizeof r) != (ssize_t)sizeof r) {
    set_err(s, "append idx");
    return false;
  }
  return true;
}

Chunk* get_chunk(Store* s, uint64_t id, bool create) {
  std::lock_guard<std::mutex> g(s->mu);
  auto it = s->chunks.find(id);
  if (it != s->chunks.end()) return it->second;
  if (!create) {
    // lazily open if the chunk exists on disk; the idx is the one file
    // every generation keeps (the data filename changes on compaction)
    std::string ip = chunk_path(s, id, "idx");
    if (access(ip.c_str(), F_OK) != 0) {
      set_err(s, "no such chunk");
      return nullptr;
    }
  }
  Chunk* c = new Chunk();
  if (!load_chunk(s, id, c)) {
    delete c;
    return nullptr;
  }
  s->chunks[id] = c;
  return c;
}

}  // namespace

extern "C" {

void* cs_open(const char* dir) {
  Store* s = new Store();
  s->dir = dir;
  ::mkdir(dir, 0755);
  struct stat st;
  if (stat(dir, &st) != 0 || !S_ISDIR(st.st_mode)) {
    set_err(nullptr, "store dir unusable");
    delete s;
    return nullptr;
  }
  return s;
}

void cs_close(void* h) {
  Store* s = (Store*)h;
  if (!s) return;
  for (auto& kv : s->chunks) {
    if (kv.second->data_fd >= 0) ::close(kv.second->data_fd);
    if (kv.second->idx_fd >= 0) ::close(kv.second->idx_fd);
    delete kv.second;
  }
  delete s;
}

const char* cs_last_error(void* h) { return h ? ((Store*)h)->err : g_err; }

int cs_create_chunk(void* h, uint64_t chunk_id) {
  Store* s = (Store*)h;
  return get_chunk(s, chunk_id, true) ? 0 : -1;
}

// Write a shard; returns 0 and fills out_crc. Overwrite of an existing
// bid appends new data and repoints the index (last-wins), matching the
// append-only chunk file + meta-update model.
int cs_put_shard(void* h, uint64_t chunk_id, uint64_t bid, const uint8_t* buf,
                 uint32_t len, uint32_t* out_crc) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, true);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  uint32_t crc = crc32_ieee(0, buf, len);
  uint64_t off = c->data_end;
  ssize_t wr = pwrite(c->data_fd, buf, len, (off_t)off);
  if (wr != (ssize_t)len) {
    set_err(s, "pwrite shard");
    return -1;
  }
  c->data_end += len;
  IdxRec rec{bid, off, len, crc, 0, 0};
  if (!append_idx(s, c, rec)) return -1;
  c->shards[bid] = ShardLoc{off, len, crc};
  if (out_crc) *out_crc = crc;
  return 0;
}

// Returns the shard's size from the index, or -1 (missing): what a reader
// sizes its buffer from. A put between this and cs_get_shard can change
// it; a longer shard then answers -3 there and the reader asks again.
int64_t cs_shard_size(void* h, uint64_t chunk_id, uint64_t bid) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  auto it = c->shards.find(bid);
  if (it == c->shards.end()) {
    set_err(s, "shard not found");
    return -1;
  }
  return (int64_t)it->second.size;
}

// Returns shard size, or -1 (missing) / -2 (crc mismatch) / -3 (short buf).
int64_t cs_get_shard(void* h, uint64_t chunk_id, uint64_t bid, uint8_t* buf,
                     uint32_t buf_len, uint32_t* out_crc) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  auto it = c->shards.find(bid);
  if (it == c->shards.end()) {
    set_err(s, "shard not found");
    return -1;
  }
  const ShardLoc& loc = it->second;
  if (buf_len < loc.size) {
    set_err(s, "buffer too small");
    return -3;
  }
  if (pread(c->data_fd, buf, loc.size, (off_t)loc.offset) != (ssize_t)loc.size) {
    set_err(s, "pread shard");
    return -1;
  }
  uint32_t crc = crc32_ieee(0, buf, loc.size);
  if (out_crc) *out_crc = crc;
  if (crc != loc.crc) {
    set_err(s, "crc mismatch");
    return -2;
  }
  return (int64_t)loc.size;
}

int cs_delete_shard(void* h, uint64_t chunk_id, uint64_t bid) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  auto it = c->shards.find(bid);
  if (it == c->shards.end()) {
    set_err(s, "shard not found");
    return -1;
  }
  IdxRec rec{bid, 0, 0, 0, 1, 0};
  if (!append_idx(s, c, rec)) return -1;
  c->shards.erase(it);
  return 0;
}

// Fill up to cap entries with (bid, size, crc) triples; returns count.
int64_t cs_list_shards(void* h, uint64_t chunk_id, uint64_t* bids,
                       uint32_t* sizes, uint32_t* crcs, int64_t cap) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  int64_t i = 0;
  for (auto& kv : c->shards) {
    if (i >= cap) break;
    bids[i] = kv.first;
    sizes[i] = kv.second.size;
    crcs[i] = kv.second.crc;
    i++;
  }
  return i;
}

int64_t cs_shard_count(void* h, uint64_t chunk_id) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  return (int64_t)c->shards.size();
}

int cs_sync(void* h, uint64_t chunk_id) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  if (fsync(c->data_fd) != 0 || fsync(c->idx_fd) != 0) {
    set_err(s, "fsync");
    return -1;
  }
  return 0;
}

// Compaction: rewrite only the LIVE shards into fresh data+idx files and
// atomically swap them in (role parity: blobnode chunk compaction,
// core/chunk/compact.go) — append-only writes + tombstones otherwise
// grow files forever. Returns bytes reclaimed, or -1.
int64_t cs_compact_chunk(void* h, uint64_t chunk_id) {
  Store* s = (Store*)h;
  Chunk* c = get_chunk(s, chunk_id, false);
  if (!c) return -1;
  std::lock_guard<std::mutex> g(c->mu);
  uint64_t new_gen = c->gen + 1;
  std::string ip = chunk_path(s, chunk_id, "idx");
  std::string ndp = data_path(s, chunk_id, new_gen);
  std::string itmp = ip + ".compact";
  int dfd = ::open(ndp.c_str(), O_RDWR | O_CREAT | O_TRUNC, 0644);
  int ifd = ::open(itmp.c_str(), O_RDWR | O_CREAT | O_TRUNC | O_APPEND, 0644);
  auto fail = [&](const char* msg, int64_t code) {
    set_err(s, msg);
    if (dfd >= 0) close(dfd);
    if (ifd >= 0) close(ifd);
    unlink(ndp.c_str());
    unlink(itmp.c_str());
    return code;
  };
  if (dfd < 0 || ifd < 0) return fail("open compact files", -1);
  IdxHdr hdr{kIdxMagic, new_gen};
  if (write(ifd, &hdr, sizeof hdr) != (ssize_t)sizeof hdr)
    return fail("compact hdr write", -1);
  uint64_t new_end = 0;
  std::map<uint64_t, ShardLoc> new_shards;
  // ONE pooled scratch for the whole pass, sized to the largest shard —
  // per-iteration allocation (pooled or not) would be pure churn, and
  // shards can exceed the pool's largest class
  uint64_t max_size = 0;
  for (auto& kv : c->shards)
    max_size = std::max(max_size, (uint64_t)kv.second.size);
  PoolBuf buf(max_size ? max_size : 1);
  for (auto& kv : c->shards) {
    const ShardLoc& loc = kv.second;
    if (pread(c->data_fd, buf.data, loc.size, (off_t)loc.offset) !=
        (ssize_t)loc.size)
      return fail("compact pread", -1);
    if (crc32_ieee(0, buf.data, loc.size) != loc.crc)
      return fail("compact crc mismatch (refusing to carry corruption)", -2);
    if (pwrite(dfd, buf.data, loc.size, (off_t)new_end) != (ssize_t)loc.size)
      return fail("compact pwrite", -1);
    IdxRec rec{kv.first, new_end, loc.size, loc.crc, 0, 0};
    rec.rec_crc = crc32_ieee(0, (const uint8_t*)&rec, sizeof rec - 4);
    if (write(ifd, &rec, sizeof rec) != (ssize_t)sizeof rec)
      return fail("compact idx write", -1);
    new_shards[kv.first] = ShardLoc{new_end, loc.size, loc.crc};
    new_end += loc.size;
  }
  fsync(dfd);
  fsync(ifd);
  int64_t reclaimed = (int64_t)c->data_end - (int64_t)new_end;
  std::string old_dp = data_path(s, chunk_id, c->gen);
  // SINGLE commit point: the idx rename flips both idx records and (via
  // the header) the data generation; a crash before it leaves the old
  // pair fully intact, a crash after it leaves the new pair in effect
  fsync_dir(s);  // make the new-generation data file's dirent durable
  if (rename(itmp.c_str(), ip.c_str()) != 0)
    return fail("compact commit rename", -1);
  fsync_dir(s);  // make the commit rename itself durable
  close(c->data_fd);
  close(c->idx_fd);
  c->data_fd = dfd;
  c->idx_fd = ifd;
  c->data_end = new_end;
  c->gen = new_gen;
  c->shards = std::move(new_shards);
  unlink(old_dp.c_str());  // best-effort; stray cleaned at next open too
  return reclaimed;
}

// CPU CRC baseline entry point (benchmarked against the TPU kernel).
uint32_t cs_crc32(const uint8_t* buf, uint64_t len) {
  return crc32_ieee(0, buf, len);
}

}  // extern "C"
