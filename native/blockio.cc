// Native block I/O engine: fused checksum+durable-write and pread+verify.
//
// TPU-host twin of the reference's Rust hot I/O (write_block_async /
// read_block_async / verify_partial_read, dfs/chunkserver/src/
// chunkserver.rs:192-351). One ctypes call per block operation: the GIL is
// released for the whole open/CRC/write/fsync/rename (or pread/verify)
// sequence instead of bouncing between Python-level read, numpy CRC, and
// os.* syscalls.
//
// Sidecar layout must match tpudfs/chunkserver/blockstore.py exactly:
//   <4sHHII little-endian: magic "TPUM", version=1, reserved, chunk_size,
//   count> followed by count little-endian u32 chunk CRCs.
//
// Exported C ABI (loaded in tpudfs/common/native.py):
//   int64_t tpudfs_block_write(const char* data_path, const char* meta_path,
//                              const uint8_t* data, uint64_t len,
//                              uint32_t chunk, uint32_t* out_crcs);
//     -> number of chunks, or -errno on I/O failure.
//   int64_t tpudfs_block_read_verify(const char* data_path,
//                                    const char* meta_path, uint64_t offset,
//                                    uint64_t length, uint8_t* out,
//                                    int verify, uint32_t expected_chunk);
//     -> bytes copied into out, TPUDFS_EBADMETA (-200001) on malformed or
//        chunk-size-mismatched sidecars, TPUDFS_ECORRUPT (-200002) on
//        checksum mismatch, TPUDFS_ENOMETA (-200003) when the sidecar file
//        is absent, or -errno on I/O failure. expected_chunk=0 skips the
//        store-chunk-size cross-check.
//   int64_t tpudfs_block_write_staged(...same as tpudfs_block_write...);
//     -> writes data_path/meta_path EXACTLY AS GIVEN, no fsync/rename —
//        group-commit staging: the caller passes its own per-writer tmp
//        paths (unique names, so concurrent same-block stagers can never
//        truncate each other) and publishes with renames + tpudfs_syncfs.
//   int64_t tpudfs_syncfs(const char* path);
//     -> syncfs(2) on the filesystem containing path (one syscall makes a
//        whole staged batch durable), or -errno.

#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fcntl.h>
#include <string>
#include <sys/stat.h>
#include <unistd.h>
#include <vector>

extern "C" uint32_t tpudfs_crc32c(uint32_t crc, const uint8_t* buf,
                                  size_t len);

namespace {

constexpr int64_t kBadMeta = -200001;
constexpr int64_t kCorrupt = -200002;
constexpr int64_t kNoMeta = -200003;   // sidecar file absent
constexpr char kMagic[4] = {'T', 'P', 'U', 'M'};
constexpr uint16_t kVersion = 1;
constexpr size_t kHeader = 16;  // 4s + u16 + u16 + u32 + u32

// Write whole buffer to exactly `tmp`; fsync iff `durable`.
int64_t write_tmp(const std::string& tmp, const uint8_t* data, uint64_t len,
                  bool durable) {
  int fd = ::open(tmp.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
  if (fd < 0) return -errno;
  uint64_t done = 0;
  while (done < len) {
    ssize_t n = ::write(fd, data + done, len - done);
    if (n < 0) {
      if (errno == EINTR) continue;
      int e = errno;
      ::close(fd);
      ::unlink(tmp.c_str());
      return -e;
    }
    done += static_cast<uint64_t>(n);
  }
  if (durable && ::fsync(fd) != 0) {
    int e = errno;
    ::close(fd);
    ::unlink(tmp.c_str());
    return -e;
  }
  ::close(fd);
  return 0;
}

// Durable publish: write whole buffer to <path>.tmp, fsync, rename.
int64_t write_durable(const std::string& path, const uint8_t* data,
                      uint64_t len) {
  std::string tmp = path + ".tmp";
  int64_t rc = write_tmp(tmp, data, len, /*durable=*/true);
  if (rc != 0) return rc;
  if (::rename(tmp.c_str(), path.c_str()) != 0) return -errno;
  return 0;
}

void put_u16(uint8_t* p, uint16_t v) {
  p[0] = v & 0xff;
  p[1] = (v >> 8) & 0xff;
}
void put_u32(uint8_t* p, uint32_t v) {
  p[0] = v & 0xff;
  p[1] = (v >> 8) & 0xff;
  p[2] = (v >> 16) & 0xff;
  p[3] = (v >> 24) & 0xff;
}
uint32_t get_u32(const uint8_t* p) {
  return static_cast<uint32_t>(p[0]) | (static_cast<uint32_t>(p[1]) << 8) |
         (static_cast<uint32_t>(p[2]) << 16) |
         (static_cast<uint32_t>(p[3]) << 24);
}

}  // namespace

namespace {

int64_t block_write_impl(const char* data_path, const char* meta_path,
                         const uint8_t* data, uint64_t len, uint32_t chunk,
                         uint32_t* out_crcs, bool staged) {
  if (chunk == 0) return kBadMeta;
  uint64_t n = (len + chunk - 1) / chunk;
  std::vector<uint8_t> meta(kHeader + n * 4);
  std::memcpy(meta.data(), kMagic, 4);
  put_u16(meta.data() + 4, kVersion);
  put_u16(meta.data() + 6, 0);
  put_u32(meta.data() + 8, chunk);
  put_u32(meta.data() + 12, static_cast<uint32_t>(n));
  for (uint64_t i = 0; i < n; i++) {
    uint64_t off = i * chunk;
    uint64_t clen = (off + chunk <= len) ? chunk : len - off;
    uint32_t c = tpudfs_crc32c(0, data + off, clen);
    put_u32(meta.data() + kHeader + i * 4, c);
    if (out_crcs) out_crcs[i] = c;
  }
  int64_t rc;
  if (staged) {
    rc = write_tmp(data_path, data, len, /*durable=*/false);
    if (rc != 0) return rc;
    rc = write_tmp(meta_path, meta.data(), meta.size(), /*durable=*/false);
  } else {
    rc = write_durable(data_path, data, len);
    if (rc != 0) return rc;
    rc = write_durable(meta_path, meta.data(), meta.size());
  }
  if (rc != 0) return rc;
  return static_cast<int64_t>(n);
}

// Fused pread+CRC of one whole block file: reads up to `stride` bytes
// into dst in 256 KiB slices, folding the CRC32C over each slice while it
// is still cache-hot (a separate checksum pass would re-read from DRAM).
// Shared by tpudfs_blocks_read_crc and the sweep pump so the two read
// paths stay bit-identical by construction. On success *size_out = bytes
// read and *crc_out their CRC; on failure *size_out = -errno, *crc_out=0.
void read_block_crc_fused(const char* path, uint8_t* dst, uint64_t stride,
                          int64_t* size_out, uint32_t* crc_out) {
  constexpr uint64_t kSlice = 256 * 1024;
  *crc_out = 0;
  int fd = ::open(path, O_RDONLY);
  if (fd < 0) {
    *size_out = -errno;
    return;
  }
  uint64_t done = 0;
  uint32_t c = 0;
  int64_t err = 0;
  while (done < stride) {
    uint64_t want = stride - done;
    if (want > kSlice) want = kSlice;
    ssize_t r = ::pread(fd, dst + done, want, done);
    if (r < 0) {
      if (errno == EINTR) continue;
      err = -errno;
      break;
    }
    if (r == 0) break;  // EOF: block shorter than stride
    c = tpudfs_crc32c(c, dst + done, static_cast<uint64_t>(r));
    done += static_cast<uint64_t>(r);
  }
  ::close(fd);
  if (err != 0) {
    *size_out = err;
  } else {
    *size_out = static_cast<int64_t>(done);
    *crc_out = c;
  }
}

}  // namespace

extern "C" {

int64_t tpudfs_block_write(const char* data_path, const char* meta_path,
                           const uint8_t* data, uint64_t len, uint32_t chunk,
                           uint32_t* out_crcs) {
  return block_write_impl(data_path, meta_path, data, len, chunk, out_crcs,
                          /*staged=*/false);
}

int64_t tpudfs_block_write_staged(const char* data_path,
                                  const char* meta_path, const uint8_t* data,
                                  uint64_t len, uint32_t chunk,
                                  uint32_t* out_crcs) {
  return block_write_impl(data_path, meta_path, data, len, chunk, out_crcs,
                          /*staged=*/true);
}

// Batched unverified reads: pread N whole block files into one contiguous
// caller buffer (slot i at out + i*stride), releasing the GIL for the WHOLE
// batch — one ctypes call replaces N rounds of Python open/fstat/pread plus
// N thread-pool hops. Verification is the caller's business: the TPU read
// path checks the on-device CRC fold against the recorded whole-block
// checksum, so a host-side CRC pass here would be redundant work on the
// single bench core. sizes[i] = bytes read, or -errno for that slot (other
// slots still proceed). Returns the number of slots read without error.
int64_t tpudfs_blocks_read(const char** paths, uint64_t n, uint64_t stride,
                           uint8_t* out, int64_t* sizes) {
  int64_t ok = 0;
  for (uint64_t i = 0; i < n; i++) {
    uint8_t* dst = out + i * stride;
    int fd = ::open(paths[i], O_RDONLY);
    if (fd < 0) {
      sizes[i] = -errno;
      continue;
    }
    uint64_t done = 0;
    int64_t err = 0;
    while (done < stride) {
      ssize_t r = ::pread(fd, dst + done, stride - done, done);
      if (r < 0) {
        if (errno == EINTR) continue;
        err = -errno;
        break;
      }
      if (r == 0) break;  // EOF: block shorter than stride
      done += static_cast<uint64_t>(r);
    }
    ::close(fd);
    if (err != 0) {
      sizes[i] = err;
    } else {
      sizes[i] = static_cast<int64_t>(done);
      ok++;
    }
  }
  return ok;
}

// Fused variant: additionally computes each slot's WHOLE-block CRC32C
// (hardware-accelerated where available) so a host-verified batched read is
// one native call — the CPU-fallback twin of the on-device batch CRC fold
// (the caller compares crcs[i] against the CompleteFile-recorded checksum).
// The CRC is folded INTO the pread loop at 256 KiB slices, so the checksum
// pass reads L2-hot data instead of making a second trip through DRAM
// (measured on the bench host: two-pass 4.6 GB/s -> fused ~6 GB/s).
int64_t tpudfs_blocks_read_crc(const char** paths, uint64_t n,
                               uint64_t stride, uint8_t* out, int64_t* sizes,
                               uint32_t* crcs) {
  int64_t ok = 0;
  for (uint64_t i = 0; i < n; i++) {
    read_block_crc_fused(paths[i], out + i * stride, stride, &sizes[i],
                         &crcs[i]);
    if (sizes[i] >= 0) ok++;
  }
  return ok;
}

int64_t tpudfs_syncfs(const char* path) {
  int fd = ::open(path, O_RDONLY);
  if (fd < 0) return -errno;
  int rc = ::syncfs(fd);
  int e = errno;
  ::close(fd);
  return rc == 0 ? 0 : -e;
}

int64_t tpudfs_block_read_verify(const char* data_path, const char* meta_path,
                                 uint64_t offset, uint64_t length,
                                 uint8_t* out, int verify,
                                 uint32_t expected_chunk) {
  int fd = ::open(data_path, O_RDONLY);
  if (fd < 0) return -errno;
  struct stat st;
  if (::fstat(fd, &st) != 0) {
    int e = errno;
    ::close(fd);
    return -e;
  }
  uint64_t total = static_cast<uint64_t>(st.st_size);
  if (offset >= total) {
    ::close(fd);
    return 0;
  }
  if (offset + length > total) length = total - offset;

  if (!verify) {
    uint64_t done = 0;
    while (done < length) {
      ssize_t n = ::pread(fd, out + done, length - done, offset + done);
      if (n < 0) {
        if (errno == EINTR) continue;
        int e = errno;
        ::close(fd);
        return -e;
      }
      if (n == 0) break;
      done += static_cast<uint64_t>(n);
    }
    ::close(fd);
    return static_cast<int64_t>(done);
  }

  // Verified read: load the sidecar, pread the chunk-aligned span covering
  // [offset, offset+length), CRC each affected chunk, then hand back the
  // requested subrange (reference verify_partial_read chunkserver.rs:296-351).
  int mfd = ::open(meta_path, O_RDONLY);
  if (mfd < 0) {
    int e = errno;
    ::close(fd);
    return e == ENOENT ? kNoMeta : -e;
  }
  struct stat mst;
  if (::fstat(mfd, &mst) != 0 ||
      static_cast<size_t>(mst.st_size) < kHeader) {
    ::close(mfd);
    ::close(fd);
    return kBadMeta;
  }
  std::vector<uint8_t> meta(mst.st_size);
  {
    uint64_t done = 0;
    while (done < meta.size()) {
      ssize_t n = ::pread(mfd, meta.data() + done, meta.size() - done, done);
      if (n <= 0) {
        if (n < 0 && errno == EINTR) continue;
        ::close(mfd);
        ::close(fd);
        return kBadMeta;
      }
      done += static_cast<uint64_t>(n);
    }
  }
  ::close(mfd);
  if (std::memcmp(meta.data(), kMagic, 4) != 0 ||
      (meta[4] | (meta[5] << 8)) != kVersion)
    { ::close(fd); return kBadMeta; }
  uint32_t chunk = get_u32(meta.data() + 8);
  uint32_t count = get_u32(meta.data() + 12);
  if (chunk == 0 || meta.size() < kHeader + static_cast<size_t>(count) * 4)
    { ::close(fd); return kBadMeta; }
  if (expected_chunk != 0 && chunk != expected_chunk)
    { ::close(fd); return kBadMeta; }  // mismatched store chunk size

  uint64_t first = offset / chunk;
  uint64_t last = (offset + length - 1) / chunk;
  if (last >= count) {
    ::close(fd);
    return kBadMeta;
  }
  uint64_t span_off = first * chunk;
  uint64_t span_len = (last - first + 1) * chunk;
  if (span_off + span_len > total) span_len = total - span_off;
  std::vector<uint8_t> span(span_len);
  {
    uint64_t done = 0;
    while (done < span_len) {
      ssize_t n =
          ::pread(fd, span.data() + done, span_len - done, span_off + done);
      if (n < 0) {
        if (errno == EINTR) continue;
        int e = errno;
        ::close(fd);
        return -e;
      }
      if (n == 0) break;
      done += static_cast<uint64_t>(n);
    }
    span_len = done;
  }
  ::close(fd);
  for (uint64_t i = first; i <= last; i++) {
    uint64_t off = (i - first) * chunk;
    if (off >= span_len) return kCorrupt;  // shorter than sidecar says
    uint64_t clen = (off + chunk <= span_len) ? chunk : span_len - off;
    uint32_t want = get_u32(meta.data() + kHeader + i * 4);
    if (tpudfs_crc32c(0, span.data() + off, clen) != want) return kCorrupt;
  }
  uint64_t rel = offset - span_off;
  if (rel >= span_len) return 0;
  uint64_t avail = span_len - rel;
  if (length > avail) length = avail;
  std::memcpy(out, span.data() + rel, length);
  return static_cast<int64_t>(length);
}

}  // extern "C"

// ---------------------------------------------------------- sweep pump
//
// The steady-state infeed loop, native end-to-end (round-4 verdict: the
// per-round Python between tpudfs_blocks_read and device_put was 30-50%
// of the read window). Python hands the WHOLE sweep over once — block
// paths, a ring of round-sized buffers, and the per-block sizes/crcs
// result arrays — and a small fixed team of producer threads fills round
// after round ahead of the consumer, each thread one BLOCK at a time
// (fused pread+CRC, same slices as tpudfs_blocks_read_crc): reads of block
// files scale with the number of readers where one thread's pread + CRC
// is what a sweep waits for (PERF.md §5, ha_colocated_sweep).
// Python's per-round work shrinks to: one wait, one device_put of the
// filled buffer, one release. All waits
// release the GIL (ctypes), so the producers overlap the device copies —
// no executor hops, no futures, no per-block staging.

#include <algorithm>
#include <condition_variable>
#include <mutex>
#include <thread>

namespace {

// Producer threads of one sweep, where the machine has the cores and a
// round the blocks for them. From a chip sweep of 2 / 4 / 8 (PERF.md §6,
// PR 30): 2.5-2.7 / 3.7-3.9 / 4.4-4.6 GB/s into HBM.
constexpr uint64_t kSweepProducers = 8;

struct SweepPump {
  std::vector<std::string> paths;
  uint64_t stride = 0;        // bytes per block slot
  uint64_t round_blocks = 0;  // slots per round
  std::vector<uint8_t*> bufs; // ring of round-sized buffers (caller-owned)
  int64_t* sizes = nullptr;   // n entries (caller-owned)
  uint32_t* crcs = nullptr;   // n entries (caller-owned)
  uint64_t n = 0;
  int64_t nrounds = 0;
  uint64_t next_block = 0;    // the shared cursor: lowest block not claimed
  std::vector<uint64_t> open_blocks;  // per round: blocks not yet filled
  int64_t produced = 0;   // rounds fully filled (contiguous prefix)
  int64_t released = 0;   // lowest round whose buffer is NOT yet released
  int64_t ready_waits = 0;  // tpudfs_sweep_wait calls that did not block
  std::vector<bool> release_flags;
  bool stopping = false;
  std::mutex mu;
  std::condition_variable cv_producer, cv_consumer;
  std::vector<std::thread> team;

  // One producer: claim the next block, fill its slot, count it into its
  // round. A thread may start on round r+1 while a straggler finishes r.
  void run() {
    std::unique_lock<std::mutex> lk(mu);
    for (;;) {
      // The next block's ring buffer must be free again (the consumer
      // released round r - nbufs).
      cv_producer.wait(lk, [&] {
        return stopping || next_block >= n ||
               static_cast<int64_t>(next_block / round_blocks) - released <
                   static_cast<int64_t>(bufs.size());
      });
      if (stopping || next_block >= n) return;
      uint64_t i = next_block++;
      lk.unlock();
      uint64_t r = i / round_blocks;
      // Same fused pread+CRC as tpudfs_blocks_read_crc, by construction.
      read_block_crc_fused(paths[i].c_str(),
                           bufs[r % bufs.size()] +
                               (i - r * round_blocks) * stride,
                           stride, &sizes[i], &crcs[i]);
      // Publishing under mu orders the slot, sizes[i] and crcs[i] before
      // any tpudfs_sweep_wait that sees the round produced.
      lk.lock();
      if (--open_blocks[r] == 0 && static_cast<int64_t>(r) == produced) {
        while (produced < nrounds && open_blocks[produced] == 0) produced++;
        cv_consumer.notify_all();
      }
    }
  }
};

}  // namespace

extern "C" {

// -> opaque handle; caller keeps paths/bufs/sizes/crcs alive until
//    tpudfs_sweep_stop. Round r fills bufs[r % nbufs]; slot i of the
//    sweep lands at offset ((i - r*round_blocks) * stride) of its round's
//    buffer, with sizes[i] = bytes read (or -errno) and crcs[i] its
//    whole-block CRC32C.
int64_t tpudfs_sweep_start(const char** paths, uint64_t n, uint64_t stride,
                           uint64_t round_blocks, uint8_t** bufs,
                           uint64_t nbufs, int64_t* sizes, uint32_t* crcs) {
  if (n == 0 || round_blocks == 0 || nbufs == 0) return 0;
  auto* p = new SweepPump();
  p->paths.reserve(n);
  for (uint64_t i = 0; i < n; i++) p->paths.emplace_back(paths[i]);
  p->stride = stride;
  p->round_blocks = round_blocks;
  p->bufs.assign(bufs, bufs + nbufs);
  p->sizes = sizes;
  p->crcs = crcs;
  p->n = n;
  p->nrounds = static_cast<int64_t>((n + round_blocks - 1) / round_blocks);
  p->release_flags.assign(static_cast<size_t>(p->nrounds), false);
  p->open_blocks.assign(static_cast<size_t>(p->nrounds), round_blocks);
  p->open_blocks.back() = n - (p->nrounds - 1) * round_blocks;
  // One core stays with the consumer; a one-core machine runs one producer.
  uint64_t cores = std::thread::hardware_concurrency();
  uint64_t k = std::min({kSweepProducers, round_blocks,
                         cores > 1 ? cores - 1 : uint64_t{1}});
  p->team.reserve(k);
  for (uint64_t t = 0; t < k; t++) p->team.emplace_back([p] { p->run(); });
  return reinterpret_cast<int64_t>(p);
}

// Blocks (GIL released by ctypes) until round_idx is filled: every slot of
// the round and its sizes/crcs are then final. Returns the number of slots
// in that round, or -1 if the pump is stopping.
int64_t tpudfs_sweep_wait(int64_t handle, int64_t round_idx) {
  auto* p = reinterpret_cast<SweepPump*>(handle);
  std::unique_lock<std::mutex> lk(p->mu);
  if (p->produced > round_idx) p->ready_waits++;
  p->cv_consumer.wait(lk, [&] {
    return p->stopping || p->produced > round_idx;
  });
  if (p->stopping && p->produced <= round_idx) return -1;
  uint64_t lo = static_cast<uint64_t>(round_idx) * p->round_blocks;
  uint64_t hi = lo + p->round_blocks;
  if (hi > p->n) hi = p->n;
  return static_cast<int64_t>(hi - lo);
}

// Consumer is done with round_idx's buffer; the producers may refill it.
// Rounds may be released out of order; the producer gate advances over
// the contiguous released prefix.
void tpudfs_sweep_release(int64_t handle, int64_t round_idx) {
  auto* p = reinterpret_cast<SweepPump*>(handle);
  {
    std::lock_guard<std::mutex> lk(p->mu);
    if (round_idx >= 0 && round_idx < p->nrounds)
      p->release_flags[static_cast<size_t>(round_idx)] = true;
    while (p->released < p->nrounds &&
           p->release_flags[static_cast<size_t>(p->released)])
      p->released++;
  }
  p->cv_producer.notify_all();
}

// out[0] = producer threads started; out[1] = tpudfs_sweep_wait calls so
// far that found their round already produced (the team ran ahead).
void tpudfs_sweep_info(int64_t handle, int64_t* out) {
  auto* p = reinterpret_cast<SweepPump*>(handle);
  std::lock_guard<std::mutex> lk(p->mu);
  out[0] = static_cast<int64_t>(p->team.size());
  out[1] = p->ready_waits;
}

// Returns once no producer can write a ring buffer or a result array
// again: parked ones wake and leave, one inside a pread finishes its block.
void tpudfs_sweep_stop(int64_t handle) {
  auto* p = reinterpret_cast<SweepPump*>(handle);
  {
    std::lock_guard<std::mutex> lk(p->mu);
    p->stopping = true;
  }
  p->cv_producer.notify_all();
  p->cv_consumer.notify_all();
  for (auto& t : p->team) t.join();
  delete p;
}

}  // extern "C"
