// Native data-plane engine: the blockport protocol served without Python.
//
// The TPU-host twin of the reference's compiled Rust chunkserver hot path
// (WriteBlock / ReplicateBlock / ReadBlock, dfs/chunkserver/src/
// chunkserver.rs:722-1087) — and the "unwired io_uring pool, done right"
// from SURVEY §2.2: on the single-core bench host the Python asyncio
// handler costs more per 1 MiB hop than the durable write itself, so the
// chunkserver starts this engine (a small threaded TCP server) on its
// blockport and the whole chain — in-flight CRC verify (hardware CRC32C),
// group-committed durable staging, downstream forward, ack aggregation —
// runs in C++. Python keeps every control path: heartbeats, healing,
// recovery, scrubbing, fencing-term distribution, and the gRPC fallback
// handlers (which remain byte-compatible with this engine's on-disk
// format — native/blockio.cc's staged sidecar layout).
//
// Wire protocol: identical to tpudfs/common/blocknet.py —
//   u32 header_len | msgpack(header) | u64 payload_len | payload
// with the "_d" header flag marking a real (possibly empty) data field.
// Methods: WriteBlock, ReplicateBlock (same handling), ReadBlock; others
// answer UNIMPLEMENTED so a client can fall back.
//
// Chain forwarding needs no discovery here: the sender resolves every
// chain member's data port (blocknet probe) and passes "next_data_ports"
// beside "next_servers"; a 0 port means "skip the forward, let the healer
// repair" (same degraded contract as a dead tail).
//
// Python integration (ctypes, tpudfs/common/native.py):
//   int64_t  tpudfs_dataplane_start(host, hot_dir, cold_dir, chunk_size,
//                                   port, cache_blocks,
//                                   srv_cert, srv_key, srv_client_ca,
//                                   out_ca, out_cert, out_key)
//                                   -> handle or -errno (TLS paths may all
//                                   be empty/null = plaintext; unusable
//                                   TLS material fails start, it never
//                                   silently downgrades)
//   int32_t  tpudfs_dataplane_port(handle)
//   void     tpudfs_dataplane_set_term(handle, shard, term) // heartbeats
//   uint64_t tpudfs_dataplane_term(handle, shard)      // learned from reqs
//   int64_t  tpudfs_dataplane_take_terms(handle, buf, cap)
//                                   // "shard\tterm\n" dump, see below
//   int64_t  tpudfs_dataplane_take_bad(handle, buf, cap) // '\n'-joined ids
//   void     tpudfs_dataplane_invalidate(handle, block_id) // cache drop
//   void     tpudfs_dataplane_stats(handle, uint64_t out[6])
//               // writes, reads, forwards, errors, cache_hits, cache_misses
//   void     tpudfs_dataplane_read_stats(handle, uint64_t out[14])
//               // the read path's stage clocks (Engine::read_stage_stats)
//   void     tpudfs_dataplane_set_qos(handle, cfg, len)
//               // push the QosShedder config (msgpack flat map from
//               // resilience.qos_wire_config) — admission/fair-queue/
//               // rate-limit ladder, weights, jitter seed
//   void     tpudfs_dataplane_qos_stats(handle, uint64_t out[8])
//   int64_t  tpudfs_dataplane_take_qos(handle, buf, cap)
//               // per-tenant counter lines, take_terms contract
//   int64_t  tpudfs_dataplane_stop(handle)
//
// Fencing parity: reference chunkserver.rs:732-743 — requests carrying a
// stale master term are rejected FAILED_PRECONDITION; newer terms are
// learned per shard. Python pushes heartbeat-learned terms in (set_term)
// and drains request-learned terms back out (take_terms, polled from the
// heartbeat loop) so BOTH fencing planes converge — without the drain, a
// deposed master's stale write arriving on the gRPC plane would still be
// accepted until the next master heartbeat taught Python the new term.
//
// LRU block cache: full verified blocks, capacity in blocks (the native
// twin of the Python service's _LruCache, reference chunkserver.rs:67-76
// — without it the engine's hot read path re-reads + re-CRCs the disk on
// every repeated remote read). Writes and corrupt-read findings
// invalidate; Python invalidates through tpudfs_dataplane_invalidate on
// its own delete / tiering-move / recovery paths.

#include <algorithm>
#include <arpa/inet.h>
#include <atomic>
#include <cerrno>
#include <dlfcn.h>
#include <list>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <set>
#include <string>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/time.h>
#include <chrono>
#include <fcntl.h>
#include <memory>
#include <thread>
#include <unistd.h>
#include <vector>

extern "C" {
uint32_t tpudfs_crc32c(uint32_t crc, const uint8_t* buf, size_t len);
int64_t tpudfs_block_write_staged(const char* data_path,
                                  const char* meta_path, const uint8_t* data,
                                  uint64_t len, uint32_t chunk,
                                  uint32_t* out_crcs);
int64_t tpudfs_block_read_verify(const char* data_path, const char* meta_path,
                                 uint64_t offset, uint64_t length,
                                 uint8_t* out, int verify,
                                 uint32_t expected_chunk);
int64_t tpudfs_syncfs(const char* path);
}

namespace {

constexpr int64_t kCorrupt = -200002;
constexpr uint64_t kMaxHeader = 1 << 20;
constexpr uint64_t kMaxPayload = 100ull * 1024 * 1024;
// Watermark ack cadence of the streaming write path — must match
// tpudfs/common/writestream.py ACK_EVERY.
constexpr uint64_t kAckEvery = 8;
// Streamed-block ceiling — must match tpudfs/common/writestream.py
// MAX_STREAM_BYTES (the per-frame kMaxPayload cap does not bound the
// whole stream; without this check a native hop would accept streams
// the Python side rejects, and a rogue begin header could stage
// unbounded bytes).
constexpr uint64_t kMaxStreamBytes = 1ull << 30;

// ----------------------------------------------------------- msgpack mini

struct Value {
  enum Kind { NIL, BOOL, INT, FLT, STR, ASTR, AINT } kind = NIL;
  bool b = false;
  int64_t i = 0;
  double f = 0.0;
  std::string s;
  std::vector<std::string> astr;
  std::vector<int64_t> aint;
};

struct Reader {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  uint8_t u8() {
    if (p >= end) { ok = false; return 0; }
    return *p++;
  }
  uint64_t be(int n) {
    uint64_t v = 0;
    for (int k = 0; k < n; k++) v = (v << 8) | u8();
    return v;
  }
  bool bytes(size_t n, std::string* out) {
    if (static_cast<size_t>(end - p) < n) { ok = false; return false; }
    out->assign(reinterpret_cast<const char*>(p), n);
    p += n;
    return true;
  }
};

bool parse_str(Reader& r, std::string* out) {
  uint8_t t = r.u8();
  size_t n;
  if ((t & 0xe0) == 0xa0) n = t & 0x1f;
  else if (t == 0xd9) n = r.be(1);
  else if (t == 0xda) n = r.be(2);
  else if (t == 0xdb) n = r.be(4);
  else if (t == 0xc4) n = r.be(1);   // bin accepted for str slots
  else if (t == 0xc5) n = r.be(2);
  else if (t == 0xc6) n = r.be(4);
  else { r.ok = false; return false; }
  return r.bytes(n, out);
}

bool parse_int(Reader& r, int64_t* out) {
  uint8_t t = r.u8();
  if (t <= 0x7f) { *out = t; return r.ok; }
  if (t >= 0xe0) { *out = static_cast<int8_t>(t); return r.ok; }
  switch (t) {
    case 0xcc: *out = static_cast<int64_t>(r.be(1)); return r.ok;
    case 0xcd: *out = static_cast<int64_t>(r.be(2)); return r.ok;
    case 0xce: *out = static_cast<int64_t>(r.be(4)); return r.ok;
    case 0xcf: *out = static_cast<int64_t>(r.be(8)); return r.ok;
    case 0xd0: *out = static_cast<int8_t>(r.be(1)); return r.ok;
    case 0xd1: *out = static_cast<int16_t>(r.be(2)); return r.ok;
    case 0xd2: *out = static_cast<int32_t>(r.be(4)); return r.ok;
    case 0xd3: *out = static_cast<int64_t>(r.be(8)); return r.ok;
    default: r.ok = false; return false;
  }
}

// Parse one value of the limited shapes our headers use.
bool parse_value(Reader& r, Value* v) {
  if (r.p >= r.end) { r.ok = false; return false; }
  uint8_t t = *r.p;
  if (t == 0xc0) { r.u8(); v->kind = Value::NIL; return true; }
  if (t == 0xc2 || t == 0xc3) {
    r.u8();
    v->kind = Value::BOOL;
    v->b = (t == 0xc3);
    return true;
  }
  if (t == 0xca || t == 0xcb) {
    // float32/float64 — advisory headers like the deadline budget `_db`
    // ride every hop; rejecting them would tear the whole connection.
    r.u8();
    v->kind = Value::FLT;
    if (t == 0xca) {
      uint32_t bits = static_cast<uint32_t>(r.be(4));
      float f32;
      std::memcpy(&f32, &bits, sizeof(f32));
      v->f = f32;
    } else {
      uint64_t bits = r.be(8);
      std::memcpy(&v->f, &bits, sizeof(v->f));
    }
    return r.ok;
  }
  if (t <= 0x7f || t >= 0xcc) {
    if (t <= 0x7f || (t >= 0xcc && t <= 0xd3) || t >= 0xe0) {
      v->kind = Value::INT;
      return parse_int(r, &v->i);
    }
  }
  if ((t & 0xe0) == 0xa0 || t == 0xd9 || t == 0xda || t == 0xdb ||
      t == 0xc4 || t == 0xc5 || t == 0xc6) {
    v->kind = Value::STR;
    return parse_str(r, &v->s);
  }
  size_t n;
  if ((t & 0xf0) == 0x90) { r.u8(); n = t & 0x0f; }
  else if (t == 0xdc) { r.u8(); n = r.be(2); }
  else if (t == 0xdd) { r.u8(); n = r.be(4); }
  else { r.ok = false; return false; }
  // Array of strings or ints (peek first element; empty -> ASTR).
  if (n == 0) { v->kind = Value::ASTR; return true; }
  if (r.p >= r.end) { r.ok = false; return false; }
  uint8_t et = *r.p;
  // Ints are fixint/uintN/intN ONLY — str8-32 (0xd9-0xdb) and bin
  // (0xc4-0xc6) live above 0xcc too and must classify as strings (long
  // FQDN-addressed peers encode as str8).
  if (et <= 0x7f || (et >= 0xcc && et <= 0xd3) || et >= 0xe0) {
    v->kind = Value::AINT;
    v->aint.resize(n);
    for (size_t k = 0; k < n; k++)
      if (!parse_int(r, &v->aint[k])) return false;
    return true;
  }
  v->kind = Value::ASTR;
  v->astr.resize(n);
  for (size_t k = 0; k < n; k++)
    if (!parse_str(r, &v->astr[k])) return false;
  return true;
}

bool parse_header(const uint8_t* buf, size_t len,
                  std::map<std::string, Value>* out) {
  Reader r{buf, buf + len};
  uint8_t t = r.u8();
  size_t n;
  if ((t & 0xf0) == 0x80) n = t & 0x0f;
  else if (t == 0xde) n = r.be(2);
  else if (t == 0xdf) n = r.be(4);
  else return false;
  for (size_t k = 0; k < n; k++) {
    std::string key;
    if (!parse_str(r, &key)) return false;
    Value v;
    if (!parse_value(r, &v)) return false;
    (*out)[key] = std::move(v);
  }
  return r.ok;
}

struct Writer {
  std::string out;
  void raw(uint8_t b) { out.push_back(static_cast<char>(b)); }
  void be(uint64_t v, int n) {
    for (int k = n - 1; k >= 0; k--) raw((v >> (8 * k)) & 0xff);
  }
  void str(const std::string& s) {
    if (s.size() < 32) raw(0xa0 | s.size());
    else if (s.size() < 256) { raw(0xd9); be(s.size(), 1); }
    else if (s.size() < 65536) { raw(0xda); be(s.size(), 2); }
    else { raw(0xdb); be(s.size() & 0xffffffffull, 4); }  // str32
    out += s;
  }
  void uint(uint64_t v) {
    if (v < 128) raw(static_cast<uint8_t>(v));
    else if (v < 256) { raw(0xcc); be(v, 1); }
    else if (v < 65536) { raw(0xcd); be(v, 2); }
    else if (v <= 0xffffffffull) { raw(0xce); be(v, 4); }
    else { raw(0xcf); be(v, 8); }
  }
  void boolean(bool b) { raw(b ? 0xc3 : 0xc2); }
  void flt(double v) {
    raw(0xcb);
    uint64_t bits;
    std::memcpy(&bits, &v, sizeof(bits));
    be(bits, 8);
  }
  void map_head(size_t n) {
    if (n < 16) raw(0x80 | n);
    else { raw(0xde); be(n, 2); }
  }
  void astr(const std::vector<std::string>& v) {
    if (v.size() < 16) raw(0x90 | v.size());
    else { raw(0xdc); be(v.size(), 2); }
    for (const auto& s : v) str(s);
  }
  void aint(const std::vector<int64_t>& v) {
    if (v.size() < 16) raw(0x90 | v.size());
    else { raw(0xdc); be(v.size(), 2); }
    for (int64_t x : v) uint(static_cast<uint64_t>(x < 0 ? 0 : x));
  }
};

// ------------------------------------------------------------------- tls
//
// Images ship an OpenSSL RUNTIME (libssl.so.3, or only libssl.so.1.1 on
// older bases) but no dev headers, so the needed entry points — a C ABI
// stable since 1.1.0 — are declared here and resolved with dlopen at
// first use. When libssl is absent or a context
// can't be built, engine start FAILS and the chunkserver falls back to
// the asyncio blockport (which wraps Python's ssl) — never to plaintext.
// Parity target: tpudfs/common/rpc.py ServerTls/ClientTls semantics
// (reference dfs/common/src/security.rs:33-105 — TLS on every transport).

constexpr int kPem = 1;            // SSL_FILETYPE_PEM
constexpr int kVerifyPeer = 1;     // SSL_VERIFY_PEER
constexpr int kVerifyFailNo = 2;   // SSL_VERIFY_FAIL_IF_NO_PEER_CERT

constexpr int kSslErrSyscall = 5;  // SSL_ERROR_SYSCALL

struct SslApi {
  void* (*tls_server_method)();
  void* (*tls_client_method)();
  void* (*ctx_new)(void*);
  void (*ctx_free)(void*);
  int (*ctx_use_cert_chain)(void*, const char*);
  int (*ctx_use_key)(void*, const char*, int);
  int (*ctx_load_verify)(void*, const char*, const char*);
  void (*ctx_set_verify)(void*, int, void*);
  void* (*ssl_new)(void*);
  void (*ssl_free)(void*);
  int (*set_fd)(void*, int);
  int (*accept)(void*);
  int (*connect)(void*);
  int (*read)(void*, void*, int);
  int (*write)(void*, const void*, int);
  int (*shutdown)(void*);
  int (*set1_host)(void*, const char*);
  void* (*get0_param)(void*);
  int (*param_set1_ip_asc)(void*, const char*);
  long (*verify_result)(void*);
  int (*get_error)(const void*, int);
};

const SslApi* ssl_api() {
  static const SslApi* api = []() -> const SslApi* {
    // RTLD_LOCAL + an explicit same-generation libcrypto handle: the
    // hosting process (Python) may map a DIFFERENT OpenSSL generation;
    // global-scope symbol resolution could then mix ABIs on one object.
    // Candidates are PAIRS for the same reason — every entry point bound
    // below is present and ABI-stable from 1.1.0 on, so 1.1 images work.
    static const char* kPairs[][2] = {
        {"libssl.so.3", "libcrypto.so.3"},
        {"libssl.so.1.1", "libcrypto.so.1.1"},
        {"libssl.so", "libcrypto.so"},
    };
    void* h = nullptr;
    void* hc = nullptr;
    for (const auto& pair : kPairs) {
      h = ::dlopen(pair[0], RTLD_NOW | RTLD_LOCAL);
      hc = ::dlopen(pair[1], RTLD_NOW | RTLD_LOCAL);
      if (h && hc) break;
      if (h) ::dlclose(h);
      if (hc) ::dlclose(hc);
      h = hc = nullptr;
    }
    if (!h || !hc) return nullptr;
    auto sym = [&](const char* n) { return ::dlsym(h, n); };
    auto csym = [&](const char* n) { return ::dlsym(hc, n); };
    auto* a = new SslApi();
    bool ok = true;
    auto bind = [&ok](auto& fp, void* p) {
      if (!p) { ok = false; return; }
      fp = reinterpret_cast<std::remove_reference_t<decltype(fp)>>(p);
    };
    bind(a->tls_server_method, sym("TLS_server_method"));
    bind(a->tls_client_method, sym("TLS_client_method"));
    bind(a->ctx_new, sym("SSL_CTX_new"));
    bind(a->ctx_free, sym("SSL_CTX_free"));
    bind(a->ctx_use_cert_chain, sym("SSL_CTX_use_certificate_chain_file"));
    bind(a->ctx_use_key, sym("SSL_CTX_use_PrivateKey_file"));
    bind(a->ctx_load_verify, sym("SSL_CTX_load_verify_locations"));
    bind(a->ctx_set_verify, sym("SSL_CTX_set_verify"));
    bind(a->ssl_new, sym("SSL_new"));
    bind(a->ssl_free, sym("SSL_free"));
    bind(a->set_fd, sym("SSL_set_fd"));
    bind(a->accept, sym("SSL_accept"));
    bind(a->connect, sym("SSL_connect"));
    bind(a->read, sym("SSL_read"));
    bind(a->write, sym("SSL_write"));
    bind(a->shutdown, sym("SSL_shutdown"));
    bind(a->set1_host, sym("SSL_set1_host"));
    bind(a->get0_param, sym("SSL_get0_param"));
    bind(a->param_set1_ip_asc, csym("X509_VERIFY_PARAM_set1_ip_asc"));
    bind(a->verify_result, sym("SSL_get_verify_result"));
    bind(a->get_error, sym("SSL_get_error"));
    if (!ok) { delete a; return nullptr; }
    return a;
  }();
  return api;
}

// One duplex connection: plaintext fd, or TLS over it. All frame I/O
// below goes through rd/wr so handlers are transport-agnostic.
struct Stream {
  int fd = -1;
  void* ssl = nullptr;  // SSL* (owned; freed by close())

  ssize_t rd(void* b, size_t n) {
    if (ssl) {
      const SslApi* api = ssl_api();
      for (;;) {
        int r = api->read(ssl, b,
                          static_cast<int>(std::min<size_t>(n, 1u << 30)));
        if (r > 0) return r;
        // Same-args retry on an EINTR'd blocking read is permitted.
        if (api->get_error(ssl, r) == kSslErrSyscall && errno == EINTR)
          continue;
        return r;
      }
    }
    return ::recv(fd, b, n, 0);
  }
  ssize_t wr(const void* b, size_t n) {
    if (ssl) {
      const SslApi* api = ssl_api();
      for (;;) {
        int r = api->write(ssl, b,
                           static_cast<int>(std::min<size_t>(n, 1u << 30)));
        if (r > 0) return r;
        if (api->get_error(ssl, r) == kSslErrSyscall && errno == EINTR)
          continue;
        return r;
      }
    }
    return ::send(fd, b, n, MSG_NOSIGNAL);
  }
  void free_ssl() {
    if (ssl) {
      ssl_api()->shutdown(ssl);  // best-effort close_notify
      ssl_api()->ssl_free(ssl);
      ssl = nullptr;
    }
  }
};

// ------------------------------------------------------------- socket io

// Pinning socket buffers disables kernel autotuning and clamps to
// net.core.{w,r}mem_max; only worth it when the caps allow >= 1 MiB —
// then one sendmsg hands a whole block to the kernel instead of
// trickling in lockstep with a (possibly same-core) reader.
int sock_buf_size() {
  static int cached = [] {
    long w = 0, r = 0;
    for (auto [path, out] : {std::pair<const char*, long*>{
             "/proc/sys/net/core/wmem_max", &w},
         std::pair<const char*, long*>{"/proc/sys/net/core/rmem_max", &r}}) {
      FILE* f = ::fopen(path, "r");
      if (f) {
        if (::fscanf(f, "%ld", out) != 1) *out = 0;
        ::fclose(f);
      }
    }
    long cap = static_cast<long>(4 << 20);
    if (w < cap) cap = w;
    if (r < cap) cap = r;
    return cap >= (1 << 20) ? static_cast<int>(cap) : 0;
  }();
  return cached;
}

void tune_buffers(int fd) {
  int buf = sock_buf_size();
  if (!buf) return;
  ::setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &buf, sizeof(buf));
  ::setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &buf, sizeof(buf));
}

bool read_exact(Stream& s, void* buf, size_t n) {
  uint8_t* p = static_cast<uint8_t*>(buf);
  while (n) {
    ssize_t r = s.rd(p, n);
    if (r < 0) {
      if (!s.ssl && errno == EINTR) continue;
      return false;
    }
    if (r == 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool write_all(Stream& s, const void* buf, size_t n) {
  const uint8_t* p = static_cast<const uint8_t*>(buf);
  while (n) {
    ssize_t r = s.wr(p, n);
    if (r <= 0) {
      if (!s.ssl && r < 0 && errno == EINTR) continue;
      return false;
    }
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

// A frame's header and payload length; its plen payload bytes follow.
bool send_frame_head(Stream& s, const std::string& header, uint64_t plen) {
  // Length prefixes are little-endian ("<I"/"<Q") — x86-64 is LE.
  uint32_t hl = static_cast<uint32_t>(header.size());
  if (!write_all(s, &hl, 4)) return false;
  if (!write_all(s, header.data(), header.size())) return false;
  return write_all(s, &plen, 8);
}

bool send_frame(Stream& s, const std::string& header, const uint8_t* payload,
                uint64_t plen) {
  if (!send_frame_head(s, header, plen)) return false;
  if (plen && !write_all(s, payload, plen)) return false;
  return true;
}

// Exactly n bytes of fd at off; false on an error or an early end of file.
bool pread_exact(int fd, uint8_t* buf, size_t n, uint64_t off) {
  while (n) {
    ssize_t r = ::pread(fd, buf, n, static_cast<off_t>(off));
    if (r < 0 && errno == EINTR) continue;
    if (r <= 0) return false;
    buf += r;
    off += static_cast<uint64_t>(r);
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool recv_frame(Stream& s, std::map<std::string, Value>* header,
                std::vector<uint8_t>* payload) {
  uint32_t hl;
  if (!read_exact(s, &hl, 4)) return false;
  if (hl > kMaxHeader) return false;
  std::vector<uint8_t> hbuf(hl);
  if (!read_exact(s, hbuf.data(), hl)) return false;
  uint64_t pl;
  if (!read_exact(s, &pl, 8)) return false;
  if (pl > kMaxPayload) return false;
  payload->resize(pl);
  if (pl && !read_exact(s, payload->data(), pl)) return false;
  return parse_header(hbuf.data(), hl, header);
}

// Streaming variant: the payload lands in a caller-owned reusable buffer
// (the frame ring) instead of a fresh vector. A payload larger than `cap`
// cannot be consumed without losing the request boundary, so it reports a
// transport tear.
bool recv_frame_into(Stream& s, std::map<std::string, Value>* header,
                     uint8_t* buf, uint64_t cap, uint64_t* plen) {
  uint32_t hl;
  if (!read_exact(s, &hl, 4)) return false;
  if (hl > kMaxHeader) return false;
  std::vector<uint8_t> hbuf(hl);
  if (!read_exact(s, hbuf.data(), hl)) return false;
  uint64_t pl;
  if (!read_exact(s, &pl, 8)) return false;
  if (pl > cap) return false;
  if (pl && !read_exact(s, buf, pl)) return false;
  *plen = pl;
  return parse_header(hbuf.data(), hl, header);
}

// Relative deadline budget (`_db`, seconds) — float on the wire normally,
// but accept ints too (a client may send a whole-second budget).
bool deadline_budget(std::map<std::string, Value>& h, double* out) {
  auto it = h.find("_db");
  if (it == h.end()) return false;
  if (it->second.kind == Value::FLT) { *out = it->second.f; return true; }
  if (it->second.kind == Value::INT) {
    *out = static_cast<double>(it->second.i);
    return true;
  }
  return false;
}

bool write_fd_all(int fd, const void* buf, size_t n) {
  const uint8_t* p = static_cast<const uint8_t*>(buf);
  while (n) {
    ssize_t r = ::write(fd, p, n);
    if (r < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    p += static_cast<size_t>(r);
    n -= static_cast<size_t>(r);
  }
  return true;
}

// Sidecar for a streamed block, chunk CRCs accumulated frame-by-frame —
// byte-identical to blockio.cc block_write_impl's meta ("<4sHHII" + <u4
// array; x86-64 is LE so native-width stores match the wire layout).
bool write_meta_tmp(const std::string& path, uint32_t chunk,
                    const std::vector<uint32_t>& sums) {
  int fd = ::open(path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
  if (fd < 0) return false;
  uint8_t hdr[16];
  std::memcpy(hdr, "TPUM", 4);
  uint16_t ver = 1, reserved = 0;
  std::memcpy(hdr + 4, &ver, 2);
  std::memcpy(hdr + 6, &reserved, 2);
  uint32_t count = static_cast<uint32_t>(sums.size());
  std::memcpy(hdr + 8, &chunk, 4);
  std::memcpy(hdr + 12, &count, 4);
  bool ok = write_fd_all(fd, hdr, sizeof(hdr)) &&
            (sums.empty() ||
             write_fd_all(fd, sums.data(), sums.size() * sizeof(uint32_t)));
  ::close(fd);
  return ok;
}

// ---------------------------------------------------- crc32c GF(2) combine
//
// Mirror of tpudfs/common/checksum.py crc32c_combine/_zero_operator (the
// zlib crc32_combine structure): crc(A+B) = M_{len(B)} * crc(A) ^ crc(B),
// where M_n is the GF(2) matrix advancing a CRC register across n zero
// bytes. The streaming write path folds per-frame CRCs into the
// whole-block CRC with this — no second pass over the data.

constexpr uint32_t kCrcPoly = 0x82F63B78u;

uint32_t crc_matrix_times(const uint32_t mat[32], uint32_t vec) {
  uint32_t total = 0;
  for (int i = 0; vec; vec >>= 1, i++)
    if (vec & 1) total ^= mat[i];
  return total;
}

void crc_matrix_square(uint32_t out[32], const uint32_t mat[32]) {
  for (int i = 0; i < 32; i++) out[i] = crc_matrix_times(mat, mat[i]);
}

void crc_zero_operator(uint64_t len2, uint32_t result[32]) {
  uint32_t odd[32], even[32];
  odd[0] = kCrcPoly;  // operator for one zero bit
  for (int i = 1; i < 32; i++) odd[i] = 1u << (i - 1);
  crc_matrix_square(even, odd);  // two zero bits
  crc_matrix_square(odd, even);  // four zero bits
  for (int i = 0; i < 32; i++) result[i] = 1u << i;  // identity
  uint64_t n = len2;
  while (n) {
    crc_matrix_square(even, odd);  // next power-of-two byte count
    if (n & 1) {
      uint32_t tmp[32];
      for (int i = 0; i < 32; i++) tmp[i] = crc_matrix_times(even, result[i]);
      std::memcpy(result, tmp, sizeof(tmp));
    }
    std::memcpy(odd, even, sizeof(even));
    n >>= 1;
  }
}

// ------------------------------------------------------------- qos plane
//
// Thread-blocking twin of tpudfs/common/resilience.py's QosShedder: the
// same queue -> rate-limit -> shed degradation ladder, per-tenant
// time-refilled token buckets, deficit-round-robin fair queueing, and
// jittered retry_after hints. Python pushes the active QosShedder config
// in at start (and on change) via tpudfs_dataplane_set_qos — a msgpack
// flat map built by resilience.qos_wire_config() — and drains the
// per-tenant counters back out with tpudfs_dataplane_qos_stats /
// tpudfs_dataplane_take_qos, the same in/out pattern as set_term /
// take_terms.
//
// Determinism contract: both sides draw retry_after jitter from an
// identical SplitMix64 stream (seeded via the config's jitter_seed), and
// exactly ONE draw happens per rejection and ZERO per admission, so a
// fixed seed + fixed request schedule yields the same retry_after values
// from either engine (tests/test_qos.py holds this draw-for-draw).
//
// Failpoints (chaos injection) are re-read from TPUDFS_QOS_FAILPOINT at
// configure time, same grammar as resilience.QosFailpoints:
//   freeze_refill       — rate buckets stop refilling (clock frozen)
//   delay_admit=<secs>  — every admitted request stalls before dispatch
//   force_shed=<n>      — next n acquires (or in-flight stream frames)
//                         are refused unconditionally

// Deterministic jitter PRNG — algorithm-identical to
// resilience.SplitMix64 (same state advance, finalizer, and 53-bit
// double in [0, 1)).
struct SplitMix64 {
  uint64_t s = 0;
  double next() {
    s += 0x9E3779B97F4A7C15ull;
    uint64_t z = s;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
    z ^= z >> 31;
    return static_cast<double>(z >> 11) * 0x1.0p-53;
  }
};

// DRR per-visit credit — must match resilience.py QOS_DRR_QUANTUM.
constexpr int kQosDrrQuantum = 1;
// Per-tenant admission-queue bound — resilience.py QOS_QUEUE_DEPTH_DEFAULT.
constexpr int kQosQueueDepthDefault = 32;
// Rate-bucket burst floor — resilience.py QOS_MIN_BURST.
constexpr int kQosMinBurst = 1;
// Per-tenant latency ring capacity — resilience.py _LATENCY_RING.
constexpr int kQosLatencyRing = 256;

struct QosConfig {
  bool enabled = false;
  int64_t max_inflight = 64;
  double base_retry_after = 0.1;
  double rate = 0.0;    // per-tenant req/s; <= 0 = unlimited
  double burst = 1.0;   // resolved Python-side (QosShedder.burst)
  int64_t queue_depth = kQosQueueDepthDefault;
  double queue_wait = 0.25;
  double default_weight = 1.0;
  std::map<std::string, double> weights;
};

// One parked admission request (resilience._Waiter). Stack-allocated in
// Qos::acquire; the DRR holds pointers, and every state transition
// happens under Qos::mu_, so the pointer never outlives its frame.
struct QosWaiter {
  std::string tenant;
  int state = 0;  // 0 waiting, 1 admitted, 2 rejected
  std::string detail;
  double retry_after = 0.0;
  bool has_deadline = false;
  double deadline_s = 0.0;
};

// Deficit round-robin over per-tenant FIFOs — a faithful port of
// resilience.DeficitRoundRobin (Shreedhar & Varghese): quantum×weight
// credit per visit, a drained tenant forfeits leftover deficit, and an
// arbitrarily deep queue buys a tenant zero extra service.
class QosDrr {
 public:
  double quantum = static_cast<double>(kQosDrrQuantum);
  double default_weight = 1.0;
  std::map<std::string, double> weights;

  double weight(const std::string& t) const {
    auto it = weights.find(t);
    return std::max(it == weights.end() ? default_weight : it->second, 1e-6);
  }
  size_t size() const {
    size_t n = 0;
    for (const auto& kv : queues_) n += kv.second.size();
    return n;
  }
  size_t depth(const std::string& t) const {
    auto it = queues_.find(t);
    return it == queues_.end() ? 0 : it->second.size();
  }
  std::vector<std::string> tenants() const {
    return std::vector<std::string>(ring_.begin(), ring_.end());
  }
  void push(const std::string& t, QosWaiter* w) {
    ensure(t);
    queues_[t].push_back(w);
  }
  // Return an item to the head of its FIFO (dispatch backed out — the
  // tenant's rate bucket was empty at dispatch time).
  void push_front(const std::string& t, QosWaiter* w) {
    ensure(t);
    queues_[t].push_front(w);
  }
  // Next (tenant, item) by DRR order; {"", nullptr} when empty or every
  // queued tenant is in `skip` (rate-limited this dispatch round).
  std::pair<std::string, QosWaiter*> pop(const std::set<std::string>& skip) {
    if (ring_.empty()) return {std::string(), nullptr};
    // Termination: every eligible visit grows that tenant's deficit by
    // quantum*weight > 0, so within bounded cycles some head is served.
    double min_w = weight(ring_.front());
    for (const auto& t : ring_) min_w = std::min(min_w, weight(t));
    int visits = 0;
    const int max_visits = static_cast<int>(ring_.size()) *
                           (2 + static_cast<int>(1.0 / min_w));
    while (!ring_.empty() && visits <= max_visits) {
      visits++;
      const std::string tenant = ring_.front();
      if (!skip.empty() && skip.count(tenant)) {
        bool all = true;
        for (const auto& t : ring_)
          if (!skip.count(t)) { all = false; break; }
        if (all) return {std::string(), nullptr};
        rotate();
        continue;
      }
      auto& q = queues_[tenant];
      const double cost = 1.0;  // _Waiter.cost default — always 1.0 here
      if (deficit_[tenant] >= cost) {
        QosWaiter* item = q.front();
        q.pop_front();
        deficit_[tenant] -= cost;
        if (q.empty()) {
          // A drained tenant forfeits its leftover deficit: credit must
          // not accumulate while idle (classic DRR rule).
          deficit_[tenant] = 0.0;
          retire(tenant);
        }
        return {tenant, item};
      }
      deficit_[tenant] += quantum * weight(tenant);
      rotate();
    }
    return {std::string(), nullptr};
  }
  // Remove and return every queued item matching `pred` (expired
  // waiters); tenants left empty retire from the ring.
  template <typename Pred>
  std::vector<QosWaiter*> evict(Pred pred) {
    std::vector<QosWaiter*> out;
    std::vector<std::string> names;
    names.reserve(queues_.size());
    for (const auto& kv : queues_) names.push_back(kv.first);
    for (const auto& tenant : names) {
      auto& q = queues_[tenant];
      std::deque<QosWaiter*> kept;
      for (QosWaiter* w : q) {
        if (pred(w)) out.push_back(w);
        else kept.push_back(w);
      }
      q = std::move(kept);
      retire(tenant);
    }
    return out;
  }

 private:
  void ensure(const std::string& t) {
    if (queues_.find(t) == queues_.end()) {
      queues_[t];
      ring_.push_back(t);
      deficit_.emplace(t, 0.0);
    }
  }
  void rotate() {  // Python deque.rotate(-1): front -> back
    ring_.push_back(ring_.front());
    ring_.pop_front();
  }
  void retire(const std::string& t) {
    auto it = queues_.find(t);
    if (it != queues_.end() && it->second.empty()) {
      queues_.erase(it);
      deficit_.erase(t);
      for (auto rit = ring_.begin(); rit != ring_.end(); ++rit)
        if (*rit == t) { ring_.erase(rit); break; }
    }
  }
  std::map<std::string, std::deque<QosWaiter*>> queues_;
  std::deque<std::string> ring_;
  std::map<std::string, double> deficit_;
};

// Time-refilled token bucket (resilience.RateBucket): monotone refill —
// a clock that stalls (the freeze_refill failpoint) never drains tokens.
struct QosBucket {
  double rate = 0.0, burst = 0.0, tokens = 0.0, last = 0.0;
};

// The admission plane. Connection threads block in acquire() (the
// asyncio shedder parks a future; here the thread parks on a condition
// variable — same ladder, same counters, same jitter draws).
class Qos {
 public:
  bool enabled() const { return enabled_.load(std::memory_order_relaxed); }

  void configure(const QosConfig& cfg, uint64_t seed) {
    std::lock_guard<std::mutex> lk(mu_);
    cfg_ = cfg;
    // System outweighs any single default-weight tenant unless the
    // operator explicitly pinned it (QosShedder.__init__).
    if (cfg_.weights.find("system") == cfg_.weights.end())
      cfg_.weights["system"] = std::max(4.0, cfg_.default_weight);
    drr_.default_weight = cfg_.default_weight;
    drr_.weights = cfg_.weights;
    if (seed != 0) {
      rng_.s = seed;
    } else {
      // Entropy-seeded Python side: decorrelate from other servers so a
      // shed wave never hands out lockstep retry hints.
      rng_.s ^= static_cast<uint64_t>(
          std::chrono::steady_clock::now().time_since_epoch().count());
    }
    fp_freeze_refill_ = false;
    fp_delay_admit_ = 0.0;
    fp_force_shed_ = 0;
    const char* raw = ::getenv("TPUDFS_QOS_FAILPOINT");
    if (raw != nullptr) parse_failpoints(raw);
    frozen_now_ = now_s();
    buckets_.clear();
    enabled_.store(cfg_.enabled, std::memory_order_relaxed);
    cv_.notify_all();
  }

  // Admit, queue, or refuse one request. Returns true when admitted
  // (pair with release()); false fills detail + retry_after. The ladder,
  // counter increments, and jitter-draw pattern mirror
  // QosShedder.acquire exactly.
  bool acquire(const std::string& tenant, bool has_db, double budget,
               std::string* detail, double* retry_after) {
    double delay = 0.0;
    {
      std::unique_lock<std::mutex> lk(mu_);
      if (fp_force_shed_ > 0) {
        fp_force_shed_--;
        count_shed(tenant);
        *detail = "failpoint forced shed";
        *retry_after = retry_after_for(tenant);
        return false;
      }
      QosBucket* b = bucket(tenant);
      if (inflight_ < cfg_.max_inflight && drr_.size() == 0 &&
          (b == nullptr || try_spend(b))) {
        admit(tenant);
        delay = fp_delay_admit_;
      } else {
        // Contended (or over-rate): degrade to the fair queue.
        if (drr_.depth(tenant) >= static_cast<size_t>(cfg_.queue_depth)) {
          evict_expired_locked();
          if (drr_.depth(tenant) >= static_cast<size_t>(cfg_.queue_depth)) {
            count_shed(tenant);
            *detail = "tenant queue full";
            *retry_after = retry_after_for(tenant);
            return false;
          }
        }
        QosWaiter w;
        w.tenant = tenant;
        if (has_db) {
          w.has_deadline = true;
          w.deadline_s = now_s() + budget;
        }
        drr_.push(tenant, &w);
        queued_total_++;
        queued_by_tenant_[tenant]++;
        kick_locked();
        double wait = cfg_.queue_wait;
        if (has_db) wait = std::min(wait, std::max(budget, 0.0));
        const double give_up = now_s() + wait;
        while (w.state == 0) {
          double now = now_s();
          if (now >= give_up) break;
          double wake = give_up;
          if (refill_kick_at_ > 0 && refill_kick_at_ < wake)
            wake = refill_kick_at_;
          // wait_until on system_clock, NOT wait_for: wait_for rides the
          // steady clock through pthread_cond_clockwait, which TSan does
          // not intercept (gcc 10 / glibc 2.31) — the missed unlock
          // corrupts the whole mutex's happens-before state. The loop
          // re-derives its own deadline from now_s() every iteration, so
          // a wall-clock step only perturbs one wakeup.
          cv_.wait_until(
              lk, std::chrono::system_clock::now() +
                      std::chrono::microseconds(static_cast<int64_t>(
                          std::max(wake - now, 1e-4) * 1e6)));
          if (w.state == 0 && refill_kick_at_ > 0 &&
              now_s() >= refill_kick_at_) {
            // QosShedder._timer_kick twin: the first waiter past the
            // earliest bucket refill re-runs eviction + dispatch, so
            // rate-limited waiters don't rely on unrelated traffic.
            refill_kick_at_ = 0.0;
            evict_expired_locked();
            kick_locked();
          }
        }
        if (w.state == 0) {
          // Timed out parked (the asyncio TimeoutError path): reap our
          // queue slot now rather than waiting for a sweep.
          drr_.evict([&](QosWaiter* x) { return x == &w; });
          rate_limited_total_++;
          rate_limited_by_tenant_[tenant]++;
          count_shed(tenant);
          *detail = "rate limited";
          *retry_after = retry_after_for(tenant);
          return false;
        }
        if (w.state == 2) {
          *detail = w.detail;
          *retry_after = w.retry_after;
          return false;
        }
        delay = fp_delay_admit_;
      }
    }
    if (delay > 0)
      std::this_thread::sleep_for(std::chrono::duration<double>(delay));
    return true;
  }

  void release(const std::string& tenant, double elapsed) {
    std::lock_guard<std::mutex> lk(mu_);
    inflight_--;
    auto& ring = latency_by_tenant_[tenant];
    ring.push_back(elapsed);
    if (ring.size() > static_cast<size_t>(kQosLatencyRing))
      ring.pop_front();
    kick_locked();
  }

  // Mid-stream per-frame shed (force_shed failpoint re-armed by a config
  // re-push while a stream is in flight) — lets chaos abort an admitted
  // stream partway, exercising the client's Overloaded retry path.
  bool shed_frame(const std::string& tenant, double* retry_after) {
    if (!enabled()) return false;
    std::lock_guard<std::mutex> lk(mu_);
    if (fp_force_shed_ <= 0) return false;
    fp_force_shed_--;
    count_shed(tenant);
    *retry_after = retry_after_for(tenant);
    return true;
  }

  // inflight, peak_inflight, admitted_total, shed_total, queue_depth,
  // queued_total, rate_limited_total, evicted_total.
  void stats(uint64_t out[8]) {
    std::lock_guard<std::mutex> lk(mu_);
    out[0] = inflight_ > 0 ? static_cast<uint64_t>(inflight_) : 0;
    out[1] = static_cast<uint64_t>(peak_inflight_);
    out[2] = admitted_total_;
    out[3] = shed_total_;
    out[4] = static_cast<uint64_t>(drr_.size());
    out[5] = queued_total_;
    out[6] = rate_limited_total_;
    out[7] = evicted_total_;
  }

  // Per-tenant counter dump: "tenant\tadmitted\tshed\trate_limited\t
  // queue_depth\tp99_ns\n" lines. Non-destructive (counters only grow;
  // re-reading is idempotent). Returns bytes written, or -needed when
  // cap is short — the take_terms contract.
  int64_t take(char* buf, uint64_t cap) {
    std::lock_guard<std::mutex> lk(mu_);
    std::set<std::string> names;
    for (const auto& kv : admitted_by_tenant_) names.insert(kv.first);
    for (const auto& kv : shed_by_tenant_) names.insert(kv.first);
    for (const auto& kv : rate_limited_by_tenant_) names.insert(kv.first);
    for (const auto& kv : latency_by_tenant_) names.insert(kv.first);
    for (const auto& t : drr_.tenants()) names.insert(t);
    std::string joined;
    for (const auto& raw : names) {
      std::string t = raw;
      for (char& c : t)
        if (c == '\t' || c == '\n') c = '_';
      uint64_t p99_ns = 0;
      auto lit = latency_by_tenant_.find(raw);
      if (lit != latency_by_tenant_.end() && !lit->second.empty()) {
        std::vector<double> ordered(lit->second.begin(), lit->second.end());
        std::sort(ordered.begin(), ordered.end());
        size_t idx = std::min(ordered.size() - 1,
                              static_cast<size_t>(
                                  0.99 * (ordered.size() - 1)));
        p99_ns = static_cast<uint64_t>(ordered[idx] * 1e9);
      }
      joined += t + "\t" + std::to_string(counter(admitted_by_tenant_, raw)) +
                "\t" + std::to_string(counter(shed_by_tenant_, raw)) + "\t" +
                std::to_string(counter(rate_limited_by_tenant_, raw)) + "\t" +
                std::to_string(drr_.depth(raw)) + "\t" +
                std::to_string(p99_ns) + "\n";
    }
    if (joined.size() + 1 > cap)
      return -static_cast<int64_t>(joined.size() + 1);
    std::memcpy(buf, joined.c_str(), joined.size() + 1);
    return static_cast<int64_t>(joined.size());
  }

 private:
  static double now_s() {
    return std::chrono::duration<double>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
  }
  static uint64_t counter(const std::map<std::string, uint64_t>& m,
                          const std::string& t) {
    auto it = m.find(t);
    return it == m.end() ? 0 : it->second;
  }
  // tpulint: guarded-by(mu_)
  void parse_failpoints(const std::string& raw) {
    size_t pos = 0;
    while (pos <= raw.size()) {
      size_t comma = raw.find(',', pos);
      std::string part = raw.substr(
          pos, comma == std::string::npos ? std::string::npos : comma - pos);
      size_t a = part.find_first_not_of(" \t");
      size_t z = part.find_last_not_of(" \t");
      part = a == std::string::npos ? "" : part.substr(a, z - a + 1);
      size_t eq = part.find('=');
      std::string name = eq == std::string::npos ? part : part.substr(0, eq);
      std::string value = eq == std::string::npos ? "" : part.substr(eq + 1);
      if (name == "freeze_refill") fp_freeze_refill_ = true;
      else if (name == "delay_admit")
        fp_delay_admit_ = std::strtod(value.c_str(), nullptr);
      else if (name == "force_shed")
        fp_force_shed_ = std::strtol(value.c_str(), nullptr, 10);
      if (comma == std::string::npos) break;
      pos = comma + 1;
    }
  }
  // tpulint: guarded-by(mu_)
  double bucket_now() const { return fp_freeze_refill_ ? frozen_now_ : now_s(); }
  // tpulint: guarded-by(mu_)
  QosBucket* bucket(const std::string& tenant) {
    // The system tenant (control plane, untenanted clients) is never
    // rate-limited — QosShedder._bucket parity.
    if (cfg_.rate <= 0 || tenant == "system") return nullptr;
    auto it = buckets_.find(tenant);
    if (it == buckets_.end()) {
      QosBucket b;
      b.rate = cfg_.rate;
      b.burst = std::max(cfg_.burst, static_cast<double>(kQosMinBurst));
      b.tokens = b.burst;
      b.last = bucket_now();
      it = buckets_.emplace(tenant, b).first;
    }
    return &it->second;
  }
  void refill(QosBucket* b) const {
    double now = bucket_now();
    // now <= last: clock stall/regression — tokens unchanged, and last
    // keeps its high-water mark (RateBucket._refill).
    if (now > b->last) {
      b->tokens = std::min(b->burst, b->tokens + (now - b->last) * b->rate);
      b->last = now;
    }
  }
  bool try_spend(QosBucket* b) const {
    refill(b);
    if (b->tokens >= 1.0) {
      b->tokens -= 1.0;
      return true;
    }
    return false;
  }
  double bucket_retry_after(QosBucket* b) const {
    refill(b);
    if (b->tokens >= 1.0) return 0.0;
    return (1.0 - b->tokens) / b->rate;
  }
  // tpulint: guarded-by(mu_)
  double jittered(double seconds) {
    return std::max(0.0,
                    seconds * (1.0 + 0.25 * (2.0 * rng_.next() - 1.0)));
  }
  // Per-tenant retry-after: the tenant's refill schedule when it has
  // one, else the pressure-scaled global hint. Exactly one jitter draw —
  // QosShedder.retry_after_for parity.
  // tpulint: guarded-by(mu_)
  double retry_after_for(const std::string& tenant) {
    QosBucket* b = bucket(tenant);
    if (b != nullptr) {
      double hinted = bucket_retry_after(b);
      if (hinted > 0)
        return jittered(std::max(hinted, cfg_.base_retry_after));
    }
    int64_t over =
        std::max<int64_t>(0, inflight_ - cfg_.max_inflight + 1) +
        static_cast<int64_t>(drr_.size());
    double hint = cfg_.base_retry_after *
                  (1.0 + static_cast<double>(over) /
                             static_cast<double>(
                                 std::max<int64_t>(1, cfg_.max_inflight)));
    return jittered(hint);
  }
  // tpulint: guarded-by(mu_)
  void admit(const std::string& tenant) {
    inflight_++;
    admitted_total_++;
    if (inflight_ > peak_inflight_) peak_inflight_ = inflight_;
    admitted_by_tenant_[tenant]++;
  }
  // tpulint: guarded-by(mu_)
  void count_shed(const std::string& tenant) {
    shed_total_++;
    shed_by_tenant_[tenant]++;
  }
  // Drop queued waiters whose ambient deadline already expired —
  // admitting doomed work just burns an inflight slot. Caller holds mu_.
  // tpulint: guarded-by(mu_)
  void evict_expired_locked() {
    const double now = now_s();
    auto evicted = drr_.evict([&](QosWaiter* w) {
      return w->state != 0 || (w->has_deadline && now >= w->deadline_s);
    });
    uint64_t n = 0;
    for (QosWaiter* w : evicted) {
      if (w->state != 0) continue;
      n++;
      count_shed(w->tenant);
      w->state = 2;
      w->detail = "deadline expired in admission queue";
      w->retry_after = retry_after_for(w->tenant);
    }
    evicted_total_ += n;
    if (n) cv_.notify_all();
  }
  // Dispatch queued waiters into free inflight slots, DRR order
  // (QosShedder._kick). Tenants whose rate bucket is empty are skipped
  // this round (waiter returns to its FIFO head) and refill_kick_at_
  // arms the timer-kick twin above. Caller holds mu_.
  // tpulint: guarded-by(mu_)
  void kick_locked() {
    std::set<std::string> skip;
    double min_refill = -1.0;
    while (inflight_ < cfg_.max_inflight) {
      auto nxt = drr_.pop(skip);
      if (nxt.second == nullptr) break;
      const std::string& tenant = nxt.first;
      QosWaiter* w = nxt.second;
      if (w->state != 0) continue;  // timed out while parked
      if (w->has_deadline && now_s() >= w->deadline_s) {
        count_shed(tenant);
        evicted_total_++;
        w->state = 2;
        w->detail = "deadline expired in admission queue";
        w->retry_after = retry_after_for(tenant);
        continue;
      }
      QosBucket* b = bucket(tenant);
      if (b != nullptr && !try_spend(b)) {
        drr_.push_front(tenant, w);
        skip.insert(tenant);
        double refill_in = bucket_retry_after(b);
        if (min_refill < 0 || refill_in < min_refill)
          min_refill = refill_in;
        continue;
      }
      admit(tenant);
      w->state = 1;
    }
    if (min_refill >= 0 && drr_.size() > 0) {
      double at = now_s() + std::max(min_refill, 0.005);
      if (refill_kick_at_ <= 0 || at < refill_kick_at_)
        refill_kick_at_ = at;
    }
    cv_.notify_all();
  }

  std::mutex mu_;
  std::condition_variable cv_;
  QosConfig cfg_;
  std::atomic<bool> enabled_{false};
  SplitMix64 rng_;
  QosDrr drr_;
  std::map<std::string, QosBucket> buckets_;
  bool fp_freeze_refill_ = false;
  double fp_delay_admit_ = 0.0;
  int64_t fp_force_shed_ = 0;
  double frozen_now_ = 0.0;
  double refill_kick_at_ = 0.0;  // earliest pending timer-kick (0 = none)
  int64_t inflight_ = 0;
  int64_t peak_inflight_ = 0;
  uint64_t admitted_total_ = 0, shed_total_ = 0, queued_total_ = 0,
      rate_limited_total_ = 0, evicted_total_ = 0;
  std::map<std::string, uint64_t> admitted_by_tenant_, shed_by_tenant_,
      queued_by_tenant_, rate_limited_by_tenant_;
  std::map<std::string, std::deque<double>> latency_by_tenant_;
};

// --------------------------------------------------------------- engine

struct CommitEntry {
  std::string data_tmp, meta_tmp, data_final, meta_final;
  bool done = false;
  bool failed = false;
  std::string error;
};

class Engine {
 public:
  Engine(std::string host, std::string hot, std::string cold,
         uint32_t chunk, size_t cache_blocks)
      : host_(std::move(host)), hot_(std::move(hot)),
        cold_(std::move(cold)), chunk_(chunk), cache_cap_(cache_blocks) {}

  ~Engine() {
    const SslApi* api = ssl_api();
    if (api != nullptr) {
      if (srv_ctx_ != nullptr) api->ctx_free(srv_ctx_);
      if (cli_ctx_ != nullptr) api->ctx_free(cli_ctx_);
    }
  }

  // TLS config (all paths empty = plaintext). srv_*: this listener's cert
  // material, srv_client_ca non-empty = require + verify client certs
  // (mTLS, ServerTls.ca_path parity). out_*: chain-forward client side —
  // out_ca verifies downstream peers (with hostname/IP SAN matching like
  // BlockConnPool), out_cert/key presented when the cluster runs mTLS.
  // Returns false when libssl or the cert material is unusable — the
  // caller must NOT fall back to plaintext (it reports start failure and
  // Python uses the asyncio blockport instead).
  // Runs on the ctypes caller's thread before start() spawns the
  // accept/commit threads — srv_ctx_/cli_ctx_ are set-once config
  // after this returns.
  // tpulint: pre-start
  bool configure_tls(const std::string& srv_cert, const std::string& srv_key,
                     const std::string& srv_client_ca,
                     const std::string& out_ca, const std::string& out_cert,
                     const std::string& out_key) {
    if (srv_cert.empty() && srv_key.empty() && srv_client_ca.empty() &&
        out_ca.empty() && out_cert.empty() && out_key.empty())
      return true;  // plaintext: no libssl needed at all
    const SslApi* api = ssl_api();
    if (api == nullptr) return false;
    if (!srv_cert.empty()) {
      srv_ctx_ = api->ctx_new(api->tls_server_method());
      if (srv_ctx_ == nullptr) return false;
      if (api->ctx_use_cert_chain(srv_ctx_, srv_cert.c_str()) != 1 ||
          api->ctx_use_key(srv_ctx_, srv_key.c_str(), kPem) != 1)
        return false;
      if (!srv_client_ca.empty()) {
        if (api->ctx_load_verify(srv_ctx_, srv_client_ca.c_str(),
                                 nullptr) != 1)
          return false;
        api->ctx_set_verify(srv_ctx_, kVerifyPeer | kVerifyFailNo, nullptr);
      }
    }
    if (!out_ca.empty()) {
      cli_ctx_ = api->ctx_new(api->tls_client_method());
      if (cli_ctx_ == nullptr) return false;
      if (api->ctx_load_verify(cli_ctx_, out_ca.c_str(), nullptr) != 1)
        return false;
      api->ctx_set_verify(cli_ctx_, kVerifyPeer, nullptr);
      if (!out_cert.empty() && !out_key.empty()) {
        if (api->ctx_use_cert_chain(cli_ctx_, out_cert.c_str()) != 1 ||
            api->ctx_use_key(cli_ctx_, out_key.c_str(), kPem) != 1)
          return false;
      }
    }
    return true;
  }

  // tpulint: pre-start (listener setup; listen_fd_/port_ are written
  // only here, before the accept/commit threads spawn at the end)
  int64_t start(uint16_t port) {
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd_ < 0) return -errno;
    int one = 1;
    ::setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    tune_buffers(listen_fd_);
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    // Bind the same interface the gRPC listener uses (resolve names via
    // getaddrinfo) so the advertised data port is reachable wherever the
    // control port is.
    addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
    if (!host_.empty() && host_ != "localhost") {
      if (::inet_pton(AF_INET, host_.c_str(), &addr.sin_addr) != 1) {
        addrinfo hints{};
        hints.ai_family = AF_INET;
        hints.ai_socktype = SOCK_STREAM;
        addrinfo* res = nullptr;
        if (::getaddrinfo(host_.c_str(), nullptr, &hints, &res) == 0 &&
            res != nullptr) {
          addr.sin_addr =
              reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
          ::freeaddrinfo(res);
        }
      }
    }
    addr.sin_port = htons(port);
    if (::bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr),
               sizeof(addr)) != 0 ||
        ::listen(listen_fd_, 128) != 0) {
      int e = errno;
      ::close(listen_fd_);
      return -e;
    }
    socklen_t alen = sizeof(addr);
    ::getsockname(listen_fd_, reinterpret_cast<sockaddr*>(&addr), &alen);
    port_ = ntohs(addr.sin_port);
    running_.store(true);
    commit_thread_ = std::thread([this] { commit_loop(); });
    accept_thread_ = std::thread([this] { accept_loop(); });
    return port_;
  }

  // Returns true when every connection thread has exited; false means a
  // detached thread is still inside a handler (e.g. a slow disk stage) —
  // the caller must then LEAK the engine rather than delete it out from
  // under the thread.
  bool stop() {
    running_.store(false);
    ::shutdown(listen_fd_, SHUT_RDWR);
    ::close(listen_fd_);
    {
      std::lock_guard<std::mutex> g(conns_mu_);
      for (int fd : conns_) ::shutdown(fd, SHUT_RDWR);
    }
    if (accept_thread_.joinable()) accept_thread_.join();
    // Connection threads are detached; the shutdowns above unblock socket
    // waits immediately. Allow a generous window for in-flight disk work.
    for (int i = 0; i < 1000 && active_.load() > 0; i++)
      std::this_thread::sleep_for(std::chrono::milliseconds(10));
    {
      // Notify under commit_mu_: the commit loop's predicated wait
      // re-checks running_ with the mutex held, so pairing the notify
      // with the lock means it can never fire in the window between the
      // loop's predicate check and its block — the shutdown wakeup
      // cannot be lost.
      std::lock_guard<std::mutex> g(commit_mu_);
      commit_cv_.notify_all();
    }
    if (commit_thread_.joinable()) commit_thread_.join();
    return active_.load() == 0;
  }

  int32_t port() const { return port_; }

  // Epoch fencing is scoped per issuing Raft group (shard): one shard's
  // failover must not fence writes allocated by a different shard.
  void set_term(const std::string& shard, uint64_t t) {
    std::lock_guard<std::mutex> g(term_mu_);
    uint64_t& cur = terms_[shard];
    if (t > cur) cur = t;
  }
  uint64_t term(const std::string& shard) {
    std::lock_guard<std::mutex> g(term_mu_);
    auto it = terms_.find(shard);
    return it == terms_.end() ? 0 : it->second;
  }

  // Dump every (shard, term) pair as "shard\tterm\n" lines — the
  // heartbeat loop polls this so request-learned terms reach the Python
  // fencing plane too. Non-destructive (terms only ever grow; re-reading
  // is idempotent). Returns bytes written, or -needed when cap is short.
  int64_t take_terms(char* buf, uint64_t cap) {
    std::lock_guard<std::mutex> g(term_mu_);
    std::string joined;
    for (const auto& kv : terms_)
      joined += kv.first + "\t" + std::to_string(kv.second) + "\n";
    if (joined.size() + 1 > cap)
      return -static_cast<int64_t>(joined.size() + 1);
    std::memcpy(buf, joined.c_str(), joined.size() + 1);
    return static_cast<int64_t>(joined.size());
  }

  int64_t take_bad(char* buf, uint64_t cap) {
    // Drain as many WHOLE ids as fit; the rest stay for the next poll —
    // an oversized backlog must never wedge reporting.
    std::lock_guard<std::mutex> g(bad_mu_);
    std::string joined;
    auto it = bad_.begin();
    while (it != bad_.end()) {
      size_t need = joined.size() + (joined.empty() ? 0 : 1) + it->size() + 1;
      if (need > cap) break;
      if (!joined.empty()) joined += '\n';
      joined += *it;
      it = bad_.erase(it);
    }
    if (joined.empty() && !bad_.empty())
      return -static_cast<int64_t>(bad_.begin()->size() + 1);
    std::memcpy(buf, joined.c_str(), joined.size() + 1);
    return static_cast<int64_t>(joined.size());
  }

  void stats(uint64_t out[6]) const {
    out[0] = writes_.load();
    out[1] = reads_.load();
    out[2] = forwards_.load();
    out[3] = errors_.load();
    out[4] = cache_hits_.load();
    out[5] = cache_misses_.load();
  }

  // Write-path stage budget (round-5: isolate fsync scheduling from
  // protocol cost in the chain write). All nanoseconds except the counts.
  void stage_stats(uint64_t out[8]) const {
    out[0] = stage_ns_.load();        // tpudfs_block_write_staged wall
    out[1] = commit_wait_ns_.load();  // queued -> durable (group commit)
    out[2] = syncfs_ns_.load();       // commit loop's syncfs calls
    out[3] = fwd_ack_ns_.load();      // downstream ack recv wall
    out[4] = commit_batches_.load();
    out[5] = commit_entries_.load();
    out[6] = staged_bytes_.load();
    out[7] = rename_ns_.load();       // publish renames
  }

  // Streaming write pipeline occupancy — slot order MUST match the
  // Python service's _stream_stats keys (service.py stream_stage_stats
  // zips them): net_ns, crc_ns, disk_ns, fanout_ns, frames, streams,
  // stream_bytes, aborts.
  void stream_stage_stats(uint64_t out[8]) const {
    out[0] = stream_net_ns_.load();
    out[1] = stream_crc_ns_.load();
    out[2] = stream_disk_ns_.load();
    out[3] = stream_fanout_ns_.load();
    out[4] = stream_frames_.load();
    out[5] = streams_started_.load();
    out[6] = stream_bytes_.load();
    out[7] = stream_aborts_.load();
  }

  // The read path's stage clocks — slot order MUST match the Python
  // service's _read_stats keys (service.py read_stage_stats zips them).
  // ReadBlock: calls, bytes sent, read_ns (handler start to the response
  // header built: cache lookup, stat, pread + sidecar verify, or the
  // cache copy), send_ns (send_frame), NOT_FOUND answers, calls served
  // from the block cache, QoS admission wait. ReadBlocks: frames, slots,
  // bytes sent, read_ns (the engine's own read time: every slot's open and
  // fstat up to the header, then each block's pread), send_ns (the header's
  // and each block's write, summed: paced by the transfer to the client),
  // slots answered -1, QoS admission wait, frames torn after their header
  // (a pread failed or came up short).
  // Each connection is served by a thread of its own, so there is no queue
  // on this side but admission (0 while QoS is off).
  void read_stage_stats(uint64_t out[15]) const {
    out[0] = rb_calls_.load();
    out[1] = rb_bytes_.load();
    out[2] = rb_read_ns_.load();
    out[3] = rb_send_ns_.load();
    out[4] = rb_not_found_.load();
    out[5] = rb_cache_calls_.load();
    out[6] = rb_admit_ns_.load();
    out[7] = rbs_frames_.load();
    out[8] = rbs_slots_.load();
    out[9] = rbs_bytes_.load();
    out[10] = rbs_read_ns_.load();
    out[11] = rbs_send_ns_.load();
    out[12] = rbs_missing_.load();
    out[13] = rbs_admit_ns_.load();
    out[14] = rbs_torn_.load();
  }

  // ------------------------------------------------------------ qos plane

  // Parse + install a QoS config pushed from Python (resilience.
  // qos_wire_config() as a msgpack flat map — scalars and string arrays
  // only, which is all parse_header reads). Unknown keys are ignored; a
  // map with enabled=0 switches admission off for subsequent requests.
  void qos_configure(const uint8_t* buf, uint64_t len) {
    std::map<std::string, Value> h;
    if (!parse_header(buf, static_cast<size_t>(len), &h)) return;
    auto num = [&](const char* key, double dflt) {
      auto it = h.find(key);
      if (it == h.end()) return dflt;
      if (it->second.kind == Value::FLT) return it->second.f;
      if (it->second.kind == Value::INT)
        return static_cast<double>(it->second.i);
      return dflt;
    };
    QosConfig cfg;
    cfg.enabled = num("enabled", 0) != 0;
    cfg.max_inflight = static_cast<int64_t>(num("max_inflight", 64));
    cfg.base_retry_after = num("base_retry_after", 0.1);
    cfg.rate = num("rate", 0.0);
    cfg.burst = num("burst", 1.0);
    cfg.queue_depth =
        static_cast<int64_t>(num("queue_depth", kQosQueueDepthDefault));
    cfg.queue_wait = num("queue_wait", 0.25);
    cfg.default_weight = num("default_weight", 1.0);
    auto wit = h.find("weights");
    if (wit != h.end() && wit->second.kind == Value::ASTR) {
      // Weights travel flat as "tenant=weight" strings (the header
      // parser has no nested maps); split on the LAST '=' so tenant
      // names containing '=' still round-trip.
      for (const auto& pair : wit->second.astr) {
        size_t eq = pair.rfind('=');
        if (eq == std::string::npos || eq == 0) continue;
        cfg.weights[pair.substr(0, eq)] =
            std::strtod(pair.c_str() + eq + 1, nullptr);
      }
    }
    uint64_t seed = 0;
    auto sit = h.find("jitter_seed");
    if (sit != h.end() && sit->second.kind == Value::INT)
      seed = static_cast<uint64_t>(sit->second.i);
    qos_.configure(cfg, seed);
  }

  void qos_stats(uint64_t out[8]) { qos_.stats(out); }
  int64_t take_qos(char* buf, uint64_t cap) { return qos_.take(buf, cap); }

  // ------------------------------------------------------ LRU block cache

  using CacheData = std::shared_ptr<std::vector<uint8_t>>;

  CacheData cache_get(const std::string& id) {
    if (!cache_cap_) return nullptr;
    std::lock_guard<std::mutex> g(cache_mu_);
    auto it = cache_map_.find(id);
    if (it == cache_map_.end()) {
      cache_misses_.fetch_add(1);
      return nullptr;
    }
    cache_list_.splice(cache_list_.begin(), cache_list_, it->second);
    cache_hits_.fetch_add(1);
    return it->second->second;
  }

  // Invalidation generation for the insert-vs-invalidate race: a reader
  // captures cache_gen(id) BEFORE its pread; cache_put only inserts if no
  // invalidation landed in between (checked under cache_mu_, so an
  // invalidate can never slip between the check and the insert — the
  // re-stat signature alone leaves a window between its stat and the
  // put).
  uint64_t cache_gen(const std::string& id) {
    if (!cache_cap_) return 0;
    std::lock_guard<std::mutex> g(cache_mu_);
    auto it = inval_gen_.find(id);
    return it == inval_gen_.end() ? gen_floor_ : it->second;
  }

  void cache_put(const std::string& id, CacheData data, uint64_t gen) {
    if (!cache_cap_) return;
    std::lock_guard<std::mutex> g(cache_mu_);
    auto git = inval_gen_.find(id);
    if ((git == inval_gen_.end() ? gen_floor_ : git->second) != gen)
      return;  // a write/invalidate raced the read: don't pin old bytes
    auto it = cache_map_.find(id);
    if (it != cache_map_.end()) {
      it->second->second = std::move(data);
      cache_list_.splice(cache_list_.begin(), cache_list_, it->second);
      return;
    }
    cache_list_.emplace_front(id, std::move(data));
    cache_map_[id] = cache_list_.begin();
    while (cache_list_.size() > cache_cap_) {
      cache_map_.erase(cache_list_.back().first);
      cache_list_.pop_back();
    }
  }

  void cache_invalidate(const std::string& id) {
    if (!cache_cap_) return;
    std::lock_guard<std::mutex> g(cache_mu_);
    // Bound the generation map. Generations come from one monotone
    // counter and a clear raises the floor past every value ever issued,
    // so an id evicted from the map can never REUSE a generation a
    // concurrent reader captured earlier (a plain per-id counter reset
    // to zero could: capture 0 -> invalidate -> clear -> absent reads 0
    // again and the stale cache_put would pass).
    if (inval_gen_.size() > 65536) {
      inval_gen_.clear();
      gen_floor_ = ++gen_counter_;
    }
    inval_gen_[id] = ++gen_counter_;
    auto it = cache_map_.find(id);
    if (it != cache_map_.end()) {
      cache_list_.erase(it->second);
      cache_map_.erase(it);
    }
  }

  // Write-vs-read race guard for cache inserts: a block republished
  // between the pread and the cache_put must NOT be cached from the old
  // bytes (the concurrent writer's invalidate would land before our
  // insert, pinning stale data until the next write). The publish is a
  // rename (new inode), so re-statting and comparing (inode, mtime, size)
  // from before the read detects it — the same signature discipline the
  // Python service's cache uses (service.py _block_sig).
  static bool same_sig(const struct stat& a, const struct stat& b) {
    return a.st_ino == b.st_ino && a.st_size == b.st_size &&
           a.st_mtim.tv_sec == b.st_mtim.tv_sec &&
           a.st_mtim.tv_nsec == b.st_mtim.tv_nsec;
  }

 private:
  // ------------------------------------------------------------- accept

  void accept_loop() {
    while (running_.load()) {
      int fd = ::accept(listen_fd_, nullptr, nullptr);
      if (fd < 0) {
        if (errno == EINTR) continue;
        return;  // listener closed
      }
      int one = 1;
      ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      tune_buffers(fd);
      {
        std::lock_guard<std::mutex> g(conns_mu_);
        conns_.insert(fd);
      }
      active_.fetch_add(1);
      std::thread([this, fd] {
        Stream s{fd, nullptr};
        bool handshake_ok = true;
        if (srv_ctx_ != nullptr) {
          const SslApi* api = ssl_api();
          s.ssl = api->ssl_new(srv_ctx_);
          handshake_ok = s.ssl != nullptr && api->set_fd(s.ssl, fd) == 1 &&
                         api->accept(s.ssl) == 1;
        }
        if (handshake_ok) conn_loop(s);
        s.free_ssl();
        {
          std::lock_guard<std::mutex> g2(conns_mu_);
          conns_.erase(fd);
        }
        ::close(fd);
        active_.fetch_sub(1);
      }).detach();
    }
  }

  void conn_loop(Stream& s) {
    // Per-connection cache of downstream chain streams.
    std::map<std::string, Stream> downstream;
    // Per-connection buffer a ReadBlocks frame's blocks are read into.
    std::vector<uint8_t> read_buf;
    while (running_.load()) {
      std::map<std::string, Value> h;
      std::vector<uint8_t> payload;
      if (!recv_frame(s, &h, &payload)) break;
      const std::string method = h.count("m") ? h["m"].s : "";
      bool has_data = h.count("_d") && h["_d"].i;
      const bool known =
          method == "WriteBlock" || method == "ReplicateBlock" ||
          method == "WriteStream" || method == "ReadBlock" ||
          method == "ReadBlocks";
      if (!known) {
        respond_err(s, "UNIMPLEMENTED",
                    "no native blockport method " + method);
        continue;
      }
      // Central pre-execution deadline gate — the twin of
      // blocknet.BlockPortServer._handle's _db check, message included:
      // an already-expired budget is refused before admission charges
      // the QoS plane (or any handler touches the disk) for doomed work.
      double budget = 0.0;
      const bool has_db = deadline_budget(h, &budget);
      if (has_db && budget <= 0) {
        respond_err(s, "DEADLINE_EXCEEDED",
                    "deadline budget exhausted before blockport " + method +
                        " executed");
        continue;
      }
      const std::string tenant =
          (h.count("_tn") && !h["_tn"].s.empty()) ? h["_tn"].s : "system";
      bool admitted = false;
      uint64_t t_admit = 0;
      if (qos_.enabled()) {
        std::string detail;
        double retry_after = 0.0;
        const uint64_t t_queued = now_ns();
        if (!qos_.acquire(tenant, has_db, budget, &detail, &retry_after)) {
          respond_shed(s, tenant, detail, retry_after);
          continue;
        }
        admitted = true;
        t_admit = now_ns();
        if (method == "ReadBlock")
          rb_admit_ns_.fetch_add(t_admit - t_queued);
        else if (method == "ReadBlocks")
          rbs_admit_ns_.fetch_add(t_admit - t_queued);
      }
      bool keep = true;
      if (method == "WriteBlock" || method == "ReplicateBlock") {
        handle_write(s, h, has_data ? &payload : nullptr, &downstream);
      } else if (method == "WriteStream") {
        // false = the stream aborted after the ready ack: pipelined
        // frames may still sit unread in the socket, so the request
        // boundary is lost and the connection must close.
        keep = handle_write_stream(s, h, &downstream);
      } else if (method == "ReadBlock") {
        handle_read(s, h);
      } else {
        // false = the frame was cut after its header: the client's
        // read of its payload must see the connection close.
        keep = handle_read_batch(s, h, read_buf);
      }
      if (admitted)
        qos_.release(tenant,
                     static_cast<double>(now_ns() - t_admit) * 1e-9);
      if (!keep) break;
    }
    for (auto& kv : downstream) close_downstream(kv.second);
  }

  void close_downstream(Stream& d) {
    {
      std::lock_guard<std::mutex> g(conns_mu_);
      conns_.erase(d.fd);
    }
    d.free_ssl();
    ::close(d.fd);
    d.fd = -1;
  }

  // ------------------------------------------------------------ replies

  void respond_err(Stream& s, const std::string& code, const std::string& msg) {
    errors_.fetch_add(1);
    Writer w;
    w.map_head(3);
    w.str("ok");
    w.boolean(false);
    w.str("code");
    w.str(code);
    w.str("message");
    w.str(msg);
    send_frame(s, w.out, nullptr, 0);
  }

  // QoS refusal frame. Message parity with resilience.overloaded_message
  // as raised by admission_controlled — "Overloaded|<hint>|ChunkServer
  // <detail> (tenant=<t>)" — so client.py's text parser finds the hint,
  // and the explicit retry_after key is the structured twin blocknet.py
  // reads first.
  void respond_shed(Stream& s, const std::string& tenant,
                    const std::string& detail, double retry_after) {
    errors_.fetch_add(1);
    char hint[32];
    std::snprintf(hint, sizeof(hint), "%.3f", retry_after);
    Writer w;
    w.map_head(4);
    w.str("ok");
    w.boolean(false);
    w.str("code");
    w.str("RESOURCE_EXHAUSTED");
    w.str("message");
    w.str(std::string("Overloaded|") + hint + "|ChunkServer " + detail +
          " (tenant=" + tenant + ")");
    w.str("retry_after");
    w.flt(retry_after);
    send_frame(s, w.out, nullptr, 0);
  }

  void respond_write(Stream& s, bool success, const std::string& err,
                     int64_t replicas) {
    Writer w;
    w.map_head(4);
    w.str("ok");
    w.boolean(true);
    w.str("success");
    w.boolean(success);
    w.str("error_message");
    w.str(err);
    w.str("replicas_written");
    w.uint(static_cast<uint64_t>(replicas));
    send_frame(s, w.out, nullptr, 0);
  }

  // -------------------------------------------------------------- write

  void handle_write(Stream& s, std::map<std::string, Value>& h,
                    std::vector<uint8_t>* data,
                    std::map<std::string, Stream>* downstream) {
    writes_.fetch_add(1);
    const std::string block_id =
        h.count("block_id") ? h["block_id"].s : "";
    if (block_id.empty() || block_id[0] == '.' ||
        block_id.find('/') != std::string::npos || data == nullptr) {
      respond_err(s, "INVALID_ARGUMENT", "bad block id or missing data");
      return;
    }
    // QoS parity with the asyncio blockport: an already-expired deadline
    // budget is rejected before any disk work, and the remaining budget /
    // tenant header ride every chain hop (computed at the forward below).
    double budget = 0.0;
    const bool has_db = deadline_budget(h, &budget);
    if (has_db && budget <= 0) {
      respond_err(s, "DEADLINE_EXCEEDED",
                  "deadline budget exhausted before WriteBlock executed");
      return;
    }
    const uint64_t t_recv = now_ns();
    uint64_t req_term =
        h.count("master_term") ? static_cast<uint64_t>(h["master_term"].i) : 0;
    const std::string shard =
        h.count("master_shard") ? h["master_shard"].s : "";
    uint64_t known = term(shard);
    if (req_term > 0 && req_term < known) {
      respond_err(s, "FAILED_PRECONDITION",
                  "Stale master term: request has " +
                      std::to_string(req_term) + " but known term is " +
                      std::to_string(known));
      return;
    }
    if (req_term > known) set_term(shard, req_term);

    uint64_t expected =
        h.count("expected_crc32c")
            ? static_cast<uint64_t>(h["expected_crc32c"].i)
            : 0;
    if (expected != 0) {
      uint32_t actual = tpudfs_crc32c(0, data->data(), data->size());
      if (actual != static_cast<uint32_t>(expected)) {
        respond_write(s, false,
                      "Checksum mismatch: expected " +
                          std::to_string(expected) + ", actual " +
                          std::to_string(actual),
                      0);
        return;
      }
    }

    // Kick the downstream forward BEFORE the local durable write (the
    // overlapped pipeline the Python handler uses; in-flight CRC above
    // means forwarding can't propagate corruption).
    std::vector<std::string> next =
        h.count("next_servers") ? h["next_servers"].astr
                                : std::vector<std::string>{};
    std::vector<int64_t> next_ports =
        h.count("next_data_ports") ? h["next_data_ports"].aint
                                   : std::vector<int64_t>{};
    Stream* fwd = nullptr;
    std::string fwd_err;
    if (!next.empty()) {
      int64_t port = !next_ports.empty() ? next_ports[0] : 0;
      if (port <= 0) {
        fwd_err = "downstream " + next[0] + " has no data port";
      } else {
        std::string host = next[0].substr(0, next[0].rfind(':'));
        std::string key = host + ":" + std::to_string(port);
        double db_left = budget - (now_ns() - t_recv) * 1e-9;
        fwd = forward_request(downstream, key, host,
                              static_cast<uint16_t>(port), h, next,
                              next_ports, *data, has_db, db_left, &fwd_err);
      }
    }

    // Stage + group commit (ack only after durable). Any write attempt
    // invalidates the cached copy — the publish rename may have replaced
    // the bytes a cached reader would otherwise keep serving.
    std::string err;
    bool ok = stage_and_commit(block_id, *data, &err);
    cache_invalidate(block_id);

    int64_t replicas = ok ? 1 : 0;
    if (fwd != nullptr) {
      forwards_.fetch_add(1);
      std::map<std::string, Value> fh;
      std::vector<uint8_t> fp;
      uint64_t ta = now_ns();
      bool got = recv_frame(*fwd, &fh, &fp);
      fwd_ack_ns_.fetch_add(now_ns() - ta);
      if (got && fh.count("ok") && fh["ok"].b &&
          fh.count("success") && fh["success"].b) {
        replicas += fh.count("replicas_written") ? fh["replicas_written"].i : 0;
      } else {
        // Downstream failure: drop the cached stream (unknown state).
        for (auto it = downstream->begin(); it != downstream->end(); ++it) {
          if (&it->second == fwd) {
            close_downstream(it->second);
            downstream->erase(it);
            break;
          }
        }
      }
    }
    if (!ok) {
      respond_write(s, false, err, replicas);
      return;
    }
    respond_write(s, true, fwd_err, replicas);
  }

  // ------------------------------------------------ streaming write path
  //
  // WriteStream: the block arrives as sub-block frames (protocol spec:
  // tpudfs/common/writestream.py) and is CRC-folded, staged, and fanned
  // out hop-by-hop without ever materializing in memory. Stage overlap:
  // this (receiver) thread runs net read -> CRC fold -> fanout send over
  // a small ring of reusable frame buffers, a per-stream writer thread
  // drains the ring to the staged file, and the shared commit thread
  // makes the block durable (group commit) before the final ack.
  // Returns false when the connection must close: any post-ready failure
  // leaves pipelined frames unread in the socket, so the request boundary
  // is lost. Pre-ready rejections answer an error frame and return true
  // (the connection stays poolable).
  bool handle_write_stream(Stream& s, std::map<std::string, Value>& h,
                           std::map<std::string, Stream>* downstream) {
    writes_.fetch_add(1);
    const std::string block_id =
        h.count("block_id") ? h["block_id"].s : "";
    if (block_id.empty() || block_id[0] == '.' ||
        block_id.find('/') != std::string::npos) {
      respond_err(s, "INVALID_ARGUMENT", "bad block id");
      return true;
    }
    uint64_t req_term =
        h.count("master_term") ? static_cast<uint64_t>(h["master_term"].i) : 0;
    const std::string shard =
        h.count("master_shard") ? h["master_shard"].s : "";
    uint64_t known = term(shard);
    if (req_term > 0 && req_term < known) {
      respond_err(s, "FAILED_PRECONDITION",
                  "Stale master term: request has " +
                      std::to_string(req_term) + " but known term is " +
                      std::to_string(known));
      return true;
    }
    if (req_term > known) set_term(shard, req_term);
    int64_t size_i = h.count("size") ? h["size"].i : -1;
    int64_t fsz_i = h.count("frame_size") ? h["frame_size"].i : 0;
    if (size_i < 0 || fsz_i <= 0 ||
        static_cast<uint64_t>(size_i) > kMaxStreamBytes ||
        static_cast<uint64_t>(fsz_i) > kMaxPayload) {
      respond_err(s, "INVALID_ARGUMENT", "bad stream size or frame_size");
      return true;
    }
    const uint64_t size = static_cast<uint64_t>(size_i);
    const uint64_t frame_size = static_cast<uint64_t>(fsz_i);
    const uint64_t nframes =
        std::max<uint64_t>(1, (size + frame_size - 1) / frame_size);
    const uint32_t expected =
        h.count("expected_crc32c")
            ? static_cast<uint32_t>(h["expected_crc32c"].i)
            : 0;
    double budget = 0.0;
    const bool has_db = deadline_budget(h, &budget);
    if (has_db && budget <= 0) {
      respond_err(s, "DEADLINE_EXCEEDED",
                  "deadline budget exhausted before WriteStream started");
      return true;
    }
    const uint64_t t_start = now_ns();
    const uint64_t deadline_ns =
        has_db ? t_start + static_cast<uint64_t>(budget * 1e9) : 0;
    const std::string qos_tenant =
        (h.count("_tn") && !h["_tn"].s.empty()) ? h["_tn"].s : "system";

    // Open the staged file before acking ready; a failure here is still a
    // clean in-sync rejection.
    uint64_t token = token_seq_.fetch_add(1);
    std::string base = hot_ + "/" + block_id;
    auto entry = std::make_shared<CommitEntry>();
    entry->data_tmp = base + ".tmp-n" + std::to_string(token);
    entry->meta_tmp = base + ".meta.tmp-n" + std::to_string(token);
    entry->data_final = base;
    entry->meta_final = base + ".meta";
    int dfd = ::open(entry->data_tmp.c_str(),
                     O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (dfd < 0) {
      respond_err(s, "INTERNAL",
                  "stage open failed: " + std::string(::strerror(errno)));
      return true;
    }

    // Relay the stream when the next hop has a data port; port 0 or any
    // begin failure degrades like a dead tail (healer repairs) while the
    // local replica still lands. Downstream acks are deliberately NOT
    // read until the tail drain below — they are tiny (one watermark per
    // kAckEvery frames) and fit in socket buffers, so deferring them
    // keeps this thread off the ack path while frames flow.
    std::vector<std::string> next =
        h.count("next_servers") ? h["next_servers"].astr
                                : std::vector<std::string>{};
    std::vector<int64_t> next_ports =
        h.count("next_data_ports") ? h["next_data_ports"].aint
                                   : std::vector<int64_t>{};
    Stream* fwd = nullptr;
    std::string fwd_key;
    if (!next.empty() && !next_ports.empty() && next_ports[0] > 0) {
      std::string host = next[0].substr(0, next[0].rfind(':'));
      fwd_key = host + ":" + std::to_string(next_ports[0]);
      std::string dial_err;
      fwd = open_downstream(downstream, fwd_key, host,
                            static_cast<uint16_t>(next_ports[0]), &dial_err);
      if (fwd != nullptr) {
        forwards_.fetch_add(1);
        const std::string tenant = h.count("_tn") ? h["_tn"].s : "";
        Writer w;
        w.map_head(9 + (has_db ? 1 : 0) + (tenant.empty() ? 0 : 1));
        w.str("m");
        w.str("WriteStream");
        w.str("block_id");
        w.str(block_id);
        w.str("size");
        w.uint(size);
        w.str("frame_size");
        w.uint(frame_size);
        w.str("expected_crc32c");
        w.uint(expected);
        w.str("master_term");
        w.uint(req_term);
        w.str("master_shard");
        w.str(shard);
        w.str("next_servers");
        w.astr(std::vector<std::string>(next.begin() + 1, next.end()));
        w.str("next_data_ports");
        w.aint(next_ports.size() > 1
                   ? std::vector<int64_t>(next_ports.begin() + 1,
                                          next_ports.end())
                   : std::vector<int64_t>{});
        if (has_db) {
          w.str("_db");
          w.flt(budget - (now_ns() - t_start) * 1e-9);
        }
        if (!tenant.empty()) {
          w.str("_tn");
          w.str(tenant);
        }
        std::map<std::string, Value> rh;
        std::vector<uint8_t> rp;
        if (!send_frame(*fwd, w.out, nullptr, 0) ||
            !recv_frame(*fwd, &rh, &rp)) {
          close_downstream(*fwd);
          downstream->erase(fwd_key);
          fwd = nullptr;
        } else if (!(rh.count("ready") && rh["ready"].i)) {
          // Clean pre-ready rejection (e.g. an ICI collective member or
          // an older peer): the downstream connection stays in sync, so
          // keep it pooled and just skip the relay.
          fwd = nullptr;
        }
      }
    }

    {
      Writer w;
      w.map_head(2);
      w.str("ok");
      w.boolean(true);
      w.str("ready");
      w.uint(1);
      if (!send_frame(s, w.out, nullptr, 0)) {
        ::close(dfd);
        ::unlink(entry->data_tmp.c_str());
        if (fwd != nullptr) {
          close_downstream(*fwd);
          downstream->erase(fwd_key);
        }
        return false;
      }
    }
    streams_started_.fetch_add(1);

    // Ring of reusable frame buffers between this thread and the disk
    // writer thread; a slot is refilled only after its disk write
    // finished, so net/CRC/fanout of frame N overlap the write of N-1.
    constexpr size_t kRing = 4;
    struct Slot {
      std::vector<uint8_t> buf;
      uint64_t len = 0;
    };
    Slot ring[kRing];
    for (auto& sl : ring) sl.buf.resize(frame_size);
    std::mutex ring_mu;
    std::condition_variable ring_free_cv, ring_full_cv;
    size_t ring_head = 0, ring_tail = 0, ring_count = 0;
    bool ring_done = false, disk_failed = false;
    std::thread disk([&] {
      std::unique_lock<std::mutex> lk(ring_mu);
      for (;;) {
        ring_full_cv.wait(lk, [&] { return ring_count > 0 || ring_done; });
        if (ring_count == 0) return;
        Slot& sl = ring[ring_tail];
        bool prior_fail = disk_failed;
        lk.unlock();
        uint64_t t0 = now_ns();
        bool wrote =
            !prior_fail && write_fd_all(dfd, sl.buf.data(), sl.len);
        stream_disk_ns_.fetch_add(now_ns() - t0);
        lk.lock();
        if (!wrote) disk_failed = true;
        ring_tail = (ring_tail + 1) % kRing;
        ring_count--;
        ring_free_cv.notify_one();
      }
    });

    // Per-chunk sidecar CRCs carry across frame boundaries; the
    // whole-block CRC is folded from per-frame CRCs via the GF(2)
    // combine — one CRC pass per cache-hot frame, none over the
    // assembled block.
    std::vector<uint32_t> sums;
    sums.reserve(size / chunk_ + 2);
    uint32_t carry_crc = 0;
    uint64_t carry_len = 0;
    uint32_t whole = 0;
    uint32_t op_frame[32];
    crc_zero_operator(frame_size, op_frame);

    bool torn = false;
    std::string err_code, err_msg;
    uint64_t received = 0;
    for (uint64_t seq = 0; seq < nframes; seq++) {
      if (has_db && now_ns() > deadline_ns) {
        err_code = "DEADLINE_EXCEEDED";
        err_msg = "deadline budget exhausted at frame " +
                  std::to_string(seq);
        break;
      }
      // Mid-stream shed: the force_shed failpoint (re-armed by a config
      // re-push while this stream is in flight) aborts an ADMITTED
      // stream between frames — the rpc_write_stream twin of the
      // per-frame deadline abort above, driving the client's Overloaded
      // retry path from inside a stream.
      double shed_after = 0.0;
      if (qos_.shed_frame(qos_tenant, &shed_after)) {
        char hint[32];
        std::snprintf(hint, sizeof(hint), "%.3f", shed_after);
        err_code = "RESOURCE_EXHAUSTED";
        err_msg = std::string("Overloaded|") + hint +
                  "|ChunkServer stream shed at frame " +
                  std::to_string(seq) + " (tenant=" + qos_tenant + ")";
        break;
      }
      Slot* sl;
      {
        std::unique_lock<std::mutex> lk(ring_mu);
        ring_free_cv.wait(lk, [&] { return ring_count < kRing; });
        if (disk_failed) {
          err_code = "INTERNAL";
          err_msg = "staged stream write failed";
          break;
        }
        sl = &ring[ring_head];
      }
      uint64_t t0 = now_ns();
      std::map<std::string, Value> fh;
      uint64_t plen = 0;
      if (!recv_frame_into(s, &fh, sl->buf.data(), frame_size, &plen)) {
        torn = true;
        break;
      }
      uint64_t t1 = now_ns();
      stream_net_ns_.fetch_add(t1 - t0);
      uint64_t want = std::min(frame_size, size - received);
      int64_t fseq = fh.count("q") ? fh["q"].i : -1;
      if (static_cast<uint64_t>(fseq) != seq ||
          !(fh.count("_d") && fh["_d"].i) || plen != want) {
        err_code = "INVALID_ARGUMENT";
        err_msg = "unexpected frame " + std::to_string(fseq) +
                  " (want " + std::to_string(seq) + ")";
        break;
      }
      uint32_t fcrc = tpudfs_crc32c(0, sl->buf.data(), plen);
      uint32_t want_crc =
          fh.count("c") ? static_cast<uint32_t>(fh["c"].i) : 0;
      if (fcrc != want_crc) {
        err_code = "DATA_LOSS";
        err_msg = "frame " + std::to_string(seq) +
                  " CRC mismatch; staged block " + block_id +
                  " quarantined";
        break;
      }
      if (seq == 0) {
        whole = fcrc;
      } else if (plen == frame_size) {
        whole = crc_matrix_times(op_frame, whole) ^ fcrc;
      } else {
        uint32_t op_tail[32];
        crc_zero_operator(plen, op_tail);
        whole = crc_matrix_times(op_tail, whole) ^ fcrc;
      }
      uint64_t off = 0;
      if (carry_len) {
        uint64_t take = std::min<uint64_t>(chunk_ - carry_len, plen);
        carry_crc = tpudfs_crc32c(carry_crc, sl->buf.data(), take);
        carry_len += take;
        off = take;
        if (carry_len == chunk_) {
          sums.push_back(carry_crc);
          carry_crc = 0;
          carry_len = 0;
        }
      }
      while (off + chunk_ <= plen) {
        sums.push_back(tpudfs_crc32c(0, sl->buf.data() + off, chunk_));
        off += chunk_;
      }
      if (off < plen) {
        carry_crc = tpudfs_crc32c(0, sl->buf.data() + off, plen - off);
        carry_len = plen - off;
      }
      uint64_t t2 = now_ns();
      stream_crc_ns_.fetch_add(t2 - t1);
      // Fan out before handing the slot to the disk stage (the slot is
      // reused only after its disk write, so the send reads stable bytes).
      if (fwd != nullptr) {
        Writer w;
        w.map_head(3);
        w.str("q");
        w.uint(seq);
        w.str("c");
        w.uint(fcrc);
        w.str("_d");
        w.uint(1);
        if (!send_frame(*fwd, w.out, sl->buf.data(), plen)) {
          // Downstream died mid-stream: degrade like a dead tail, keep
          // the local replica going.
          close_downstream(*fwd);
          downstream->erase(fwd_key);
          fwd = nullptr;
        }
      }
      uint64_t t3 = now_ns();
      stream_fanout_ns_.fetch_add(t3 - t2);
      {
        std::lock_guard<std::mutex> lk(ring_mu);
        sl->len = plen;
        ring_head = (ring_head + 1) % kRing;
        ring_count++;
      }
      ring_full_cv.notify_one();
      received += plen;
      stream_frames_.fetch_add(1);
      stream_bytes_.fetch_add(plen);
      // Group-committed acks: per-frame progress coalesces into watermark
      // acks; the covering ack for the last frames is the final frame,
      // sent only after the durable commit below.
      if ((seq + 1) % kAckEvery == 0 && seq + 1 < nframes) {
        Writer w;
        w.map_head(2);
        w.str("ok");
        w.boolean(true);
        w.str("w");
        w.uint(seq + 1);
        if (!send_frame(s, w.out, nullptr, 0)) {
          torn = true;
          break;
        }
      }
    }

    // Drain the disk stage before touching the staged file.
    {
      std::lock_guard<std::mutex> lk(ring_mu);
      ring_done = true;
    }
    ring_full_cv.notify_all();
    disk.join();
    ::close(dfd);

    auto scrap = [&] {
      stream_aborts_.fetch_add(1);
      ::unlink(entry->data_tmp.c_str());
      ::unlink(entry->meta_tmp.c_str());
      if (fwd != nullptr) {
        // Tear the relay too so the abort propagates down the chain.
        close_downstream(*fwd);
        downstream->erase(fwd_key);
        fwd = nullptr;
      }
    };
    if (torn) {  // transport tear: nobody left to answer
      scrap();
      return false;
    }
    if (!err_code.empty()) {
      scrap();
      respond_err(s, err_code, err_msg);
      return false;
    }
    if (disk_failed) {
      scrap();
      respond_err(s, "INTERNAL", "staged stream write failed");
      return false;
    }

    if (carry_len) sums.push_back(carry_crc);
    bool success = true;
    std::string errmsg;
    if (expected != 0 && whole != expected) {
      // Every frame CRC-verified yet the whole disagrees (sender-side
      // corruption before framing): quarantine the staged bytes and
      // report a soft failure — all frames were consumed, so the
      // protocol stays in sync.
      ::unlink(entry->data_tmp.c_str());
      success = false;
      errmsg = "Checksum mismatch: expected " + std::to_string(expected) +
               ", actual " + std::to_string(whole);
    }
    if (success && !write_meta_tmp(entry->meta_tmp, chunk_, sums)) {
      ::unlink(entry->data_tmp.c_str());
      ::unlink(entry->meta_tmp.c_str());
      success = false;
      errmsg = "meta stage failed";
    }
    int64_t replicas = 0;
    if (success) {
      staged_bytes_.fetch_add(size);
      std::string cerr;
      if (commit_entry_and_wait(entry, &cerr)) {
        replicas = 1;
      } else {
        success = false;
        errmsg = cerr;
      }
      cache_invalidate(block_id);
    }

    if (fwd != nullptr) {
      // Drain the relay's coalesced watermarks down to its final verdict
      // (sent only after ITS durable commit and its own tail's final).
      uint64_t ta = now_ns();
      for (;;) {
        std::map<std::string, Value> ah;
        std::vector<uint8_t> ap;
        if (!recv_frame(*fwd, &ah, &ap)) {
          close_downstream(*fwd);
          downstream->erase(fwd_key);
          fwd = nullptr;
          break;
        }
        if (ah.count("final") && ah["final"].i) {
          if (ah.count("success") && ah["success"].b)
            replicas +=
                ah.count("replicas_written") ? ah["replicas_written"].i : 0;
          break;
        }
        if (!(ah.count("ok") && ah["ok"].b)) {
          // Error frame ends the downstream stream; the peer closes.
          close_downstream(*fwd);
          downstream->erase(fwd_key);
          fwd = nullptr;
          break;
        }
      }
      fwd_ack_ns_.fetch_add(now_ns() - ta);
    }

    // Final group-commit ack: the watermark covers the whole block and
    // the local replica (plus everything downstream reported) is durable.
    Writer w;
    w.map_head(6);
    w.str("ok");
    w.boolean(true);
    w.str("final");
    w.uint(1);
    w.str("w");
    w.uint(nframes);
    w.str("success");
    w.boolean(success);
    w.str("error_message");
    w.str(errmsg);
    w.str("replicas_written");
    w.uint(static_cast<uint64_t>(replicas));
    return send_frame(s, w.out, nullptr, 0);
  }

  // Dial (or reuse) the per-connection downstream stream for `key`,
  // including the outbound TLS policy (never plaintext off a secured
  // listener). Shared by the whole-block forward and the stream relay.
  Stream* open_downstream(std::map<std::string, Stream>* downstream,
                          const std::string& key, const std::string& host,
                          uint16_t port, std::string* err) {
    auto it = downstream->find(key);
    if (it == downstream->end()) {
      int dfd = dial(host, port);
      if (dfd < 0) {
        *err = "dial " + key + " failed";
        return nullptr;
      }
      Stream d{dfd, nullptr};
      if (cli_ctx_ != nullptr) {
        // TLS to the downstream peer, with the same target-name
        // verification the Python BlockConnPool applies (hostname or IP
        // SAN must match the dialed host).
        const SslApi* api = ssl_api();
        d.ssl = api->ssl_new(cli_ctx_);
        bool ok = d.ssl != nullptr && api->set_fd(d.ssl, dfd) == 1;
        if (ok) {
          in_addr tmp;
          if (::inet_pton(AF_INET, host.c_str(), &tmp) == 1)
            ok = api->param_set1_ip_asc(api->get0_param(d.ssl),
                                        host.c_str()) == 1;
          else
            ok = api->set1_host(d.ssl, host.c_str()) == 1;
        }
        ok = ok && api->connect(d.ssl) == 1 &&
             api->verify_result(d.ssl) == 0;
        if (!ok) {
          d.free_ssl();
          ::close(dfd);
          *err = "tls to " + key + " failed";
          return nullptr;
        }
      } else if (srv_ctx_ != nullptr) {
        // Secured listener but no outbound material: never forward in
        // plaintext — degrade like a dead tail (healer repairs).
        ::close(dfd);
        *err = "no outbound TLS material for " + key;
        return nullptr;
      }
      it = downstream->emplace(key, d).first;
      // Registered so stop() can shutdown a thread blocked on the
      // downstream ack recv (up to SO_RCVTIMEO otherwise — long past
      // stop()'s drain window, a use-after-free).
      std::lock_guard<std::mutex> g(conns_mu_);
      conns_.insert(dfd);
    }
    return &it->second;
  }

  Stream* forward_request(std::map<std::string, Stream>* downstream,
                          const std::string& key, const std::string& host,
                          uint16_t port, std::map<std::string, Value>& h,
                          const std::vector<std::string>& next,
                          const std::vector<int64_t>& next_ports,
                          const std::vector<uint8_t>& data,
                          bool has_db, double db_left,
                          std::string* err) {
    Stream* d = open_downstream(downstream, key, host, port, err);
    if (d == nullptr) return nullptr;
    const std::string tenant = h.count("_tn") ? h["_tn"].s : "";
    Writer w;
    w.map_head(8 + (has_db ? 1 : 0) + (tenant.empty() ? 0 : 1));
    w.str("m");
    w.str("ReplicateBlock");
    w.str("_d");
    w.uint(1);
    w.str("block_id");
    w.str(h["block_id"].s);
    w.str("next_servers");
    w.astr(std::vector<std::string>(next.begin() + 1, next.end()));
    w.str("next_data_ports");
    w.aint(next_ports.size() > 1
               ? std::vector<int64_t>(next_ports.begin() + 1,
                                      next_ports.end())
               : std::vector<int64_t>{});
    w.str("expected_crc32c");
    w.uint(h.count("expected_crc32c")
               ? static_cast<uint64_t>(h["expected_crc32c"].i)
               : 0);
    w.str("master_term");
    w.uint(h.count("master_term") ? static_cast<uint64_t>(h["master_term"].i)
                                  : 0);
    w.str("master_shard");
    w.str(h.count("master_shard") ? h["master_shard"].s : "");
    if (has_db) {
      w.str("_db");
      w.flt(db_left);
    }
    if (!tenant.empty()) {
      w.str("_tn");
      w.str(tenant);
    }
    if (!send_frame(*d, w.out, data.data(), data.size())) {
      close_downstream(*d);
      downstream->erase(key);
      *err = "forward to " + key + " failed";
      return nullptr;
    }
    return d;
  }

  static int dial(const std::string& host, uint16_t port) {
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(port);
    if (::inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
      // Hostname-addressed peer (the asyncio path resolves these too).
      addrinfo hints{};
      hints.ai_family = AF_INET;
      hints.ai_socktype = SOCK_STREAM;
      addrinfo* res = nullptr;
      if (::getaddrinfo(host.c_str(), nullptr, &hints, &res) != 0 ||
          res == nullptr)
        return -1;
      addr.sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
      ::freeaddrinfo(res);
    }
    int fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    if (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0) {
      ::close(fd);
      return -1;
    }
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    tune_buffers(fd);
    timeval tv{30, 0};
    ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    return fd;
  }

  static uint64_t now_ns() {
    return static_cast<uint64_t>(
        std::chrono::duration_cast<std::chrono::nanoseconds>(
            std::chrono::steady_clock::now().time_since_epoch())
            .count());
  }

  bool stage_and_commit(const std::string& block_id,
                        const std::vector<uint8_t>& data, std::string* err) {
    uint64_t token = token_seq_.fetch_add(1);
    std::string base = hot_ + "/" + block_id;
    auto entry = std::make_shared<CommitEntry>();
    entry->data_tmp = base + ".tmp-n" + std::to_string(token);
    entry->meta_tmp = base + ".meta.tmp-n" + std::to_string(token);
    entry->data_final = base;
    entry->meta_final = base + ".meta";
    uint64_t t0 = now_ns();
    int64_t rc = tpudfs_block_write_staged(
        entry->data_tmp.c_str(), entry->meta_tmp.c_str(), data.data(),
        data.size(), chunk_, nullptr);
    stage_ns_.fetch_add(now_ns() - t0);
    staged_bytes_.fetch_add(data.size());
    if (rc < 0) {
      *err = "stage failed: errno " + std::to_string(-rc);
      return false;
    }
    return commit_entry_and_wait(entry, err);
  }

  // Queue a staged entry for the group-commit loop and block until its
  // verdict — shared tail of the whole-block and streaming write paths.
  bool commit_entry_and_wait(const std::shared_ptr<CommitEntry>& entry,
                             std::string* err) {
    uint64_t tq = now_ns();
    std::unique_lock<std::mutex> lk(commit_mu_);
    commit_queue_.push_back(entry);
    commit_cv_.notify_one();
    // Wake either when the commit loop resolved this entry, or when the
    // engine is stopping AND the entry is still queued — in the latter
    // case WE dequeue it (under the lock, so the loop can never also take
    // it) and unlink the staged tmps, making "engine stopping" a DEFINITE
    // failure. An entry already taken into an in-flight batch is past the
    // point of no return (the loop drains its batch before exiting), so
    // we keep waiting for its real verdict instead of reporting a write
    // failure for data that durably published.
    bool dequeued = false;
    commit_done_cv_.wait(lk, [&] {
      if (entry->done) return true;
      if (!running_.load()) {
        auto it = std::find(commit_queue_.begin(), commit_queue_.end(),
                            entry);
        if (it != commit_queue_.end()) {
          commit_queue_.erase(it);
          dequeued = true;
          return true;
        }
      }
      return false;
    });
    commit_wait_ns_.fetch_add(now_ns() - tq);
    if (dequeued) {
      ::unlink(entry->data_tmp.c_str());
      ::unlink(entry->meta_tmp.c_str());
      *err = "engine stopping";
      return false;
    }
    if (entry->failed) {
      *err = entry->error;
      return false;
    }
    return true;
  }

  void commit_loop() {
    // No artificial accumulation window: the write pipeline is a closed
    // latency loop (fixed client concurrency), so delaying commits to
    // widen batches proportionally lowers the arrival rate instead —
    // measured round 5: a 6 ms window moved batches only
    // 1.7 -> 2.1 entries at equal throughput. The stage budgets put the
    // chain at 75-93% of the disk's sustained fdatasync rate already;
    // arrivals during an in-flight sync batch naturally.
    std::unique_lock<std::mutex> lk(commit_mu_);
    while (running_.load() || !commit_queue_.empty()) {
      if (commit_queue_.empty()) {
        // Predicated wait, not a 50 ms wait_for poll: stop() notifies
        // under commit_mu_ after flipping running_, so the wakeup cannot
        // be lost — and wait() stays on pthread_cond_wait, which the
        // TSan gate (scripts/native_sanitize.py) can model (glibc's
        // pthread_cond_clockwait behind wait_for has no interceptor and
        // corrupts its lock state, drowning real races in noise).
        commit_cv_.wait(lk, [&] {
          return !commit_queue_.empty() || !running_.load();
        });
        continue;
      }
      std::deque<std::shared_ptr<CommitEntry>> batch;
      batch.swap(commit_queue_);
      lk.unlock();
      // One filesystem sync makes every staged file durable, renames
      // publish, a second sync persists the renames (the group-commit
      // batch path of tpudfs/chunkserver/blockstore.py).
      uint64_t t0 = now_ns();
      tpudfs_syncfs(hot_.c_str());
      uint64_t t1 = now_ns();
      syncfs_ns_.fetch_add(t1 - t0);
      for (auto& e : batch) {
        if (::rename(e->data_tmp.c_str(), e->data_final.c_str()) != 0 ||
            ::rename(e->meta_tmp.c_str(), e->meta_final.c_str()) != 0) {
          e->failed = true;
          e->error = "publish rename failed: " +
                     std::string(::strerror(errno));
        }
      }
      uint64_t t2 = now_ns();
      rename_ns_.fetch_add(t2 - t1);
      tpudfs_syncfs(hot_.c_str());
      syncfs_ns_.fetch_add(now_ns() - t2);
      commit_batches_.fetch_add(1);
      commit_entries_.fetch_add(batch.size());
      lk.lock();
      for (auto& e : batch) e->done = true;
      commit_done_cv_.notify_all();
    }
    // Drain-out on stop: wake any stragglers (they dequeue + unlink their
    // own staged entries under the lock — see stage_and_commit).
    commit_done_cv_.notify_all();
  }

  // --------------------------------------------------------------- read

  // "_rt" in a read's request asks for its read_ns back as "_rns" in the
  // response header (the client sets it while its tracing is on, and lays
  // it on its blockport.wait_header span); without it the frames are the
  // same bytes as ever.
  void handle_read(Stream& s, std::map<std::string, Value>& h) {
    const uint64_t t0 = now_ns();
    reads_.fetch_add(1);
    rb_calls_.fetch_add(1);
    const bool timed = h.count("_rt") != 0;
    auto fail = [&](const std::string& code, const std::string& msg) {
      if (code == "NOT_FOUND") rb_not_found_.fetch_add(1);
      rb_read_ns_.fetch_add(now_ns() - t0);
      respond_err(s, code, msg);
    };
    auto reply = [&](const uint8_t* data, uint64_t n, uint64_t total) {
      const uint64_t read_ns = now_ns() - t0;
      Writer w;
      w.map_head(timed ? 5 : 4);
      w.str("ok");
      w.boolean(true);
      w.str("_d");
      w.uint(1);
      w.str("bytes_read");
      w.uint(n);
      w.str("total_size");
      w.uint(total);
      if (timed) {
        w.str("_rns");
        w.uint(read_ns);
      }
      rb_read_ns_.fetch_add(read_ns);
      const uint64_t t1 = now_ns();
      send_frame(s, w.out, data, n);
      rb_send_ns_.fetch_add(now_ns() - t1);
      rb_bytes_.fetch_add(n);
    };
    const std::string block_id =
        h.count("block_id") ? h["block_id"].s : "";
    if (block_id.empty() || block_id[0] == '.' ||
        block_id.find('/') != std::string::npos) {
      fail("INVALID_ARGUMENT", "bad block id");
      return;
    }
    uint64_t offset =
        h.count("offset") ? static_cast<uint64_t>(h["offset"].i) : 0;
    uint64_t length =
        h.count("length") ? static_cast<uint64_t>(h["length"].i) : 0;
    // Cache first: a hit serves straight from memory (bytes were verified
    // when cached; writes/corruption findings invalidate). Range reads
    // slice the cached block.
    if (CacheData cached = cache_get(block_id)) {
      rb_cache_calls_.fetch_add(1);
      uint64_t total = cached->size();
      if (offset >= total && !(offset == 0 && total == 0)) {
        fail("OUT_OF_RANGE", "Offset " + std::to_string(offset) +
                                 " exceeds block size " +
                                 std::to_string(total));
        return;
      }
      uint64_t want = length == 0 ? total - offset
                                  : std::min(length, total - offset);
      reply(cached->data() + offset, want, total);
      return;
    }
    const uint64_t gen = cache_gen(block_id);  // before the pread
    std::string data_path = hot_ + "/" + block_id;
    struct stat st;
    if (::stat(data_path.c_str(), &st) != 0) {
      if (!cold_.empty()) {
        data_path = cold_ + "/" + block_id;
        if (::stat(data_path.c_str(), &st) != 0) {
          fail("NOT_FOUND", "Block not found");
          return;
        }
      } else {
        fail("NOT_FOUND", "Block not found");
        return;
      }
    }
    uint64_t total = static_cast<uint64_t>(st.st_size);
    if (length == 0) length = total > offset ? total - offset : 0;
    if (offset >= total && !(offset == 0 && total == 0)) {
      fail("OUT_OF_RANGE", "Offset " + std::to_string(offset) +
                               " exceeds block size " +
                               std::to_string(total));
      return;
    }
    uint64_t want = std::min(length, total - offset);
    std::vector<uint8_t> buf(want);
    std::string meta_path = data_path + ".meta";
    int64_t rc = tpudfs_block_read_verify(
        data_path.c_str(), meta_path.c_str(), offset, want,
        buf.data(), 1, chunk_);
    if (rc == kCorrupt || rc < -200000) {
      // Corrupt or unreadable sidecar: flag for Python (heartbeat
      // bad-block report + recovery), serve the raw bytes for partial
      // reads (chunkserver.rs:893-911 parity) but fail full reads — the
      // caller's replica failover handles those.
      {
        std::lock_guard<std::mutex> g(bad_mu_);
        bad_.insert(block_id);
      }
      cache_invalidate(block_id);
      bool full = offset == 0 && want == total;
      if (full) {
        fail("DATA_LOSS", "Data corruption detected on native read");
        return;
      }
      rc = tpudfs_block_read_verify(data_path.c_str(), meta_path.c_str(),
                                    offset, want, buf.data(), 0, chunk_);
      if (rc < 0) {
        fail("INTERNAL", "read failed after verify failure");
        return;
      }
    } else if (rc < 0) {
      fail(rc == -ENOENT ? "NOT_FOUND" : "INTERNAL",
           rc == -ENOENT ? "Block not found"
                         : "native read error " + std::to_string(-rc));
      return;
    }
    CacheData keep;
    if (rc >= 0 && offset == 0 && want == total) {
      // Full block, freshly verified: cache for repeated readers — unless
      // a concurrent publish replaced the file mid-read (see same_sig).
      // Moving buf avoids a full-block copy on every miss; the response
      // is sent from the cached vector.
      struct stat st2;
      if (::stat(data_path.c_str(), &st2) == 0 && same_sig(st, st2)) {
        keep = std::make_shared<std::vector<uint8_t>>(std::move(buf));
        cache_put(block_id, keep, gen);
      }
    }
    reply(keep ? keep->data() : buf.data(), static_cast<uint64_t>(rc),
          total);
  }

  // Batched UNVERIFIED full reads: header {"block_ids": [...]}; response
  // header carries "sizes" (bytes per slot, -1 = missing/unreadable/
  // over-budget — the caller falls back per block) and the payload
  // concatenates the successful blocks in request order. One frame
  // replaces N round trips for a remote reader's fused round. No sidecar
  // verify here: every consumer (the combiner's remote rounds)
  // re-verifies end-to-end against the recorded whole-block checksum and
  // routes mismatches to the per-block VERIFIED path, which detects the
  // rot, reports it, and triggers recovery.
  //
  // The frame goes out as it is read: every slot is opened and sized
  // first (the header needs the sizes), the header goes out, then each
  // block is pread into `buf` and written before the next is read, so the
  // client receives the first blocks while the engine reads the rest. An
  // open fd keeps a block's bytes readable if it is unlinked or replaced
  // meanwhile (a balancer move deletes its source while a frame may still
  // be in flight). A pread that fails or comes up short after the header
  // tears the frame: nothing more is sent (never a byte that was not
  // read), and false closes the connection, so the client's round fails
  // and falls back per block as on any transport error.
  bool handle_read_batch(Stream& s, std::map<std::string, Value>& h,
                         std::vector<uint8_t>& buf) {
    const uint64_t t0 = now_ns();
    const bool timed = h.count("_rt") != 0;
    const std::vector<std::string> ids =
        h.count("block_ids") ? h["block_ids"].astr
                             : std::vector<std::string>{};
    constexpr size_t kMaxSlots = 256;
    constexpr uint64_t kMaxBatchBytes = 96ull << 20;  // < 100 MiB frame caps
    // A block larger than this is read and sent in pieces of it, so the
    // buffer stays bounded whatever the block size.
    constexpr uint64_t kMaxPiece = 4ull << 20;
    // A slot is sent from the cache (`cached`), from its open file (`fd`),
    // or not at all (size -1).
    struct Slot {
      int64_t size = -1;
      int fd = -1;
      CacheData cached;
    };
    std::vector<Slot> slots(ids.size());
    uint64_t plen = 0;
    for (size_t i = 0; i < ids.size(); i++) {
      const std::string& block_id = ids[i];
      Slot& sl = slots[i];
      reads_.fetch_add(1);
      if (i >= kMaxSlots || plen >= kMaxBatchBytes)
        continue;  // over budget: caller falls back/re-requests
      if (block_id.empty() || block_id[0] == '.' ||
          block_id.find('/') != std::string::npos)
        continue;
      if (CacheData cached = cache_get(block_id)) {
        if (plen + cached->size() <= kMaxBatchBytes) {
          sl.size = static_cast<int64_t>(cached->size());
          sl.cached = std::move(cached);
          plen += sl.cached->size();
        }
        continue;
      }
      // The open is the existence check: hot tier, then cold.
      int fd = ::open((hot_ + "/" + block_id).c_str(), O_RDONLY | O_CLOEXEC);
      if (fd < 0 && !cold_.empty())
        fd = ::open((cold_ + "/" + block_id).c_str(), O_RDONLY | O_CLOEXEC);
      if (fd < 0) continue;
      struct stat st;
      if (::fstat(fd, &st) != 0 ||
          plen + static_cast<uint64_t>(st.st_size) > kMaxBatchBytes) {
        ::close(fd);
        continue;
      }
      sl.fd = fd;
      sl.size = static_cast<int64_t>(st.st_size);
      plen += static_cast<uint64_t>(st.st_size);
    }
    Writer w;
    w.map_head(timed ? 4 : 3);
    w.str("ok");
    w.boolean(true);
    w.str("_d");
    w.uint(1);
    w.str("sizes");
    uint64_t missing = 0;
    {
      // Writer::aint clamps negatives to 0; hand-encode -1 slots.
      if (slots.size() < 16) w.raw(0x90 | slots.size());
      else { w.raw(0xdc); w.be(slots.size(), 2); }
      for (const Slot& sl : slots) {
        if (sl.size < 0) {
          w.raw(0xff);  // negative fixint -1
          missing++;
        } else {
          w.uint(static_cast<uint64_t>(sl.size));
        }
      }
    }
    uint64_t read_ns = now_ns() - t0;
    if (timed) {
      // The time to the header: every slot opened and sized.
      w.str("_rns");
      w.uint(read_ns);
    }
    uint64_t t = now_ns();
    bool ok = send_frame_head(s, w.out, plen);
    uint64_t send_ns = now_ns() - t;
    uint64_t sent = 0;
    bool torn = false;
    for (Slot& sl : slots) {
      if (ok && sl.cached) {
        t = now_ns();
        ok = write_all(s, sl.cached->data(), sl.cached->size());
        send_ns += now_ns() - t;
        if (ok) sent += sl.cached->size();
      } else if (ok && sl.fd >= 0) {
        // NOT cached: the batch read is unverified (consumers re-verify
        // end-to-end), and the LRU must only ever hold VERIFIED bytes —
        // caching here would let a corrupt replica poison later per-block
        // reads that trust cache hits. (The streaming sweep shouldn't
        // wash the cache anyway.)
        const uint64_t size = static_cast<uint64_t>(sl.size);
        for (uint64_t off = 0; ok && off < size;) {
          const size_t n =
              static_cast<size_t>(std::min(size - off, kMaxPiece));
          if (buf.size() < n) buf.resize(n);
          t = now_ns();
          torn = !pread_exact(sl.fd, buf.data(), n, off);
          read_ns += now_ns() - t;
          if (torn) {
            ok = false;
            break;
          }
          t = now_ns();
          ok = write_all(s, buf.data(), n);
          send_ns += now_ns() - t;
          if (ok) sent += n;
          off += n;
        }
      }
      if (sl.fd >= 0) ::close(sl.fd);
    }
    rbs_frames_.fetch_add(1);
    rbs_slots_.fetch_add(ids.size());
    rbs_missing_.fetch_add(missing);
    rbs_bytes_.fetch_add(sent);
    rbs_read_ns_.fetch_add(read_ns);
    rbs_send_ns_.fetch_add(send_ns);
    if (torn) rbs_torn_.fetch_add(1);
    return ok;
  }

  std::string host_, hot_, cold_;
  uint32_t chunk_;
  int listen_fd_ = -1;
  int32_t port_ = 0;
  std::atomic<bool> running_{false};
  std::mutex term_mu_;
  std::map<std::string, uint64_t> terms_;
  std::atomic<uint64_t> token_seq_{1};
  std::atomic<uint64_t> writes_{0}, reads_{0}, forwards_{0}, errors_{0};
  std::atomic<uint64_t> stage_ns_{0}, commit_wait_ns_{0}, syncfs_ns_{0},
      fwd_ack_ns_{0}, commit_batches_{0}, commit_entries_{0},
      staged_bytes_{0}, rename_ns_{0};
  std::atomic<uint64_t> stream_net_ns_{0}, stream_crc_ns_{0},
      stream_disk_ns_{0}, stream_fanout_ns_{0}, stream_frames_{0},
      streams_started_{0}, stream_bytes_{0}, stream_aborts_{0};
  std::atomic<uint64_t> rb_calls_{0}, rb_bytes_{0}, rb_read_ns_{0},
      rb_send_ns_{0}, rb_not_found_{0}, rb_cache_calls_{0}, rb_admit_ns_{0};
  std::atomic<uint64_t> rbs_frames_{0}, rbs_slots_{0}, rbs_bytes_{0},
      rbs_read_ns_{0}, rbs_send_ns_{0}, rbs_missing_{0}, rbs_admit_ns_{0},
      rbs_torn_{0};
  std::thread accept_thread_, commit_thread_;
  std::atomic<int> active_{0};
  std::mutex conns_mu_;
  std::set<int> conns_;
  std::mutex commit_mu_;
  std::condition_variable commit_cv_, commit_done_cv_;
  std::deque<std::shared_ptr<CommitEntry>> commit_queue_;
  std::mutex bad_mu_;
  std::set<std::string> bad_;
  size_t cache_cap_;
  std::mutex cache_mu_;
  std::list<std::pair<std::string, CacheData>> cache_list_;  // front = MRU
  std::map<std::string, std::list<std::pair<std::string, CacheData>>::iterator>
      cache_map_;
  std::map<std::string, uint64_t> inval_gen_;  // see cache_gen/cache_put
  uint64_t gen_counter_ = 0;  // monotone source of every generation
  uint64_t gen_floor_ = 0;    // generation reported for absent ids
  std::atomic<uint64_t> cache_hits_{0}, cache_misses_{0};
  void* srv_ctx_ = nullptr;  // SSL_CTX*, set by configure_tls
  void* cli_ctx_ = nullptr;  // SSL_CTX* for chain forwards
  Qos qos_;  // tenant admission plane (off until set_qos enables it)
};

std::mutex g_engines_mu;
std::vector<Engine*> g_engines;

Engine* get_engine(int64_t h) {
  std::lock_guard<std::mutex> g(g_engines_mu);
  if (h < 0 || static_cast<size_t>(h) >= g_engines.size()) return nullptr;
  return g_engines[h];
}

}  // namespace

extern "C" {

// Bumped on any signature/behavior change of the dataplane C ABI; the
// Python loader refuses to bind mismatched prebuilt libraries
// (TPUDFS_NATIVE_LIB) instead of calling with wrong arity.
int64_t tpudfs_dataplane_abi(void) { return 8; }

int64_t tpudfs_dataplane_start(const char* host, const char* hot_dir,
                               const char* cold_dir, uint32_t chunk_size,
                               uint16_t port, uint64_t cache_blocks,
                               const char* srv_cert, const char* srv_key,
                               const char* srv_client_ca,
                               const char* out_ca, const char* out_cert,
                               const char* out_key) {
  auto* e = new Engine(host ? host : "", hot_dir,
                       cold_dir ? cold_dir : "", chunk_size,
                       static_cast<size_t>(cache_blocks));
  auto str = [](const char* c) { return std::string(c ? c : ""); };
  if (!e->configure_tls(str(srv_cert), str(srv_key), str(srv_client_ca),
                        str(out_ca), str(out_cert), str(out_key))) {
    delete e;
    return -EPROTO;  // caller falls back to the asyncio blockport
  }
  int64_t rc = e->start(port);
  if (rc < 0) {
    delete e;
    return rc;
  }
  std::lock_guard<std::mutex> g(g_engines_mu);
  g_engines.push_back(e);
  return static_cast<int64_t>(g_engines.size() - 1);
}

int32_t tpudfs_dataplane_port(int64_t h) {
  Engine* e = get_engine(h);
  return e ? e->port() : 0;
}

void tpudfs_dataplane_set_term(int64_t h, const char* shard,
                               uint64_t term) {
  Engine* e = get_engine(h);
  if (e) e->set_term(shard ? shard : "", term);
}

uint64_t tpudfs_dataplane_term(int64_t h, const char* shard) {
  Engine* e = get_engine(h);
  return e ? e->term(shard ? shard : "") : 0;
}

int64_t tpudfs_dataplane_take_bad(int64_t h, char* buf, uint64_t cap) {
  Engine* e = get_engine(h);
  return e ? e->take_bad(buf, cap) : -1;
}

int64_t tpudfs_dataplane_take_terms(int64_t h, char* buf, uint64_t cap) {
  Engine* e = get_engine(h);
  return e ? e->take_terms(buf, cap) : -1;
}

void tpudfs_dataplane_invalidate(int64_t h, const char* block_id) {
  Engine* e = get_engine(h);
  if (e && block_id) e->cache_invalidate(block_id);
}

void tpudfs_dataplane_stats(int64_t h, uint64_t out[6]) {
  Engine* e = get_engine(h);
  if (e) e->stats(out);
  else for (int i = 0; i < 6; i++) out[i] = 0;
}

// Write-path stage budgets: stage_ns, commit_wait_ns, syncfs_ns,
// fwd_ack_ns, commit_batches, commit_entries, staged_bytes, rename_ns.
void tpudfs_dataplane_stage_stats(int64_t h, uint64_t out[8]) {
  Engine* e = get_engine(h);
  if (e) e->stage_stats(out);
  else for (int i = 0; i < 8; i++) out[i] = 0;
}

// Streaming write pipeline occupancy: net_ns, crc_ns, disk_ns,
// fanout_ns, frames, streams, stream_bytes, aborts.
void tpudfs_dataplane_stream_stats(int64_t h, uint64_t out[8]) {
  Engine* e = get_engine(h);
  if (e) e->stream_stage_stats(out);
  else for (int i = 0; i < 8; i++) out[i] = 0;
}

// Read path stage clocks: rb_calls, rb_bytes, rb_read_ns, rb_send_ns,
// rb_not_found, rb_cache_calls, rb_admit_ns, rbs_frames, rbs_slots,
// rbs_bytes, rbs_read_ns, rbs_send_ns, rbs_missing, rbs_admit_ns,
// rbs_torn (ABI 8).
void tpudfs_dataplane_read_stats(int64_t h, uint64_t out[15]) {
  Engine* e = get_engine(h);
  if (e) e->read_stage_stats(out);
  else for (int i = 0; i < 15; i++) out[i] = 0;
}

// QoS control contract (ABI 6). Python pushes the QosShedder config in
// (a msgpack flat map built by resilience.qos_wire_config) at start and
// on every change — the set_term of the admission plane.
void tpudfs_dataplane_set_qos(int64_t h, const char* cfg, uint64_t len) {
  Engine* e = get_engine(h);
  if (e && cfg != nullptr)
    e->qos_configure(reinterpret_cast<const uint8_t*>(cfg), len);
}

// Aggregate QoS counters: inflight, peak_inflight, admitted_total,
// shed_total, queue_depth, queued_total, rate_limited_total,
// evicted_total.
void tpudfs_dataplane_qos_stats(int64_t h, uint64_t out[8]) {
  Engine* e = get_engine(h);
  if (e) e->qos_stats(out);
  else for (int i = 0; i < 8; i++) out[i] = 0;
}

// Per-tenant "tenant\tadmitted\tshed\trate_limited\tqueue_depth\tp99_ns"
// lines (non-destructive); returns bytes written, or -needed when cap is
// short — the take_terms contract.
int64_t tpudfs_dataplane_take_qos(int64_t h, char* buf, uint64_t cap) {
  Engine* e = get_engine(h);
  return e ? e->take_qos(buf, cap) : -1;
}

int64_t tpudfs_dataplane_stop(int64_t h) {
  Engine* e = get_engine(h);
  if (!e) return -1;
  bool drained = e->stop();
  {
    std::lock_guard<std::mutex> g(g_engines_mu);
    g_engines[h] = nullptr;
  }
  if (drained) {
    delete e;
    return 0;
  }
  // A connection thread is still alive inside the engine: leaking it is
  // the only memory-safe option (shutdown already unblocked its sockets;
  // it will exit soon and touch only still-valid memory).
  return 1;
}

}  // extern "C"
