// Concurrency stress driver for the native runtime, built and run under
// ThreadSanitizer / AddressSanitizer by scripts/sanitize_native.sh.
//
// Reference discipline being mirrored: the Go repo runs its whole test
// suite with -race (tests.mk:56); the C++ surface here gets the TSAN
// equivalent — hammer the WAL handle from multiple threads (append,
// sync, size), the batch packer, the old entry point and the in-place
// one, the validator set's root, and the signature cache's key pass and its
// store (one store shared by every thread), concurrently, then verify the
// WAL contents are a clean sequence of CRC-framed records.
//
// Exit code 0 = no sanitizer report and all invariants held.

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <list>
#include <map>
#include <string>
#include <thread>
#include <vector>

extern "C" {
void* wal_open(const char* path);
int wal_append(void* h, int kind, const uint8_t* data, int64_t len, int sync);
int wal_sync(void* h);
int64_t wal_size(void* h);
void wal_close(void* h);
int ed25519_pack(const uint8_t* pubs, const uint8_t* sigs, const uint8_t* msgs,
                 const int64_t* offs, int64_t n, uint8_t* s_out,
                 uint8_t* m_out, uint8_t* ok_out);
int ed25519_pack_into(const uint8_t* pubs, const uint8_t* sigs,
                      const uint8_t* msgs, const int64_t* msg_len, int64_t n,
                      const int64_t* idx, int64_t rows, uint8_t* a_rows,
                      uint8_t* r_rows, uint8_t* s_rows, uint8_t* m_rows,
                      uint8_t* s_ok_rows);
int valset_root_ed25519(const uint8_t* keys32, const int64_t* powers,
                        int64_t n, uint8_t* out32);
int valset_root_ed25519_ni(const uint8_t* keys32, const int64_t* powers,
                           int64_t n, uint8_t* out32, int ni);
int sha256_ni(const uint8_t* data, int64_t len, uint8_t* out32, int ni);
int sigcache_keys(const uint8_t* pubs, const int64_t* pub_lens,
                  int64_t pub_len, const uint8_t* msgs,
                  const int64_t* msg_lens, const uint8_t* sigs,
                  const int64_t* sig_lens, int64_t sig_len, int64_t n,
                  uint8_t* out32, int ni);
void* sigcache_new(int64_t cap);
void sigcache_free(void* h);
int sigcache_get_many(void* h, const uint8_t* keys, int64_t n, uint8_t* out);
int sigcache_put_many(void* h, const uint8_t* keys, const uint8_t* oks,
                      int64_t n);
int64_t sigcache_len(void* h);
void sigcache_clear(void* h);
void sigcache_counts(void* h, int64_t* out);
int64_t sigcache_items(void* h, uint8_t* keys_out, uint8_t* oks_out,
                       int64_t most);
}

static std::atomic<int> failures{0};

// zlib CRC32, same polynomial/table construction as cometbft_native.cpp —
// recomputed here so the verifier is independent of the code under test
static uint32_t crc32_zlib(const uint8_t* buf, size_t len) {
  static uint32_t table[256];
  static bool ready = [] {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      table[i] = c;
    }
    return true;
  }();
  (void)ready;
  uint32_t c = 0xFFFFFFFFu;
  for (size_t i = 0; i < len; i++)
    c = table[(c ^ buf[i]) & 0xFF] ^ (c >> 8);
  return c ^ 0xFFFFFFFFu;
}

static void wal_writer(void* h, int tid, int iters) {
  std::string payload = "record-from-thread-" + std::to_string(tid);
  for (int i = 0; i < iters; i++) {
    if (wal_append(h, tid, (const uint8_t*)payload.data(),
                   (int64_t)payload.size(), i % 16 == 0) != 0)
      failures++;
    if (i % 64 == 0 && wal_sync(h) != 0) failures++;
    (void)wal_size(h);
  }
}

static void packer(int tid, int iters) {
  const int64_t n = 32;
  std::vector<uint8_t> pubs(n * 32, (uint8_t)tid);
  std::vector<uint8_t> sigs(n * 64, (uint8_t)(tid + 1));
  std::vector<uint8_t> msgs(n * 8, (uint8_t)(tid + 2));
  std::vector<int64_t> offs(n + 1);
  for (int64_t i = 0; i <= n; i++) offs[i] = i * 8;
  std::vector<uint8_t> s_out(n * 32), m_out(n * 32), ok(n);
  for (int i = 0; i < iters; i++) {
    if (ed25519_pack(pubs.data(), sigs.data(), msgs.data(), offs.data(), n,
                     s_out.data(), m_out.data(), ok.data()) != 0)
      failures++;
  }
}

// The in-place pack beside the old one, into tables allocated at EXACTLY
// the padded size (the sanitizer's red zone starts at the last row's last
// byte): message lengths either side of every block-count edge so groups
// of four, lone neighbours and tails all run; straight rows, then an
// index that names the first and the last row; a guard row in the middle
// that no signature names must stay zero, the old symbol must agree on
// s, m and s_ok, and a row outside the tables must be refused unwritten.
static void packer_into(int tid, int iters) {
  const int64_t n = 37, rows = 64;
  std::vector<int64_t> len(n), idx(n);
  std::vector<int64_t> offs(n + 1, 0);
  for (int64_t i = 0; i < n; i++) {
    static const int64_t edges[] = {0, 47, 48, 122, 122, 122, 122, 175,
                                    176, 192, 320, 1};
    len[i] = edges[(i + tid) % 12];
    offs[i + 1] = offs[i] + len[i];
    idx[i] = i == 0 ? rows - 1 : i == n - 1 ? 0 : i + 1;  // row 1 unnamed
  }
  std::vector<uint8_t> pubs(n * 32), sigs(n * 64), msgs(offs[n] + 1);
  for (size_t i = 0; i < pubs.size(); i++) pubs[i] = (uint8_t)(i * 7 + tid);
  for (size_t i = 0; i < sigs.size(); i++) sigs[i] = (uint8_t)(i * 13 + tid);
  for (size_t i = 0; i < msgs.size(); i++) msgs[i] = (uint8_t)(i * 31 + tid);
  msgs.resize(offs[n]);  // exactly the bytes the lengths name
  msgs.shrink_to_fit();
  std::vector<uint8_t> s_old(n * 32), m_old(n * 32), ok_old(n);
  for (int it = 0; it < iters; it++) {
    const bool indexed = it & 1;
    std::vector<uint8_t> a(rows * 32), r(rows * 32), s(rows * 32),
        m(rows * 32), ok(rows);
    if (ed25519_pack_into(pubs.data(), sigs.data(), msgs.data(), len.data(), n,
                          indexed ? idx.data() : nullptr, rows, a.data(),
                          r.data(), s.data(), m.data(), ok.data()) != 0 ||
        ed25519_pack(pubs.data(), sigs.data(), msgs.data(), offs.data(), n,
                     s_old.data(), m_old.data(), ok_old.data()) != 0) {
      failures++;
      continue;
    }
    for (int64_t i = 0; i < n; i++) {
      const int64_t row = indexed ? idx[i] : i;
      if (std::memcmp(&a[row * 32], &pubs[i * 32], 32) ||
          std::memcmp(&r[row * 32], &sigs[i * 64], 32) ||
          std::memcmp(&s[row * 32], &s_old[i * 32], 32) ||
          std::memcmp(&m[row * 32], &m_old[i * 32], 32) ||
          ok[row] != ok_old[i])
        failures++;
    }
    // rows no signature names: row 1 under the index, rows n.. without
    for (int64_t row = indexed ? 1 : n; row < (indexed ? 2 : rows); row++)
      for (int b = 0; b < 32; b++)
        if (a[row * 32 + b] | r[row * 32 + b] | s[row * 32 + b] |
            m[row * 32 + b] | ok[row])
          failures++;
    // a row outside the tables: refused, and nothing written
    std::vector<uint8_t> z(rows * 32), zok(rows);
    std::vector<int64_t> outside(idx);
    outside[n / 2] = rows;
    if (ed25519_pack_into(pubs.data(), sigs.data(), msgs.data(), len.data(), n,
                          outside.data(), rows, z.data(), z.data(), z.data(),
                          z.data(), zok.data()) != -1)
      failures++;
    for (uint8_t v : z)
      if (v) failures++;
  }
}

// The validator set's root over random sets, keys and powers in buffers of
// EXACTLY n entries (n = 0 included, up to 10,240): the library's root
// (the best block function, then each one by name) against a root built
// here from the leaves' bytes, the split at the largest power of two below
// n, and the library's scalar SHA-256 alone.
static void leaf_bytes(const uint8_t* key, int64_t power, std::vector<uint8_t>& b) {
  static const uint8_t head[5] = {0x00, 0x0a, 0x22, 0x0a, 0x20};
  b.assign(head, head + 5);
  b.insert(b.end(), key, key + 32);
  if (power != 0) {
    b.push_back(0x10);
    for (uint64_t v = (uint64_t)power;; v >>= 7) {
      if (v < 0x80) { b.push_back((uint8_t)v); break; }
      b.push_back((uint8_t)(v | 0x80));
    }
  }
}

static std::vector<uint8_t> split_root(const std::vector<std::vector<uint8_t>>& h,
                                       size_t lo, size_t hi) {
  if (hi - lo == 1) return h[lo];
  size_t k = 1;
  while (k * 2 < hi - lo) k *= 2;
  std::vector<uint8_t> b(1, 0x01), l = split_root(h, lo, lo + k),
                                   r = split_root(h, lo + k, hi);
  b.insert(b.end(), l.begin(), l.end());
  b.insert(b.end(), r.begin(), r.end());
  std::vector<uint8_t> out(32);
  sha256_ni(b.data(), (int64_t)b.size(), out.data(), 0);
  return out;
}

static void valset_roots(int tid, int iters) {
  uint64_t x = 0x9e3779b97f4a7c15ULL * (uint64_t)(tid + 1);
  auto next = [&x] {  // xorshift64
    x ^= x << 13; x ^= x >> 7; x ^= x << 17;
    return x;
  };
  static const int64_t sizes[] = {0, 1, 2, 3, 5, 8, 9, 100, 1000, 1001, 10240};
  const int nsizes = sizeof(sizes) / sizeof(sizes[0]);
  for (int it = 0; it < iters; it++) {
    const int64_t n =
        it < nsizes ? sizes[(it + tid) % nsizes] : (int64_t)(next() % 2049);
    std::vector<uint8_t> keys((size_t)n * 32);
    std::vector<int64_t> powers((size_t)n);
    for (auto& k : keys) k = (uint8_t)next();
    for (auto& p : powers) {
      // 0 a quarter of the time, else any width, negative ones included
      p = next() % 4 == 0 ? 0 : (int64_t)(next() >> (next() % 64));
    }
    std::vector<uint8_t> want(32);
    if (n == 0) {
      sha256_ni(nullptr, 0, want.data(), 0);
    } else {
      std::vector<std::vector<uint8_t>> h((size_t)n, std::vector<uint8_t>(32));
      std::vector<uint8_t> b;
      for (int64_t i = 0; i < n; i++) {
        leaf_bytes(&keys[(size_t)i * 32], powers[(size_t)i], b);
        sha256_ni(b.data(), (int64_t)b.size(), h[(size_t)i].data(), 0);
      }
      want = split_root(h, 0, (size_t)n);
    }
    uint8_t got[32];
    if (valset_root_ed25519(keys.data(), powers.data(), n, got) != 0 ||
        std::memcmp(got, want.data(), 32))
      failures++;
    for (int ni = 0; ni < 2; ni++) {
      int rc = valset_root_ed25519_ni(keys.data(), powers.data(), n, got, ni);
      if (rc == -2 && ni == 1) continue;  // no SHA extensions on this CPU
      if (rc != 0 || std::memcmp(got, want.data(), 32)) failures++;
    }
  }
  uint8_t out[32];
  if (valset_root_ed25519(nullptr, nullptr, -1, out) != -1) failures++;
}

static uint64_t xorshift(uint64_t& x) {
  x ^= x << 13; x ^= x >> 7; x ^= x << 17;
  return x;
}

// The signature cache's keys of random triples (pubs of 32 or 33 bytes,
// messages of 0 to 300, signatures of 64 or an odd size), the three fields
// in buffers of EXACTLY their bytes, each length given or, where a field is
// of one size, not: against SHA-256 of the framed bytes built here, with
// the library's scalar SHA-256 alone; each block function by name.
static void sigcache_keys_check(int tid, int iters) {
  uint64_t x = 0x2545f4914f6cdd1dULL * (uint64_t)(tid + 3);
  for (int it = 0; it < iters; it++) {
    const int64_t n = (int64_t)(xorshift(x) % 40);
    const bool fixed = it & 1;  // every pub 32 and every sig 64, not listed
    std::vector<int64_t> pl(n), ml(n), sl(n);
    size_t pt = 0, mt = 0, st = 0;
    for (int64_t i = 0; i < n; i++) {
      pl[i] = fixed ? 32 : 32 + (int64_t)(xorshift(x) % 2);
      ml[i] = (int64_t)(xorshift(x) % 301);
      sl[i] = fixed ? 64 : (xorshift(x) % 2 ? 64 : 1 + 2 * (int64_t)(xorshift(x) % 50));
      pt += pl[i]; mt += ml[i]; st += sl[i];
    }
    std::vector<uint8_t> pubs(pt), msgs(mt), sigs(st), want((size_t)n * 32);
    for (auto& b : pubs) b = (uint8_t)xorshift(x);
    for (auto& b : msgs) b = (uint8_t)xorshift(x);
    for (auto& b : sigs) b = (uint8_t)xorshift(x);
    size_t po = 0, mo = 0, so = 0;
    for (int64_t i = 0; i < n; i++) {
      std::vector<uint8_t> f;
      for (int k = 0; k < 4; k++) f.push_back((uint8_t)(pl[i] >> (8 * k)));
      f.insert(f.end(), pubs.begin() + po, pubs.begin() + po + pl[i]);
      for (int k = 0; k < 4; k++) f.push_back((uint8_t)(ml[i] >> (8 * k)));
      f.insert(f.end(), msgs.begin() + mo, msgs.begin() + mo + ml[i]);
      f.insert(f.end(), sigs.begin() + so, sigs.begin() + so + sl[i]);
      sha256_ni(f.data(), (int64_t)f.size(), &want[(size_t)i * 32], 0);
      po += pl[i]; mo += ml[i]; so += sl[i];
    }
    for (int ni = -1; ni < 2; ni++) {
      std::vector<uint8_t> got((size_t)n * 32);
      int rc = sigcache_keys(pubs.data(), fixed ? nullptr : pl.data(), 32,
                             msgs.data(), ml.data(), sigs.data(),
                             fixed ? nullptr : sl.data(), 64, n, got.data(),
                             ni);
      if (rc == -2 && ni == 1) continue;  // no SHA extensions on this CPU
      if (rc != 0 || got != want) failures++;
    }
  }
  if (sigcache_keys(nullptr, nullptr, 0, nullptr, nullptr, nullptr, nullptr,
                    0, -1, nullptr, -1) != -1)
    failures++;
}

// A key whose verdict is its own low bit, so a verdict torn between
// threads shows as a wrong bit.
static void cache_key(int k, uint8_t out[32]) {
  uint64_t x = 0x9e3779b97f4a7c15ULL * (uint64_t)(k + 1);
  for (int b = 0; b < 32; b++) out[b] = (uint8_t)xorshift(x);
  out[0] = (uint8_t)((out[0] & 0xFE) | (k & 1));
}

// One store, capacity 64, shared by every thread: gets and puts of batches
// over 200 keys, every verdict read checked against its key; the counts
// are checked whole after the join (main).
static std::atomic<int64_t> cache_gets{0}, cache_puts{0};

static void sigcache_hammer(void* store, int tid, int iters) {
  uint64_t x = 0xda942042e4dd58b5ULL * (uint64_t)(tid + 1);
  for (int it = 0; it < iters; it++) {
    const int64_t n = 1 + (int64_t)(xorshift(x) % 24);
    std::vector<uint8_t> keys((size_t)n * 32), oks((size_t)n), got((size_t)n);
    for (int64_t i = 0; i < n; i++) {
      int k = (int)(xorshift(x) % 200);
      cache_key(k, &keys[(size_t)i * 32]);
      oks[(size_t)i] = (uint8_t)(k & 1);
    }
    if (xorshift(x) % 2) {
      if (sigcache_put_many(store, keys.data(), oks.data(), n) != 0) failures++;
      cache_puts += n;
    } else {
      if (sigcache_get_many(store, keys.data(), n, got.data()) != 0) failures++;
      cache_gets += n;
      for (int64_t i = 0; i < n; i++)
        if (got[(size_t)i] != 2 && got[(size_t)i] != oks[(size_t)i]) failures++;
    }
    if (it % 50 == 0 && sigcache_len(store) > 64) failures++;
  }
}

// The store against an LRU built here (a list, newest last, and a map)
// over random gets and puts at capacities 1, 7 and 64: every answer, the
// counts, and the entries in their order.
static void sigcache_oracle_check() {
  static const int64_t caps[] = {1, 7, 64};
  for (int64_t cap : caps) {
    void* s = sigcache_new(cap);
    if (!s) { failures++; continue; }
    std::list<std::pair<std::vector<uint8_t>, uint8_t>> lru;
    std::map<std::vector<uint8_t>, decltype(lru)::iterator> at;
    int64_t hits = 0, misses = 0, puts = 0;
    uint64_t x = 0x853c49e6748fea9bULL + (uint64_t)cap;
    for (int it = 0; it < 2000; it++) {
      const int64_t n = (int64_t)(xorshift(x) % 9);
      std::vector<uint8_t> keys((size_t)n * 32), oks((size_t)n), got((size_t)n);
      for (int64_t i = 0; i < n; i++) {
        cache_key((int)(xorshift(x) % (3 * cap + 2)), &keys[(size_t)i * 32]);
        oks[(size_t)i] = (uint8_t)(xorshift(x) % 2);
      }
      const bool put = xorshift(x) % 2;
      if (put) sigcache_put_many(s, keys.data(), oks.data(), n);
      else sigcache_get_many(s, keys.data(), n, got.data());
      for (int64_t i = 0; i < n; i++) {
        std::vector<uint8_t> k(&keys[(size_t)i * 32], &keys[(size_t)i * 32] + 32);
        auto f = at.find(k);
        if (put) {
          if (f != at.end()) lru.erase(f->second);
          lru.emplace_back(k, oks[(size_t)i]);
          at[k] = std::prev(lru.end());
          if ((int64_t)lru.size() > cap) {
            at.erase(lru.front().first);
            lru.pop_front();
          }
          puts++;
        } else if (f == at.end()) {
          misses++;
          if (got[(size_t)i] != 2) failures++;
        } else {
          hits++;
          if (got[(size_t)i] != f->second->second) failures++;
          lru.splice(lru.end(), lru, f->second);
        }
      }
      if (it % 97 == 0) {
        int64_t c[4];
        sigcache_counts(s, c);
        if (c[0] != hits || c[1] != misses || c[2] != puts ||
            c[3] != (int64_t)lru.size() || sigcache_len(s) != c[3])
          failures++;
        std::vector<uint8_t> ks((size_t)cap * 32), vs((size_t)cap);
        int64_t k = sigcache_items(s, ks.data(), vs.data(), cap);
        if (k != (int64_t)lru.size()) failures++;
        int64_t j = 0;
        for (auto& e : lru) {
          if (j >= k || std::memcmp(&ks[(size_t)j * 32], e.first.data(), 32) ||
              vs[(size_t)j] != e.second)
            failures++;
          j++;
        }
      }
    }
    sigcache_clear(s);
    int64_t c[4];
    sigcache_counts(s, c);
    if (c[0] || c[1] || c[2] || c[3]) failures++;
    sigcache_free(s);
  }
  if (sigcache_new(0) != nullptr) failures++;
}

int main(int argc, char** argv) {
  const char* path = argc > 1 ? argv[1] : "/tmp/native_stress.wal";
  std::remove(path);
  void* h = wal_open(path);
  if (!h) {
    std::fprintf(stderr, "wal_open failed\n");
    return 2;
  }
  std::vector<std::thread> ts;
  const int kThreads = 8, kIters = 500;
  for (int t = 0; t < kThreads; t++) ts.emplace_back(wal_writer, h, t, kIters);
  for (int t = 0; t < 4; t++) ts.emplace_back(packer, t, 200);
  for (int t = 0; t < 4; t++) ts.emplace_back(packer_into, t, 100);
  for (int t = 0; t < 4; t++) ts.emplace_back(valset_roots, t, 16);
  for (int t = 0; t < 4; t++) ts.emplace_back(sigcache_keys_check, t, 40);
  void* store = sigcache_new(64);
  if (!store) return 2;
  for (int t = 0; t < 6; t++) ts.emplace_back(sigcache_hammer, store, t, 2000);
  ts.emplace_back(sigcache_oracle_check);
  for (auto& t : ts) t.join();
  int64_t counts[4];
  sigcache_counts(store, counts);
  if (counts[0] + counts[1] != cache_gets.load() ||
      counts[2] != cache_puts.load() || counts[3] > 64)
    failures++;
  sigcache_free(store);
  wal_sync(h);
  int64_t size = wal_size(h);
  wal_close(h);
  if (failures.load() != 0) {
    std::fprintf(stderr, "%d operation failures\n", failures.load());
    return 3;
  }
  // frame layout (cometbft_native.cpp wal_append): u32be crc | u32be len
  // | body (kind byte + payload).  Verify the file walks cleanly to EOF
  // with the expected record count — torn/interleaved frames fail here.
  FILE* f = std::fopen(path, "rb");
  if (!f) return 4;
  int records = 0;
  for (;;) {
    uint8_t hdr[8];
    size_t got = std::fread(hdr, 1, sizeof hdr, f);
    if (got == 0) break;
    if (got != sizeof hdr) {
      std::fprintf(stderr, "torn header after %d records\n", records);
      return 5;
    }
    uint64_t len = ((uint64_t)hdr[4] << 24) | ((uint64_t)hdr[5] << 16) |
                   ((uint64_t)hdr[6] << 8) | (uint64_t)hdr[7];
    if (len == 0 || len > (1u << 20)) {
      std::fprintf(stderr, "corrupt length %llu\n", (unsigned long long)len);
      return 6;
    }
    std::vector<uint8_t> payload(len);
    if (std::fread(payload.data(), 1, len, f) != len) {
      std::fprintf(stderr, "torn payload after %d records\n", records);
      return 7;
    }
    uint32_t want = ((uint32_t)hdr[0] << 24) | ((uint32_t)hdr[1] << 16) |
                    ((uint32_t)hdr[2] << 8) | (uint32_t)hdr[3];
    if (crc32_zlib(payload.data(), payload.size()) != want) {
      std::fprintf(stderr, "CRC mismatch in record %d (interleaved "
                   "payload bytes?)\n", records);
      return 9;
    }
    records++;
  }
  std::fclose(f);
  if (records != kThreads * kIters) {
    std::fprintf(stderr, "expected %d records, found %d\n", kThreads * kIters,
                 records);
    return 8;
  }
  std::printf("native_stress: OK (%d records, %lld bytes)\n", records,
              (long long)size);
  return 0;
}
