// Concurrency stress driver for the native runtime, built and run under
// ThreadSanitizer / AddressSanitizer by scripts/sanitize_native.sh.
//
// Reference discipline being mirrored: the Go repo runs its whole test
// suite with -race (tests.mk:56); the C++ surface here gets the TSAN
// equivalent — hammer the WAL handle from multiple threads (append,
// sync, size), the batch packer, the old entry point and the in-place
// one, and the validator set's root, concurrently, then verify the WAL
// contents are a clean sequence of CRC-framed records.
//
// Exit code 0 = no sanitizer report and all invariants held.

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
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
  for (auto& t : ts) t.join();
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
