// Concurrency stress driver for the native runtime, built and run under
// ThreadSanitizer / AddressSanitizer by scripts/sanitize_native.sh.
//
// Reference discipline being mirrored: the Go repo runs its whole test
// suite with -race (tests.mk:56); the C++ surface here gets the TSAN
// equivalent — hammer the WAL handle from multiple threads (append,
// sync, size) and the batch packer, the old entry point and the in-place
// one, concurrently, then verify the WAL contents are a clean sequence of
// CRC-framed records.
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
