// Native runtime components (reference §2.1: the reference's native surface
// — blst, curve25519-voi asm, RocksDB — maps here to a C++ host library).
//
//  * WAL engine: CRC32+length framed append log with fsync discipline,
//    byte-compatible with cometbft_tpu/consensus/wal.py's Python framing
//    (reference: internal/consensus/wal.go WALEncoder + autofile).
//  * Ed25519 batch packer: the host side of the TPU verify pipeline —
//    SHA-512(R||A||m) mod L and scalar complement per signature
//    (reference: the curve25519-voi batch preparation the Go code runs
//    per-signature on the CPU) — C++ so 10k-signature commits don't pay a
//    Python loop before the kernel launch.  One pass from the joined
//    inputs into the padded tables the launch hands to the device
//    (ed25519_pack_into); SHA-512 four signatures at a time in AVX2
//    registers where the CPU has them, h mod L by folding at 2^252.
//    One thread: the caller's.
//  * The validator set's Merkle root: every SimpleValidator leaf of an
//    ed25519 set encoded and the RFC 6962 tree reduced in one call
//    (valset_root_ed25519), SHA-256 with the SHA extensions where the CPU
//    has them.
//  * The signature cache: a segment's keys in one pass (sigcache_keys, the
//    same SHA-256) and the bounded LRU of verdicts they index (sigcache_new
//    and the calls on its handle), behind one mutex.
//
// Build: g++ -O3 -shared -fPIC -std=c++17, no -march (driven by
// cometbft_tpu/native/__init__.py); what needs AVX2 says so itself.

#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <new>
#include <random>
#include <vector>
#include <cstdlib>
#include <fcntl.h>
#include <unistd.h>
#if defined(__x86_64__) && defined(__GNUC__)
#include <immintrin.h>  // the four-lane SHA-512 and SHA-NI SHA-256 below
#endif

// ---------------------------------------------------------------------------
// CRC32 (zlib polynomial, matches Python's zlib.crc32)
// ---------------------------------------------------------------------------

static uint32_t crc_table[256];

static int crc_init() {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        crc_table[i] = c;
    }
    return 0;
}

static uint32_t crc32_of(const uint8_t* buf, size_t len) {
    // magic static: guaranteed one-time, thread-safe initialization
    static const int crc_ready = crc_init();
    (void)crc_ready;
    uint32_t c = 0xFFFFFFFFu;
    for (size_t i = 0; i < len; i++)
        c = crc_table[(c ^ buf[i]) & 0xFF] ^ (c >> 8);
    return c ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// WAL engine
// ---------------------------------------------------------------------------

struct Wal {
    int fd;
    int64_t size;
    std::mutex mtx;  // appends must be whole-frame atomic across threads
};

extern "C" {

void* wal_open(const char* path) {
    int fd = ::open(path, O_WRONLY | O_CREAT | O_APPEND, 0644);
    if (fd < 0) return nullptr;
    Wal* w = new Wal();
    w->fd = fd;
    w->size = ::lseek(fd, 0, SEEK_END);
    return w;
}

// frame: u32be crc | u32be len | kind byte | payload
int wal_append(void* h, int kind, const uint8_t* data, int64_t len, int sync) {
    Wal* w = static_cast<Wal*>(h);
    if (!w || len < 0) return -1;
    size_t body_len = static_cast<size_t>(len) + 1;
    uint8_t* frame = static_cast<uint8_t*>(malloc(8 + body_len));
    if (!frame) return -1;
    frame[8] = static_cast<uint8_t>(kind);
    memcpy(frame + 9, data, len);
    uint32_t crc = crc32_of(frame + 8, body_len);
    uint32_t blen = static_cast<uint32_t>(body_len);
    for (int i = 0; i < 4; i++) {
        frame[i] = (crc >> (24 - 8 * i)) & 0xFF;
        frame[4 + i] = (blen >> (24 - 8 * i)) & 0xFF;
    }
    size_t total = 8 + body_len;
    {
        // hold the lock across the partial-write loop: a frame must hit
        // the file contiguously even if write() returns short (TSAN
        // stress gate: scripts/sanitize_native.sh)
        std::lock_guard<std::mutex> g(w->mtx);
        size_t off = 0;
        while (off < total) {
            ssize_t nw = ::write(w->fd, frame + off, total - off);
            if (nw < 0) { free(frame); return -1; }
            off += static_cast<size_t>(nw);
        }
        w->size += static_cast<int64_t>(total);
    }
    free(frame);
    if (sync && ::fsync(w->fd) != 0) return -1;
    return 0;
}

int wal_sync(void* h) {
    Wal* w = static_cast<Wal*>(h);
    return w ? ::fsync(w->fd) : -1;
}

int64_t wal_size(void* h) {
    Wal* w = static_cast<Wal*>(h);
    if (!w) return -1;
    std::lock_guard<std::mutex> g(w->mtx);
    return w->size;
}

void wal_close(void* h) {
    Wal* w = static_cast<Wal*>(h);
    if (!w) return;
    ::fsync(w->fd);
    ::close(w->fd);
    delete w;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// SHA-512 (FIPS 180-4)
// ---------------------------------------------------------------------------

static const uint64_t K512[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

static const uint64_t SHA512_IV[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};

static inline uint64_t load_le64(const uint8_t* p) {
    uint64_t v;
    memcpy(&v, p, 8);
#if __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
    v = __builtin_bswap64(v);
#endif
    return v;
}

static inline void store_le64(uint8_t* p, uint64_t v) {
#if __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
    v = __builtin_bswap64(v);
#endif
    memcpy(p, &v, 8);
}

static inline uint64_t load_be64(const uint8_t* p) {
    return __builtin_bswap64(load_le64(p));
}

static inline uint64_t rotr64(uint64_t x, int n) {
    return (x >> n) | (x << (64 - n));
}

// one round, the eight working variables named by the caller so that a
// group of eight rounds rotates them by renaming and moves nothing
#define SHA512_ROUND(a, b, c, d, e, f, g, h, kw)                            \
    do {                                                                    \
        uint64_t t1 = h + (rotr64(e, 14) ^ rotr64(e, 18) ^ rotr64(e, 41)) + \
                      ((e & f) ^ (~e & g)) + (kw);                          \
        uint64_t t2 = (rotr64(a, 28) ^ rotr64(a, 34) ^ rotr64(a, 39)) +     \
                      ((a & b) ^ (a & c) ^ (b & c));                        \
        d += t1;                                                            \
        h = t1 + t2;                                                        \
    } while (0)

// the compression function over one 128-byte block, read where it lies
static void sha512_block(uint64_t st[8], const uint8_t* p) {
    uint64_t w[16];
    for (int i = 0; i < 16; i++) w[i] = load_be64(p + 8 * i);
    uint64_t a = st[0], b = st[1], c = st[2], d = st[3];
    uint64_t e = st[4], f = st[5], g = st[6], h = st[7];
    for (int i = 0; i < 80; i += 8) {
        if (i >= 16) {
            for (int j = 0; j < 8; j++) {
                uint64_t w15 = w[(i + j + 1) & 15], w2 = w[(i + j + 14) & 15];
                w[(i + j) & 15] +=
                    (rotr64(w15, 1) ^ rotr64(w15, 8) ^ (w15 >> 7)) +
                    w[(i + j + 9) & 15] +
                    (rotr64(w2, 19) ^ rotr64(w2, 61) ^ (w2 >> 6));
            }
        }
        const uint64_t* k = K512 + i;
        const uint64_t* x = w + (i & 15);
        SHA512_ROUND(a, b, c, d, e, f, g, h, k[0] + x[0]);
        SHA512_ROUND(h, a, b, c, d, e, f, g, k[1] + x[1]);
        SHA512_ROUND(g, h, a, b, c, d, e, f, k[2] + x[2]);
        SHA512_ROUND(f, g, h, a, b, c, d, e, k[3] + x[3]);
        SHA512_ROUND(e, f, g, h, a, b, c, d, k[4] + x[4]);
        SHA512_ROUND(d, e, f, g, h, a, b, c, k[5] + x[5]);
        SHA512_ROUND(c, d, e, f, g, h, a, b, k[6] + x[6]);
        SHA512_ROUND(b, c, d, e, f, g, h, a, k[7] + x[7]);
    }
    st[0] += a; st[1] += b; st[2] += c; st[3] += d;
    st[4] += e; st[5] += f; st[6] += g; st[7] += h;
}

// The padded input of one hash, block by block: an optional 64-byte head
// in two halves (R, A), the body, then the FIPS padding.  The length is
// known up front, so the padding is laid down in one step and a block
// that lies whole in the body is hashed where it is.
struct Padded {
    const uint8_t* h0;  // head: 32 + 32 bytes, or null for none
    const uint8_t* h1;
    const uint8_t* body;
    size_t head_len;  // 0 or 64
    size_t body_len;
    size_t total;  // head_len + body_len
    size_t nblocks;
};

static inline Padded padded_of(const uint8_t* h0, const uint8_t* h1,
                               const uint8_t* body, size_t body_len) {
    Padded p;
    p.h0 = h0;
    p.h1 = h1;
    p.body = body;
    p.head_len = h0 ? 64 : 0;
    p.body_len = body_len;
    p.total = p.head_len + body_len;
    p.nblocks = (p.total + 1 + 16 + 127) / 128;  // 0x80, 128-bit length
    return p;
}

// Block k of the padded input: a pointer into the body where the whole
// block lies in it, else the block assembled in ``scratch``.
static inline const uint8_t* padded_block(const Padded& p, size_t k,
                                          uint8_t scratch[128]) {
    size_t s = 128 * k;  // offset of the block in the unpadded input
    if (s >= p.head_len && s - p.head_len + 128 <= p.body_len)
        return p.body + (s - p.head_len);
    size_t fill = 0;
    if (s < p.head_len) {  // k == 0 under a head
        memcpy(scratch, p.h0, 32);
        memcpy(scratch + 32, p.h1, 32);
        fill = 64;
    }
    size_t boff = s + fill - p.head_len;
    if (boff < p.body_len) {
        size_t take = p.body_len - boff;
        if (take > 128 - fill) take = 128 - fill;
        memcpy(scratch + fill, p.body + boff, take);
        fill += take;
    }
    if (fill < 128) {
        memset(scratch + fill, 0, 128 - fill);
        if (s + fill == p.total) scratch[fill] = 0x80;
        if (k + 1 == p.nblocks) {
            uint64_t bits = (uint64_t)p.total * 8;
            for (int i = 0; i < 8; i++)
                scratch[127 - i] = (uint8_t)(bits >> (8 * i));
        }
    }
    return scratch;
}

// the hash state of one padded input
static void sha512_padded(const Padded& p, uint64_t st[8]) {
    uint8_t scratch[128];
    memcpy(st, SHA512_IV, sizeof(SHA512_IV));
    for (size_t k = 0; k < p.nblocks; k++)
        sha512_block(st, padded_block(p, k, scratch));
}

// Four inputs of one block count hashed side by side, one 64-bit lane of
// an AVX2 register each.  Compiled for AVX2 whatever the build's flags
// are and called only where the CPU has it (``cpu_has_wide``).
#if defined(__x86_64__) && defined(__GNUC__)
#define SHA512_HAVE_X4 1

#define X4_TARGET __attribute__((target("avx2")))

#define x4_rotr(x, n) \
    _mm256_or_si256(_mm256_srli_epi64(x, n), _mm256_slli_epi64(x, 64 - (n)))

X4_TARGET static inline __m256i x4_xor3(__m256i a, __m256i b, __m256i c) {
    return _mm256_xor_si256(_mm256_xor_si256(a, b), c);
}

#define X4_ROUND(a, b, c, d, e, f, g, h, kw)                                 \
    do {                                                                     \
        __m256i S1 = x4_xor3(x4_rotr(e, 14), x4_rotr(e, 18), x4_rotr(e, 41)); \
        __m256i ch = _mm256_xor_si256(                                       \
            g, _mm256_and_si256(e, _mm256_xor_si256(f, g)));                 \
        __m256i t1 = _mm256_add_epi64(                                       \
            _mm256_add_epi64(h, S1), _mm256_add_epi64(ch, (kw)));            \
        __m256i S0 = x4_xor3(x4_rotr(a, 28), x4_rotr(a, 34), x4_rotr(a, 39)); \
        __m256i maj = _mm256_or_si256(                                       \
            _mm256_and_si256(a, b),                                          \
            _mm256_and_si256(c, _mm256_or_si256(a, b)));                     \
        d = _mm256_add_epi64(d, t1);                                         \
        h = _mm256_add_epi64(t1, _mm256_add_epi64(S0, maj));                 \
    } while (0)

X4_TARGET static void sha512_block_x4(__m256i st[8],
                                      const uint8_t* const p[4]) {
    // big-endian words of four blocks, transposed: w[i] holds word i of
    // each block
    const __m256i bswap = _mm256_setr_epi8(
        7, 6, 5, 4, 3, 2, 1, 0, 15, 14, 13, 12, 11, 10, 9, 8,
        7, 6, 5, 4, 3, 2, 1, 0, 15, 14, 13, 12, 11, 10, 9, 8);
    __m256i w[16];
    for (int q = 0; q < 4; q++) {
        __m256i x0 = _mm256_shuffle_epi8(
            _mm256_loadu_si256((const __m256i*)(p[0] + 32 * q)), bswap);
        __m256i x1 = _mm256_shuffle_epi8(
            _mm256_loadu_si256((const __m256i*)(p[1] + 32 * q)), bswap);
        __m256i x2 = _mm256_shuffle_epi8(
            _mm256_loadu_si256((const __m256i*)(p[2] + 32 * q)), bswap);
        __m256i x3 = _mm256_shuffle_epi8(
            _mm256_loadu_si256((const __m256i*)(p[3] + 32 * q)), bswap);
        __m256i t0 = _mm256_unpacklo_epi64(x0, x1);
        __m256i t1 = _mm256_unpackhi_epi64(x0, x1);
        __m256i t2 = _mm256_unpacklo_epi64(x2, x3);
        __m256i t3 = _mm256_unpackhi_epi64(x2, x3);
        w[4 * q + 0] = _mm256_permute2x128_si256(t0, t2, 0x20);
        w[4 * q + 1] = _mm256_permute2x128_si256(t1, t3, 0x20);
        w[4 * q + 2] = _mm256_permute2x128_si256(t0, t2, 0x31);
        w[4 * q + 3] = _mm256_permute2x128_si256(t1, t3, 0x31);
    }
    __m256i a = st[0], b = st[1], c = st[2], d = st[3];
    __m256i e = st[4], f = st[5], g = st[6], h = st[7];
    for (int i = 0; i < 80; i += 8) {
        if (i >= 16) {
            for (int j = 0; j < 8; j++) {
                __m256i w15 = w[(i + j + 1) & 15], w2 = w[(i + j + 14) & 15];
                __m256i s0 = x4_xor3(x4_rotr(w15, 1), x4_rotr(w15, 8),
                                     _mm256_srli_epi64(w15, 7));
                __m256i s1 = x4_xor3(x4_rotr(w2, 19), x4_rotr(w2, 61),
                                     _mm256_srli_epi64(w2, 6));
                w[(i + j) & 15] = _mm256_add_epi64(
                    _mm256_add_epi64(w[(i + j) & 15], s0),
                    _mm256_add_epi64(w[(i + j + 9) & 15], s1));
            }
        }
        const uint64_t* k = K512 + i;
        const __m256i* x = w + (i & 15);
#define X4_KW(j) \
    _mm256_add_epi64(_mm256_set1_epi64x((long long)k[j]), x[j])
        X4_ROUND(a, b, c, d, e, f, g, h, X4_KW(0));
        X4_ROUND(h, a, b, c, d, e, f, g, X4_KW(1));
        X4_ROUND(g, h, a, b, c, d, e, f, X4_KW(2));
        X4_ROUND(f, g, h, a, b, c, d, e, X4_KW(3));
        X4_ROUND(e, f, g, h, a, b, c, d, X4_KW(4));
        X4_ROUND(d, e, f, g, h, a, b, c, X4_KW(5));
        X4_ROUND(c, d, e, f, g, h, a, b, X4_KW(6));
        X4_ROUND(b, c, d, e, f, g, h, a, X4_KW(7));
#undef X4_KW
    }
    st[0] = _mm256_add_epi64(st[0], a);
    st[1] = _mm256_add_epi64(st[1], b);
    st[2] = _mm256_add_epi64(st[2], c);
    st[3] = _mm256_add_epi64(st[3], d);
    st[4] = _mm256_add_epi64(st[4], e);
    st[5] = _mm256_add_epi64(st[5], f);
    st[6] = _mm256_add_epi64(st[6], g);
    st[7] = _mm256_add_epi64(st[7], h);
}

// the hash states of four padded inputs of the SAME block count
X4_TARGET static void sha512_padded_x4(const Padded p[4], uint64_t st[4][8]) {
    uint8_t scratch[4][128];
    __m256i v[8];
    for (int i = 0; i < 8; i++)
        v[i] = _mm256_set1_epi64x((long long)SHA512_IV[i]);
    for (size_t k = 0; k < p[0].nblocks; k++) {
        const uint8_t* blk[4];
        for (int j = 0; j < 4; j++)
            blk[j] = padded_block(p[j], k, scratch[j]);
        sha512_block_x4(v, blk);
    }
    uint64_t lanes[8][4];
    for (int i = 0; i < 8; i++)
        _mm256_storeu_si256((__m256i*)lanes[i], v[i]);
    for (int j = 0; j < 4; j++)
        for (int i = 0; i < 8; i++) st[j][i] = lanes[i][j];
}

static bool cpu_has_wide() {
    static const bool has = __builtin_cpu_supports("avx2");
    return has;
}
#else
static bool cpu_has_wide() { return false; }
#endif

// ---------------------------------------------------------------------------
// mod-L arithmetic (L = 2^252 + c, c = 27742317777372353535851937790883648493)
// ---------------------------------------------------------------------------

// little-endian u64 limbs throughout; c is 125 bits, two limbs
static const uint64_t LC0 = 0x5812631a5cf5d3edULL;
static const uint64_t LC1 = 0x14def9dea2f79cd6ULL;
static const uint64_t L_LIMBS[4] = {LC0, LC1, 0, 0x1000000000000000ULL};
static const uint64_t MASK60 = 0x0fffffffffffffffULL;

typedef unsigned __int128 u128;

static inline bool ge256(const uint64_t a[4], const uint64_t b[4]) {
    for (int i = 3; i >= 0; i--)
        if (a[i] != b[i]) return a[i] > b[i];
    return true;
}

static inline void add256(uint64_t a[4], const uint64_t b[4]) {  // mod 2^256
    u128 carry = 0;
    for (int i = 0; i < 4; i++) {
        carry += (u128)a[i] + b[i];
        a[i] = (uint64_t)carry;
        carry >>= 64;
    }
}

static inline void sub256(uint64_t a[4], const uint64_t b[4]) {  // mod 2^256
    uint64_t borrow = 0;
    for (int i = 0; i < 4; i++) {
        u128 d = (u128)a[i] - b[i] - borrow;
        a[i] = (uint64_t)d;
        borrow = (uint64_t)(d >> 64) & 1;
    }
}

// out[N + 2] = a[N] * c
template <int N>
static inline void mul_c(const uint64_t* a, uint64_t* out) {
    u128 carry = 0;
    for (int i = 0; i < N; i++) {
        carry += (u128)a[i] * LC0;
        out[i] = (uint64_t)carry;
        carry >>= 64;
    }
    out[N] = (uint64_t)carry;
    carry = 0;
    for (int i = 0; i < N; i++) {
        carry += (u128)a[i] * LC1 + out[i + 1];
        out[i + 1] = (uint64_t)carry;
        carry >>= 64;
    }
    out[N + 1] = (uint64_t)carry;
}

// x[N] cut at bit 252: lo[4] the low 252 bits, hi[N - 3] the rest
template <int N>
static inline void split252(const uint64_t* x, uint64_t lo[4], uint64_t* hi) {
    lo[0] = x[0];
    lo[1] = x[1];
    lo[2] = x[2];
    lo[3] = x[3] & MASK60;
    for (int j = 0; j < N - 4; j++) hi[j] = (x[3 + j] >> 60) | (x[4 + j] << 4);
    hi[N - 4] = x[N - 1] >> 60;
}

// r = x mod L for a 512-bit x, in three folds at 2^252: with
// x = hi * 2^252 + lo and 2^252 = -c (mod L), x = lo - hi * c.  Each fold
// takes 127 bits off the product (260 -> 133 -> 6 bits of ``hi``), so
//   x = lo1 - lo2 + lo3 - hi3 * c   (mod L),
// every term under 2^252 and the last under 2^131; 2L is added first so
// that the sum stays positive, and what is left is under 4L.
static void mod_l_512(const uint64_t x[8], uint64_t r[4]) {
    uint64_t lo1[4], hi1[5], p1[7];
    split252<8>(x, lo1, hi1);
    mul_c<5>(hi1, p1);  // under 2^385
    uint64_t lo2[4], hi2[4], p2[5];
    split252<7>(p1, lo2, hi2);  // hi2 under 2^133: hi2[3] is 0
    mul_c<3>(hi2, p2);  // under 2^258
    uint64_t lo3[4], hi3[2], p3[4];
    split252<5>(p2, lo3, hi3);  // hi3 under 2^6: hi3[1] is 0
    mul_c<1>(hi3, p3);  // under 2^131
    p3[3] = 0;
    memcpy(r, L_LIMBS, sizeof(L_LIMBS));
    add256(r, L_LIMBS);
    add256(r, lo1);
    add256(r, lo3);
    sub256(r, lo2);
    sub256(r, p3);
    while (ge256(r, L_LIMBS)) sub256(r, L_LIMBS);
}

// the digest as the little-endian integer ed25519 reads it: limb j is
// state word j with its bytes in the other order
static inline void digest_limbs(const uint64_t st[8], uint64_t x[8]) {
    for (int i = 0; i < 8; i++) x[i] = __builtin_bswap64(st[i]);
}

// ---------------------------------------------------------------------------
// Ed25519 batch packer
// ---------------------------------------------------------------------------

namespace {

// Where one signature's outputs go: five row-major tables of 32-byte rows
// (``ok``: one byte a row), ``a`` and ``r`` optional.
struct PackOut {
    uint8_t* a;
    uint8_t* r;
    uint8_t* s;
    uint8_t* m;
    uint8_t* ok;
};

// everything of one signature but the hash: ``st`` is SHA-512(R || A || msg)
static inline void pack_finish(const uint8_t* pub, const uint8_t* sig,
                               const uint64_t st[8], const PackOut& o,
                               int64_t row) {
    if (o.a) memcpy(o.a + row * 32, pub, 32);
    if (o.r) memcpy(o.r + row * 32, sig, 32);

    // s < L, else the lane carries s = 0 and fails
    uint64_t s[4];
    for (int i = 0; i < 4; i++) s[i] = load_le64(sig + 32 + 8 * i);
    bool s_ok = !ge256(s, L_LIMBS);
    o.ok[row] = (uint8_t)s_ok;
    if (s_ok)
        memcpy(o.s + row * 32, sig + 32, 32);
    else
        memset(o.s + row * 32, 0, 32);

    // m = (L - h) mod L, h = digest mod L
    uint64_t x[8], h[4], m[4] = {0, 0, 0, 0};
    digest_limbs(st, x);
    mod_l_512(x, h);
    if (h[0] | h[1] | h[2] | h[3]) {
        memcpy(m, L_LIMBS, sizeof(m));
        sub256(m, h);
    }
    for (int i = 0; i < 4; i++) store_le64(o.m + row * 32 + 8 * i, m[i]);
}

// The pack: signature i (pubs i*32, sigs i*64, the next len[i] bytes of
// msgs) to row idx[i], or row i without an index.  ``wide``: hash four
// neighbours of one block count side by side where the CPU can.  -1,
// before anything is written, where a row would lie outside the tables.
static int pack_rows(const uint8_t* pubs, const uint8_t* sigs,
                     const uint8_t* msgs, const int64_t* len, int64_t n,
                     const int64_t* idx, int64_t rows, const PackOut& o,
                     bool wide) {
    if (n < 0 || rows < 0 || (!idx && n > rows)) return -1;
    for (int64_t i = 0; i < n; i++) {
        if (len[i] < 0) return -1;
        if (idx && (idx[i] < 0 || idx[i] >= rows)) return -1;
    }
    auto input = [&](int64_t i, int64_t at) {
        return padded_of(sigs + i * 64, pubs + i * 32, msgs + at,
                         (size_t)len[i]);
    };
    auto finish = [&](int64_t i, const uint64_t st[8]) {
        pack_finish(pubs + i * 32, sigs + i * 64, st, o, idx ? idx[i] : i);
    };
    int64_t i = 0, at = 0;  // message i starts at msgs + at
#ifdef SHA512_HAVE_X4
    if (wide && cpu_has_wide()) {
        while (i + 4 <= n) {
            int64_t at1 = at + len[i], at2 = at1 + len[i + 1],
                    at3 = at2 + len[i + 2];
            Padded p[4] = {input(i, at), input(i + 1, at1),
                           input(i + 2, at2), input(i + 3, at3)};
            uint64_t st[4][8];
            if (p[1].nblocks == p[0].nblocks && p[2].nblocks == p[0].nblocks &&
                p[3].nblocks == p[0].nblocks) {
                sha512_padded_x4(p, st);
                for (int j = 0; j < 4; j++) finish(i + j, st[j]);
                at = at3 + len[i + 3];
                i += 4;
            } else {  // a neighbour of another length: this one alone
                sha512_padded(p[0], st[0]);
                finish(i, st[0]);
                at = at1;
                i += 1;
            }
        }
    }
#else
    (void)wide;
#endif
    for (; i < n; at += len[i], i++) {
        uint64_t st[8];
        sha512_padded(input(i, at), st);
        finish(i, st);
    }
    return 0;
}

}  // namespace

extern "C" {

// pubs: n*32, sigs: n*64, msgs concatenated with (n+1) offsets.
// Outputs (all caller-allocated):
//   s_out n*32 (zeroed when s >= L), m_out n*32 ((L - h) mod L, LE),
//   s_ok_out n bytes.
int ed25519_pack(const uint8_t* pubs, const uint8_t* sigs,
                 const uint8_t* msgs, const int64_t* msg_off, int64_t n,
                 uint8_t* s_out, uint8_t* m_out, uint8_t* s_ok_out) {
    if (n <= 0) return n < 0 ? -1 : 0;
    std::vector<int64_t> len((size_t)n);
    for (int64_t i = 0; i < n; i++) len[i] = msg_off[i + 1] - msg_off[i];
    PackOut o = {nullptr, nullptr, s_out, m_out, s_ok_out};
    return pack_rows(pubs, sigs, msgs + msg_off[0], len.data(), n, nullptr, n,
                     o, true);
}

// The same pack written where the launch reads it: signature i, whose
// message is the next msg_len[i] bytes of msgs, goes to row idx[i] (row i
// where ``idx`` is null) of the caller's zeroed ``rows`` x 32 tables a
// (the key), r, s (zero where s >= L), m and of ``s_ok`` (``rows``
// bytes); rows no signature names stay as they were.  Returns -1, with
// nothing written, where a row lies outside the tables.
int ed25519_pack_into(const uint8_t* pubs, const uint8_t* sigs,
                      const uint8_t* msgs, const int64_t* msg_len, int64_t n,
                      const int64_t* idx, int64_t rows, uint8_t* a_rows,
                      uint8_t* r_rows, uint8_t* s_rows, uint8_t* m_rows,
                      uint8_t* s_ok_rows) {
    PackOut o = {a_rows, r_rows, s_rows, m_rows, s_ok_rows};
    return pack_rows(pubs, sigs, msgs, msg_len, n, idx, rows, o, true);
}

// for tests: ``ed25519_pack_into`` with the block function named: lanes 1
// the scalar one, 4 the four-lane one; -2 where this CPU has no such one
int ed25519_pack_into_lanes(const uint8_t* pubs, const uint8_t* sigs,
                            const uint8_t* msgs, const int64_t* msg_len,
                            int64_t n, const int64_t* idx, int64_t rows,
                            uint8_t* a_rows, uint8_t* r_rows, uint8_t* s_rows,
                            uint8_t* m_rows, uint8_t* s_ok_rows, int lanes) {
    if (lanes != 1 && !(lanes == 4 && cpu_has_wide())) return -2;
    PackOut o = {a_rows, r_rows, s_rows, m_rows, s_ok_rows};
    return pack_rows(pubs, sigs, msgs, msg_len, n, idx, rows, o, lanes == 4);
}

// for tests: x (64 bytes, little-endian) mod L, 32 bytes little-endian
void ed25519_mod_l(const uint8_t* x64, uint8_t* out32) {
    uint64_t x[8], r[4];
    for (int i = 0; i < 8; i++) x[i] = load_le64(x64 + 8 * i);
    mod_l_512(x, r);
    for (int i = 0; i < 4; i++) store_le64(out32 + 8 * i, r[i]);
}

// standalone SHA-512 for tests
void sha512(const uint8_t* data, int64_t len, uint8_t* out64) {
    uint64_t st[8];
    sha512_padded(padded_of(nullptr, nullptr, data, (size_t)len), st);
    for (int i = 0; i < 8; i++)
        store_le64(out64 + 8 * i, __builtin_bswap64(st[i]));
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Canonical precommit sign bytes (reference: types/canonical.go:57 +
// types/vote.go:151; byte-exact mirror of types/canonical.py +
// libs/protoenc.py — differential-tested in tests/test_native.py)
// ---------------------------------------------------------------------------

namespace {

struct Buf {
    uint8_t* p;
    int64_t cap;
    int64_t len;
    bool overflow;
    void put(uint8_t b) {
        if (len >= cap) { overflow = true; return; }
        p[len++] = b;
    }
    void put_bytes(const uint8_t* d, int64_t n) {
        if (len + n > cap) { overflow = true; return; }
        memcpy(p + len, d, n);
        len += n;
    }
};

static void put_uvarint(Buf& b, uint64_t n) {
    while (true) {
        uint8_t byte = n & 0x7F;
        n >>= 7;
        if (n) b.put(byte | 0x80);
        else { b.put(byte); return; }
    }
}

static void put_tag(Buf& b, int field, int wire) {
    put_uvarint(b, (uint64_t)((field << 3) | wire));
}

// t_varint semantics: omitted when zero; negatives as 64-bit two's
// complement (proto3 int64)
static void put_t_varint(Buf& b, int field, int64_t v) {
    if (v == 0) return;
    put_tag(b, field, 0);
    put_uvarint(b, (uint64_t)v);
}

static void put_t_sfixed64(Buf& b, int field, int64_t v) {
    if (v == 0) return;
    put_tag(b, field, 1);
    uint64_t u = (uint64_t)v;
    for (int i = 0; i < 8; i++) b.put((uint8_t)(u >> (8 * i)));
}

static void put_t_bytes(Buf& b, int field, const uint8_t* d, int64_t n) {
    if (n <= 0) return;
    put_tag(b, field, 2);
    put_uvarint(b, (uint64_t)n);
    b.put_bytes(d, n);
}

}  // namespace

extern "C" {

// Sign bytes for every signature of one commit: the protoio
// length-delimited CanonicalVote per validator.  All votes share
// (chain_id, height, round, block_id); only the timestamp and the
// block-id flag (2 = COMMIT -> block_id present; else nil -> omitted)
// vary per lane.  ``out_off`` receives n+1 offsets into ``out``.
// Returns total bytes written, or -1 when ``cap`` is too small.
int64_t commit_sign_bytes(
    const uint8_t* chain_id, int64_t chain_id_len,
    int64_t height, int64_t round_,
    const uint8_t* bid_hash, int64_t bid_hash_len,
    int64_t psh_total, const uint8_t* psh_hash, int64_t psh_hash_len,
    const uint8_t* flags, const int64_t* ts_s, const int64_t* ts_ns,
    int64_t n, uint8_t* out, int64_t cap, int64_t* out_off) {
    // canonical block id submessage (shared by every COMMIT-flag vote):
    //   1: bytes hash, 2: message{1: varint total, 2: bytes hash}
    uint8_t bid_buf[128];
    Buf bid{bid_buf, (int64_t)sizeof(bid_buf), 0, false};
    put_t_bytes(bid, 1, bid_hash, bid_hash_len);
    {
        uint8_t psh_buf[64];
        Buf psh{psh_buf, (int64_t)sizeof(psh_buf), 0, false};
        put_t_varint(psh, 1, psh_total);
        put_t_bytes(psh, 2, psh_hash, psh_hash_len);
        if (psh.overflow) return -1;
        if (psh.len > 0) {  // t_message: omitted when empty
            put_tag(bid, 2, 2);
            put_uvarint(bid, (uint64_t)psh.len);
            bid.put_bytes(psh_buf, psh.len);
        }
    }
    if (bid.overflow) return -1;

    Buf o{out, cap, 0, false};
    for (int64_t i = 0; i < n; i++) {
        out_off[i] = o.len;
        // body assembled in a scratch buffer (max ~200B)
        uint8_t body_buf[256];
        Buf body{body_buf, (int64_t)sizeof(body_buf), 0, false};
        put_t_varint(body, 1, 2);  // type = PRECOMMIT
        put_t_sfixed64(body, 2, height);
        put_t_sfixed64(body, 3, round_);
        if (flags[i] == 2 && bid.len > 0) {  // BLOCK_ID_FLAG_COMMIT
            put_tag(body, 4, 2);
            put_uvarint(body, (uint64_t)bid.len);
            body.put_bytes(bid_buf, bid.len);
        }
        {
            uint8_t ts_buf[24];
            Buf ts{ts_buf, (int64_t)sizeof(ts_buf), 0, false};
            put_t_varint(ts, 1, ts_s[i]);
            put_t_varint(ts, 2, ts_ns[i]);
            if (ts.len > 0) {  // t_message: zero timestamp -> omitted
                put_tag(body, 5, 2);
                put_uvarint(body, (uint64_t)ts.len);
                body.put_bytes(ts_buf, ts.len);
            }
        }
        put_t_bytes(body, 6, chain_id, chain_id_len);
        if (body.overflow) return -1;
        // protoio delimited framing
        put_uvarint(o, (uint64_t)body.len);
        o.put_bytes(body_buf, body.len);
        if (o.overflow) return -1;
    }
    out_off[n] = o.len;
    return o.len;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// SHA-256 (FIPS 180-4) and the validator set's Merkle root (reference:
// types/validator_set.go Hash -> crypto/merkle/tree.go; byte-exact mirror
// of Validator.simple_encode + crypto/merkle.hash_from_byte_slices,
// differential-tested in tests/test_valset_root_native.py)
// ---------------------------------------------------------------------------

static const uint32_t K256[64] = {
    0x428a2f98u, 0x71374491u, 0xb5c0fbcfu, 0xe9b5dba5u, 0x3956c25bu,
    0x59f111f1u, 0x923f82a4u, 0xab1c5ed5u, 0xd807aa98u, 0x12835b01u,
    0x243185beu, 0x550c7dc3u, 0x72be5d74u, 0x80deb1feu, 0x9bdc06a7u,
    0xc19bf174u, 0xe49b69c1u, 0xefbe4786u, 0x0fc19dc6u, 0x240ca1ccu,
    0x2de92c6fu, 0x4a7484aau, 0x5cb0a9dcu, 0x76f988dau, 0x983e5152u,
    0xa831c66du, 0xb00327c8u, 0xbf597fc7u, 0xc6e00bf3u, 0xd5a79147u,
    0x06ca6351u, 0x14292967u, 0x27b70a85u, 0x2e1b2138u, 0x4d2c6dfcu,
    0x53380d13u, 0x650a7354u, 0x766a0abbu, 0x81c2c92eu, 0x92722c85u,
    0xa2bfe8a1u, 0xa81a664bu, 0xc24b8b70u, 0xc76c51a3u, 0xd192e819u,
    0xd6990624u, 0xf40e3585u, 0x106aa070u, 0x19a4c116u, 0x1e376c08u,
    0x2748774cu, 0x34b0bcb5u, 0x391c0cb3u, 0x4ed8aa4au, 0x5b9cca4fu,
    0x682e6ff3u, 0x748f82eeu, 0x78a5636fu, 0x84c87814u, 0x8cc70208u,
    0x90befffau, 0xa4506cebu, 0xbef9a3f7u, 0xc67178f2u};

static const uint32_t SHA256_IV[8] = {0x6a09e667u, 0xbb67ae85u, 0x3c6ef372u,
                                      0xa54ff53au, 0x510e527fu, 0x9b05688cu,
                                      0x1f83d9abu, 0x5be0cd19u};

static inline uint32_t load_be32(const uint8_t* p) {
    return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
           ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

static inline void store_be32(uint8_t* p, uint32_t v) {
    p[0] = (uint8_t)(v >> 24);
    p[1] = (uint8_t)(v >> 16);
    p[2] = (uint8_t)(v >> 8);
    p[3] = (uint8_t)v;
}

static inline uint32_t rotr32(uint32_t x, int n) {
    return (x >> n) | (x << (32 - n));
}

// the compression function over ``nblocks`` consecutive 64-byte blocks
typedef void (*Sha256Block)(uint32_t st[8], const uint8_t* p, size_t nblocks);

static void sha256_block_scalar(uint32_t st[8], const uint8_t* p,
                                size_t nblocks) {
    for (; nblocks; nblocks--, p += 64) {
        uint32_t w[64];
        for (int i = 0; i < 16; i++) w[i] = load_be32(p + 4 * i);
        for (int i = 16; i < 64; i++) {
            uint32_t s0 = rotr32(w[i - 15], 7) ^ rotr32(w[i - 15], 18) ^
                          (w[i - 15] >> 3);
            uint32_t s1 = rotr32(w[i - 2], 17) ^ rotr32(w[i - 2], 19) ^
                          (w[i - 2] >> 10);
            w[i] = w[i - 16] + s0 + w[i - 7] + s1;
        }
        uint32_t a = st[0], b = st[1], c = st[2], d = st[3];
        uint32_t e = st[4], f = st[5], g = st[6], h = st[7];
        for (int i = 0; i < 64; i++) {
            uint32_t t1 = h + (rotr32(e, 6) ^ rotr32(e, 11) ^ rotr32(e, 25)) +
                          ((e & f) ^ (~e & g)) + K256[i] + w[i];
            uint32_t t2 = (rotr32(a, 2) ^ rotr32(a, 13) ^ rotr32(a, 22)) +
                          ((a & b) ^ (a & c) ^ (b & c));
            h = g;
            g = f;
            f = e;
            e = d + t1;
            d = c;
            c = b;
            b = a;
            a = t1 + t2;
        }
        st[0] += a; st[1] += b; st[2] += c; st[3] += d;
        st[4] += e; st[5] += f; st[6] += g; st[7] += h;
    }
}

// The same blocks with the SHA extensions: compiled for them whatever the
// build's flags are and called only where the CPU has them
// (``cpu_has_sha``).  The state lives as ABEF / CDGH, the instructions'
// order; each group of four rounds adds four message words to their
// constants and makes two ``sha256rnds2``.
#if defined(__x86_64__) && defined(__GNUC__)
#define SHA256_HAVE_NI 1

#define NI_TARGET __attribute__((target("sha,sse4.1,ssse3")))

NI_TARGET static void sha256_block_ni(uint32_t st[8], const uint8_t* p,
                                      size_t nblocks) {
    const __m128i bswap =
        _mm_set_epi64x(0x0c0d0e0f08090a0bLL, 0x0405060700010203LL);
    __m128i t = _mm_loadu_si128((const __m128i*)&st[0]);     // DCBA
    __m128i s1 = _mm_loadu_si128((const __m128i*)&st[4]);    // HGFE
    t = _mm_shuffle_epi32(t, 0xB1);                          // CDAB
    s1 = _mm_shuffle_epi32(s1, 0x1B);                        // EFGH
    __m128i s0 = _mm_alignr_epi8(t, s1, 8);                  // ABEF
    s1 = _mm_blend_epi16(s1, t, 0xF0);                       // CDGH
    for (size_t k = 0; k < nblocks; k++, p += 64) {
        const __m128i abef = s0, cdgh = s1;
        __m128i w[4];  // the last four groups of message words, a ring
        for (int q = 0; q < 4; q++)
            w[q] = _mm_shuffle_epi8(
                _mm_loadu_si128((const __m128i*)(p + 16 * q)), bswap);
#pragma GCC unroll 16
        for (int g = 0; g < 16; g++) {
            if (g >= 4)  // group g from groups g-4 .. g-1
                w[g & 3] = _mm_sha256msg2_epu32(
                    _mm_add_epi32(
                        _mm_sha256msg1_epu32(w[g & 3], w[(g + 1) & 3]),
                        _mm_alignr_epi8(w[(g + 3) & 3], w[(g + 2) & 3], 4)),
                    w[(g + 3) & 3]);
            __m128i kw = _mm_add_epi32(
                w[g & 3], _mm_loadu_si128((const __m128i*)(K256 + 4 * g)));
            s1 = _mm_sha256rnds2_epu32(s1, s0, kw);
            s0 = _mm_sha256rnds2_epu32(s0, s1, _mm_shuffle_epi32(kw, 0x0E));
        }
        s0 = _mm_add_epi32(s0, abef);
        s1 = _mm_add_epi32(s1, cdgh);
    }
    t = _mm_shuffle_epi32(s0, 0x1B);                         // FEBA
    s1 = _mm_shuffle_epi32(s1, 0xB1);                        // DCHG
    _mm_storeu_si128((__m128i*)&st[0], _mm_blend_epi16(t, s1, 0xF0));  // DCBA
    _mm_storeu_si128((__m128i*)&st[4], _mm_alignr_epi8(s1, t, 8));     // HGFE
}

static bool cpu_has_sha() {
    static const bool has = __builtin_cpu_supports("sha");
    return has;
}
#else
static bool cpu_has_sha() { return false; }
#endif

// The block function ``ni`` names: 1 the SHA-NI one, 0 the scalar one, -1
// the best this CPU has; null where it names one the CPU lacks.
static Sha256Block sha256_block_fn(int ni) {
#ifdef SHA256_HAVE_NI
    if (ni != 0 && cpu_has_sha()) return sha256_block_ni;
#endif
    return ni == 1 ? nullptr : sha256_block_scalar;
}

static inline void sha256_out(const uint32_t st[8], uint8_t out[32]) {
    for (int i = 0; i < 8; i++) store_be32(out + 4 * i, st[i]);
}

// SHA-256 of one message: its whole blocks where they lie, the tail and
// the padding (one or two blocks) on the stack
static void sha256_msg(Sha256Block blk, const uint8_t* data, size_t len,
                       uint8_t out[32]) {
    uint32_t st[8];
    memcpy(st, SHA256_IV, sizeof(st));
    size_t whole = len / 64, rem = len % 64;
    if (whole) blk(st, data, whole);
    uint8_t tail[128];
    memset(tail, 0, sizeof(tail));
    if (rem) memcpy(tail, data + 64 * whole, rem);
    tail[rem] = 0x80;
    size_t tlen = rem + 1 + 8 <= 64 ? 64 : 128;
    uint64_t bits = (uint64_t)len * 8;
    for (int i = 0; i < 8; i++) tail[tlen - 1 - i] = (uint8_t)(bits >> (8 * i));
    blk(st, tail, tlen / 64);
    sha256_out(st, out);
}

// The leaf hash of one SimpleValidator with an ed25519 key:
// SHA-256(0x00 || 0a 22 0a 20 || key || [10 || uvarint(power)]), the power
// left out where it is 0 and a negative one its 64-bit two's complement
// (``pe.t_varint``).  At most 48 bytes: one block, padded in place.
static void valset_leaf(Sha256Block blk, const uint8_t key[32], int64_t power,
                        uint8_t out[32]) {
    static const uint8_t head[5] = {0x00, 0x0a, 0x22, 0x0a, 0x20};
    uint8_t b[64];
    memset(b, 0, sizeof(b));
    memcpy(b, head, 5);
    memcpy(b + 5, key, 32);
    size_t len = 37;
    if (power != 0) {
        b[len++] = 0x10;  // field 2, varint
        uint64_t v = (uint64_t)power;
        for (; v >= 0x80; v >>= 7) b[len++] = (uint8_t)(v | 0x80);
        b[len++] = (uint8_t)v;
    }
    b[len] = 0x80;
    b[62] = (uint8_t)((len * 8) >> 8);
    b[63] = (uint8_t)(len * 8);
    uint32_t st[8];
    memcpy(st, SHA256_IV, sizeof(st));
    blk(st, b, 1);
    sha256_out(st, out);
}

// An inner node: SHA-256(0x01 || left || right), 65 bytes, two blocks
// padded in place.  Reads both children before it writes ``out``, which
// may be ``left``.
static void merkle_inner(Sha256Block blk, const uint8_t* left,
                         const uint8_t* right, uint8_t out[32]) {
    uint8_t b[128];
    memset(b, 0, sizeof(b));
    b[0] = 0x01;
    memcpy(b + 1, left, 32);
    memcpy(b + 33, right, 32);
    b[65] = 0x80;
    b[126] = (uint8_t)((65 * 8) >> 8);
    b[127] = (uint8_t)(65 * 8);
    uint32_t st[8];
    memcpy(st, SHA256_IV, sizeof(st));
    blk(st, b, 2);
    sha256_out(st, out);
}

// The root over n leaf digests, level by level in place: neighbours paired,
// an odd last one carried up.  The same tree as the reference's split at
// the largest power of two below n: that split leaves the left part a
// whole power of two, whose pairs never straddle it.
static void merkle_reduce(Sha256Block blk, uint8_t* d, size_t n) {
    while (n > 1) {
        size_t half = n / 2;
        for (size_t i = 0; i < half; i++)
            merkle_inner(blk, d + 64 * i, d + 64 * i + 32, d + 32 * i);
        if (n & 1) memmove(d + 32 * half, d + 32 * (n - 1), 32);
        n = half + (n & 1);
    }
}

static int valset_root(const uint8_t* keys32, const int64_t* powers, int64_t n,
                       uint8_t* out32, Sha256Block blk) {
    if (n < 0) return -1;
    if (n == 0) {
        sha256_msg(blk, nullptr, 0, out32);
        return 0;
    }
    uint8_t* d = static_cast<uint8_t*>(malloc((size_t)n * 32));
    if (!d) return -1;
    for (int64_t i = 0; i < n; i++)
        valset_leaf(blk, keys32 + 32 * i, powers[i], d + 32 * i);
    merkle_reduce(blk, d, (size_t)n);
    memcpy(out32, d, 32);
    free(d);
    return 0;
}

extern "C" {

// The Merkle root of a validator set whose keys are all ed25519: keys32
// n x 32 bytes and powers n int64 in set order, the root to out32.  The
// leaves encoded and the tree reduced in one pass, SHA-NI where the CPU has
// it; one allocation, of n digests.  -1 for n < 0 or no memory.
int valset_root_ed25519(const uint8_t* keys32, const int64_t* powers,
                        int64_t n, uint8_t* out32) {
    return valset_root(keys32, powers, n, out32, sha256_block_fn(-1));
}

// for tests: ``valset_root_ed25519`` with the block function named: ni 0
// the scalar one, 1 the SHA-NI one; -2 where this CPU has no such one
int valset_root_ed25519_ni(const uint8_t* keys32, const int64_t* powers,
                           int64_t n, uint8_t* out32, int ni) {
    Sha256Block blk = (ni == 0 || ni == 1) ? sha256_block_fn(ni) : nullptr;
    if (!blk) return -2;
    return valset_root(keys32, powers, n, out32, blk);
}

// for tests: SHA-256 of one message with the block function named as above
int sha256_ni(const uint8_t* data, int64_t len, uint8_t* out32, int ni) {
    Sha256Block blk = (ni == 0 || ni == 1) ? sha256_block_fn(ni) : nullptr;
    if (!blk) return -2;
    if (len < 0) return -1;
    sha256_msg(blk, data, (size_t)len, out32);
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The signature cache (cometbft_tpu/crypto/sigcache.py): a segment's keys in
// one pass, and the bounded LRU of verdicts they index.  Differential-tested
// against ``sigcache._key`` and the OrderedDict store in
// tests/test_sigcache_native.py.
// ---------------------------------------------------------------------------

static inline void store_le32(uint8_t* p, uint32_t v) {
    p[0] = (uint8_t)v;
    p[1] = (uint8_t)(v >> 8);
    p[2] = (uint8_t)(v >> 16);
    p[3] = (uint8_t)(v >> 24);
}

static int sigcache_keys_with(Sha256Block blk, const uint8_t* pubs,
                              const int64_t* pub_lens, int64_t pub_len,
                              const uint8_t* msgs, const int64_t* msg_lens,
                              const uint8_t* sigs, const int64_t* sig_lens,
                              int64_t sig_len, int64_t n, uint8_t* out32) {
    if (n < 0) return -1;
    size_t most = 0;  // the longest framed message, for the one scratch buffer
    for (int64_t i = 0; i < n; i++) {
        int64_t p = pub_lens ? pub_lens[i] : pub_len;
        int64_t s = sig_lens ? sig_lens[i] : sig_len;
        int64_t m = msg_lens[i];
        if (p < 0 || m < 0 || s < 0 || p > UINT32_MAX || m > UINT32_MAX)
            return -1;
        size_t len = 8 + (size_t)p + (size_t)m + (size_t)s;
        if (len > most) most = len;
    }
    // each framed message assembled in ``buf`` and padded there: its blocks
    // in one call of the block function
    std::vector<uint8_t> buf(most + 72);
    uint8_t* b = buf.data();
    for (int64_t i = 0; i < n; i++) {
        size_t p = (size_t)(pub_lens ? pub_lens[i] : pub_len);
        size_t m = (size_t)msg_lens[i];
        size_t s = (size_t)(sig_lens ? sig_lens[i] : sig_len);
        size_t len = 8 + p + m + s;
        store_le32(b, (uint32_t)p);
        memcpy(b + 4, pubs, p);
        store_le32(b + 4 + p, (uint32_t)m);
        memcpy(b + 8 + p, msgs, m);
        memcpy(b + 8 + p + m, sigs, s);
        size_t blocks = (len + 9 + 63) / 64;
        memset(b + len, 0, 64 * blocks - len);
        b[len] = 0x80;
        uint64_t bits = (uint64_t)len * 8;
        for (int k = 0; k < 8; k++) b[64 * blocks - 1 - k] = (uint8_t)(bits >> (8 * k));
        uint32_t st[8];
        memcpy(st, SHA256_IV, sizeof(st));
        blk(st, b, blocks);
        sha256_out(st, out32 + 32 * i);
        pubs += p;
        msgs += m;
        sigs += s;
    }
    return 0;
}

// The bounded LRU: ``cap`` entries (a 32-byte digest, its verdict, its place
// in the list, oldest first, and its slot in the index) and an open-addressed
// index of twice as many slots or more, linear probing, backward-shift
// deletion.  The probe starts from the digest mixed with a seed of the
// store's own, so that keys ground to collide in the index cannot be made
// without it.  One mutex: a store may be called from several threads at once.
struct SigEntry {
    uint8_t key[32];
    int32_t prev, next, slot;
    uint8_t ok;
};

struct SigSlot {
    int32_t e;   // the entry, -1 empty
    uint32_t h;  // its hash, whose low bits are its home slot
};

struct SigStore {
    std::mutex mtx;
    int64_t cap = 0;
    uint32_t mask = 0;
    uint64_t seed = 0;
    std::vector<SigSlot> index;
    std::vector<SigEntry> ent;
    int32_t head = -1, tail = -1;  // the oldest and the newest
    int64_t size = 0;
    int64_t hits = 0, misses = 0, puts = 0;
};

static inline uint32_t store_hash(const SigStore* s, const uint8_t* k) {
    uint64_t x;
    memcpy(&x, k, 8);
    x ^= s->seed;  // splitmix64's finaliser
    x ^= x >> 30;
    x *= 0xbf58476d1ce4e5b9ULL;
    x ^= x >> 27;
    x *= 0x94d049bb133111ebULL;
    x ^= x >> 31;
    return (uint32_t)x;
}

// the slot that holds ``k``, or -1
static int64_t store_find(const SigStore* s, const uint8_t* k, uint32_t h) {
    for (uint32_t i = h & s->mask;; i = (i + 1) & s->mask) {
        const SigSlot& sl = s->index[i];
        if (sl.e < 0) return -1;
        if (sl.h == h && memcmp(s->ent[sl.e].key, k, 32) == 0) return i;
    }
}

static void list_unlink(SigStore* s, int32_t e) {
    SigEntry& x = s->ent[e];
    if (x.prev >= 0) s->ent[x.prev].next = x.next; else s->head = x.next;
    if (x.next >= 0) s->ent[x.next].prev = x.prev; else s->tail = x.prev;
}

static void list_append(SigStore* s, int32_t e) {
    SigEntry& x = s->ent[e];
    x.prev = s->tail;
    x.next = -1;
    if (s->tail >= 0) s->ent[s->tail].next = e; else s->head = e;
    s->tail = e;
}

static void list_touch(SigStore* s, int32_t e) {
    if (s->tail == e) return;
    list_unlink(s, e);
    list_append(s, e);
}

// empties slot ``i``, moving each later slot of its run back where its home
// allows, so that no probe meets a hole before its key
static void index_remove(SigStore* s, uint32_t i) {
    const uint32_t mask = s->mask;
    for (uint32_t j = (i + 1) & mask;; j = (j + 1) & mask) {
        SigSlot sj = s->index[j];
        if (sj.e < 0) break;
        uint32_t home = sj.h & mask;
        if (((j - home) & mask) >= ((j - i) & mask)) {  // home not in (i, j]
            s->index[i] = sj;
            s->ent[sj.e].slot = (int32_t)i;
            i = j;
        }
    }
    s->index[i].e = -1;
}

static void store_put(SigStore* s, const uint8_t* k, uint8_t ok) {
    uint32_t h = store_hash(s, k);
    int64_t at = store_find(s, k, h);
    if (at >= 0) {
        int32_t e = s->index[at].e;
        s->ent[e].ok = ok;
        list_touch(s, e);
        return;
    }
    int32_t e;
    if (s->size < s->cap) {
        e = (int32_t)s->size++;
    } else {  // evict the oldest and take its entry
        e = s->head;
        list_unlink(s, e);
        index_remove(s, (uint32_t)s->ent[e].slot);
    }
    uint32_t i = h & s->mask;
    while (s->index[i].e >= 0) i = (i + 1) & s->mask;
    s->index[i] = SigSlot{e, h};
    SigEntry& x = s->ent[e];
    memcpy(x.key, k, 32);
    x.ok = ok;
    x.slot = (int32_t)i;
    list_append(s, e);
}

static void store_reset(SigStore* s) {
    for (SigSlot& sl : s->index) sl.e = -1;
    s->head = s->tail = -1;
    s->size = s->hits = s->misses = s->puts = 0;
}

extern "C" {

// The signature cache's keys of n triples, each SHA-256(u32le(len pub) ||
// pub || u32le(len msg) || msg || sig), n x 32 bytes to out32.  Each field
// list joined; its lengths int64 [n], or, for pubs and sigs, null and every
// one ``pub_len`` / ``sig_len`` bytes.  ni as ``sha256_ni``: -1 the best
// block function this CPU has, 0 the scalar one, 1 SHA-NI (-2 where the CPU
// lacks it).  -1 for n < 0 or a length out of range.
int sigcache_keys(const uint8_t* pubs, const int64_t* pub_lens,
                  int64_t pub_len, const uint8_t* msgs,
                  const int64_t* msg_lens, const uint8_t* sigs,
                  const int64_t* sig_lens, int64_t sig_len, int64_t n,
                  uint8_t* out32, int ni) {
    Sha256Block blk = (ni >= -1 && ni <= 1) ? sha256_block_fn(ni) : nullptr;
    if (!blk) return -2;
    return sigcache_keys_with(blk, pubs, pub_lens, pub_len, msgs, msg_lens,
                              sigs, sig_lens, sig_len, n, out32);
}

// A store of ``cap`` entries, 1 <= cap < 2^29; null where cap is out of that
// range or the memory is not there.
void* sigcache_new(int64_t cap) {
    if (cap < 1 || cap >= ((int64_t)1 << 29)) return nullptr;
    SigStore* s = new (std::nothrow) SigStore();
    if (!s) return nullptr;
    size_t slots = 2;
    while (slots < 2 * (size_t)cap) slots *= 2;
    try {
        s->index.assign(slots, SigSlot{-1, 0});
        s->ent.resize((size_t)cap);
    } catch (...) {
        delete s;
        return nullptr;
    }
    s->cap = cap;
    s->mask = (uint32_t)(slots - 1);
    uint64_t seed = (uint64_t)(uintptr_t)s;
    try {
        std::random_device rd;
        seed ^= ((uint64_t)rd() << 32) ^ rd();
    } catch (...) {
    }
    seed ^= (uint64_t)std::chrono::steady_clock::now().time_since_epoch().count();
    s->seed = seed;
    return s;
}

void sigcache_free(void* h) { delete static_cast<SigStore*>(h); }

// The verdicts of n keys (n x 32 bytes) to out: 0 false, 1 true, 2 absent;
// each key found becomes the newest, in order.  Counts the hits and misses.
int sigcache_get_many(void* h, const uint8_t* keys, int64_t n, uint8_t* out) {
    SigStore* s = static_cast<SigStore*>(h);
    if (!s || n < 0) return -1;
    std::lock_guard<std::mutex> g(s->mtx);
    int64_t hits = 0;
    for (int64_t i = 0; i < n; i++) {
        const uint8_t* k = keys + 32 * i;
        int64_t at = store_find(s, k, store_hash(s, k));
        if (at < 0) {
            out[i] = 2;
            continue;
        }
        int32_t e = s->index[at].e;
        out[i] = s->ent[e].ok;
        list_touch(s, e);
        hits++;
    }
    s->hits += hits;
    s->misses += n - hits;
    return 0;
}

// Stores n verdicts (oks: n bytes, nonzero true) under their keys, in order,
// each the newest, the oldest evicted at capacity: the survivors and their
// order are a loop of single puts'.  Counts n puts.
int sigcache_put_many(void* h, const uint8_t* keys, const uint8_t* oks,
                      int64_t n) {
    SigStore* s = static_cast<SigStore*>(h);
    if (!s || n < 0) return -1;
    std::lock_guard<std::mutex> g(s->mtx);
    for (int64_t i = 0; i < n; i++) store_put(s, keys + 32 * i, oks[i] != 0);
    s->puts += n;
    return 0;
}

int64_t sigcache_len(void* h) {
    SigStore* s = static_cast<SigStore*>(h);
    std::lock_guard<std::mutex> g(s->mtx);
    return s->size;
}

// Drops every entry and zeroes the counts.
void sigcache_clear(void* h) {
    SigStore* s = static_cast<SigStore*>(h);
    std::lock_guard<std::mutex> g(s->mtx);
    store_reset(s);
}

// hits, misses, puts and size to out[4], read together
void sigcache_counts(void* h, int64_t* out) {
    SigStore* s = static_cast<SigStore*>(h);
    std::lock_guard<std::mutex> g(s->mtx);
    out[0] = s->hits;
    out[1] = s->misses;
    out[2] = s->puts;
    out[3] = s->size;
}

// The entries oldest first, at most ``most``: keys to keys_out (x 32 bytes),
// verdicts to oks_out; returns how many were written.
int64_t sigcache_items(void* h, uint8_t* keys_out, uint8_t* oks_out,
                       int64_t most) {
    SigStore* s = static_cast<SigStore*>(h);
    std::lock_guard<std::mutex> g(s->mtx);
    int64_t k = 0;
    for (int32_t e = s->head; e >= 0 && k < most; e = s->ent[e].next, k++) {
        memcpy(keys_out + 32 * k, s->ent[e].key, 32);
        oks_out[k] = s->ent[e].ok;
    }
    return k;
}

}  // extern "C"
