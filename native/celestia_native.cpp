// Native host library: GF(256) Reed-Solomon square extension + SHA-256 /
// NMT hashing on the CPU.
//
// Role: the TPU framework's equivalent of the reference's performance-native
// dependencies (Leopard-RS SIMD codec via klauspost/reedsolomon and
// crypto/sha256 — SURVEY.md §2.2).  Used as (a) the honest CPU comparison
// leg for bench.py, and (b) a host-side fallback behind the same Python
// interfaces as the device kernels.  Exposed via a C ABI for ctypes.
//
// GF(256): primitive polynomial 0x11D, multiply via a 64 KiB full product
// table (the classic table method; with -O3 and auto-vectorization this is
// the strongest portable single-thread baseline short of hand-written
// pshufb kernels).  Encode matrices arrive from Python (the same Lagrange
// matrices the device uses), so native and device outputs are bit-identical.

#include <cstdint>
#include <cstring>
#include <pthread.h>
#if defined(__AVX2__)
#include <immintrin.h>
#endif

#include <algorithm>
#include <array>
#include <atomic>
#include <condition_variable>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// GF(256)
// ---------------------------------------------------------------------------

static uint8_t MUL[256][256];
static int gf_ready = 0;

static uint8_t gf_mul_slow(uint8_t a, uint8_t b) {
    uint16_t p = 0;
    uint16_t aa = a;
    for (int i = 0; i < 8; i++) {
        if (b & 1) p ^= aa;
        b >>= 1;
        aa <<= 1;
        if (aa & 0x100) aa ^= 0x11D;
    }
    return (uint8_t)p;
}

void gf_init(void) {
    if (gf_ready) return;
    for (int a = 0; a < 256; a++)
        for (int b = 0; b < 256; b++)
            MUL[a][b] = gf_mul_slow((uint8_t)a, (uint8_t)b);
    gf_ready = 1;
}

// Override the multiplication table with a caller-supplied 256x256 one —
// the Python side loads the active codec's field representation (the
// leopard codec works in the Cantor-index domain, gf256.mul_table) so
// every table-method leg here computes in the same field as the device.
//
// INVARIANT (ADVICE r5): MUL is process-global and this write is not
// synchronized against readers.  The Python binding
// (celestia_tpu/utils/native.py) therefore holds one lock across BOTH
// the gf_load_mul call and every table-method entry point
// (rs_extend_square / extend_block_cpu / gf_matmul_axes), so a codec
// switch can never interleave with an in-flight table-method call and
// compute in a mixed field.  Callers bypassing the Python binding must
// uphold the same discipline: never call gf_load_mul while a
// table-method function is running on another thread.
void gf_load_mul(const uint8_t* table) {
    memcpy(MUL, table, 256 * 256);
    gf_ready = 1;  // later gf_init() calls must not clobber the load
}

// parity[i][b] ^= MUL[E[i][j]][data[j][b]] for a row of k shares of B bytes.
// E: k*k row-major; data: k*B; parity out: k*B.
static void rs_encode_axis(const uint8_t* E, const uint8_t* data,
                           uint8_t* parity, int k, int B) {
    memset(parity, 0, (size_t)k * B);
    for (int i = 0; i < k; i++) {
        uint8_t* out = parity + (size_t)i * B;
        for (int j = 0; j < k; j++) {
            const uint8_t c = E[i * k + j];
            if (c == 0) continue;
            const uint8_t* row = MUL[c];
            const uint8_t* in = data + (size_t)j * B;
            for (int b = 0; b < B; b++) out[b] ^= row[in[b]];
        }
    }
}

// Extend a k x k x B square into a 2k x 2k x B EDS (quadrant layout as the
// device kernel: Q1 row parity, Q2 column parity, Q3 parity of parity).
// square: k*k*B row-major; eds out: 2k*2k*B; E: k*k encode matrix.
void rs_extend_square(const uint8_t* square, const uint8_t* E, uint8_t* eds,
                      int k, int B) {
    gf_init();
    const int n = 2 * k;
    const size_t row_bytes = (size_t)n * B;
    // Q0
    for (int r = 0; r < k; r++)
        memcpy(eds + r * row_bytes, square + (size_t)r * k * B, (size_t)k * B);
    // Q1: row parity
    for (int r = 0; r < k; r++)
        rs_encode_axis(E, eds + r * row_bytes, eds + r * row_bytes + (size_t)k * B,
                       k, B);
    // Q2/Q3: column parity over the top half. Gather each column, encode,
    // scatter. (Columns are strided; gather keeps the inner loop dense.)
    uint8_t* col = new uint8_t[(size_t)k * B];
    uint8_t* par = new uint8_t[(size_t)k * B];
    for (int c = 0; c < n; c++) {
        for (int r = 0; r < k; r++)
            memcpy(col + (size_t)r * B, eds + r * row_bytes + (size_t)c * B, B);
        rs_encode_axis(E, col, par, k, B);
        for (int r = 0; r < k; r++)
            memcpy(eds + (size_t)(k + r) * row_bytes + (size_t)c * B,
                   par + (size_t)r * B, B);
    }
    delete[] col;
    delete[] par;
}

// ---------------------------------------------------------------------------
// SHA-256 (FIPS 180-4), portable
// ---------------------------------------------------------------------------

static const uint32_t K256[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

static inline uint32_t rotr(uint32_t x, int n) {
    return (x >> n) | (x << (32 - n));
}

#if defined(__SHA__) && defined(__AVX2__)
// SHA-NI block compress (~5-8x the portable loop).  The reference's Go
// crypto/sha256 uses these instructions on every validator, so the CPU
// comparison legs must too or the bench baseline is understated.
// Message-schedule recurrence per 4-word group X_g (g >= 4):
//   X_g = sha256msg2( sha256msg1(X_{g-4}, X_{g-3})
//                     + alignr(X_{g-1}, X_{g-2}, 4), X_{g-1} )
static void sha256_compress_ni(uint32_t st[8], const uint8_t* block) {
    // the sha256* instructions have no VEX encoding (legacy SSE); with
    // surrounding -march=native code leaving ymm uppers dirty, every
    // one of them pays an AVX->SSE transition/merge penalty (~100x
    // observed here).  Clearing the uppers first makes them run at
    // native speed.
    _mm256_zeroupper();
    const __m128i MASK = _mm_set_epi64x(
        0x0c0d0e0f08090a0bULL, 0x0405060700010203ULL);
    __m128i tmp = _mm_loadu_si128((const __m128i*)&st[0]);   // DCBA
    __m128i s1 = _mm_loadu_si128((const __m128i*)&st[4]);    // HGFE
    tmp = _mm_shuffle_epi32(tmp, 0xB1);                      // CDAB
    s1 = _mm_shuffle_epi32(s1, 0x1B);                        // EFGH
    __m128i s0 = _mm_alignr_epi8(tmp, s1, 8);                // ABEF
    s1 = _mm_blend_epi16(s1, tmp, 0xF0);                     // CDGH
    const __m128i abef_save = s0, cdgh_save = s1;
    __m128i m[4];
    for (int i = 0; i < 4; i++)
        m[i] = _mm_shuffle_epi8(
            _mm_loadu_si128((const __m128i*)(block + 16 * i)), MASK);
    for (int g = 0; g < 16; g++) {
        __m128i msg = _mm_add_epi32(
            m[g & 3], _mm_loadu_si128((const __m128i*)&K256[4 * g]));
        s1 = _mm_sha256rnds2_epu32(s1, s0, msg);
        msg = _mm_shuffle_epi32(msg, 0x0E);
        s0 = _mm_sha256rnds2_epu32(s0, s1, msg);
        if (g >= 3 && g < 15) {
            // m[(g+1)&3] holds X_{g-3}, m[(g+2)&3] holds X_{g-2}
            __m128i t = _mm_alignr_epi8(m[g & 3], m[(g + 3) & 3], 4);
            m[(g + 1) & 3] = _mm_sha256msg2_epu32(
                _mm_add_epi32(
                    _mm_sha256msg1_epu32(m[(g + 1) & 3], m[(g + 2) & 3]),
                    t),
                m[g & 3]);
        }
    }
    s0 = _mm_add_epi32(s0, abef_save);
    s1 = _mm_add_epi32(s1, cdgh_save);
    tmp = _mm_shuffle_epi32(s0, 0x1B);                       // FEBA
    s1 = _mm_shuffle_epi32(s1, 0xB1);                        // DCHG
    s0 = _mm_blend_epi16(tmp, s1, 0xF0);                     // DCBA
    s1 = _mm_alignr_epi8(s1, tmp, 8);                        // HGFE
    _mm_storeu_si128((__m128i*)&st[0], s0);
    _mm_storeu_si128((__m128i*)&st[4], s1);
}
#endif

static void sha256_compress(uint32_t st[8], const uint8_t* block) {
#if defined(__SHA__) && defined(__AVX2__)
    sha256_compress_ni(st, block);
    return;
#endif
    uint32_t w[64];
    for (int i = 0; i < 16; i++)
        w[i] = ((uint32_t)block[4 * i] << 24) | ((uint32_t)block[4 * i + 1] << 16) |
               ((uint32_t)block[4 * i + 2] << 8) | block[4 * i + 3];
    for (int i = 16; i < 64; i++) {
        uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
        uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
        w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint32_t a = st[0], b = st[1], c = st[2], d = st[3];
    uint32_t e = st[4], f = st[5], g = st[6], h = st[7];
    for (int i = 0; i < 64; i++) {
        uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
        uint32_t ch = (e & f) ^ (~e & g);
        uint32_t t1 = h + S1 + ch + K256[i] + w[i];
        uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
        uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
        uint32_t t2 = S0 + maj;
        h = g; g = f; f = e; e = d + t1;
        d = c; c = b; b = a; a = t1 + t2;
    }
    st[0] += a; st[1] += b; st[2] += c; st[3] += d;
    st[4] += e; st[5] += f; st[6] += g; st[7] += h;
}

static void sha256_one(const uint8_t* msg, size_t len, uint8_t out[32]) {
    uint32_t st[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                      0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};
    size_t i = 0;
    for (; i + 64 <= len; i += 64) sha256_compress(st, msg + i);
    uint8_t tail[128];
    size_t rem = len - i;
    memcpy(tail, msg + i, rem);
    tail[rem] = 0x80;
    size_t padded = (rem + 9 <= 64) ? 64 : 128;
    memset(tail + rem + 1, 0, padded - rem - 9);
    uint64_t bits = (uint64_t)len * 8;
    for (int j = 0; j < 8; j++) tail[padded - 1 - j] = (uint8_t)(bits >> (8 * j));
    for (size_t o = 0; o < padded; o += 64) sha256_compress(st, tail + o);
    for (int j = 0; j < 8; j++) {
        out[4 * j] = (uint8_t)(st[j] >> 24);
        out[4 * j + 1] = (uint8_t)(st[j] >> 16);
        out[4 * j + 2] = (uint8_t)(st[j] >> 8);
        out[4 * j + 3] = (uint8_t)st[j];
    }
}

// Batch API: n equal-length messages.
void sha256_batch(const uint8_t* msgs, int n, int len, uint8_t* out) {
    for (int i = 0; i < n; i++)
        sha256_one(msgs + (size_t)i * len, len, out + (size_t)i * 32);
}

// ---------------------------------------------------------------------------
// NMT roots over an EDS (namespaced digests, ignore-max rule)
// ---------------------------------------------------------------------------

static const int NS = 29;
static const int DIGEST = 2 * NS + 32;  // 90

static void nmt_leaf(const uint8_t* ns_prefixed, int len, uint8_t* out) {
    uint8_t buf[1 + 29 + 4096];
    buf[0] = 0x00;
    memcpy(buf + 1, ns_prefixed, len);
    memcpy(out, ns_prefixed, NS);
    memcpy(out + NS, ns_prefixed, NS);
    sha256_one(buf, len + 1, out + 2 * NS);
}

static void nmt_node(const uint8_t* l, const uint8_t* r, uint8_t* out) {
    uint8_t buf[1 + 2 * DIGEST];
    buf[0] = 0x01;
    memcpy(buf + 1, l, DIGEST);
    memcpy(buf + 1 + DIGEST, r, DIGEST);
    memcpy(out, l, NS);  // min = left.min
    int r_min_is_max = 1;
    for (int i = 0; i < NS; i++)
        if (r[i] != 0xFF) { r_min_is_max = 0; break; }
    memcpy(out + NS, r_min_is_max ? l + NS : r + NS, NS);
    sha256_one(buf, 1 + 2 * DIGEST, out + 2 * NS);
}

// Root of one tree whose leaves are ns-prefixed payloads (n a power of two).
void nmt_root(const uint8_t* leaves, int n, int leaf_len, uint8_t* out) {
    uint8_t* lvl = new uint8_t[(size_t)n * DIGEST];
    for (int i = 0; i < n; i++)
        nmt_leaf(leaves + (size_t)i * leaf_len, leaf_len, lvl + (size_t)i * DIGEST);
    int m = n;
    while (m > 1) {
        for (int i = 0; i < m / 2; i++)
            nmt_node(lvl + (size_t)(2 * i) * DIGEST,
                     lvl + (size_t)(2 * i + 1) * DIGEST,
                     lvl + (size_t)i * DIGEST);
        m /= 2;
    }
    memcpy(out, lvl, DIGEST);
    delete[] lvl;
}

// All 4k NMT axis roots of an EDS (2k x 2k x B): rows then columns, each
// with the Q0 namespace-prefix rule. out: (4k) x 90.
void eds_nmt_roots(const uint8_t* eds, int k, int B, uint8_t* out) {
    const int n = 2 * k;
    const int leaf_len = NS + B;
    uint8_t* leaves = new uint8_t[(size_t)n * leaf_len];
    // rows
    for (int r = 0; r < n; r++) {
        for (int c = 0; c < n; c++) {
            const uint8_t* cell = eds + ((size_t)r * n + c) * B;
            uint8_t* leaf = leaves + (size_t)c * leaf_len;
            if (r < k && c < k) memcpy(leaf, cell, NS);
            else memset(leaf, 0xFF, NS);
            memcpy(leaf + NS, cell, B);
        }
        nmt_root(leaves, n, leaf_len, out + (size_t)r * DIGEST);
    }
    // columns
    for (int c = 0; c < n; c++) {
        for (int r = 0; r < n; r++) {
            const uint8_t* cell = eds + ((size_t)r * n + c) * B;
            uint8_t* leaf = leaves + (size_t)r * leaf_len;
            if (r < k && c < k) memcpy(leaf, cell, NS);
            else memset(leaf, 0xFF, NS);
            memcpy(leaf + NS, cell, B);
        }
        nmt_root(leaves, n, leaf_len, out + (size_t)(n + c) * DIGEST);
    }
    delete[] leaves;
}

// RFC-6962 merkle root over n leaves of leaf_len bytes (any n >= 1):
// leaf = sha256(0x00||data), node = sha256(0x01||l||r), split at the
// largest power of two < n.
static void rfc6962_rec(const uint8_t* leaves, int n, int leaf_len,
                        uint8_t* out32) {
    if (n == 1) {
        uint8_t buf[1 + 256];
        buf[0] = 0x00;
        memcpy(buf + 1, leaves, leaf_len);
        sha256_one(buf, 1 + leaf_len, out32);
        return;
    }
    int split = 1;
    while (split * 2 < n) split *= 2;
    uint8_t lr[1 + 64];
    lr[0] = 0x01;
    rfc6962_rec(leaves, split, leaf_len, lr + 1);
    rfc6962_rec(leaves + (size_t)split * leaf_len, n - split, leaf_len,
                lr + 33);
    sha256_one(lr, 65, out32);
}

// ---------------------------------------------------------------------------
// Blob share commitments straight from the blob bytes (go-square/inclusion
// CreateCommitment role): per blob, the RFC-6962 root over the NMT roots
// of its merkle-mountain-range subtrees.
// ---------------------------------------------------------------------------

// Sparse-share layout, as da/shares.py blob_shares_array writes it: the
// namespace, the info byte (version << 1 | sequence start), the big-endian
// sequence length on the first share only, then the blob's bytes in 478-
// (first) or 482-byte (continuation) chunks, zero-padded to SHARE.
static const int SHARE = 512;
static const int FIRST_CONTENT = SHARE - NS - 1 - 4;  // 478
static const int CONT_CONTENT = SHARE - NS - 1;       // 482
// A helper below this many leaves (~20 us of hashing) costs about as
// much to wake as it saves, so a call runs at most one thread per that
// many leaves.
static const int LEAVES_PER_THREAD = 32;
// Tasks per thread the grain aims for: enough for the queue to even out
// tasks of unequal size, few enough that the queue costs nothing.
static const int TASKS_PER_THREAD = 8;

struct CommitCtx {
    const uint8_t* const* data;  // per blob: its bytes
    const int64_t* lens;         // per blob: its length
    const uint8_t* ns;           // per blob: its namespace (NS bytes)
    const uint8_t* versions;     // per blob: its share version
    const int32_t* size_off;     // blob b's mountains: [size_off[b], size_off[b + 1])
    std::vector<int32_t> blob;   // per mountain: its blob
    std::vector<int64_t> first;  // per mountain: its first share in the blob
    std::vector<int32_t> width;  // per mountain: its leaf count
    std::vector<int32_t> task_begin, task_end;  // per task: its mountains
    std::vector<int32_t> order;  // dispatch order: tasks with most leaves first
    std::unique_ptr<std::atomic<int>[]> pending;  // per blob: mountains left
    std::vector<uint8_t> roots;  // per mountain: its NMT root
    uint8_t* out;                // per blob: its commitment
};

// NMT leaf digest of share j of blob b: ns || ns || sha256(0x00 || ns ||
// share), the share laid out in a stack buffer (the Q0 namespace-prefix
// rule: a blob share is prefixed with its own namespace).
static void blob_leaf(const CommitCtx& c, int b, int64_t j, uint8_t* out) {
    const uint8_t* ns = c.ns + (size_t)b * NS;
    const int64_t len = c.lens[b];
    uint8_t msg[1 + NS + SHARE];
    uint8_t* share = msg + 1 + NS;
    msg[0] = 0x00;
    memcpy(msg + 1, ns, NS);
    memcpy(share, ns, NS);
    share[NS] = (uint8_t)((c.versions[b] << 1) | (j == 0 ? 1 : 0));
    uint8_t* content;
    int64_t off;
    int cap;
    if (j == 0) {
        for (int i = 0; i < 4; i++)
            share[NS + 1 + i] = (uint8_t)(len >> (8 * (3 - i)));
        content = share + NS + 5;
        off = 0;
        cap = FIRST_CONTENT;
    } else {
        content = share + NS + 1;
        off = FIRST_CONTENT + (j - 1) * (int64_t)CONT_CONTENT;
        cap = CONT_CONTENT;
    }
    int64_t n = len - off;  // >= 1: every share of a blob holds some of it
    if (n > cap) n = cap;
    memcpy(content, c.data[b] + off, (size_t)n);
    memset(content + n, 0, (size_t)(cap - n));
    memcpy(out, ns, NS);
    memcpy(out + NS, ns, NS);
    sha256_one(msg, sizeof msg, out + 2 * NS);
}

// NMT root of mountain m, its leaves folded on a stack of subtree roots
// as they are hashed.
static void mountain_root(const CommitCtx& c, int m, uint8_t* out) {
    const int b = c.blob[m];
    uint8_t stack[32][DIGEST];
    int height[32];
    int top = 0;
    for (int i = 0; i < c.width[m]; i++) {
        blob_leaf(c, b, c.first[m] + i, stack[top]);
        height[top++] = 0;
        while (top >= 2 && height[top - 1] == height[top - 2]) {
            uint8_t node[DIGEST];
            nmt_node(stack[top - 2], stack[top - 1], node);
            memcpy(stack[top - 2], node, DIGEST);
            height[top - 2]++;
            top--;
        }
    }
    memcpy(out, stack[0], DIGEST);
}

// Task t: the roots of its mountains.  The thread that finishes a blob's
// last mountain folds the blob's roots into its commitment.
static void commit_task(CommitCtx& c, int t) {
    const int task = c.order[t];
    for (int m = c.task_begin[task]; m < c.task_end[task]; m++) {
        mountain_root(c, m, c.roots.data() + (size_t)m * DIGEST);
        const int b = c.blob[m];
        if (c.pending[b].fetch_sub(1, std::memory_order_acq_rel) == 1) {
            const int m0 = c.size_off[b];
            rfc6962_rec(c.roots.data() + (size_t)m0 * DIGEST,
                        c.size_off[b + 1] - m0, DIGEST,
                        c.out + (size_t)b * 32);
        }
    }
}

static void run_pool(void (*fn)(void*, int), void* ctx, int count,
                     int nthreads);
static int resolve_threads(int nthreads);

// Commitments of nblobs blobs in one call, straight from their bytes.
// Blob b is data[b] (lens[b] >= 1 bytes, namespace ns[b * NS ..], share
// version versions[b]); its mountain widths (powers of two, summing to its
// share count) are sizes[size_off[b] .. size_off[b + 1]).  The grain comes
// from the input: a blob of more leaves than the grain is split into one
// task per mountain, and smaller blobs are whole, gathered with their
// neighbours into tasks of about the grain.  run_pool's queue hands out
// the largest tasks first.  out: nblobs x 32.  Returns the number of tasks.
int blob_commitments(const uint8_t* const* data, const int64_t* lens,
                     const uint8_t* ns, const uint8_t* versions, int nblobs,
                     const int32_t* sizes, const int32_t* size_off,
                     uint8_t* out, int nthreads) {
    CommitCtx c;
    c.data = data;
    c.lens = lens;
    c.ns = ns;
    c.versions = versions;
    c.size_off = size_off;
    c.out = out;
    const int mountains = size_off[nblobs];
    c.blob.resize(mountains);
    c.first.resize(mountains);
    c.width.assign(sizes, sizes + mountains);
    c.roots.resize((size_t)mountains * DIGEST);
    c.pending.reset(new std::atomic<int>[nblobs > 0 ? nblobs : 1]);
    std::vector<int64_t> blob_leaves(nblobs);
    int64_t leaves = 0;
    for (int b = 0; b < nblobs; b++) {
        c.pending[b].store(size_off[b + 1] - size_off[b]);
        int64_t first = 0;
        for (int m = size_off[b]; m < size_off[b + 1]; m++) {
            c.blob[m] = b;
            c.first[m] = first;
            first += sizes[m];
        }
        blob_leaves[b] = first;
        leaves += first;
    }
    int nt = resolve_threads(nthreads);
    const int64_t most = (leaves + LEAVES_PER_THREAD - 1) / LEAVES_PER_THREAD;
    if (nt > most) nt = (int)(most > 0 ? most : 1);
    const int64_t per = (int64_t)nt * TASKS_PER_THREAD;
    const int64_t grain = (leaves + per - 1) / per;
    std::vector<int64_t> task_leaves;
    auto add_task = [&](int begin, int end, int64_t n) {
        c.task_begin.push_back(begin);
        c.task_end.push_back(end);
        task_leaves.push_back(n);
    };
    int open = -1;  // first mountain of the task small blobs are filling
    int64_t filled = 0;
    for (int b = 0; b < nblobs; b++) {
        if (blob_leaves[b] > grain) {
            if (open >= 0) add_task(open, size_off[b], filled);
            open = -1;
            for (int m = size_off[b]; m < size_off[b + 1]; m++)
                add_task(m, m + 1, sizes[m]);
            continue;
        }
        if (open < 0) {
            open = size_off[b];
            filled = 0;
        }
        filled += blob_leaves[b];
        if (filled >= grain) {
            add_task(open, size_off[b + 1], filled);
            open = -1;
        }
    }
    if (open >= 0) add_task(open, mountains, filled);
    const int tasks = (int)task_leaves.size();
    c.order.resize(tasks);
    for (int t = 0; t < tasks; t++) c.order[t] = t;
    std::stable_sort(c.order.begin(), c.order.end(), [&](int x, int y) {
        return task_leaves[x] > task_leaves[y];
    });
    run_pool([](void* p, int t) { commit_task(*(CommitCtx*)p, t); }, &c,
             tasks, nt);
    return tasks;
}

// Batched per-axis GF(256) matmul: out[i] = D[i] (rows_out x k) * X[i]
// (k x B), striped across nthreads threads.  The decode step of
// rsmt2d.Repair-style reconstruction: one matrix per axis (every axis can
// carry a different availability mask).
void gf_matmul_axes(const uint8_t* D, const uint8_t* X, uint8_t* out, int n,
                    int rows_out, int k, int B, int nthreads) {
    gf_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    if (nthreads > n) nthreads = n > 0 ? n : 1;
    auto work = [=](int t) {
        for (int i = t; i < n; i += nthreads) {
            const uint8_t* Di = D + (size_t)i * rows_out * k;
            const uint8_t* Xi = X + (size_t)i * k * B;
            uint8_t* Oi = out + (size_t)i * rows_out * B;
            memset(Oi, 0, (size_t)rows_out * B);
            for (int r = 0; r < rows_out; r++) {
                uint8_t* orow = Oi + (size_t)r * B;
                for (int j = 0; j < k; j++) {
                    const uint8_t c = Di[r * k + j];
                    if (c == 0) continue;
                    const uint8_t* mul = MUL[c];
                    const uint8_t* in = Xi + (size_t)j * B;
                    for (int b = 0; b < B; b++) orow[b] ^= mul[in[b]];
                }
            }
        }
    };
    if (nthreads == 1) {
        work(0);
    } else {
        std::vector<std::thread> ts;
        for (int t = 0; t < nthreads; t++) ts.emplace_back(work, t);
        for (auto& th : ts) th.join();
    }
}

// ---------------------------------------------------------------------------
// Threaded full CPU pipeline: extend + all NMT axis roots + data root.
// This is the honest CPU comparison leg for bench.py (the role Leopard-RS +
// crypto/sha256 play for the reference, SURVEY.md §2.2): full k, threaded,
// no extrapolation.
// ---------------------------------------------------------------------------

static void rfc6962_root_pow2_cpu(const uint8_t* leaves, int n, int leaf_len,
                                  uint8_t* out32) {
    // n a power of two; leaf hash = sha256(0x00||leaf), inner = sha256(0x01||l||r)
    uint8_t* lvl = new uint8_t[(size_t)n * 32];
    uint8_t buf[1 + 256];
    for (int i = 0; i < n; i++) {
        buf[0] = 0x00;
        memcpy(buf + 1, leaves + (size_t)i * leaf_len, leaf_len);
        sha256_one(buf, 1 + leaf_len, lvl + (size_t)i * 32);
    }
    int m = n;
    while (m > 1) {
        for (int i = 0; i < m / 2; i++) {
            buf[0] = 0x01;
            memcpy(buf + 1, lvl + (size_t)(2 * i) * 32, 32);
            memcpy(buf + 33, lvl + (size_t)(2 * i + 1) * 32, 32);
            sha256_one(buf, 65, lvl + (size_t)i * 32);
        }
        m /= 2;
    }
    memcpy(out32, lvl, 32);
    delete[] lvl;
}

// Thread-striping helper shared by the CPU pipelines.
static void run_striped(void (*fn)(void*, int, int), void* ctx, int count,
                        int nthreads) {
    int nt = nthreads < count ? nthreads : count;
    if (nt <= 1) {
        fn(ctx, 0, 1);
        return;
    }
    std::vector<std::thread> ts;
    for (int t = 0; t < nt; t++) ts.emplace_back(fn, ctx, t, nt);
    for (auto& th : ts) th.join();
}

// Atomic work-queue scheduler: tasks are pulled one at a time from a
// shared counter, so unevenly sized work items (an NMT axis root costs
// ~4x a Leopard column encode) load-balance across the pool — the
// property the overlapped extend->roots phase depends on.  Task order is
// PRESERVED in dispatch (item i is claimed before item i+n), which lets
// a mixed phase list its latency-critical items first.
//
// The helper threads persist across calls, parked on a condition
// variable: on a TPU v5e host, starting and joining a thread per call cost
// ~0.25 ms per thread (13 threads 3.1 ms above one, for 0.4 ms of work),
// more than a block's whole commitment pass at 13 threads.  One job runs
// on the pool at a time; a call that finds it busy (a concurrent caller,
// or a task that itself calls run_pool) starts threads of its own.
struct WorkerPool {
    std::mutex job;  // held by the caller whose job the pool runs
    std::mutex m;    // guards everything below
    std::condition_variable wake, done;
    int size = 0;       // helper threads started
    uint64_t gen = 0;   // bumped per job
    int want = 0;       // helpers the current job may still take
    int running = 0;    // helpers working on it
    void (*fn)(void*, int) = nullptr;
    void* ctx = nullptr;
    int count = 0;
    std::atomic<int> next{0};
};

static WorkerPool* g_pool = nullptr;
static std::mutex g_pool_init;

// Set while a thread runs a pool job's tasks: a task that calls run_pool
// must not wait on the pool it is running on.
static thread_local bool t_in_job = false;

static void pool_drain(WorkerPool& p) {
    t_in_job = true;
    int i;
    while ((i = p.next.fetch_add(1, std::memory_order_relaxed)) < p.count)
        p.fn(p.ctx, i);
    t_in_job = false;
}

static void pool_worker(WorkerPool* p) {
    uint64_t seen = 0;
    std::unique_lock<std::mutex> lk(p->m);
    for (;;) {
        p->wake.wait(lk, [&] { return p->gen != seen; });
        seen = p->gen;
        if (p->want <= 0) continue;
        p->want--;
        p->running++;
        lk.unlock();
        pool_drain(*p);
        lk.lock();
        if (--p->running == 0) p->done.notify_one();
    }
}

// A forked child has none of the parent's threads: it starts a pool of
// its own, leaving the parent's (whose locks may be held) untouched.
static void pool_forget(void) { g_pool = nullptr; }

static WorkerPool* pool_get(void) {
    std::lock_guard<std::mutex> lk(g_pool_init);
    if (g_pool == nullptr) {
        static bool hooked = false;
        if (!hooked) pthread_atfork(nullptr, nullptr, pool_forget);
        hooked = true;
        g_pool = new WorkerPool();  // lives as long as the process
    }
    return g_pool;
}

static void run_pool(void (*fn)(void*, int), void* ctx, int count,
                     int nthreads) {
    int nt = nthreads < count ? nthreads : count;
    if (nt <= 1) {
        for (int i = 0; i < count; i++) fn(ctx, i);
        return;
    }
    WorkerPool* p = pool_get();
    std::unique_lock<std::mutex> job(p->job, std::defer_lock);
    if (t_in_job || !job.try_lock()) {
        std::atomic<int> next(0);
        auto work = [&]() {
            int i;
            while ((i = next.fetch_add(1, std::memory_order_relaxed)) < count)
                fn(ctx, i);
        };
        std::vector<std::thread> ts;
        for (int t = 0; t < nt; t++) ts.emplace_back(work);
        for (auto& th : ts) th.join();
        return;
    }
    {
        std::lock_guard<std::mutex> lk(p->m);
        for (; p->size < nt - 1; p->size++) std::thread(pool_worker, p).detach();
        p->fn = fn;
        p->ctx = ctx;
        p->count = count;
        p->next.store(0, std::memory_order_relaxed);
        p->want = nt - 1;
        p->gen++;
    }
    p->wake.notify_all();
    pool_drain(*p);  // the caller works too
    std::unique_lock<std::mutex> lk(p->m);
    p->want = 0;  // no helper joins a drained job
    p->done.wait(lk, [&] { return p->running == 0; });
}

static int resolve_threads(int nthreads) {
    if (nthreads > 0) return nthreads;
    int hc = (int)std::thread::hardware_concurrency();
    return hc > 0 ? hc : 1;
}

struct RootsCtx {
    const uint8_t* eds;
    uint8_t* roots;
    int k, B, n;
};

// One NMT axis root of an EDS.  a in [0, 2n): rows first, then columns;
// the Q0 namespace-prefix rule matches eds_nmt_roots.  The ~139 KB leaf
// scratch (k=128) is thread_local — one allocation per worker thread,
// not one mmap/munmap pair per axis on the hot path.
static void eds_axis_root(const RootsCtx& c, int a) {
    const int leaf_len = NS + c.B;
    thread_local std::vector<uint8_t> scratch;
    if (scratch.size() < (size_t)c.n * leaf_len)
        scratch.resize((size_t)c.n * leaf_len);
    uint8_t* leaves = scratch.data();
    const int is_col = a >= c.n;
    const int idx = is_col ? a - c.n : a;
    for (int j = 0; j < c.n; j++) {
        const int r = is_col ? j : idx;
        const int col = is_col ? idx : j;
        const uint8_t* cell = c.eds + ((size_t)r * c.n + col) * c.B;
        uint8_t* leaf = leaves + (size_t)j * leaf_len;
        if (r < c.k && col < c.k) memcpy(leaf, cell, NS);
        else memset(leaf, 0xFF, NS);
        memcpy(leaf + NS, cell, c.B);
    }
    nmt_root(leaves, c.n, leaf_len, c.roots + (size_t)a * DIGEST);
}

// Standalone threaded exports of the hashing stage: the Python host
// pipeline (celestia_tpu/ops/nmt.py, da/dah.py host regime) calls these
// directly so Python-side hashing disappears from the hot loop.

// All 4k NMT axis roots of an EDS, sharded across nthreads worker
// threads (0 = hardware concurrency).  out: 4k x 90, rows then columns.
void eds_nmt_roots_mt(const uint8_t* eds, int k, int B, uint8_t* out,
                      int nthreads) {
    nthreads = resolve_threads(nthreads);
    const int n = 2 * k;
    RootsCtx ctx = {eds, out, k, B, n};
    run_pool(
        [](void* p, int i) { eds_axis_root(*(RootsCtx*)p, i); },
        &ctx, 2 * n, nthreads);
}

// Threaded batch SHA-256 over n equal-length messages (rows), striped
// across nthreads threads — the batched SHA-256-over-rows entry point.
void sha256_batch_mt(const uint8_t* msgs, int n, int len, uint8_t* out,
                     int nthreads) {
    nthreads = resolve_threads(nthreads);
    struct Ctx {
        const uint8_t* msgs;
        int n, len;
        uint8_t* out;
    } ctx = {msgs, n, len, out};
    run_striped(
        [](void* p, int t, int nt) {
            Ctx& c = *(Ctx*)p;
            for (int i = t; i < c.n; i += nt)
                sha256_one(c.msgs + (size_t)i * c.len, c.len,
                           c.out + (size_t)i * 32);
        },
        &ctx, n, nthreads);
}

// ---------------------------------------------------------------------------
// Overlapped extend -> roots pipeline (shared by the table-method and
// leopard legs).  Three phases over one worker pool:
//
//   1. Q0 + Q1 per original row (the top half of the EDS is complete
//      at the barrier);
//   2. column extension (produces Q2/Q3) INTERLEAVED with the top-half
//      ROW roots, which depend only on phase 1 — row-root hashing
//      starts while the extension is still producing the remaining
//      quadrants instead of waiting for the whole square;
//   3. the remaining axis roots (bottom rows + all columns), then the
//      RFC-6962 data root.
//
// Phase 2 lists the columns first: the critical path runs through the
// extension, and run_pool's in-order dispatch makes the roots pure
// filler for threads that run out of column work.
// ---------------------------------------------------------------------------

void leo_encode(const uint8_t* data, int k, int B, uint8_t* parity);

struct ExtendRootsCtx {
    const uint8_t* square;
    const uint8_t* E;  // encode matrix (table method); null for leopard
    uint8_t* eds;
    RootsCtx roots;
    int k, B, n, use_leo;
    size_t row_bytes;
};

static void ext_row_task(ExtendRootsCtx& c, int r) {
    uint8_t* row = c.eds + (size_t)r * c.row_bytes;
    memcpy(row, c.square + (size_t)r * c.k * c.B, (size_t)c.k * c.B);
    if (c.use_leo) leo_encode(row, c.k, c.B, row + (size_t)c.k * c.B);
    else rs_encode_axis(c.E, row, row + (size_t)c.k * c.B, c.k, c.B);
}

static void ext_col_task(ExtendRootsCtx& c, int cc) {
    thread_local std::vector<uint8_t> gather;
    if (gather.size() < 2 * (size_t)c.k * c.B)
        gather.resize(2 * (size_t)c.k * c.B);
    uint8_t* col = gather.data();
    uint8_t* par = col + (size_t)c.k * c.B;
    for (int r = 0; r < c.k; r++)
        memcpy(col + (size_t)r * c.B,
               c.eds + (size_t)r * c.row_bytes + (size_t)cc * c.B, c.B);
    if (c.use_leo) leo_encode(col, c.k, c.B, par);
    else rs_encode_axis(c.E, col, par, c.k, c.B);
    for (int r = 0; r < c.k; r++)
        memcpy(c.eds + (size_t)(c.k + r) * c.row_bytes + (size_t)cc * c.B,
               par + (size_t)r * c.B, c.B);
}

static void extend_block_overlapped(const uint8_t* square, const uint8_t* E,
                                    int use_leo, int k, int B, int nthreads,
                                    uint8_t* eds, uint8_t* roots,
                                    uint8_t* data_root) {
    nthreads = resolve_threads(nthreads);
    const int n = 2 * k;
    ExtendRootsCtx ctx = {square, E,     eds, {eds, roots, k, B, n},
                          k,      B,     n,   use_leo,
                          (size_t)n * B};
    // phase 1: Q0 + Q1 rows
    run_pool(
        [](void* p, int i) { ext_row_task(*(ExtendRootsCtx*)p, i); },
        &ctx, k, nthreads);
    // phase 2: columns + top-half row roots, overlapped
    run_pool(
        [](void* p, int i) {
            ExtendRootsCtx& c = *(ExtendRootsCtx*)p;
            if (i < c.n) ext_col_task(c, i);
            else eds_axis_root(c.roots, i - c.n);  // row roots [0, k)
        },
        &ctx, n + k, nthreads);
    // phase 3: remaining axis roots (rows [k, n) + columns [n, 2n))
    run_pool(
        [](void* p, int i) {
            ExtendRootsCtx& c = *(ExtendRootsCtx*)p;
            eds_axis_root(c.roots, c.k + i);
        },
        &ctx, 3 * k, nthreads);
    rfc6962_root_pow2_cpu(roots, 2 * n, DIGEST, data_root);
}

// Full ExtendBlock on the CPU: square k*k*B -> EDS 2k*2k*B, 4k NMT axis
// roots (4k x 90) and the RFC-6962 data root (32 bytes), using nthreads
// worker threads (0 = hardware concurrency), extend and roots overlapped.
void extend_block_cpu(const uint8_t* square, const uint8_t* E, int k, int B,
                      int nthreads, uint8_t* eds, uint8_t* roots,
                      uint8_t* data_root) {
    gf_init();
    extend_block_overlapped(square, E, 0, k, B, nthreads, eds, roots,
                            data_root);
}

// ---------------------------------------------------------------------------
// Leopard-compatible O(n log n) codec: the LCH novel-basis FFT over
// GF(2^8)/0x11D with the catid/leopard Cantor basis, high-rate layout
// (parity at positions [0, k), data at [k, 2k)).  This is the reference
// chain's erasure code (rsmt2d.NewLeoRSCodec ->
// klauspost/reedsolomon's leopard FF8 port; selected at
// /root/reference/pkg/appconsts/global_consts.go:91-92).  Field elements
// are represented in the Cantor-index domain exactly as leopard's tables
// do (see celestia_tpu/ops/gf256.py "codec selection"); correctness is
// pinned by tests/test_leopard_codec.py: this FFT must agree
// byte-for-byte with the independent Lagrange-matrix construction.
// Role here: the honest CPU comparison leg for bench.py (vs_leopard_cpu)
// and a fast host encode for the leopard codec.
// ---------------------------------------------------------------------------

static uint8_t LEO_MUL_TAB[256][256];
static uint8_t LEO_SKEW[8][256];  // SKEW[j][x] = W_j(x) / W_j(2^j) in F'
static int leo_ready = 0;

static void leo_init(void) {
    if (leo_ready) return;
    // standard log/exp over 0x11D (LFSR), then remap through the Cantor
    // index bijection C so multiplication is leopard's conjugated form
    uint8_t lg[256] = {0};
    uint8_t ex[255];
    int x = 1;
    for (int i = 0; i < 255; i++) {
        ex[i] = (uint8_t)x;
        lg[x] = (uint8_t)i;
        x <<= 1;
        if (x & 0x100) x ^= 0x11D;
    }
    static const uint8_t basis[8] = {1, 214, 152, 146, 86, 200, 88, 230};
    uint8_t C[256];
    C[0] = 0;
    for (int j = 0; j < 8; j++) {
        int w = 1 << j;
        for (int i = 0; i < w; i++) C[w + i] = C[i] ^ basis[j];
    }
    uint8_t Cinv[256];
    for (int i = 0; i < 256; i++) Cinv[C[i]] = (uint8_t)i;
    uint8_t leo_log[256] = {0};
    uint8_t leo_exp[255];
    for (int v = 1; v < 256; v++) leo_log[v] = lg[C[v]];
    for (int e = 0; e < 255; e++) leo_exp[e] = Cinv[ex[e]];
    for (int a = 0; a < 256; a++)
        for (int b = 0; b < 256; b++)
            LEO_MUL_TAB[a][b] =
                (a && b) ? leo_exp[(leo_log[a] + leo_log[b]) % 255] : 0;
    // subspace vanishing polynomials: W_0(x) = x,
    // W_{j+1}(x) = W_j(x) * W_j(x ^ 2^j)  (evaluated over all 256 points)
    uint8_t W[256];
    for (int xv = 0; xv < 256; xv++) W[xv] = (uint8_t)xv;
    for (int j = 0; j < 8; j++) {
        const uint8_t wj = W[1 << j];  // W_j(v_j) != 0 (v_j not in V_j)
        const uint8_t inv = leo_exp[(255 - leo_log[wj]) % 255];
        for (int xv = 0; xv < 256; xv++)
            LEO_SKEW[j][xv] = LEO_MUL_TAB[W[xv]][inv];
        if (j < 7) {
            uint8_t Wn[256];
            for (int xv = 0; xv < 256; xv++)
                Wn[xv] = LEO_MUL_TAB[W[xv]][W[xv ^ (1 << j)]];
            memcpy(W, Wn, 256);
        }
    }
    leo_ready = 1;
}

static inline void leo_mul_add(uint8_t* x, const uint8_t* y, uint8_t c,
                               int B) {
    if (c == 0) return;
    const uint8_t* row = LEO_MUL_TAB[c];
#if defined(__AVX2__)
    // pshufb 4-bit-split constant multiply — the same kernel shape real
    // Leopard uses, so the bench leg is an honest SIMD comparison:
    // y = ylo ^ (yhi << 4), mul(c, y) = LO[ylo] ^ HI[yhi] by linearity
    // of GF multiplication over XOR.
    if (B >= 32) {
        uint8_t lot[16], hit[16];
        for (int v = 0; v < 16; v++) {
            lot[v] = row[v];
            hit[v] = row[v << 4];
        }
        const __m256i lo =
            _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)lot));
        const __m256i hi =
            _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)hit));
        const __m256i mask = _mm256_set1_epi8(0x0F);
        int b = 0;
        for (; b + 32 <= B; b += 32) {
            __m256i yv = _mm256_loadu_si256((const __m256i*)(y + b));
            __m256i pl = _mm256_shuffle_epi8(lo, _mm256_and_si256(yv, mask));
            __m256i ph = _mm256_shuffle_epi8(
                hi, _mm256_and_si256(_mm256_srli_epi16(yv, 4), mask));
            __m256i xv = _mm256_loadu_si256((const __m256i*)(x + b));
            _mm256_storeu_si256(
                (__m256i*)(x + b),
                _mm256_xor_si256(xv, _mm256_xor_si256(pl, ph)));
        }
        for (; b < B; b++) x[b] ^= row[y[b]];
        return;
    }
#endif
    for (int b = 0; b < B; b++) x[b] ^= row[y[b]];
}

static inline void leo_xor_blk(uint8_t* x, const uint8_t* y, int B) {
    for (int b = 0; b < B; b++) x[b] ^= y[b];
}

// FFT: novel-basis coefficients -> evaluations at coset ^ [0, n).
// Butterfly (a, b) -> (a + s*b, a + (s+1)*b) with s the coset skew; the
// paired point differs by v_j, and W_j(x + v_j)/W_j(v_j) = s + 1 because
// W_j is GF(2)-linearized.
static void leo_fft(uint8_t* work, int n, int coset, int B) {
    for (int dist = n >> 1, j = 0; dist >= 1; dist >>= 1) {
        for (j = 0; (1 << j) < dist; j++) {}
        for (int b0 = 0; b0 < n; b0 += 2 * dist) {
            const uint8_t skew = LEO_SKEW[j][coset ^ b0];
            for (int i = b0; i < b0 + dist; i++) {
                uint8_t* a = work + (size_t)i * B;
                uint8_t* b = work + (size_t)(i + dist) * B;
                leo_mul_add(a, b, skew, B);  // a += s*b
                leo_xor_blk(b, a, B);        // b  = a_old + (s+1)*b_old
            }
        }
    }
}

// exact inverse of leo_fft (same skews, reversed order + inverted
// butterfly: b' = a ^ b recovers the f1 half, then a ^= s*b')
static void leo_ifft(uint8_t* work, int n, int coset, int B) {
    for (int dist = 1; dist < n; dist <<= 1) {
        int j = 0;
        for (j = 0; (1 << j) < dist; j++) {}
        for (int b0 = 0; b0 < n; b0 += 2 * dist) {
            const uint8_t skew = LEO_SKEW[j][coset ^ b0];
            for (int i = b0; i < b0 + dist; i++) {
                uint8_t* a = work + (size_t)i * B;
                uint8_t* b = work + (size_t)(i + dist) * B;
                leo_xor_blk(b, a, B);
                leo_mul_add(a, b, skew, B);
            }
        }
    }
}

// One axis: k data shards (B bytes each) -> k parity shards.  High-rate
// m = k (k a power of two): recover the interpolating polynomial's
// novel-basis coefficients from the data coset (offset k), then evaluate
// at the parity coset (offset 0).  O(k log k) block operations.
void leo_encode(const uint8_t* data, int k, int B, uint8_t* parity) {
    leo_init();
    memcpy(parity, data, (size_t)k * B);
    leo_ifft(parity, k, k, B);
    leo_fft(parity, k, 0, B);
}

// Leopard-codec square extension (quadrant layout as rs_extend_square).
void leo_extend_square_cpu(const uint8_t* square, uint8_t* eds, int k, int B,
                           int nthreads) {
    leo_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    const int n = 2 * k;
    const size_t row_bytes = (size_t)n * B;
    struct Ctx {
        const uint8_t* square;
        uint8_t* eds;
        int k, B, n;
        size_t row_bytes;
    } ctx = {square, eds, k, B, n, row_bytes};
    // Q0 + Q1 per original row
    run_striped(
        [](void* p, int t, int nt) {
            Ctx& c = *(Ctx*)p;
            for (int r = t; r < c.k; r += nt) {
                memcpy(c.eds + r * c.row_bytes,
                       c.square + (size_t)r * c.k * c.B, (size_t)c.k * c.B);
                leo_encode(c.eds + r * c.row_bytes, c.k, c.B,
                           c.eds + r * c.row_bytes + (size_t)c.k * c.B);
            }
        },
        &ctx, k, nthreads);
    // Q2/Q3 per column (gather, encode, scatter)
    run_striped(
        [](void* p, int t, int nt) {
            Ctx& c = *(Ctx*)p;
            uint8_t* col = new uint8_t[(size_t)c.k * c.B];
            uint8_t* par = new uint8_t[(size_t)c.k * c.B];
            for (int cc = t; cc < c.n; cc += nt) {
                for (int r = 0; r < c.k; r++)
                    memcpy(col + (size_t)r * c.B,
                           c.eds + r * c.row_bytes + (size_t)cc * c.B, c.B);
                leo_encode(col, c.k, c.B, par);
                for (int r = 0; r < c.k; r++)
                    memcpy(c.eds + (size_t)(c.k + r) * c.row_bytes +
                               (size_t)cc * c.B,
                           par + (size_t)r * c.B, c.B);
            }
            delete[] col;
            delete[] par;
        },
        &ctx, n, nthreads);
}

// --- Leopard O(n log n) ERASURE DECODE -------------------------------------
//
// Forney-style over the novel basis: with erasure set M and data poly F
// (deg < k), let E(x) = prod_{m in M} (x ^ x_m).  W = E*F has known
// evaluations EVERYWHERE on the n-point domain: W(x_i) = r_i*E(x_i) at
// received points, 0 at erased ones.  IFFT yields W's novel-basis
// coefficients (deg(E*F) <= |M|+k-1 <= n-1 since |M| <= k).  Both W and
// E vanish at x_m, so F(x_m) = W'(x_m) / E'(x_m).
//
// The formal derivative is CLEAN in the normalized novel basis: each
// basis factor s_i = W_i/W_i(v_i) is a linearized polynomial, so
// s_i' is the constant c_i = W_i'(0)/W_i(v_i) with
// W_i'(0) = prod_{v in V_i, v != 0} v, and
//   (X_j)' = sum_{i in bits(j)} c_i * X_{j - 2^i}
// i.e. derivative = for each bit level i: coeff[j - 2^i] ^= c_i * coeff[j].
//
// E' at an erased point: E'(x_m) = prod_{m' != m} (x_m ^ x_{m'}) (the
// product rule collapses — every other term contains the (x ^ x_m)
// factor).  All in the Cantor-index field; position -> point is XOR k.

static uint8_t LEO_DERIV_C[8];  // c_i per bit level
static int leo_deriv_ready = 0;

static void leo_deriv_init(void) {
    if (leo_deriv_ready) return;
    leo_init();
    for (int i = 0; i < 8; i++) {
        // W_i'(0) = prod of nonzero elements of V_i = span{v_0..v_{i-1}}
        uint8_t num = 1;
        for (int v = 1; v < (1 << i); v++) num = LEO_MUL_TAB[num][(uint8_t)v];
        // W_i(v_i): evaluate prod_{v in V_i} (v_i ^ v) directly
        uint8_t den = 1;
        for (int v = 0; v < (1 << i); v++)
            den = LEO_MUL_TAB[den][(uint8_t)((1 << i) ^ v)];
        // c_i = num / den
        uint8_t inv = 1, acc = den;  // den^254 = den^-1 (Fermat, 2^8)
        for (int e = 0; e < 7; e++) {
            acc = LEO_MUL_TAB[acc][acc];
            inv = LEO_MUL_TAB[inv][acc];
        }
        LEO_DERIV_C[i] = LEO_MUL_TAB[num][inv];
    }
    leo_deriv_ready = 1;
}

static inline uint8_t leo_inv_scalar(uint8_t a) {
    uint8_t inv = 1, acc = a;  // a^254
    for (int e = 0; e < 7; e++) {
        acc = LEO_MUL_TAB[acc][acc];
        inv = LEO_MUL_TAB[inv][acc];
    }
    return inv;
}

// Decode ONE axis in place.  shards: n x B rows in EDS POSITION order
// (data rows [0,k), parity rows [k,2k)); present: n bytes (0/1).
// Erased rows are overwritten with the reconstruction.  Returns 1 on
// success, 0 if fewer than k rows are present.  work must hold 2*n*B
// (coefficients + the derivative output).
int leo_decode_axis(uint8_t* shards, const uint8_t* present, int n, int B,
                    uint8_t* work) {
    leo_deriv_init();
    const int k = n / 2;
    int n_present = 0;
    for (int i = 0; i < n; i++) n_present += present[i] ? 1 : 0;
    if (n_present < k) return 0;
    if (n_present == n) return 1;
    // point domain: point j <-> position j ^ k
    uint8_t eloc[256];  // E evaluated at every domain point
    uint8_t is_erased[256];
    for (int j = 0; j < n; j++) {
        is_erased[j] = !present[j ^ k];
        eloc[j] = 1;
    }
    for (int m = 0; m < n; m++) {
        if (!is_erased[m]) continue;
        for (int j = 0; j < n; j++) {
            if (j == m) continue;  // skip only the OWN factor
            eloc[j] = LEO_MUL_TAB[eloc[j]][(uint8_t)(j ^ m)];
        }
    }
    // After the passes: a RECEIVED point j accumulated every erased
    // factor -> eloc[j] = E(x_j); an ERASED point m accumulated every
    // factor but its own -> eloc[m] = prod_{m' != m}(x_m ^ x_{m'})
    // = E'(x_m) (the product-rule survivor).  Never zero an entry: the
    // is_erased flag is what distinguishes the two meanings.
    // W evaluations into work (point order)
    for (int j = 0; j < n; j++) {
        uint8_t* dst = work + (size_t)j * B;
        if (is_erased[j]) {
            memset(dst, 0, B);
        } else {
            const uint8_t* row = LEO_MUL_TAB[eloc[j]];
            const uint8_t* src = shards + (size_t)(j ^ k) * B;
            if (eloc[j] == 0) {
                memset(dst, 0, B);
            } else {
                for (int b = 0; b < B; b++) dst[b] = row[src[b]];
            }
        }
    }
    leo_ifft(work, n, 0, B);  // novel-basis coefficients of W
    // formal derivative into a SEPARATE buffer: b'_m = sum over clear
    // bits i of m of c_i * b_{m + 2^i}.  It must not run in place — the
    // original b_m does not belong in the output, and later levels must
    // read unmutated inputs.
    uint8_t* deriv = work + (size_t)n * B;
    memset(deriv, 0, (size_t)n * B);
    for (int i = 0; (1 << i) < n; i++) {
        const uint8_t c = LEO_DERIV_C[i];
        for (int m = 0; m < n; m++) {
            if (m & (1 << i)) continue;
            leo_mul_add(deriv + (size_t)m * B,
                        work + (size_t)(m + (1 << i)) * B, c, B);
        }
    }
    leo_fft(deriv, n, 0, B);  // W' evaluated at every domain point
    for (int m = 0; m < n; m++) {
        if (!is_erased[m]) continue;
        const uint8_t scale = leo_inv_scalar(eloc[m]);  // 1 / E'(x_m)
        uint8_t* dst = shards + (size_t)(m ^ k) * B;
        const uint8_t* row = LEO_MUL_TAB[scale];
        const uint8_t* src = deriv + (size_t)m * B;
        for (int b = 0; b < B; b++) dst[b] = row[src[b]];
    }
    return 1;
}

// Threaded batch: axes x n x B, one availability row each.
void leo_decode_axes(uint8_t* data, const uint8_t* present, int n_axes,
                     int n, int B, uint8_t* ok, int nthreads) {
    leo_deriv_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    struct Ctx {
        uint8_t* data;
        const uint8_t* present;
        int n_axes, n, B;
        uint8_t* ok;
    } ctx = {data, present, n_axes, n, B, ok};
    run_striped(
        [](void* p, int t, int nt) {
            Ctx& c = *(Ctx*)p;
            std::vector<uint8_t> work(2 * (size_t)c.n * c.B);
            for (int a = t; a < c.n_axes; a += nt)
                c.ok[a] = (uint8_t)leo_decode_axis(
                    c.data + (size_t)a * c.n * c.B,
                    c.present + (size_t)a * c.n, c.n, c.B, work.data());
        },
        &ctx, n_axes, nthreads);
}

// Full leopard-codec ExtendBlock: the O(n log n) FFT extension with the
// NMT/data-root stage overlapped into it — the honest vs_leopard_cpu
// bench leg and the host-regime hot path.
void extend_block_leopard_cpu(const uint8_t* square, int k, int B,
                              int nthreads, uint8_t* eds, uint8_t* roots,
                              uint8_t* data_root) {
    leo_init();
    extend_block_overlapped(square, nullptr, 1, k, B, nthreads, eds, roots,
                            data_root);
}

// ---------------------------------------------------------------------------
// secp256k1 point arithmetic (host-native signature verification)
//
// Role: the reference leans on a C secp256k1 library for tx signature
// verification (decred secp256k1, SURVEY.md §2.2; go.mod:82) — a full square
// of PFBs means hundreds of ECDSA verifies per ProcessProposal, which would
// dominate block time in pure Python.  This implements the expensive part
// (double-scalar point multiplication u1*G + u2*Q over the curve) natively;
// the cheap scalar arithmetic mod the group order stays in Python, where
// CPython's pow() is already C.
//
// Field: GF(p), p = 2^256 - 0x1000003D1, four 64-bit limbs (little-endian),
// fully reduced between ops; products via unsigned __int128 with the
// standard two-stage fold of the high 256 bits (2^256 ≡ 0x1000003D1 mod p).
// Points: Jacobian coordinates, a=0 curve.  Scalars arrive as 32-byte
// big-endian from Python; the double multiplication runs a joint wNAF loop
// (w=8 fixed table of odd multiples of G built once; w=5 odd multiples of Q
// per call).  Verification-only — nothing here handles secret data, so no
// constant-time discipline is needed.
// ---------------------------------------------------------------------------

typedef unsigned __int128 u128;

struct Fe {
    uint64_t v[4];  // little-endian limbs, fully reduced (< p)
};

static const Fe FE_P = {{0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                         0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL}};
static const uint64_t P_C = 0x1000003D1ULL;  // 2^256 - p

static inline int fe_is_zero(const Fe& a) {
    return (a.v[0] | a.v[1] | a.v[2] | a.v[3]) == 0;
}

static inline int fe_cmp(const Fe& a, const Fe& b) {
    for (int i = 3; i >= 0; i--) {
        if (a.v[i] < b.v[i]) return -1;
        if (a.v[i] > b.v[i]) return 1;
    }
    return 0;
}

// r = a - b, assuming a >= b.
static inline void fe_sub_raw(Fe& r, const Fe& a, const Fe& b) {
    u128 borrow = 0;
    for (int i = 0; i < 4; i++) {
        u128 d = (u128)a.v[i] - b.v[i] - borrow;
        r.v[i] = (uint64_t)d;
        borrow = (d >> 64) & 1;
    }
}

static inline void fe_add(Fe& r, const Fe& a, const Fe& b) {
    u128 carry = 0;
    for (int i = 0; i < 4; i++) {
        u128 s = (u128)a.v[i] + b.v[i] + carry;
        r.v[i] = (uint64_t)s;
        carry = s >> 64;
    }
    if (carry) {
        // r held (a+b) mod 2^256; a+b-p = r + C, which stays < p (a,b < p).
        u128 c = P_C;
        for (int i = 0; i < 4 && c; i++) {
            u128 s = (u128)r.v[i] + c;
            r.v[i] = (uint64_t)s;
            c = s >> 64;
        }
    } else if (fe_cmp(r, FE_P) >= 0) {
        fe_sub_raw(r, r, FE_P);
    }
}

static inline void fe_sub(Fe& r, const Fe& a, const Fe& b) {
    if (fe_cmp(a, b) >= 0) {
        fe_sub_raw(r, a, b);
    } else {
        Fe t;
        fe_sub_raw(t, b, a);      // t = b - a
        fe_sub_raw(r, FE_P, t);   // r = p - t = a - b + p
    }
}

static void fe_mul(Fe& r, const Fe& a, const Fe& b) {
    uint64_t t[8] = {0};
    for (int i = 0; i < 4; i++) {
        u128 carry = 0;
        for (int j = 0; j < 4; j++) {
            u128 cur = (u128)a.v[i] * b.v[j] + t[i + j] + carry;
            t[i + j] = (uint64_t)cur;
            carry = cur >> 64;
        }
        t[i + 4] = (uint64_t)carry;
    }
    // fold high 256 bits: t[0..3] += t[4..7] * C
    uint64_t r4 = 0;
    {
        u128 carry = 0;
        for (int i = 0; i < 4; i++) {
            u128 cur = (u128)t[i + 4] * P_C + t[i] + carry;
            t[i] = (uint64_t)cur;
            carry = cur >> 64;
        }
        r4 = (uint64_t)carry;  // < 2^34
    }
    // fold the overflow limb
    {
        u128 carry = (u128)r4 * P_C;
        for (int i = 0; i < 4 && carry; i++) {
            u128 cur = (u128)t[i] + (uint64_t)carry;
            t[i] = (uint64_t)cur;
            carry = (carry >> 64) + (cur >> 64);
        }
        if (carry) {  // wrapped past 2^256 once more: add C
            u128 c = P_C;
            for (int i = 0; i < 4 && c; i++) {
                u128 cur = (u128)t[i] + c;
                t[i] = (uint64_t)cur;
                c = cur >> 64;
            }
        }
    }
    Fe out = {{t[0], t[1], t[2], t[3]}};
    if (fe_cmp(out, FE_P) >= 0) fe_sub_raw(out, out, FE_P);
    r = out;
}

static inline void fe_sqr(Fe& r, const Fe& a) { fe_mul(r, a, a); }

// r = base^e (e big-endian bytes), square-and-multiply.
static void fe_pow(Fe& r, const Fe& base, const uint8_t e[32]) {
    Fe acc = {{1, 0, 0, 0}};
    for (int i = 0; i < 32; i++) {
        for (int bit = 7; bit >= 0; bit--) {
            fe_sqr(acc, acc);
            if ((e[i] >> bit) & 1) fe_mul(acc, acc, base);
        }
    }
    r = acc;
}

static void fe_inv(Fe& r, const Fe& a) {
    static const uint8_t P_MINUS_2[32] = {
        0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
        0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
        0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFE, 0xFF, 0xFF, 0xFC, 0x2D};
    fe_pow(r, a, P_MINUS_2);
}

static void fe_sqrt(Fe& r, const Fe& a) {
    // p ≡ 3 (mod 4): sqrt = a^((p+1)/4); caller must check r^2 == a.
    static const uint8_t EXP[32] = {
        0x3F, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
        0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
        0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xBF, 0xFF, 0xFF, 0x0C};
    fe_pow(r, a, EXP);
}

static void fe_from_bytes(Fe& r, const uint8_t b[32]) {
    for (int i = 0; i < 4; i++) {
        uint64_t limb = 0;
        for (int j = 0; j < 8; j++) limb = (limb << 8) | b[(3 - i) * 8 + j];
        r.v[i] = limb;
    }
}

static void fe_to_bytes(uint8_t b[32], const Fe& a) {
    for (int i = 0; i < 4; i++)
        for (int j = 0; j < 8; j++)
            b[(3 - i) * 8 + j] = (uint8_t)(a.v[i] >> (8 * (7 - j)));
}

static inline void fe_neg(Fe& r, const Fe& a) {
    if (fe_is_zero(a)) { r = a; return; }
    fe_sub_raw(r, FE_P, a);
}

// Jacobian point; infinity encoded as z == 0.
struct Jac {
    Fe x, y, z;
};
struct Aff {
    Fe x, y;
};

static const Jac JAC_INF = {{{0, 0, 0, 0}}, {{1, 0, 0, 0}}, {{0, 0, 0, 0}}};

static inline int jac_is_inf(const Jac& p) { return fe_is_zero(p.z); }

static void jac_dbl(Jac& r, const Jac& p) {
    // Writes go through temporaries: callers double in place (r aliases p).
    if (jac_is_inf(p) || fe_is_zero(p.y)) { r = JAC_INF; return; }
    Fe A, B, C, D, E, F, t, t2, x3, y3, z3;
    fe_sqr(A, p.x);               // A = X^2
    fe_sqr(B, p.y);               // B = Y^2
    fe_sqr(C, B);                 // C = B^2
    fe_add(t, p.x, B);
    fe_sqr(t, t);
    fe_sub(t, t, A);
    fe_sub(t, t, C);
    fe_add(D, t, t);              // D = 2((X+B)^2 - A - C)
    fe_add(E, A, A);
    fe_add(E, E, A);              // E = 3A
    fe_sqr(F, E);                 // F = E^2
    fe_add(t, D, D);
    fe_sub(x3, F, t);             // X3 = F - 2D
    fe_sub(t, D, x3);
    fe_mul(t, E, t);
    fe_add(t2, C, C);
    fe_add(t2, t2, t2);
    fe_add(t2, t2, t2);           // 8C
    fe_sub(y3, t, t2);            // Y3 = E(D - X3) - 8C
    fe_mul(t, p.y, p.z);
    fe_add(z3, t, t);             // Z3 = 2YZ
    r.x = x3;
    r.y = y3;
    r.z = z3;
}

static void jac_add(Jac& r, const Jac& p, const Jac& q) {
    if (jac_is_inf(p)) { r = q; return; }
    if (jac_is_inf(q)) { r = p; return; }
    Fe z1z1, z2z2, u1, u2, s1, s2, t;
    fe_sqr(z1z1, p.z);
    fe_sqr(z2z2, q.z);
    fe_mul(u1, p.x, z2z2);
    fe_mul(u2, q.x, z1z1);
    fe_mul(t, q.z, z2z2);
    fe_mul(s1, p.y, t);
    fe_mul(t, p.z, z1z1);
    fe_mul(s2, q.y, t);
    Fe h, rr;
    fe_sub(h, u2, u1);
    fe_sub(rr, s2, s1);
    if (fe_is_zero(h)) {
        if (fe_is_zero(rr)) { jac_dbl(r, p); return; }
        r = JAC_INF;
        return;
    }
    Fe h2, h3, u1h2;
    fe_sqr(h2, h);
    fe_mul(h3, h, h2);
    fe_mul(u1h2, u1, h2);
    fe_sqr(t, rr);
    fe_sub(t, t, h3);
    fe_sub(t, t, u1h2);
    fe_sub(r.x, t, u1h2);         // X3 = R^2 - H^3 - 2 U1 H^2
    fe_sub(t, u1h2, r.x);
    fe_mul(t, rr, t);
    Fe s1h3;
    fe_mul(s1h3, s1, h3);
    fe_sub(r.y, t, s1h3);         // Y3 = R(U1 H^2 - X3) - S1 H^3
    fe_mul(t, p.z, q.z);
    fe_mul(r.z, t, h);            // Z3 = Z1 Z2 H
}

// Mixed addition: q affine (z = 1).
static void jac_add_aff(Jac& r, const Jac& p, const Aff& q) {
    if (jac_is_inf(p)) {
        r.x = q.x;
        r.y = q.y;
        r.z = {{1, 0, 0, 0}};
        return;
    }
    Fe z1z1, u2, s2, t;
    fe_sqr(z1z1, p.z);
    fe_mul(u2, q.x, z1z1);
    fe_mul(t, p.z, z1z1);
    fe_mul(s2, q.y, t);
    Fe h, rr;
    fe_sub(h, u2, p.x);
    fe_sub(rr, s2, p.y);
    if (fe_is_zero(h)) {
        if (fe_is_zero(rr)) { jac_dbl(r, p); return; }
        r = JAC_INF;
        return;
    }
    Fe h2, h3, u1h2;
    fe_sqr(h2, h);
    fe_mul(h3, h, h2);
    fe_mul(u1h2, p.x, h2);
    fe_sqr(t, rr);
    fe_sub(t, t, h3);
    fe_sub(t, t, u1h2);
    fe_sub(r.x, t, u1h2);
    fe_sub(t, u1h2, r.x);
    fe_mul(t, rr, t);
    Fe s1h3;
    fe_mul(s1h3, p.y, h3);
    fe_sub(r.y, t, s1h3);
    fe_mul(r.z, p.z, h);
}

static int jac_to_aff(Aff& r, const Jac& p) {
    if (jac_is_inf(p)) return 0;
    Fe zi, zi2;
    fe_inv(zi, p.z);
    fe_sqr(zi2, zi);
    fe_mul(r.x, p.x, zi2);
    fe_mul(zi2, zi2, zi);
    fe_mul(r.y, p.y, zi2);
    return 1;
}

// --- fixed G table: odd multiples 1G, 3G, ..., 255G (wNAF window 8) ---
// plus the same table mapped through the GLV endomorphism phi(x, y) =
// (beta*x, y), where beta is a primitive cube root of unity mod p:
// lambda*(x, y) = phi(x, y) for the matching cube root lambda mod n.

static Aff G_TAB[128];
static Aff PHI_G_TAB[128];
static Fe FE_BETA;
static int g_tab_ready = 0;

static void secp_init(void) {
    if (g_tab_ready) return;
    static const uint8_t GX[32] = {
        0x79, 0xBE, 0x66, 0x7E, 0xF9, 0xDC, 0xBB, 0xAC, 0x55, 0xA0, 0x62,
        0x95, 0xCE, 0x87, 0x0B, 0x07, 0x02, 0x9B, 0xFC, 0xDB, 0x2D, 0xCE,
        0x28, 0xD9, 0x59, 0xF2, 0x81, 0x5B, 0x16, 0xF8, 0x17, 0x98};
    static const uint8_t GY[32] = {
        0x48, 0x3A, 0xDA, 0x77, 0x26, 0xA3, 0xC4, 0x65, 0x5D, 0xA4, 0xFB,
        0xFC, 0x0E, 0x11, 0x08, 0xA8, 0xFD, 0x17, 0xB4, 0x48, 0xA6, 0x85,
        0x54, 0x19, 0x9C, 0x47, 0xD0, 0x8F, 0xFB, 0x10, 0xD4, 0xB8};
    static const uint8_t BETA[32] = {
        0x7A, 0xE9, 0x6A, 0x2B, 0x65, 0x7C, 0x07, 0x10, 0x6E, 0x64, 0x47,
        0x9E, 0xAC, 0x34, 0x34, 0xE9, 0x9C, 0xF0, 0x49, 0x75, 0x12, 0xF5,
        0x89, 0x95, 0xC1, 0x39, 0x6C, 0x28, 0x71, 0x95, 0x01, 0xEE};
    fe_from_bytes(FE_BETA, BETA);
    Jac g;
    fe_from_bytes(g.x, GX);
    fe_from_bytes(g.y, GY);
    g.z = {{1, 0, 0, 0}};
    Jac g2;
    jac_dbl(g2, g);
    Jac cur = g;
    for (int i = 0; i < 128; i++) {
        jac_to_aff(G_TAB[i], cur);
        fe_mul(PHI_G_TAB[i].x, G_TAB[i].x, FE_BETA);
        PHI_G_TAB[i].y = G_TAB[i].y;
        jac_add(cur, cur, g2);
    }
    g_tab_ready = 1;
}

// wNAF encoding of a 256-bit big-endian scalar. digits out (LSB first),
// values odd in (-2^(w-1), 2^(w-1)); returns length.
static int wnaf_encode(const uint8_t scalar_be[32], int w, int8_t* digits) {
    uint64_t k[5] = {0, 0, 0, 0, 0};
    for (int i = 0; i < 4; i++) {
        uint64_t limb = 0;
        for (int j = 0; j < 8; j++) limb = (limb << 8) | scalar_be[(3 - i) * 8 + j];
        k[i] = limb;
    }
    int len = 0;
    const uint64_t mask = (1ULL << w) - 1;
    const int64_t half = 1LL << (w - 1);
    while (k[0] | k[1] | k[2] | k[3] | k[4]) {
        int8_t d = 0;
        if (k[0] & 1) {
            int64_t m = (int64_t)(k[0] & mask);
            if (m >= half) m -= (int64_t)(mask + 1);
            d = (int8_t)m;
            if (m >= 0) {
                u128 borrow = 0;
                uint64_t sub = (uint64_t)m;
                for (int i = 0; i < 5; i++) {
                    u128 diff = (u128)k[i] - (i == 0 ? sub : 0) - borrow;
                    k[i] = (uint64_t)diff;
                    borrow = (diff >> 64) & 1;
                }
            } else {
                u128 carry = (uint64_t)(-m);
                for (int i = 0; i < 5 && carry; i++) {
                    u128 s = (u128)k[i] + carry;
                    k[i] = (uint64_t)s;
                    carry = s >> 64;
                }
            }
        }
        digits[len++] = d;
        // k >>= 1
        for (int i = 0; i < 4; i++) k[i] = (k[i] >> 1) | (k[i + 1] << 63);
        k[4] >>= 1;
    }
    return len;
}

// Decompress a 33-byte SEC1 public key into affine coords. Returns 1 if ok.
static int pubkey_decompress(const uint8_t pub33[33], Aff& out) {
    if (pub33[0] != 0x02 && pub33[0] != 0x03) return 0;
    Fe x;
    fe_from_bytes(x, pub33 + 1);
    if (fe_cmp(x, FE_P) >= 0) return 0;  // fe_from_bytes does not reduce
    Fe y2, t;
    fe_sqr(t, x);
    fe_mul(y2, t, x);
    Fe seven = {{7, 0, 0, 0}};
    fe_add(y2, y2, seven);
    Fe y;
    fe_sqrt(y, y2);
    fe_sqr(t, y);
    if (fe_cmp(t, y2) != 0) return 0;  // not a quadratic residue
    if ((y.v[0] & 1) != (uint64_t)(pub33[0] & 1)) fe_neg(y, y);
    out.x = x;
    out.y = y;
    return 1;
}

// R = u1*G + u2*Q for a compressed pubkey Q; returns 1 and writes the affine
// coordinates of R unless R is infinity / pubkey invalid.  This is the hot
// inner op of ECDSA verification; the caller (Python) computes u1, u2 and
// checks x(R) mod n == r.
int secp256k1_ecmul_double(const uint8_t* u1_be, const uint8_t* u2_be,
                           const uint8_t* pub33, uint8_t* out_x,
                           uint8_t* out_y) {
    secp_init();
    Aff q;
    if (!pubkey_decompress(pub33, q)) return 0;
    // odd multiples of Q: 1Q, 3Q, ..., 15Q (w = 5)
    Jac qtab[8];
    qtab[0].x = q.x;
    qtab[0].y = q.y;
    qtab[0].z = {{1, 0, 0, 0}};
    Jac q2;
    jac_dbl(q2, qtab[0]);
    for (int i = 1; i < 8; i++) jac_add(qtab[i], qtab[i - 1], q2);

    int8_t n1[260], n2[260];
    int l1 = wnaf_encode(u1_be, 8, n1);
    int l2 = wnaf_encode(u2_be, 5, n2);
    int len = l1 > l2 ? l1 : l2;
    Jac r = JAC_INF;
    for (int i = len - 1; i >= 0; i--) {
        jac_dbl(r, r);
        if (i < l1 && n1[i]) {
            int8_t d = n1[i];
            Aff a = G_TAB[(d > 0 ? d : -d) >> 1];
            if (d < 0) fe_neg(a.y, a.y);
            jac_add_aff(r, r, a);
        }
        if (i < l2 && n2[i]) {
            int8_t d = n2[i];
            Jac p = qtab[(d > 0 ? d : -d) >> 1];
            if (d < 0) fe_neg(p.y, p.y);
            jac_add(r, r, p);
        }
    }
    Aff ra;
    if (!jac_to_aff(ra, r)) return 0;
    fe_to_bytes(out_x, ra.x);
    fe_to_bytes(out_y, ra.y);
    return 1;
}

// GLV double-multiplication: u1*G + u2*Q with both scalars pre-split by
// the caller (Python bigints do the lattice rounding) into half-length
// components u = k1 + k2*lambda (mod n), |k1|,|k2| ~ 2^128.  The joint
// wNAF loop then runs ~128 doublings instead of ~256 — the dominant cost
// of the non-GLV path — against four tables: G, phi(G) (both static),
// Q and phi(Q) (built per call, normalized to affine with one shared
// Montgomery inversion so every addition is the cheap mixed form).
//
// ks: 4 scalars of 32 bytes big-endian (|k1_G|, |k2_G|, |k1_Q|, |k2_Q|);
// signs: 4 bytes, 1 = that component is negative (fold into the digit's
// point sign).  Verification-only, like everything here.
// Validate an uncompressed pubkey (x||y, 32+32 big-endian) and build the
// odd-multiple table 1Q..15Q (w = 5) in Jacobian form.  Shared by the
// per-call core (which keeps the table Jacobian) and the batched
// precomputation path (which normalizes ALL tables of a stripe to affine
// with one Montgomery inversion).  Returns 1 iff the key decodes onto
// the curve.
static int glv_build_qtab(const uint8_t* pub64, Jac qt[8]) {
    // the caller decompresses once per distinct key (cached Python-side),
    // saving the ~sqrt-sized field exponentiation every verify paid before
    Aff q;
    fe_from_bytes(q.x, pub64);
    fe_from_bytes(q.y, pub64 + 32);
    if (fe_cmp(q.x, FE_P) >= 0 || fe_cmp(q.y, FE_P) >= 0) return 0;
    {
        // on-curve check (y^2 == x^3 + 7): cheap insurance that a bad
        // uncompressed encoding can never validate a signature
        Fe y2, x3, t;
        fe_sqr(y2, q.y);
        fe_sqr(t, q.x);
        fe_mul(x3, t, q.x);
        Fe seven = {{7, 0, 0, 0}};
        fe_add(x3, x3, seven);
        if (fe_cmp(y2, x3) != 0) return 0;
    }
    qt[0].x = q.x;
    qt[0].y = q.y;
    qt[0].z = {{1, 0, 0, 0}};
    Jac q2;
    jac_dbl(q2, qt[0]);
    for (int i = 1; i < 8; i++) jac_add(qt[i], qt[i - 1], q2);
    return 1;
}

static int ecmul_double_glv_core(const uint8_t* ks, const uint8_t* signs,
                                 const uint8_t* pub64, Jac& out) {
    // odd multiples 1Q..15Q (w = 5), Jacobian (an affine normalization
    // would cost a field inversion per call — more than it saves; the
    // batched _pre path amortizes exactly that inversion), plus the
    // endomorphism image: phi(X:Y:Z) = (beta*X : Y : Z)
    Jac qt[8], pqt[8];
    if (!glv_build_qtab(pub64, qt)) return 0;
    for (int i = 0; i < 8; i++) {
        fe_mul(pqt[i].x, qt[i].x, FE_BETA);
        pqt[i].y = qt[i].y;
        pqt[i].z = qt[i].z;
    }
    // sized for FULL 256-bit scalars (like the non-GLV path): the caller
    // contract is ~128-bit split components, but an exported symbol must
    // not turn a fat scalar into a stack smash
    int8_t d[4][260];
    int len[4];
    len[0] = wnaf_encode(ks + 0, 8, d[0]);
    len[1] = wnaf_encode(ks + 32, 8, d[1]);
    len[2] = wnaf_encode(ks + 64, 5, d[2]);
    len[3] = wnaf_encode(ks + 96, 5, d[3]);
    int maxlen = 0;
    for (int j = 0; j < 4; j++)
        if (len[j] > maxlen) maxlen = len[j];
    Jac r = JAC_INF;
    for (int i = maxlen - 1; i >= 0; i--) {
        jac_dbl(r, r);
        for (int j = 0; j < 2; j++) {
            if (i >= len[j] || !d[j][i]) continue;
            int8_t dg = d[j][i];
            Aff a = (j == 0 ? G_TAB : PHI_G_TAB)[(dg > 0 ? dg : -dg) >> 1];
            // component sign XOR digit sign picks the point's sign
            if ((dg < 0) != (signs[j] != 0)) fe_neg(a.y, a.y);
            jac_add_aff(r, r, a);
        }
        for (int j = 2; j < 4; j++) {
            if (i >= len[j] || !d[j][i]) continue;
            int8_t dg = d[j][i];
            Jac p = (j == 2 ? qt : pqt)[(dg > 0 ? dg : -dg) >> 1];
            if ((dg < 0) != (signs[j] != 0)) fe_neg(p.y, p.y);
            jac_add(r, r, p);
        }
    }
    if (jac_is_inf(r)) return 0;
    out = r;
    return 1;
}

// Batched GLV double-multiplication across worker threads.
// ks: n*128 (four 32-byte components per verify); signs: n*4;
// pubs: n*64 UNCOMPRESSED affine keys.  The final Jacobian->affine
// normalization is batched per thread (one field inversion for the whole
// stripe via Montgomery's trick) — per-call inversions were a visible
// fixed cost of each verification.
void secp256k1_ecmul_double_glv_batch(const uint8_t* ks, const uint8_t* signs,
                                      const uint8_t* pubs, int n,
                                      uint8_t* out_x, uint8_t* ok,
                                      int nthreads) {
    secp_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    if (nthreads > n) nthreads = n > 0 ? n : 1;
    auto work = [&](int t) {
        // the thread's results stay Jacobian until one shared inversion
        std::vector<Jac> rs;
        std::vector<int> idx;
        for (int i = t; i < n; i += nthreads) {
            Jac r;
            if (ecmul_double_glv_core(ks + (size_t)i * 128,
                                      signs + (size_t)i * 4,
                                      pubs + (size_t)i * 64, r)) {
                rs.push_back(r);
                idx.push_back(i);
            } else {
                ok[i] = 0;
            }
        }
        size_t m = rs.size();
        if (!m) return;
        std::vector<Fe> pref(m + 1);
        pref[0] = {{1, 0, 0, 0}};
        for (size_t i = 0; i < m; i++) fe_mul(pref[i + 1], pref[i], rs[i].z);
        Fe acc;
        fe_inv(acc, pref[m]);
        for (size_t i = m; i-- > 0;) {
            Fe zinv, zi2;
            fe_mul(zinv, pref[i], acc);
            fe_mul(acc, acc, rs[i].z);
            fe_sqr(zi2, zinv);
            Fe x;
            fe_mul(x, rs[i].x, zi2);
            fe_to_bytes(out_x + (size_t)idx[i] * 32, x);
            ok[idx[i]] = 1;
        }
    };
    if (nthreads == 1) {
        work(0);
    } else {
        std::vector<std::thread> ts;
        for (int t = 0; t < nthreads; t++) ts.emplace_back(work, t);
        for (auto& th : ts) th.join();
    }
}

// Digit loop of the GLV double-mult with the Q and phi(Q) tables ALREADY
// normalized to affine: every table addition is the cheap mixed form
// (~11 fe_mul vs ~16 for Jacobian-Jacobian), across all four tables.
// The per-call field inversion that makes affine tables a loss in the
// single-shot path is amortized by the caller over the whole stripe
// (Montgomery's trick across all 8*live z-coordinates).
static int ecmul_double_glv_core_aff(const uint8_t* ks, const uint8_t* signs,
                                     const Aff qt[8], const Aff pqt[8],
                                     Jac& out) {
    int8_t d[4][260];
    int len[4];
    len[0] = wnaf_encode(ks + 0, 8, d[0]);
    len[1] = wnaf_encode(ks + 32, 8, d[1]);
    len[2] = wnaf_encode(ks + 64, 5, d[2]);
    len[3] = wnaf_encode(ks + 96, 5, d[3]);
    int maxlen = 0;
    for (int j = 0; j < 4; j++)
        if (len[j] > maxlen) maxlen = len[j];
    Jac r = JAC_INF;
    for (int i = maxlen - 1; i >= 0; i--) {
        jac_dbl(r, r);
        for (int j = 0; j < 4; j++) {
            if (i >= len[j] || !d[j][i]) continue;
            int8_t dg = d[j][i];
            const Aff* tab = (j == 0)   ? G_TAB
                             : (j == 1) ? PHI_G_TAB
                             : (j == 2) ? qt
                                        : pqt;
            Aff a = tab[(dg > 0 ? dg : -dg) >> 1];
            // component sign XOR digit sign picks the point's sign
            if ((dg < 0) != (signs[j] != 0)) fe_neg(a.y, a.y);
            jac_add_aff(r, r, a);
        }
    }
    if (jac_is_inf(r)) return 0;
    out = r;
    return 1;
}

// Batched GLV double-multiplication WITH per-stripe table precomputation.
// Same ABI as secp256k1_ecmul_double_glv_batch.  Three amortizations per
// stripe: (1) the fixed-base G / phi(G) wNAF tables are the static w=8
// precomputation shared by every call since secp_init; (2) phase A builds
// every live verify's Jacobian Q-table, then ONE Montgomery inversion
// over all 8*live z-coordinates normalizes them to affine, so (3) phase
// B's digit loops run all-mixed-affine (~5 fe_mul cheaper per Q-table
// addition, ~42 such additions per verify).  The final result
// normalization is batched exactly like the legacy symbol.  Worth it
// from roughly batch >= 4; below that the legacy symbol wins.
void secp256k1_ecmul_double_glv_batch_pre(const uint8_t* ks,
                                          const uint8_t* signs,
                                          const uint8_t* pubs, int n,
                                          uint8_t* out_x, uint8_t* ok,
                                          int nthreads) {
    secp_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    if (nthreads > n) nthreads = n > 0 ? n : 1;
    auto work = [&](int t) {
        // phase A: Jacobian odd-multiple tables for the stripe's live
        // verifies; all z-coordinates share one inversion
        std::vector<std::array<Jac, 8>> jtabs;
        std::vector<int> live;
        for (int i = t; i < n; i += nthreads) {
            std::array<Jac, 8> qt;
            if (glv_build_qtab(pubs + (size_t)i * 64, qt.data())) {
                jtabs.push_back(qt);
                live.push_back(i);
            } else {
                ok[i] = 0;
            }
        }
        size_t m = jtabs.size();
        if (!m) return;
        size_t nz = m * 8;
        std::vector<Fe> pref(nz + 1);
        pref[0] = {{1, 0, 0, 0}};
        for (size_t i = 0; i < nz; i++)
            fe_mul(pref[i + 1], pref[i], jtabs[i >> 3][i & 7].z);
        Fe acc;
        fe_inv(acc, pref[nz]);
        std::vector<std::array<Aff, 8>> atabs(m), patabs(m);
        for (size_t i = nz; i-- > 0;) {
            const Jac& p = jtabs[i >> 3][i & 7];
            Fe zinv, zi2, zi3;
            fe_mul(zinv, pref[i], acc);
            fe_mul(acc, acc, p.z);
            fe_sqr(zi2, zinv);
            fe_mul(zi3, zi2, zinv);
            Aff& a = atabs[i >> 3][i & 7];
            fe_mul(a.x, p.x, zi2);
            fe_mul(a.y, p.y, zi3);
            // endomorphism image on affine coords: phi(x, y) = (beta*x, y)
            Aff& pa = patabs[i >> 3][i & 7];
            fe_mul(pa.x, a.x, FE_BETA);
            pa.y = a.y;
        }
        // phase B: all-mixed-affine digit loops; results stay Jacobian
        // until the stripe's one result normalization
        std::vector<Jac> rs;
        std::vector<int> idx;
        for (size_t s = 0; s < m; s++) {
            int i = live[s];
            Jac r;
            if (ecmul_double_glv_core_aff(ks + (size_t)i * 128,
                                          signs + (size_t)i * 4,
                                          atabs[s].data(), patabs[s].data(),
                                          r)) {
                rs.push_back(r);
                idx.push_back(i);
            } else {
                ok[i] = 0;
            }
        }
        m = rs.size();
        if (!m) return;
        std::vector<Fe> rp(m + 1);
        rp[0] = {{1, 0, 0, 0}};
        for (size_t i = 0; i < m; i++) fe_mul(rp[i + 1], rp[i], rs[i].z);
        fe_inv(acc, rp[m]);
        for (size_t i = m; i-- > 0;) {
            Fe zinv, zi2;
            fe_mul(zinv, rp[i], acc);
            fe_mul(acc, acc, rs[i].z);
            fe_sqr(zi2, zinv);
            Fe x;
            fe_mul(x, rs[i].x, zi2);
            fe_to_bytes(out_x + (size_t)idx[i] * 32, x);
            ok[idx[i]] = 1;
        }
    };
    if (nthreads == 1) {
        work(0);
    } else {
        std::vector<std::thread> ts;
        for (int t = 0; t < nthreads; t++) ts.emplace_back(work, t);
        for (auto& th : ts) th.join();
    }
}

// Batched double-multiplication across worker threads.
// u1s/u2s: n*32 big-endian scalars; pubs: n*33; out_x: n*32; ok: n flags.
void secp256k1_ecmul_double_batch(const uint8_t* u1s, const uint8_t* u2s,
                                  const uint8_t* pubs, int n, uint8_t* out_x,
                                  uint8_t* ok, int nthreads) {
    secp_init();
    if (nthreads <= 0) {
        nthreads = (int)std::thread::hardware_concurrency();
        if (nthreads <= 0) nthreads = 1;
    }
    if (nthreads > n) nthreads = n > 0 ? n : 1;
    auto work = [&](int t) {
        uint8_t oy[32];
        for (int i = t; i < n; i += nthreads)
            ok[i] = (uint8_t)secp256k1_ecmul_double(
                u1s + (size_t)i * 32, u2s + (size_t)i * 32,
                pubs + (size_t)i * 33, out_x + (size_t)i * 32, oy);
    };
    if (nthreads == 1) {
        work(0);
    } else {
        std::vector<std::thread> ts;
        for (int t = 0; t < nthreads; t++) ts.emplace_back(work, t);
        for (auto& th : ts) th.join();
    }
}

}  // extern "C"
