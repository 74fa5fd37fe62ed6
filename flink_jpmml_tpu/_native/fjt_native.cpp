// fjt_native: host-side data plane for the streaming runtime.
//
// Replaces the per-record Python queue on the hot ingest path (the
// reference's data plane was Flink's Netty stack with credit-based
// backpressure; SURVEY.md §3 row D1). This is a bounded MPSC ring of
// fixed-arity float32 records guarded by a mutex + condvars:
//
//  - producers push single records or contiguous blocks (blocking with
//    backpressure or non-blocking);
//  - the consumer drains fill-or-deadline micro-batches *directly into a
//    caller-provided contiguous buffer* that numpy wraps zero-copy, so no
//    Python object per record ever exists on this path;
//  - close() wakes everyone; drains return what remains.
//
// Build: g++ -O3 -std=c++17 -shared -fPIC -o libfjt_native.so fjt_native.cpp -lpthread
// Bound via ctypes (flink_jpmml_tpu/runtime/native.py) — no pybind11 in the
// image, and the ABI below is deliberately C-plain for that reason.

#include <atomic>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#if defined(__SSE2__)
#include <emmintrin.h>
#endif
#if defined(__x86_64__)
#include <nmmintrin.h>
#endif

using namespace std::chrono;

namespace {

struct Ring {
    uint32_t capacity;   // records
    uint32_t arity;      // floats per record
    float*   data;       // capacity * arity floats
    uint64_t* offsets;   // per-record source offset (resume bookkeeping)
    uint32_t head = 0;   // next slot to pop
    uint32_t count = 0;  // records in the ring
    bool     closed = false;
    std::mutex mu;
    std::condition_variable not_full;
    std::condition_variable not_empty;
};

inline uint32_t slot(const Ring* r, uint32_t logical) {
    uint32_t s = r->head + logical;
    if (s >= r->capacity) s -= r->capacity;
    return s;
}

}  // namespace

extern "C" {

Ring* fjt_ring_create(uint32_t capacity, uint32_t arity) {
    if (capacity == 0 || arity == 0) return nullptr;
    Ring* r = new (std::nothrow) Ring();
    if (!r) return nullptr;
    r->capacity = capacity;
    r->arity = arity;
    r->data = new (std::nothrow) float[(size_t)capacity * arity];
    r->offsets = new (std::nothrow) uint64_t[capacity];
    if (!r->data || !r->offsets) {
        delete[] r->data;
        delete[] r->offsets;
        delete r;
        return nullptr;
    }
    return r;
}

void fjt_ring_destroy(Ring* r) {
    if (!r) return;
    delete[] r->data;
    delete[] r->offsets;
    delete r;
}

void fjt_ring_close(Ring* r) {
    std::lock_guard<std::mutex> lk(r->mu);
    r->closed = true;
    r->not_empty.notify_all();
    r->not_full.notify_all();
}

uint32_t fjt_ring_size(Ring* r) {
    std::lock_guard<std::mutex> lk(r->mu);
    return r->count;
}

int fjt_ring_closed(Ring* r) {
    std::lock_guard<std::mutex> lk(r->mu);
    return r->closed ? 1 : 0;
}

// Push a contiguous block of n records (n*arity floats) with consecutive
// source offsets starting at first_offset. Blocks until all records are in
// (backpressure) or timeout_us elapses. Returns the number of records
// pushed; -1 (as UINT32_MAX) never — closed ring returns what fit.
uint32_t fjt_ring_push_block(Ring* r, const float* recs, uint64_t first_offset,
                             uint32_t n, int64_t timeout_us) {
    uint32_t pushed = 0;
    auto deadline = steady_clock::now() + microseconds(timeout_us);
    std::unique_lock<std::mutex> lk(r->mu);
    while (pushed < n) {
        while (r->count == r->capacity && !r->closed) {
            if (timeout_us >= 0) {
                if (r->not_full.wait_until(lk, deadline) == std::cv_status::timeout)
                    return pushed;
            } else {
                r->not_full.wait(lk);
            }
        }
        if (r->closed) return pushed;
        uint32_t room = r->capacity - r->count;
        uint32_t take = n - pushed < room ? n - pushed : room;
        for (uint32_t i = 0; i < take; ++i) {
            uint32_t s = slot(r, r->count + i);
            std::memcpy(r->data + (size_t)s * r->arity,
                        recs + (size_t)(pushed + i) * r->arity,
                        r->arity * sizeof(float));
            r->offsets[s] = first_offset + pushed + i;
        }
        r->count += take;
        pushed += take;
        r->not_empty.notify_one();
    }
    return pushed;
}

// Fill-or-deadline drain into out (max_n*arity floats) + out_offsets
// (max_n u64). Blocks until >=1 record (or closed) — bounded by
// idle_timeout_us when >= 0 (0 records returned on expiry: lets a
// consumer with control-plane work, e.g. the dynamic serving pipeline's
// Add/Del polling, wake up on an idle stream; -1 waits indefinitely).
// Once records flow, keeps taking until max_n or deadline_us after the
// first take. Returns records drained (0 => closed-and-empty or idle
// bound expired).
uint32_t fjt_ring_drain(Ring* r, float* out, uint64_t* out_offsets,
                        uint32_t max_n, int64_t deadline_us,
                        int64_t idle_timeout_us) {
    std::unique_lock<std::mutex> lk(r->mu);
    auto idle_deadline = steady_clock::now() + microseconds(idle_timeout_us);
    while (r->count == 0) {
        if (r->closed) return 0;
        if (idle_timeout_us >= 0) {
            if (r->not_empty.wait_until(lk, idle_deadline) ==
                    std::cv_status::timeout ||
                (r->count == 0 && steady_clock::now() >= idle_deadline))
                if (r->count == 0) return 0;
        } else {
            r->not_empty.wait_for(lk, milliseconds(100));
        }
    }
    uint32_t drained = 0;
    auto deadline = steady_clock::now() + microseconds(deadline_us);
    for (;;) {
        uint32_t take = r->count < max_n - drained ? r->count : max_n - drained;
        for (uint32_t i = 0; i < take; ++i) {
            uint32_t s = slot(r, i);
            std::memcpy(out + (size_t)(drained + i) * r->arity,
                        r->data + (size_t)s * r->arity,
                        r->arity * sizeof(float));
            out_offsets[drained + i] = r->offsets[s];
        }
        r->head = slot(r, take);
        r->count -= take;
        drained += take;
        if (take) r->not_full.notify_all();
        if (drained >= max_n) break;
        if (r->count == 0) {
            if (r->closed) break;
            if (r->not_empty.wait_until(lk, deadline) == std::cv_status::timeout)
                break;
            if (r->count == 0 && r->closed) break;
            if (steady_clock::now() >= deadline) break;
        }
    }
    return drained;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Rank-wire bucketizer (compile/qtrees.py QuantizedWire.encode fast path).
//
// Maps each f32 feature value to its rank among that feature's model split
// cuts — rank = #{c in cuts[j] : c < x} — producing the uint8/uint16 codes
// the quantized TPU kernel compares against. This is host featurization
// (the reference does the analogous prepare/coerce per record in
// JPMML-Evaluator's FieldValue prep; SURVEY.md §4.1), multithreaded so the
// host keeps ahead of the device at >1M records/s.
//
//   X        [n, f] row-major f32
//   cuts     two layouts, one per entry-point family:
//            fjt_bucketize_*      — ragged: concatenated per-feature sorted
//                                   tables + offs[f+1] int32 offsets
//            fjt_bucketize_pow2_* — [f, L] rows, +inf-padded to a shared
//                                   power-of-two length L (no offs)
//   repl     [f] f32 missing-value replacement (used where has_repl)
//   has_repl [f] u8
//   mask     [n, f] u8 missing mask, may be null (NaN always = missing)
//   out      [n, f] codes; sentinel = max value of the code type
// ---------------------------------------------------------------------------

namespace {

// Shared row-range fan-out: clamp thread count (spawn/join costs ~100us a
// thread — keep >=4096 rows each) and run `rows` over [0, n) partitions.
template <typename RowsFn>
void fan_out_rows(uint64_t n, uint32_t n_threads, const RowsFn& rows) {
    if (n_threads == 0) {
        unsigned hw = std::thread::hardware_concurrency();
        n_threads = hw ? hw : 4;
    }
    uint64_t max_useful = (n + 4095) / 4096;
    if (n_threads > max_useful) n_threads = static_cast<uint32_t>(max_useful);
    if (n_threads == 0) n_threads = 1;
    if (n_threads <= 1) {
        rows(uint64_t(0), n);
        return;
    }
    std::vector<std::thread> ts;
    ts.reserve(n_threads);
    uint64_t per = (n + n_threads - 1) / n_threads;
    for (uint32_t t = 0; t < n_threads; ++t) {
        uint64_t b = t * per, e = b + per < n ? b + per : n;
        if (b >= e) break;
        ts.emplace_back(rows, b, e);
    }
    for (auto& t : ts) t.join();
}

template <typename Code>
void bucketize_rows(const float* X, uint64_t row_begin, uint64_t row_end,
                    uint32_t f, const float* cuts, const int32_t* offs,
                    const float* repl, const uint8_t* has_repl,
                    const uint8_t* mask, Code* out) {
    const Code sentinel = static_cast<Code>(~Code(0));
    for (uint64_t i = row_begin; i < row_end; ++i) {
        const float* row = X + i * f;
        const uint8_t* mrow = mask ? mask + i * f : nullptr;
        Code* orow = out + i * f;
        for (uint32_t j = 0; j < f; ++j) {
            float x = row[j];
            bool miss = (x != x) || (mrow && mrow[j]);
            if (miss) {
                if (has_repl[j]) {
                    x = repl[j];
                } else {
                    orow[j] = sentinel;
                    continue;
                }
            }
            // branchless lower_bound: rank = #{c < x}. The `* half` form
            // compiles to cmov — no data-dependent branches, which is worth
            // ~5x on random inputs (every branch would mispredict).
            const float* start = cuts + offs[j];
            const float* lo = start;
            uint32_t len = static_cast<uint32_t>(offs[j + 1] - offs[j]);
            while (len > 1) {
                uint32_t half = len / 2;
                lo += (lo[half - 1] < x) * half;
                len -= half;
            }
            orow[j] = static_cast<Code>((lo - start) + (len && lo[0] < x));
        }
    }
}

template <typename Code>
void bucketize_impl(const float* X, uint64_t n, uint32_t f, const float* cuts,
                    const int32_t* offs, const float* repl,
                    const uint8_t* has_repl, const uint8_t* mask, Code* out,
                    uint32_t n_threads) {
    fan_out_rows(n, n_threads, [&](uint64_t b, uint64_t e) {
        bucketize_rows<Code>(X, b, e, f, cuts, offs, repl, has_repl, mask,
                             out);
    });
}

// Lockstep variant over power-of-two padded tables (cuts[j*L .. j*L+L),
// padded with +inf which never counts toward a rank). The per-feature
// binary searches form f independent load-compare chains; executed
// feature-after-feature each chain's ~log2(L) dependent loads serialize,
// but interleaving them level-by-level keeps ~f independent loads in
// flight per round, which on a single host core is worth ~1.3-2x
// (dev-run on the build host; not measured on the chip machine).
template <typename Code>
void bucketize_rows_pow2(const float* X, uint64_t row_begin, uint64_t row_end,
                         uint32_t f, const float* cuts, uint32_t L,
                         const float* repl, const uint8_t* has_repl,
                         const uint8_t* mask, Code* out) {
    const Code sentinel = static_cast<Code>(~Code(0));
    std::vector<uint32_t> pos(f);
    std::vector<float> xv(f);
    std::vector<uint8_t> miss(f);
    for (uint64_t i = row_begin; i < row_end; ++i) {
        const float* row = X + i * f;
        const uint8_t* mrow = mask ? mask + i * f : nullptr;
        Code* orow = out + i * f;
        for (uint32_t j = 0; j < f; ++j) {
            float x = row[j];
            bool m = (x != x) || (mrow && mrow[j]);
            if (m && has_repl[j]) {
                x = repl[j];
                m = false;
            }
            // NaN compares false against every cut, so a missing lane
            // rides the rounds harmlessly and is overwritten at the end
            miss[j] = m;
            xv[j] = x;
            pos[j] = 0;
        }
        for (uint32_t half = L >> 1; half >= 1; half >>= 1) {
            for (uint32_t j = 0; j < f; ++j) {
                const float* t = cuts + static_cast<uint64_t>(j) * L;
                pos[j] += (t[pos[j] + half - 1] < xv[j]) * half;
            }
        }
        for (uint32_t j = 0; j < f; ++j) {
            const float* t = cuts + static_cast<uint64_t>(j) * L;
            uint32_t r = pos[j] + (t[pos[j]] < xv[j]);
            orow[j] = miss[j] ? sentinel : static_cast<Code>(r);
        }
    }
}

template <typename Code>
void bucketize_pow2_impl(const float* X, uint64_t n, uint32_t f,
                         const float* cuts, uint32_t L, const float* repl,
                         const uint8_t* has_repl, const uint8_t* mask,
                         Code* out, uint32_t n_threads) {
    fan_out_rows(n, n_threads, [&](uint64_t b, uint64_t e) {
        bucketize_rows_pow2<Code>(X, b, e, f, cuts, L, repl, has_repl, mask,
                                  out);
    });
}

}  // namespace

extern "C" {

void fjt_bucketize_pow2_u8(const float* X, uint64_t n, uint32_t f,
                           const float* cuts, uint32_t L, const float* repl,
                           const uint8_t* has_repl, const uint8_t* mask,
                           uint8_t* out, uint32_t n_threads) {
    bucketize_pow2_impl<uint8_t>(X, n, f, cuts, L, repl, has_repl, mask, out,
                                 n_threads);
}

void fjt_bucketize_pow2_u16(const float* X, uint64_t n, uint32_t f,
                            const float* cuts, uint32_t L, const float* repl,
                            const uint8_t* has_repl, const uint8_t* mask,
                            uint16_t* out, uint32_t n_threads) {
    bucketize_pow2_impl<uint16_t>(X, n, f, cuts, L, repl, has_repl, mask, out,
                                  n_threads);
}

void fjt_bucketize_u8(const float* X, uint64_t n, uint32_t f,
                      const float* cuts, const int32_t* offs,
                      const float* repl, const uint8_t* has_repl,
                      const uint8_t* mask, uint8_t* out, uint32_t n_threads) {
    bucketize_impl<uint8_t>(X, n, f, cuts, offs, repl, has_repl, mask, out,
                            n_threads);
}

void fjt_bucketize_u16(const float* X, uint64_t n, uint32_t f,
                       const float* cuts, const int32_t* offs,
                       const float* repl, const uint8_t* has_repl,
                       const uint8_t* mask, uint16_t* out,
                       uint32_t n_threads) {
    bucketize_impl<uint16_t>(X, n, f, cuts, offs, repl, has_repl, mask, out,
                             n_threads);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Kafka record-batch decoder (runtime/kafka.py's ingest fast path).
//
// The Python decoder (decode_record_batches) walks zigzag varints and runs
// a table-driven CRC32C per batch in pure Python — ~50k rec/s, which caps
// the BASELINE config-2 "Kafka tabular stream" far below the 1M rec/s
// north star. This decoder handles the tabular contract (every value
// exactly value_len bytes), checksums each batch on the CPU's crc32
// instruction where it has one (the byte table's ~2.5 ns a byte was
// nine tenths of the decode), and mirrors the Python
// semantics exactly: partial trailing batches (batch_len < 49 or
// extending past the buffer) end the walk; non-v2 magic and CRC
// mismatches are errors; a value of any other length aborts with -3 so
// the caller falls back to the general Python path.
// ---------------------------------------------------------------------------

namespace {

// byte-wise table of a reflected CRC-32 polynomial: 0x82F63B78 is CRC32C
// (Kafka's batches), 0xEDB88320 zlib's CRC32 (the state table's key hash)
template <uint32_t Poly>
struct CrcTable {
    uint32_t t[256];
    CrcTable() {
        for (uint32_t i = 0; i < 256; ++i) {
            uint32_t c = i;
            for (int k = 0; k < 8; ++k)
                c = (c >> 1) ^ (Poly & (~(c & 1u) + 1u));
            t[i] = c;
        }
    }
};

// CRC32C of a record batch's body, a byte at a time through the table: one
// dependent load-xor-shift a byte. The fallback of a host without SSE4.2.
uint32_t crc32c_table(const uint8_t* p, int64_t n) {
    static const CrcTable<0x82F63B78u> table;
    uint32_t c = 0xFFFFFFFFu;
    for (int64_t i = 0; i < n; ++i)
        c = (c >> 8) ^ table.t[(c ^ p[i]) & 0xFFu];
    return c ^ 0xFFFFFFFFu;
}

#if defined(__x86_64__)
// The same CRC on SSE4.2's crc32 instruction, which computes exactly this
// polynomial: 8 bytes a step in one stream, then the 0-7 bytes of the tail.
// Compiled for SSE4.2 alone, so the build line needs no -march; chosen at
// run time, so the library still loads on a CPU without it.
__attribute__((target("sse4.2")))
uint32_t crc32c_sse42(const uint8_t* p, int64_t n) {
    uint64_t c = 0xFFFFFFFFu;
    for (; n >= 8; p += 8, n -= 8) {
        uint64_t w;
        std::memcpy(&w, p, 8);  // unaligned
        c = _mm_crc32_u64(c, w);
    }
    uint32_t c32 = static_cast<uint32_t>(c);
    for (; n > 0; ++p, --n) c32 = _mm_crc32_u8(c32, *p);
    return c32 ^ 0xFFFFFFFFu;
}
#endif

using Crc32cFn = uint32_t (*)(const uint8_t*, int64_t);

Crc32cFn pick_crc32c() {
#if defined(__x86_64__)
    __builtin_cpu_init();  // this runs among the library's constructors
    if (__builtin_cpu_supports("sse4.2")) return crc32c_sse42;
#endif
    return crc32c_table;
}

// the host's CPU picks once, at load; both Kafka codecs go through it
const Crc32cFn crc32c_buf = pick_crc32c();

inline int64_t be64(const uint8_t* p) {
    uint64_t v = 0;
    for (int i = 0; i < 8; ++i) v = (v << 8) | p[i];
    return static_cast<int64_t>(v);
}

inline int32_t be32s(const uint8_t* p) {
    uint32_t v = (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
                 (uint32_t(p[2]) << 8) | uint32_t(p[3]);
    return static_cast<int32_t>(v);
}

// protobuf-zigzag varint (the record-framing integers of magic-v2 batches)
inline bool read_zigzag(const uint8_t* b, int64_t len, int64_t& p,
                        int64_t& out) {
    uint64_t u = 0;
    int shift = 0;
    for (;;) {
        if (p >= len || shift > 63) return false;
        uint8_t byte = b[p++];
        u |= uint64_t(byte & 0x7F) << shift;
        if (!(byte & 0x80)) break;
        shift += 7;
    }
    out = static_cast<int64_t>(u >> 1) ^ -static_cast<int64_t>(u & 1);
    return true;
}

}  // namespace

extern "C" {

// Inverse of the decoder for the producer side: encode n fixed-length
// values as ONE magic-v2 batch (null keys, no headers, timestamp 0) —
// byte-identical to runtime/kafka.py's encode_record_batch. → bytes
// written, or -1 when out_cap is too small.
int64_t fjt_kafka_encode_fixed(const uint8_t* values, int64_t n,
                               int64_t value_len, int64_t base_offset,
                               uint8_t* out, int64_t out_cap) {
    if (n <= 0 || value_len < 0) return -1;
    auto zig = [](int64_t x) -> uint64_t {
        return (uint64_t(x) << 1) ^ uint64_t(x >> 63);
    };
    auto vsize = [](uint64_t u) -> int64_t {
        int64_t s = 1;
        while (u >= 0x80) {
            u >>= 7;
            ++s;
        }
        return s;
    };
    int64_t p = 61;  // batch header (21) + post header (40)
    auto put_varint = [&](uint64_t u) {
        while (u >= 0x80) {
            out[p++] = uint8_t(u) | 0x80;
            u >>= 7;
        }
        out[p++] = uint8_t(u);
    };
    // bound: per record <= rec_len varint(<=10) + body; check coarsely
    for (int64_t i = 0; i < n; ++i) {
        // body: attr(1) vz(0)(1) vz(i) vz(-1)(1) vz(len) value vz(0)(1)
        const int64_t body_len =
            4 + vsize(zig(i)) + vsize(zig(value_len)) + value_len;
        if (p + vsize(zig(body_len)) + body_len > out_cap) return -1;
        put_varint(zig(body_len));
        out[p++] = 0;  // record attributes
        put_varint(0);  // timestamp delta
        put_varint(zig(i));  // offset delta
        put_varint(zig(-1));  // null key
        put_varint(zig(value_len));
        std::memcpy(out + p, values + i * value_len, value_len);
        p += value_len;
        put_varint(0);  // headers count
    }
    const int64_t end = p;
    auto be32w = [&](int64_t at, uint32_t v) {
        out[at] = uint8_t(v >> 24);
        out[at + 1] = uint8_t(v >> 16);
        out[at + 2] = uint8_t(v >> 8);
        out[at + 3] = uint8_t(v);
    };
    auto be64w = [&](int64_t at, uint64_t v) {
        for (int i = 0; i < 8; ++i)
            out[at + i] = uint8_t(v >> (8 * (7 - i)));
    };
    // post header (CRC-covered region starts at 21)
    out[21] = 0;
    out[22] = 0;  // attributes
    be32w(23, uint32_t(n - 1));  // last offset delta
    be64w(27, 0);  // first timestamp
    be64w(35, 0);  // max timestamp
    be64w(43, ~uint64_t(0));  // producer id -1
    out[51] = 0xFF;
    out[52] = 0xFF;  // producer epoch -1
    be32w(53, ~uint32_t(0));  // base sequence -1
    be32w(57, uint32_t(n));
    // batch header
    be64w(0, uint64_t(base_offset));
    be32w(8, uint32_t(end - 12));  // batch length (after this field)
    be32w(12, ~uint32_t(0));  // partition leader epoch -1
    out[16] = 2;  // magic
    be32w(17, crc32c_buf(out + 21, end - 21));
    return end;
}

// → records decoded (>= 0), or: -1 CRC mismatch, -2 unsupported magic,
// -3 a value's length != value_len (caller falls back to the general
// Python decoder), -4 malformed framing, -5 out capacity exhausted.
int64_t fjt_kafka_decode_fixed(const uint8_t* buf, int64_t len,
                               int64_t value_len, uint8_t* out,
                               int64_t out_cap, int64_t* offs) {
    if (value_len <= 0) return -4;
    int64_t count = 0;
    int64_t pos = 0;
    while (pos + 12 <= len) {
        const int64_t base_offset = be64(buf + pos);
        const int32_t batch_len = be32s(buf + pos + 8);
        const int64_t end = pos + 12 + batch_len;
        // 49 = minimum v2 batch body; shorter (or overhanging) trailers
        // are a truncated tail, exactly like the Python walk
        if (batch_len < 49 || end > len) break;
        if (buf[pos + 16] != 2) return -2;
        const uint32_t crc_stored =
            (uint32_t(buf[pos + 17]) << 24) | (uint32_t(buf[pos + 18]) << 16) |
            (uint32_t(buf[pos + 19]) << 8) | uint32_t(buf[pos + 20]);
        const uint8_t* body = buf + pos + 21;
        const int64_t blen = end - (pos + 21);
        if (crc32c_buf(body, blen) != crc_stored) return -1;
        // attributes(2) lastOffsetDelta(4) firstTs(8) maxTs(8)
        // producerId(8) producerEpoch(2) baseSequence(4) → count at 36
        if (blen < 40) return -4;
        const int32_t n = be32s(body + 36);
        int64_t p = 40;
        for (int32_t i = 0; i < n; ++i) {
            int64_t rec_len;
            if (!read_zigzag(body, blen, p, rec_len)) return -4;
            const int64_t rec_end = p + rec_len;
            if (rec_len < 0 || rec_end > blen) return -4;
            p += 1;  // record attributes
            int64_t tsd, offd, klen, vlen;
            if (!read_zigzag(body, blen, p, tsd)) return -4;
            if (!read_zigzag(body, blen, p, offd)) return -4;
            if (!read_zigzag(body, blen, p, klen)) return -4;
            if (klen > 0) {
                p += klen;
                if (p > blen) return -4;
            }
            if (!read_zigzag(body, blen, p, vlen)) return -4;
            if (vlen != value_len || p + vlen > blen) return -3;
            if (count >= out_cap) return -5;
            std::memcpy(out + count * value_len, body + p, value_len);
            offs[count] = base_offset + offd;
            ++count;
            p = rec_end;
        }
        pos = end;
    }
    return count;
}

// The Kafka codecs' CRC32C as the host's CPU computes it, and the byte-table
// loop alone, for the tests; fjt_crc32c_hw says which one the first is.
uint32_t fjt_crc32c(const uint8_t* buf, int64_t n) { return crc32c_buf(buf, n); }

uint32_t fjt_crc32c_table(const uint8_t* buf, int64_t n) {
    return crc32c_table(buf, n);
}

int fjt_crc32c_hw() { return crc32c_buf != crc32c_table; }

}  // extern "C"

// ---------------------------------------------------------------------------
// Keyed-state routing (runtime/state.py KeyedStateTable.route).
//
// The host mirror of the state table is three flat arrays (uint32 key
// hashes, occupancy bytes, int64 LRU stamps), hundreds of millions of
// entries in a deployment, and every record of every batch has to find its
// key's slot in them. Three entries, one call each, all bit-exact twins of
// the numpy code they stand in for (which stays as the fallback of a host
// that cannot build this file, and as the tests' oracle):
//
//  - fjt_state_hash_f32: the key column of a raw f32 block → the uint32
//    stable hash, parallel/partitioner.py stable_hash_vec of the column cast
//    to int64: CRC32 (zlib's) over b"i" + the key's low
//    abs(key).bit_length()//8 + 1 bytes, little-endian two's complement;
//  - fjt_state_resolve, route's first pass: per record, in arrival order,
//    walk the probe window from hash % capacity and stop at the first slot
//    that matches (a hit: write the slot, stamp it) or is empty, or at the
//    window's end (both: the record is left pending). It writes neither keys
//    nor occupancy, so what it resolves no claim or eviction of the same
//    call can change;
//  - fjt_state_claim, route's second pass over what the first left: the
//    claim rounds and the eviction of KeyedStateTable._claim_rounds, whose
//    docstring is the one statement of the rule. The numpy body of that
//    method is the same rounds, and what a host without this library runs.
//
// Every probe is a cache miss in a table of that size, and a full table's
// keys lie anywhere in their windows, so the resolve pass runs as a pipeline
// over its own misses: kRouteAhead records ahead of a record it asks for the
// home slot's lines, kRouteNear records ahead it walks the window (it only
// reads what the pass never writes, so the answer is the one a walk at the
// record's turn would give) and asks for the line of the stamp, and at the
// record itself it writes the answer and stamps. The walks of the records
// in between are independent of each other and of the stamps, so the lines
// a walk misses on its way are fetched beside the next walks' instead of
// one after another. (Asking for every line a window can span, ahead of the
// walk, was measured slower on the TPU host than asking for the home line
// alone: seven requests a record crowd out the ones in use.)
// ---------------------------------------------------------------------------

namespace {

// numpy's f32 → int64 cast as this platform does it (cvttss2si): NaN, ±inf
// and |x| >= 2^63 all come out INT64_MIN. Written out, since the plain C
// cast of such a value is undefined.
inline int64_t f32_to_i64(float f) {
    if (!(f >= -9223372036854775808.0f && f < 9223372036854775808.0f))
        return INT64_MIN;
    return static_cast<int64_t>(f);
}

// how many records ahead of a record's turn the resolve pass asks for its
// home slot's lines, and walks its window (measured on the TPU host, on the
// 200M-slot mirror, twenty pairs from 8/4 to 64/32: on a full table none is
// more than 6% faster than 16 and 8, and on a table whose keys sit by their
// home slots this pair alone matches the walk at the record's turn)
constexpr uint64_t kRouteAhead = 16;
constexpr uint64_t kRouteNear = 8;

// hash % capacity without a division a record (Lemire's fastmod: exact for
// every 32-bit hash and capacity); a capacity past 2^32 holds every hash
struct HomeOf {
    uint64_t m;
    uint64_t capacity;
    explicit HomeOf(uint64_t cap)
        : m(cap >> 32 ? 0 : ~uint64_t(0) / cap + 1), capacity(cap) {}
    uint64_t operator()(uint32_t h) const {
        if (capacity >> 32) return h;
        return uint64_t((static_cast<unsigned __int128>(m * h) * capacity) >> 64);
    }
};

#if defined(__SSE2__)
// Of sixteen slots, bit j set where slot j is empty or holds the hash.
inline uint32_t ends_of_16(const uint32_t* k, const uint8_t* o, __m128i h) {
    auto at = [&](int j) {
        return _mm_cmpeq_epi32(
            _mm_loadu_si128(reinterpret_cast<const __m128i*>(k + j)), h);
    };
    const __m128i held = _mm_packs_epi16(_mm_packs_epi32(at(0), at(4)),
                                         _mm_packs_epi32(at(8), at(12)));
    const __m128i empty = _mm_cmpeq_epi8(
        _mm_loadu_si128(reinterpret_cast<const __m128i*>(o)),
        _mm_setzero_si128());
    return uint32_t(_mm_movemask_epi8(_mm_or_si128(held, empty)));
}
#endif

// Where a key's walk of its window ends: → p, the slots passed, and c, the
// slot reached: the first that is empty or holds the hash, p == probe where
// the window has neither. A window that does not wrap is read sixteen slots
// a step where the host has the compares for it: a full table's keys lie
// anywhere in their windows, and four in ten records of a stream that admits
// keys walk all of theirs.
inline uint32_t walk_window(const uint32_t* keys, const uint8_t* occ,
                            uint32_t h, uint64_t home, uint32_t probe,
                            uint64_t capacity, uint64_t& c) {
    c = home;
    if (!occ[c] || keys[c] == h) return 0;
    uint32_t p = 0;
#if defined(__SSE2__)
    if (home + probe <= capacity) {
        const __m128i hv = _mm_set1_epi32(int32_t(h));
        for (; p + 16 <= probe; p += 16) {
            const uint32_t ends = ends_of_16(keys + home + p, occ + home + p, hv);
            if (ends) {
                p += uint32_t(__builtin_ctz(ends));
                c = home + p;
                return p;
            }
        }
        c = home + p;  // what a window that is no multiple of 16 has left
    }
#endif
    for (; p < probe && occ[c] && keys[c] != h; ++p)
        if (++c == capacity) c = 0;
    return p;
}

// The slot of a window with the oldest stamp, the first of equals on the way
// from the home slot → its stamp.
inline int64_t oldest_of_window(const int64_t* touch, uint64_t home,
                                uint32_t probe, uint64_t capacity,
                                uint64_t& best) {
    if (home + probe <= capacity && probe % 4 == 0) {
        const int64_t* w = touch + home;
        int64_t m[4] = {w[0], w[1], w[2], w[3]};
        // the least of four lanes, without a branch: stamps fall at random
        // along a window and a compare-and-jump a slot mispredicts a dozen
        // times a window
        for (uint32_t p = 4; p < probe; p += 4)
            for (int j = 0; j < 4; ++j)
                m[j] ^= (w[p + j] ^ m[j]) & -int64_t(w[p + j] < m[j]);
        const int64_t a = m[0] < m[1] ? m[0] : m[1];
        const int64_t b = m[2] < m[3] ? m[2] : m[3];
        const int64_t oldest = a < b ? a : b;
        uint32_t p = 0;
        while (w[p] != oldest) ++p;
        best = home + p;
        return oldest;
    }
    uint64_t c = home;
    best = c;
    int64_t oldest = touch[c];
    for (uint32_t p = 1; p < probe; ++p) {
        if (++c == capacity) c = 0;
        if (touch[c] < oldest) {
            oldest = touch[c];
            best = c;
        }
    }
    return oldest;
}

// (hash << 32 | index) ascending by hash: three stable passes over the
// hash's bits, 11, 11 and 10 of them
void sort_by_hash(std::vector<uint64_t>& a) {
    const size_t n = a.size();
    std::vector<uint64_t> b(n);
    std::vector<uint32_t> count(3 * 2048, 0);
    for (uint64_t v : a) {
        ++count[(v >> 32) & 2047];
        ++count[2048 + ((v >> 43) & 2047)];
        ++count[4096 + (v >> 54)];
    }
    for (int pass = 0; pass < 3; ++pass) {
        uint32_t* c = count.data() + 2048 * pass;
        uint32_t at = 0;
        for (int d = 0; d < 2048; ++d) {
            const uint32_t k = c[d];
            c[d] = at;
            at += k;
        }
        const int shift = 32 + 11 * pass;
        for (uint64_t v : a) b[c[(v >> shift) & 2047]++] = v;
        a.swap(b);
    }
}

}  // namespace

extern "C" {

// col: the first record's key (an f32), row_stride: bytes from one record's
// key to the next. out [n] uint32.
void fjt_state_hash_f32(const uint8_t* col, uint64_t n, int64_t row_stride,
                        uint32_t* out) {
    static const CrcTable<0xEDB88320u> table;
    const uint32_t* t = table.t;
    const uint32_t seed = t[(0xFFFFFFFFu ^ uint32_t('i')) & 0xFFu] ^
                          (0xFFFFFFFFu >> 8);
    for (uint64_t i = 0; i < n; ++i) {
        float f;
        std::memcpy(&f, col + int64_t(i) * row_stride, sizeof f);
        const int64_t k = f32_to_i64(f);
        const uint64_t u = static_cast<uint64_t>(k);
        const uint64_t mag = k < 0 ? ~u + 1 : u;
        const int bits = mag ? 64 - __builtin_clzll(mag) : 0;
        const int nbytes = bits / 8 + 1;  // 9 for -2^63 alone
        uint32_t c = seed;
        for (int b = 0; b < (nbytes < 8 ? nbytes : 8); ++b)
            c = t[(c ^ uint32_t(u >> (8 * b))) & 0xFFu] ^ (c >> 8);
        if (nbytes == 9)  // its sign-extension byte
            c = t[(c ^ 0xFFu) & 0xFFu] ^ (c >> 8);
        out[i] = c ^ 0xFFFFFFFFu;
    }
}

// khash [n]; apply [n] (0: the record bypasses the table and is skipped);
// keys/occ/touch [capacity]: the mirror; probe: the window; seq: this
// call's stamp. Writes slots[i] and touch for a hit, and the index of every
// record left to the caller into todo [n], ascending (its slots[i]
// untouched) → how many those are. *collided: the hits found past their
// home slot, counted once a key: a key's first hit of a call is the one
// that finds its slot not yet stamped seq.
uint64_t fjt_state_resolve(const uint32_t* khash, const uint8_t* apply,
                           uint64_t n, const uint32_t* keys,
                           const uint8_t* occ, int64_t* touch,
                           uint64_t capacity, uint32_t probe, int64_t seq,
                           int32_t* slots, int64_t* todo,
                           uint64_t* collided) {
    // a walk's answer on its way to the record's turn: the slot (-1: left
    // to the caller, -2: not applied) and whether it lies past the home slot
    struct Walked { int64_t slot; bool past_home; };
    constexpr uint64_t kRing = 64;  // a power of two past kRouteNear
    Walked ring[kRing];
    static_assert(kRouteNear < kRing && kRouteNear < kRouteAhead, "ring");
    const HomeOf home_slot(capacity);
    uint64_t n_todo = 0, n_collided = 0;
    for (uint64_t t = 0; t < n + kRouteNear; ++t) {
        const uint64_t far = t + (kRouteAhead - kRouteNear);
        if (far < n && apply[far]) {
            const uint64_t a = home_slot(khash[far]);
            __builtin_prefetch(keys + a);
            __builtin_prefetch(occ + a);
            __builtin_prefetch(touch + a, 1);
        }
        if (t < n) {
            Walked& w = ring[t % kRing];
            w.slot = -2;
            if (apply[t]) {
                uint64_t c;
                const uint32_t p = walk_window(
                    keys, occ, khash[t], home_slot(khash[t]),
                    probe, capacity, c);
                w.slot = -1;
                if (p < probe && occ[c]) {
                    w.slot = static_cast<int64_t>(c);
                    w.past_home = p != 0;
                    if (p) __builtin_prefetch(touch + c, 1);
                }
            }
        }
        if (t < kRouteNear) continue;
        const uint64_t i = t - kRouteNear;
        const Walked& w = ring[i % kRing];
        if (w.slot >= 0) {
            slots[i] = static_cast<int32_t>(w.slot);
            if (touch[w.slot] != seq) {
                touch[w.slot] = seq;
                n_collided += w.past_home;
            }
        } else if (w.slot == -1) {
            todo[n_todo++] = static_cast<int64_t>(i);
        }
    }
    *collided = n_collided;
    return n_todo;
}

// khash [n]: the records of one routing call that fjt_state_resolve left
// pending (or, had it not run, every applied record of the call), each to
// be answered with slots[i] (capacity: the scratch slot) and reset[i] (its
// key was given the slot by this call). The rounds and the eviction rule
// are KeyedStateTable._claim_rounds' (runtime/state.py), stated there and
// not here; what follows is how they are run.
//
// The unique hashes ascend, so "the smallest hash first" is "the first in
// the list". Round 0 looks at every key's home slot. A key off its home
// slot then has its window read once: a match before any empty slot is
// its slot (nothing before it can change: the rounds only fill empties); a
// window without either waits for the eviction; a key that sees an empty
// slot first joins the rounds at that slot's round, since the slots before
// it hold other keys and will. The rounds go a slot a round over the keys
// still probing, in ascending order, so an empty slot goes to the first to
// reach it and the others see it taken. The eviction rounds are two passes
// each: every key waiting names its slot from the stamps as the round found
// them, THEN the named slots are awarded in ascending order (an award
// stamps seq, which is how a later claimant of the round sees it gone).
// counts [4]: inserts, evictions, overflows, and the unique keys not
// resolved at their home slot.
void fjt_state_claim(const uint32_t* khash, uint64_t n, uint32_t* keys,
                     uint8_t* occ, int64_t* touch, uint64_t capacity,
                     uint32_t probe, int64_t seq, int32_t* slots,
                     uint8_t* reset, uint64_t* counts) {
    counts[0] = counts[1] = counts[2] = counts[3] = 0;
    if (!n) return;
    const HomeOf home_slot(capacity);
    std::vector<uint64_t> order(n);
    for (uint64_t i = 0; i < n; ++i) order[i] = uint64_t(khash[i]) << 32 | i;
    sort_by_hash(order);
    // the unique keys, ascending: hash, where its records start in `order`,
    // and its answer (-1: none yet)
    std::vector<uint32_t> uk, first;
    for (uint64_t j = 0; j < n; ++j)
        if (!j || order[j] >> 32 != order[j - 1] >> 32) {
            uk.push_back(uint32_t(order[j] >> 32));
            first.push_back(uint32_t(j));
        }
    const size_t m = uk.size();
    first.push_back(uint32_t(n));
    std::vector<int64_t> slot_u(m, -1);
    std::vector<uint8_t> reset_u(m, 0);

    auto claim_empty = [&](size_t u, uint64_t c) {
        occ[c] = 1;
        keys[c] = uk[u];
        touch[c] = seq;
        slot_u[u] = int64_t(c);
        reset_u[u] = 1;
        ++counts[0];
    };

    // round 0: the home slots
    std::vector<uint32_t> rest;
    for (size_t u = 0; u < m; ++u) {
        const uint64_t c = home_slot(uk[u]);
        if (!occ[c]) {
            claim_empty(u, c);
        } else if (keys[c] == uk[u]) {
            slot_u[u] = int64_t(c);
            touch[c] = seq;
        } else {
            rest.push_back(uint32_t(u));
        }
    }
    counts[3] = rest.size();

    // the windows of the keys off their home slot, each read once
    std::vector<uint32_t> probing, round_of;  // a key and the round it joins
    for (uint32_t u : rest) {
        uint64_t c;
        const uint32_t p = walk_window(
            keys, occ, uk[u], home_slot(uk[u]), probe, capacity, c);
        if (p == probe) continue;  // waits for the eviction
        if (occ[c]) {
            slot_u[u] = int64_t(c);
            touch[c] = seq;
        } else {
            probing.push_back(u);
            round_of.push_back(p);
        }
    }

    // the rounds, a slot a round
    for (uint32_t p = 1; p < probe && !probing.empty(); ++p) {
        size_t kept = 0;
        for (size_t k = 0; k < probing.size(); ++k) {
            const uint32_t u = probing[k];
            bool go_on = true;
            if (round_of[k] <= p) {
                const uint64_t c = (home_slot(uk[u]) + p) % capacity;
                if (!occ[c]) {
                    claim_empty(u, c);
                    go_on = false;
                } else if (keys[c] == uk[u]) {
                    slot_u[u] = int64_t(c);
                    touch[c] = seq;
                    go_on = false;
                }
            }
            if (go_on) {
                probing[kept] = u;
                round_of[kept++] = round_of[k];
            }
        }
        probing.resize(kept);
        round_of.resize(kept);
    }

    // the eviction: whoever has no slot yet, ascending
    std::vector<uint32_t> waiting;
    for (uint32_t u : rest)
        if (slot_u[u] < 0) waiting.push_back(u);
    std::vector<uint64_t> named(waiting.size());
    while (!waiting.empty()) {
        size_t w = 0;
        for (uint32_t u : waiting) {
            // 1: the oldest stamp, the first of equals in probe order
            uint64_t best;
            const int64_t oldest = oldest_of_window(
                touch, home_slot(uk[u]), probe, capacity, best);
            if (oldest < seq) {
                waiting[w] = u;
                named[w++] = best;
            } else {
                ++counts[2];  // 4: the whole window is this call's
            }
        }
        waiting.resize(w);
        size_t lost = 0;
        for (size_t k = 0; k < w; ++k) {
            const uint32_t u = waiting[k];
            const uint64_t c = named[k];
            if (touch[c] != seq) {  // 2: the first to name it
                keys[c] = uk[u];
                touch[c] = seq;
                slot_u[u] = int64_t(c);
                reset_u[u] = 1;
                ++counts[1];
            } else {
                waiting[lost++] = u;  // 3: names again
            }
        }
        waiting.resize(lost);
    }

    for (size_t u = 0; u < m; ++u) {
        const int32_t s = int32_t(slot_u[u] < 0 ? int64_t(capacity) : slot_u[u]);
        for (uint32_t j = first[u]; j < first[u + 1]; ++j) {
            const uint32_t i = uint32_t(order[j]);
            slots[i] = s;
            reset[i] = reset_u[u];
        }
    }
}

}  // extern "C"
