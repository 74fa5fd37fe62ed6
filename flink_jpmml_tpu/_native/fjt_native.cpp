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
// Build: g++ -O3 -march=native -shared -fPIC -o libfjt_native.so fjt_native.cpp -lpthread
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
// exactly value_len bytes) at memory speed and mirrors the Python
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

inline uint32_t crc32c_buf(const uint8_t* p, int64_t n) {
    static const CrcTable<0x82F63B78u> table;
    uint32_t c = 0xFFFFFFFFu;
    for (int64_t i = 0; i < n; ++i)
        c = (c >> 8) ^ table.t[(c ^ p[i]) & 0xFFu];
    return c ^ 0xFFFFFFFFu;
}

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

}  // extern "C"

// ---------------------------------------------------------------------------
// Keyed-state routing (runtime/state.py KeyedStateTable.route's fast path).
//
// The host mirror of the state table is three flat arrays (uint32 key
// hashes, occupancy bytes, int64 LRU stamps), hundreds of millions of
// entries in a deployment, and every record of every batch has to find its
// key's slot in them. Two passes, one call each, both bit-exact twins of the
// numpy code they stand in for (which stays as the fallback and the tests'
// oracle):
//
//  - fjt_state_hash_f32: the key column of a raw f32 block → the uint32
//    stable hash, parallel/partitioner.py stable_hash_vec of the column cast
//    to int64: CRC32 (zlib's) over b"i" + the key's low
//    abs(key).bit_length()//8 + 1 bytes, little-endian two's complement;
//  - fjt_state_resolve: per record, in arrival order, walk the probe window
//    from hash % capacity and stop at the first slot that matches (a hit:
//    write the slot, stamp it) or is empty, or at the window's end (both:
//    the record is left pending for the caller's claim/evict rounds). It
//    writes neither keys nor occupancy, so what it resolves no claim or
//    eviction of the same call can change.
//
// Every probe is a cache miss in a table of that size; the walk prefetches
// the home slots of the records a few places ahead.
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

constexpr uint64_t kRouteAhead = 16;  // records prefetched ahead of the walk

// hash % capacity, as a 32-bit division wherever the capacity allows one
inline uint64_t home_slot(uint32_t h, uint64_t capacity) {
    return capacity >> 32 ? h : h % static_cast<uint32_t>(capacity);
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
// call's stamp. Writes slots[i] and touch for a hit, pending[i] = 1 for a
// record left to the caller (slots[i] untouched), 0 otherwise. → the hits
// found past their home slot, counted once a key: a key's first hit of a
// call is the one that finds its slot not yet stamped seq.
uint64_t fjt_state_resolve(const uint32_t* khash, const uint8_t* apply,
                           uint64_t n, const uint32_t* keys,
                           const uint8_t* occ, int64_t* touch,
                           uint64_t capacity, uint32_t probe, int64_t seq,
                           int32_t* slots, uint8_t* pending) {
    uint64_t collided = 0;
    for (uint64_t i = 0; i < n; ++i) {
        if (i + kRouteAhead < n) {
            const uint64_t a = home_slot(khash[i + kRouteAhead], capacity);
            __builtin_prefetch(occ + a);
            __builtin_prefetch(keys + a);
            __builtin_prefetch(touch + a, 1);
        }
        pending[i] = 0;
        if (!apply[i]) continue;
        const uint32_t h = khash[i];
        uint64_t c = home_slot(h, capacity);
        uint32_t p = 0;
        for (; p < probe && occ[c] && keys[c] != h; ++p)
            if (++c == capacity) c = 0;
        if (p < probe && occ[c]) {
            slots[i] = static_cast<int32_t>(c);
            if (touch[c] != seq) {
                touch[c] = seq;
                collided += p != 0;
            }
        } else {
            pending[i] = 1;
        }
    }
    return collided;
}

}  // extern "C"
