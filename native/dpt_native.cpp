// Native host data-plane + transport for distributed_plonk_tpu.
//
// Plays the role of the reference's native host components:
//   - zero-copy workload serialization (/root/reference/src/utils.rs:27-43)
//     -> here an explicit, layout-documented limb codec (no unsafe
//        transmutes: the wire format is defined, not accidental)
//   - CPU transpose kernels (/root/reference/src/transpose.rs)
//     -> blocked uint32 transpose for host-side panel reassembly
//   - Cap'n Proto two-party TCP RPC (/root/reference/src/worker.rs:441-536)
//     -> a minimal length-prefixed framed message transport (TCP_NODELAY),
//        enough to express the dispatcher<->worker control plane; bulk
//        data rides the same frames
// and one piece of the witness build: a Rescue permutation's trace in
// Montgomery arithmetic, over constants the caller hands in.
//
// Exposed as a C ABI for ctypes (no pybind11 in this environment).
//
// Wire format: frame = [u64 payload_len (LE)][u32 tag (LE)][payload bytes].

#include <cstdint>
#include <cstring>
#include <cstdio>
#include <cerrno>

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/time.h>
#include <unistd.h>

extern "C" {

// --- limb codec --------------------------------------------------------------
// elements: n little-endian byte strings of elem_bytes each, concatenated.
// limbs: uint32 matrix, leading-limb layout (n_limbs, n) row-major, 16-bit
// limbs (the device layout, see distributed_plonk_tpu/backend/limbs.py).

void le_bytes_to_limbs(const uint8_t* in, uint64_t n, uint64_t elem_bytes,
                       uint32_t* out) {
    const uint64_t n_limbs = elem_bytes / 2;
    for (uint64_t i = 0; i < n; ++i) {
        const uint8_t* e = in + i * elem_bytes;
        for (uint64_t l = 0; l < n_limbs; ++l) {
            out[l * n + i] =
                (uint32_t)e[2 * l] | ((uint32_t)e[2 * l + 1] << 8);
        }
    }
}

// returns 0 on success, -1 if any limb value exceeds 16 bits (unreduced
// kernel output -- the same guard limbs.py applies at the oracle boundary)
int limbs_to_le_bytes(const uint32_t* in, uint64_t n, uint64_t elem_bytes,
                      uint8_t* out) {
    const uint64_t n_limbs = elem_bytes / 2;
    for (uint64_t l = 0; l < n_limbs; ++l) {
        const uint32_t* row = in + l * n;
        for (uint64_t i = 0; i < n; ++i) {
            uint32_t v = row[i];
            if (v > 0xFFFFu) return -1;
            out[i * elem_bytes + 2 * l] = (uint8_t)(v & 0xFF);
            out[i * elem_bytes + 2 * l + 1] = (uint8_t)(v >> 8);
        }
    }
    return 0;
}

// --- blocked transpose -------------------------------------------------------
// (rows, cols) -> (cols, rows), 64x64 tiles (cache-friendly; the reference's
// oop_transpose_medium plays this role, transpose.rs:110-198)

void transpose_u32(const uint32_t* in, uint64_t rows, uint64_t cols,
                   uint32_t* out) {
    const uint64_t T = 64;
    for (uint64_t r0 = 0; r0 < rows; r0 += T) {
        const uint64_t r1 = r0 + T < rows ? r0 + T : rows;
        for (uint64_t c0 = 0; c0 < cols; c0 += T) {
            const uint64_t c1 = c0 + T < cols ? c0 + T : cols;
            for (uint64_t r = r0; r < r1; ++r)
                for (uint64_t c = c0; c < c1; ++c)
                    out[c * rows + r] = in[r * cols + c];
        }
    }
}

// --- framed TCP transport ----------------------------------------------------

static int read_exact(int fd, uint8_t* buf, uint64_t len) {
    uint64_t got = 0;
    while (got < len) {
        ssize_t k = read(fd, buf + got, len - got);
        if (k <= 0) {
            if (k < 0 && errno == EINTR) continue;
            return -1;
        }
        got += (uint64_t)k;
    }
    return 0;
}

static int write_exact(int fd, const uint8_t* buf, uint64_t len) {
    uint64_t put = 0;
    while (put < len) {
        ssize_t k = write(fd, buf + put, len - put);
        if (k <= 0) {
            if (k < 0 && errno == EINTR) continue;
            return -1;
        }
        put += (uint64_t)k;
    }
    return 0;
}

// listener: returns listening fd or -1
int dpt_listen(const char* host, int port, int backlog) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr;
    memset(&addr, 0, sizeof(addr));
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) { close(fd); return -1; }
    if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0) { close(fd); return -1; }
    if (listen(fd, backlog) != 0) { close(fd); return -1; }
    return fd;
}

int dpt_accept(int listen_fd) {
    int fd = accept(listen_fd, nullptr, nullptr);
    if (fd < 0) return -1;
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    return fd;
}

// timeout_ms <= 0: blocking connect (OS default, ~2 min on a dropped
// SYN). > 0: non-blocking connect + poll, so a partitioned/firewalled
// peer costs a bounded wait instead of stalling the caller (the store
// peer-fetch tier runs under the scheduler's bucket lock).
int dpt_connect(const char* host, int port, int timeout_ms) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    sockaddr_in addr;
    memset(&addr, 0, sizeof(addr));
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) { close(fd); return -1; }
    if (timeout_ms <= 0) {
        if (connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) { close(fd); return -1; }
    } else {
        int flags = fcntl(fd, F_GETFL, 0);
        fcntl(fd, F_SETFL, flags | O_NONBLOCK);
        if (connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
            if (errno != EINPROGRESS) { close(fd); return -1; }
            pollfd p;
            p.fd = fd;
            p.events = POLLOUT;
            // retry on EINTR with the remaining budget: an interrupted
            // dial is not an unreachable peer (a spurious -1 here would
            // feed probe() a false death report)
            int remaining = timeout_ms;
            struct timeval tv0;
            gettimeofday(&tv0, nullptr);
            int rc;
            for (;;) {
                rc = poll(&p, 1, remaining);
                if (rc >= 0 || errno != EINTR) break;
                struct timeval tv1;
                gettimeofday(&tv1, nullptr);
                int elapsed = (int)((tv1.tv_sec - tv0.tv_sec) * 1000 +
                                    (tv1.tv_usec - tv0.tv_usec) / 1000);
                remaining = timeout_ms - elapsed;
                if (remaining <= 0) { rc = 0; break; }
            }
            if (rc <= 0) { close(fd); return -1; }
            int err = 0;
            socklen_t elen = sizeof(err);
            if (getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &elen) != 0 ||
                err != 0) { close(fd); return -1; }
        }
        fcntl(fd, F_SETFL, flags);
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    return fd;
}

// send one frame; returns 0 / -1
int dpt_send(int fd, uint32_t tag, const uint8_t* payload, uint64_t len) {
    uint8_t hdr[12];
    memcpy(hdr, &len, 8);
    memcpy(hdr + 8, &tag, 4);
    if (write_exact(fd, hdr, 12) != 0) return -1;
    if (len && write_exact(fd, payload, len) != 0) return -1;
    return 0;
}

// peek the next frame header; returns 0 and fills len/tag, or -1
int dpt_recv_header(int fd, uint64_t* len, uint32_t* tag) {
    uint8_t hdr[12];
    if (read_exact(fd, hdr, 12) != 0) return -1;
    memcpy(len, hdr, 8);
    memcpy(tag, hdr + 8, 4);
    return 0;
}

// read the payload announced by dpt_recv_header into caller buffer
int dpt_recv_payload(int fd, uint8_t* buf, uint64_t len) {
    return read_exact(fd, buf, len);
}

// receive/send timeout in milliseconds (0 = blocking forever); after a
// timeout fires mid-frame the stream is unsynchronized, so callers must
// treat it as fatal for the connection (reconnect) — returns 0 / -1
int dpt_set_timeout(int fd, int ms) {
    timeval tv;
    tv.tv_sec = ms / 1000;
    tv.tv_usec = (ms % 1000) * 1000;
    if (setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv)) != 0) return -1;
    if (setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv)) != 0) return -1;
    return 0;
}

int dpt_close(int fd) { return close(fd); }

// --- Rescue permutation trace ------------------------------------------------
// One Rescue-Prime permutation of a width-4 state, recorded as the values
// rescue.permutation_gadget creates, in its order (the Python oracle is
// circuits/merkle_witness.py::permutation_trace): the key-0 injection, then
// per round the forward half-round's outputs, the inverse S-box's roots and
// the affine layer's outputs, 4 + 12 x rounds values. Every constant comes
// from the caller: the odd modulus p < 2^255, the 2 x rounds + 1 round keys
// (4 each), the 4x4 MDS matrix row-major, and the two S-box exponents. An
// element is a 32-byte little-endian residue, canonical (< p) on the way in
// and out; inside, 4 x 64-bit limbs in Montgomery form. Stateless, so
// threads may call it at once. Returns 0, or -1 where the modulus is not
// odd and below 2^255 or an input element is not below it.

static const int kLimbs = 4;   // 64-bit limbs of an element
static const int kWidth = 4;   // state width

typedef unsigned __int128 u128;

static const uint64_t kOne[kLimbs] = {1, 0, 0, 0};

struct Field {
    uint64_t p[kLimbs];
    uint64_t n0;               // -p^-1 mod 2^64
    uint64_t r2[kLimbs];       // 2^512 mod p: into Montgomery form
};

static void load_le(uint64_t* x, const uint8_t* b) {
    for (int i = 0; i < kLimbs; ++i) {
        x[i] = 0;
        for (int k = 7; k >= 0; --k) x[i] = (x[i] << 8) | b[8 * i + k];
    }
}

static void store_le(uint8_t* b, const uint64_t* x) {
    for (int i = 0; i < kLimbs; ++i)
        for (int k = 0; k < 8; ++k) b[8 * i + k] = (uint8_t)(x[i] >> (8 * k));
}

static bool geq(const uint64_t* a, const uint64_t* b) {
    for (int i = kLimbs - 1; i >= 0; --i)
        if (a[i] != b[i]) return a[i] > b[i];
    return true;
}

static void sub_in_place(uint64_t* a, const uint64_t* b) {
    uint64_t borrow = 0;
    for (int i = 0; i < kLimbs; ++i) {
        u128 d = (u128)a[i] - b[i] - borrow;
        a[i] = (uint64_t)d;
        borrow = (uint64_t)(d >> 64) & 1;
    }
}

// a + b mod p; a, b < p < 2^255, so the sum has no carry out of 256 bits
static void add_mod(uint64_t* out, const uint64_t* a, const uint64_t* b,
                    const Field& f) {
    uint64_t carry = 0;
    for (int i = 0; i < kLimbs; ++i) {
        u128 s = (u128)a[i] + b[i] + carry;
        out[i] = (uint64_t)s;
        carry = (uint64_t)(s >> 64);
    }
    if (geq(out, f.p)) sub_in_place(out, f.p);
}

// a * b / 2^256 mod p (CIOS); out may alias a or b
static void mont_mul(uint64_t* out, const uint64_t* a, const uint64_t* b,
                     const Field& f) {
    uint64_t t[kLimbs + 2] = {0, 0, 0, 0, 0, 0};
    for (int i = 0; i < kLimbs; ++i) {
        uint64_t c = 0;
        for (int j = 0; j < kLimbs; ++j) {
            u128 s = (u128)a[j] * b[i] + t[j] + c;
            t[j] = (uint64_t)s;
            c = (uint64_t)(s >> 64);
        }
        u128 s = (u128)t[kLimbs] + c;
        t[kLimbs] = (uint64_t)s;
        t[kLimbs + 1] = (uint64_t)(s >> 64);
        const uint64_t m = t[0] * f.n0;
        s = (u128)m * f.p[0] + t[0];
        c = (uint64_t)(s >> 64);
        for (int j = 1; j < kLimbs; ++j) {
            s = (u128)m * f.p[j] + t[j] + c;
            t[j - 1] = (uint64_t)s;
            c = (uint64_t)(s >> 64);
        }
        s = (u128)t[kLimbs] + c;
        t[kLimbs - 1] = (uint64_t)s;
        t[kLimbs] = t[kLimbs + 1] + (uint64_t)(s >> 64);
    }
    if (t[kLimbs] || geq(t, f.p)) sub_in_place(t, f.p);   // t < 2p
    memcpy(out, t, sizeof(uint64_t) * kLimbs);
}

static bool field_init(Field& f, const uint8_t* modulus) {
    load_le(f.p, modulus);
    if (!(f.p[0] & 1) || (f.p[kLimbs - 1] >> 63)) return false;
    uint64_t inv = 1;                          // Newton: 1, 2, 4 ... 64 bits
    for (int i = 0; i < 6; ++i) inv *= 2 - f.p[0] * inv;
    f.n0 = (uint64_t)0 - inv;
    uint64_t x[kLimbs];
    memcpy(x, kOne, sizeof(x));
    for (int i = 0; i < 512; ++i) {            // 2^512 mod p by doubling
        uint64_t top = 0;
        for (int j = 0; j < kLimbs; ++j) {
            const uint64_t out = x[j] >> 63;
            x[j] = (x[j] << 1) | top;
            top = out;
        }
        if (geq(x, f.p)) sub_in_place(x, f.p);
    }
    memcpy(f.r2, x, sizeof(x));
    return true;
}

// a canonical element from its bytes into Montgomery form; false if >= p
static bool load_mont(uint64_t* x, const uint8_t* b, const Field& f) {
    load_le(x, b);
    if (geq(x, f.p)) return false;
    mont_mul(x, x, f.r2, f);
    return true;
}

static void store_canonical(uint8_t* b, const uint64_t* x, const Field& f) {
    uint64_t y[kLimbs];
    mont_mul(y, x, kOne, f);
    store_le(b, y);
}

// x^e, x in Montgomery form, e a 256-bit little-endian exponent
static void mont_pow(uint64_t* out, const uint64_t* x, const uint64_t* e,
                     const uint64_t* one_m, const Field& f) {
    int top = 64 * kLimbs - 1;
    while (top >= 0 && !((e[top / 64] >> (top % 64)) & 1)) --top;
    uint64_t acc[kLimbs];
    memcpy(acc, one_m, sizeof(acc));
    for (int i = top; i >= 0; --i) {
        mont_mul(acc, acc, acc, f);
        if ((e[i / 64] >> (i % 64)) & 1) mont_mul(acc, acc, x, f);
    }
    memcpy(out, acc, sizeof(acc));
}

int rescue_trace(const uint8_t* modulus, const uint8_t* round_keys,
                 const uint8_t* mds, const uint8_t* alpha,
                 const uint8_t* alpha_inv, uint64_t rounds,
                 const uint8_t* state, uint8_t* trace) {
    Field f;
    if (!field_init(f, modulus)) return -1;
    uint64_t one_m[kLimbs], e_fwd[kLimbs], e_inv[kLimbs];
    mont_mul(one_m, kOne, f.r2, f);
    load_le(e_fwd, alpha);
    load_le(e_inv, alpha_inv);
    const int el = 8 * kLimbs;                 // bytes of an element
    uint64_t m[kWidth][kWidth][kLimbs];
    for (int i = 0; i < kWidth; ++i)
        for (int j = 0; j < kWidth; ++j)
            if (!load_mont(m[i][j], mds + (i * kWidth + j) * el, f)) return -1;
    uint64_t s[kWidth][kLimbs], t[kWidth][kLimbs], key[kLimbs];
    uint64_t emitted = 0;
    for (int i = 0; i < kWidth; ++i) {
        if (!load_mont(s[i], state + i * el, f)) return -1;
        if (!load_mont(key, round_keys + i * el, f)) return -1;
        add_mod(s[i], s[i], key, f);
        store_canonical(trace + el * emitted++, s[i], f);
    }
    // out = MDS x in + the key row k; false if a key is not below p
    auto affine = [&](uint64_t (*out)[kLimbs], uint64_t (*in)[kLimbs],
                      uint64_t k) -> bool {
        for (int i = 0; i < kWidth; ++i) {
            if (!load_mont(out[i], round_keys + (k * kWidth + i) * el, f))
                return false;
            for (int j = 0; j < kWidth; ++j) {
                uint64_t prod[kLimbs];
                mont_mul(prod, m[i][j], in[j], f);
                add_mod(out[i], out[i], prod, f);
            }
            store_canonical(trace + el * emitted++, out[i], f);
        }
        return true;
    };
    for (uint64_t r = 0; r < rounds; ++r) {
        for (int i = 0; i < kWidth; ++i) mont_pow(t[i], s[i], e_fwd, one_m, f);
        if (!affine(s, t, 2 * r + 1)) return -1;
        for (int i = 0; i < kWidth; ++i) {
            mont_pow(t[i], s[i], e_inv, one_m, f);
            store_canonical(trace + el * emitted++, t[i], f);
        }
        if (!affine(s, t, 2 * r + 2)) return -1;
    }
    return 0;
}

}  // extern "C"
