// Validator keys for the signed block cell: the pubkeys of the secret keys
// sk0, sk0 + 1, ..., sk0 + n - 1.  Each thread takes a range: one scalar
// multiplication for its first key, then one addition of G1's generator
// per key, the affine conversions batched (one inversion per 1,024 keys).
// Built on the frozen reference's field and curve code, included here.
#include "../reference/bls12_381.cpp"

#include <algorithm>

static void consecutive_pks(const u8* sk32, size_t lo, size_t n, u8* out48) {
    // start = (sk0 + lo) * G, the addition done on the big-endian scalar
    u8 k[32];
    memcpy(k, sk32, 32);
    u64 carry = lo;
    for (int i = 31; i >= 0 && carry; i--) {
        u64 s = (u64)k[i] + (carry & 0xff);
        k[i] = (u8)s;
        carry = (carry >> 8) + (s >> 8);
    }
    G1 p;
    g1_mul(p, G1_GEN, k, 32);
    const size_t CH = 1024;
    std::vector<G1> pts(CH);
    std::vector<Fp> pre(CH);
    for (size_t at = 0; at < n; at += CH) {
        size_t m = std::min(CH, n - at);
        Fp acc = FP_ONE_M;
        for (size_t i = 0; i < m; i++) {
            pts[i] = p;
            pre[i] = acc;
            fp_mul(acc, acc, p.z);
            g1_add(p, p, G1_GEN);
        }
        Fp inv;
        fp_inv(inv, acc);
        for (size_t i = m; i-- > 0;) {
            Fp zi, zi2, x, y, xp;
            fp_mul(zi, inv, pre[i]);
            fp_mul(inv, inv, pts[i].z);
            fp_sqr(zi2, zi);
            fp_mul(x, pts[i].x, zi2);
            fp_mul(zi2, zi2, zi);
            fp_mul(y, pts[i].y, zi2);
            u8* o = out48 + 48 * (at + i);
            fp_from_mont(xp, x);
            fp_to_be(o, xp);
            o[0] |= 0x80;
            if (fp_lex_larger(y)) o[0] |= 0x20;
        }
    }
}

extern "C" int bench_consecutive_pks(const u8* sk32, size_t n, u8* out48,
                                     size_t threads) {
    ensure_init();
    if (threads < 1) threads = 1;
    size_t step = (n + threads - 1) / threads;
    std::vector<std::thread> th;
    for (size_t lo = 0; lo < n; lo += step)
        th.emplace_back(consecutive_pks, sk32, lo, std::min(step, n - lo),
                        out48 + 48 * lo);
    for (auto& t : th) t.join();
    return 0;
}
