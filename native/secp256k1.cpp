// secp256k1 ECDSA: sign / recover / verify — native host implementation.
//
// Role parity: the reference links bitcoin-core's C libsecp256k1 via cgo
// (crypto/secp256k1/secp256.go:20-37).  This is an independent C++
// implementation (4x64-bit limbs, __int128 accumulation, pseudo-Mersenne
// delta-folding for both moduli, Fermat inversion, RFC6979 nonces) —
// written for the host control plane; the batched TPU kernels carry the
// throughput path.  Cross-checked against the Python golden model by the
// test-suite.

#include <cstdint>
#include <cstring>
#ifdef _OPENMP
#include <omp.h>
#endif

typedef unsigned __int128 u128;

namespace {

struct U256 {
  uint64_t v[4];  // little-endian limbs
};

constexpr U256 ZERO{{0, 0, 0, 0}};
constexpr U256 ONE{{1, 0, 0, 0}};

// P = 2^256 - 2^32 - 977
constexpr U256 P{{0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                  0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL}};
constexpr U256 P_DELTA{{0x00000001000003D1ULL, 0, 0, 0}};  // 2^256 - P
// N (group order)
constexpr U256 N{{0xBFD25E8CD0364141ULL, 0xBAAEDCE6AF48A03BULL,
                  0xFFFFFFFFFFFFFFFEULL, 0xFFFFFFFFFFFFFFFFULL}};
constexpr U256 N_DELTA{{0x402DA1732FC9BEBFULL, 0x4551231950B75FC4ULL,
                        1, 0}};  // 2^256 - N
constexpr U256 GX{{0x59F2815B16F81798ULL, 0x029BFCDB2DCE28D9ULL,
                   0x55A06295CE870B07ULL, 0x79BE667EF9DCBBACULL}};
constexpr U256 GY{{0x9C47D08FFB10D4B8ULL, 0xFD17B448A6855419ULL,
                   0x5DA4FBFC0E1108A8ULL, 0x483ADA7726A3C465ULL}};

bool is_zero(const U256& a) { return !(a.v[0] | a.v[1] | a.v[2] | a.v[3]); }

int cmp(const U256& a, const U256& b) {
  for (int i = 3; i >= 0; i--) {
    if (a.v[i] < b.v[i]) return -1;
    if (a.v[i] > b.v[i]) return 1;
  }
  return 0;
}

// a += b, returns carry
uint64_t add_carry(U256& a, const U256& b) {
  u128 c = 0;
  for (int i = 0; i < 4; i++) {
    c += (u128)a.v[i] + b.v[i];
    a.v[i] = (uint64_t)c;
    c >>= 64;
  }
  return (uint64_t)c;
}

// a -= b, returns borrow
uint64_t sub_borrow(U256& a, const U256& b) {
  u128 br = 0;
  for (int i = 0; i < 4; i++) {
    u128 t = (u128)a.v[i] - b.v[i] - br;
    a.v[i] = (uint64_t)t;
    br = (t >> 64) & 1;
  }
  return (uint64_t)br;
}

struct U512 {
  uint64_t v[8];
};

U512 mul_wide(const U256& a, const U256& b) {
  U512 r{};
  for (int i = 0; i < 4; i++) {
    u128 carry = 0;
    for (int j = 0; j < 4; j++) {
      u128 t = (u128)a.v[i] * b.v[j] + r.v[i + j] + carry;
      r.v[i + j] = (uint64_t)t;
      carry = t >> 64;
    }
    r.v[i + 4] += (uint64_t)carry;
  }
  return r;
}

// reduce a 512-bit value mod m = 2^256 - delta (delta < 2^129)
U256 reduce_wide(U512 w, const U256& delta, const U256& m) {
  // repeat: value = lo + hi * delta
  for (int iter = 0; iter < 6; iter++) {
    U256 lo{{w.v[0], w.v[1], w.v[2], w.v[3]}};
    U256 hi{{w.v[4], w.v[5], w.v[6], w.v[7]}};
    if (is_zero(hi)) {
      w = U512{{lo.v[0], lo.v[1], lo.v[2], lo.v[3], 0, 0, 0, 0}};
      break;
    }
    U512 prod = mul_wide(hi, delta);
    // w = lo + prod
    u128 c = 0;
    for (int i = 0; i < 8; i++) {
      c += (u128)prod.v[i] + (i < 4 ? lo.v[i] : 0);
      w.v[i] = (uint64_t)c;
      c >>= 64;
    }
  }
  U256 r{{w.v[0], w.v[1], w.v[2], w.v[3]}};
  while (cmp(r, m) >= 0) sub_borrow(r, m);
  return r;
}

struct Mod {
  U256 m, delta;

  U256 add(const U256& a, const U256& b) const {
    U256 r = a;
    uint64_t carry = add_carry(r, b);
    if (carry) {  // r = r + 2^256 ≡ r + delta
      U256 t = r;
      uint64_t c2 = add_carry(t, delta);
      (void)c2;
      r = t;
    }
    while (cmp(r, m) >= 0) sub_borrow(r, m);
    return r;
  }

  U256 sub(const U256& a, const U256& b) const {
    U256 r = a;
    if (sub_borrow(r, b)) {
      U256 t = r;
      sub_borrow(t, delta);  // r - 2^256 ≡ r - delta... careful: borrow means
      // r = a - b + 2^256; mod m subtract (2^256 - m) = delta
      r = t;
      while (cmp(r, m) >= 0) sub_borrow(r, m);
    }
    return r;
  }

  U256 mul(const U256& a, const U256& b) const {
    return reduce_wide(mul_wide(a, b), delta, m);
  }

  U256 sqr(const U256& a) const { return mul(a, a); }

  U256 pow(const U256& a, const U256& e) const {
    U256 result = ONE, base = a;
    for (int limb = 0; limb < 4; limb++) {
      uint64_t bits = e.v[limb];
      for (int i = 0; i < 64; i++) {
        if (bits & 1) result = mul(result, base);
        base = sqr(base);
        bits >>= 1;
      }
    }
    return result;
  }

  U256 inv(const U256& a) const {
    U256 e = m;
    U256 two{{2, 0, 0, 0}};
    sub_borrow(e, two);
    return pow(a, e);
  }
};

constexpr Mod FP_{P, P_DELTA};
constexpr Mod FN_{N, N_DELTA};

// ---- Jacobian point arithmetic over FP ----

struct Pt {
  U256 x, y, z;  // z == 0 => infinity
};

Pt pt_double(const Pt& p) {
  if (is_zero(p.z) || is_zero(p.y)) return Pt{ZERO, ONE, ZERO};
  U256 a = FP_.sqr(p.x);
  U256 b = FP_.sqr(p.y);
  U256 c = FP_.sqr(b);
  U256 t = FP_.sqr(FP_.add(p.x, b));
  U256 d = FP_.sub(FP_.sub(t, a), c);
  d = FP_.add(d, d);
  U256 e = FP_.add(FP_.add(a, a), a);
  U256 f = FP_.sqr(e);
  U256 x3 = FP_.sub(f, FP_.add(d, d));
  U256 c8 = FP_.add(c, c); c8 = FP_.add(c8, c8); c8 = FP_.add(c8, c8);
  U256 y3 = FP_.sub(FP_.mul(e, FP_.sub(d, x3)), c8);
  U256 z3 = FP_.mul(p.y, p.z);
  z3 = FP_.add(z3, z3);
  return Pt{x3, y3, z3};
}

Pt pt_add(const Pt& p, const Pt& q) {
  if (is_zero(p.z)) return q;
  if (is_zero(q.z)) return p;
  U256 z1z1 = FP_.sqr(p.z);
  U256 z2z2 = FP_.sqr(q.z);
  U256 u1 = FP_.mul(p.x, z2z2);
  U256 u2 = FP_.mul(q.x, z1z1);
  U256 s1 = FP_.mul(FP_.mul(p.y, q.z), z2z2);
  U256 s2 = FP_.mul(FP_.mul(q.y, p.z), z1z1);
  if (cmp(u1, u2) == 0) {
    if (cmp(s1, s2) == 0) return pt_double(p);
    return Pt{ZERO, ONE, ZERO};
  }
  U256 h = FP_.sub(u2, u1);
  U256 r = FP_.sub(s2, s1);
  U256 hh = FP_.sqr(h);
  U256 hhh = FP_.mul(hh, h);
  U256 v = FP_.mul(u1, hh);
  U256 x3 = FP_.sub(FP_.sub(FP_.sqr(r), hhh), FP_.add(v, v));
  U256 y3 = FP_.sub(FP_.mul(r, FP_.sub(v, x3)), FP_.mul(s1, hhh));
  U256 z3 = FP_.mul(FP_.mul(p.z, q.z), h);
  return Pt{x3, y3, z3};
}

Pt pt_mul(const U256& k, const Pt& p) {
  Pt acc{ZERO, ONE, ZERO};
  for (int limb = 3; limb >= 0; limb--) {
    for (int i = 63; i >= 0; i--) {
      acc = pt_double(acc);
      if ((k.v[limb] >> i) & 1) acc = pt_add(acc, p);
    }
  }
  return acc;
}

void pt_affine(const Pt& p, U256& x, U256& y) {
  U256 zi = FP_.inv(p.z);
  U256 zi2 = FP_.sqr(zi);
  x = FP_.mul(p.x, zi2);
  y = FP_.mul(p.y, FP_.mul(zi, zi2));
}

// ---- byte conversions (big-endian 32) ----

U256 from_be(const uint8_t* b) {
  U256 r;
  for (int i = 0; i < 4; i++) {
    uint64_t limb = 0;
    for (int j = 0; j < 8; j++) limb = (limb << 8) | b[8 * i + j];
    r.v[3 - i] = limb;
  }
  return r;
}

void to_be(const U256& a, uint8_t* b) {
  for (int i = 0; i < 4; i++) {
    uint64_t limb = a.v[3 - i];
    for (int j = 7; j >= 0; j--) {
      b[8 * i + j] = (uint8_t)limb;
      limb >>= 8;
    }
  }
}

// ---- SHA-256 + HMAC (for RFC6979 nonces) ----

struct Sha256 {
  uint32_t h[8];
  uint8_t buf[64];
  uint64_t len = 0;
  size_t buflen = 0;

  Sha256() {
    static const uint32_t init[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                     0xa54ff53a, 0x510e527f, 0x9b05688c,
                                     0x1f83d9ab, 0x5be0cd19};
    std::memcpy(h, init, sizeof(h));
  }

  static uint32_t rotr(uint32_t x, int r) { return (x >> r) | (x << (32 - r)); }

  void block(const uint8_t* p) {
    static const uint32_t K[64] = {
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
    uint32_t w[64];
    for (int i = 0; i < 16; i++)
      w[i] = (p[4 * i] << 24) | (p[4 * i + 1] << 16) | (p[4 * i + 2] << 8) |
             p[4 * i + 3];
    for (int i = 16; i < 64; i++) {
      uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
      uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
      w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint32_t a = h[0], b = h[1], c = h[2], d = h[3], e = h[4], f = h[5],
             g = h[6], hh = h[7];
    for (int i = 0; i < 64; i++) {
      uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
      uint32_t ch = (e & f) ^ (~e & g);
      uint32_t t1 = hh + S1 + ch + K[i] + w[i];
      uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
      uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
      uint32_t t2 = S0 + maj;
      hh = g; g = f; f = e; e = d + t1;
      d = c; c = b; b = a; a = t1 + t2;
    }
    h[0] += a; h[1] += b; h[2] += c; h[3] += d;
    h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
  }

  void update(const uint8_t* p, size_t n) {
    len += n;
    while (n) {
      size_t take = 64 - buflen < n ? 64 - buflen : n;
      std::memcpy(buf + buflen, p, take);
      buflen += take;
      p += take;
      n -= take;
      if (buflen == 64) {
        block(buf);
        buflen = 0;
      }
    }
  }

  void final(uint8_t out[32]) {
    uint64_t bits = len * 8;
    uint8_t pad = 0x80;
    update(&pad, 1);
    uint8_t z = 0;
    while (buflen != 56) update(&z, 1);
    uint8_t lb[8];
    for (int i = 7; i >= 0; i--) {
      lb[i] = (uint8_t)bits;
      bits >>= 8;
    }
    update(lb, 8);
    for (int i = 0; i < 8; i++) {
      out[4 * i] = (uint8_t)(h[i] >> 24);
      out[4 * i + 1] = (uint8_t)(h[i] >> 16);
      out[4 * i + 2] = (uint8_t)(h[i] >> 8);
      out[4 * i + 3] = (uint8_t)h[i];
    }
  }
};

void hmac_sha256(const uint8_t* key, size_t keylen, const uint8_t* m1,
                 size_t l1, const uint8_t* m2, size_t l2, const uint8_t* m3,
                 size_t l3, uint8_t out[32]) {
  uint8_t k[64];
  std::memset(k, 0, 64);
  if (keylen > 64) {
    Sha256 s;
    s.update(key, keylen);
    s.final(k);
  } else {
    std::memcpy(k, key, keylen);
  }
  uint8_t ipad[64], opad[64];
  for (int i = 0; i < 64; i++) {
    ipad[i] = k[i] ^ 0x36;
    opad[i] = k[i] ^ 0x5c;
  }
  uint8_t inner[32];
  Sha256 si;
  si.update(ipad, 64);
  if (l1) si.update(m1, l1);
  if (l2) si.update(m2, l2);
  if (l3) si.update(m3, l3);
  si.final(inner);
  Sha256 so;
  so.update(opad, 64);
  so.update(inner, 32);
  so.final(out);
}

}  // namespace

extern "C" {

// Recover the 64-byte uncompressed pubkey from hash32 + sig65 (r||s||v).
// Returns 0 on success.
int geec_ec_recover(const uint8_t hash32[32], const uint8_t sig65[65],
                    uint8_t pub64[64]) {
  U256 r = from_be(sig65);
  U256 s = from_be(sig65 + 32);
  uint8_t v = sig65[64];
  if (v >= 4) return -1;
  if (is_zero(r) || is_zero(s) || cmp(r, N) >= 0 || cmp(s, N) >= 0) return -2;
  U256 x = r;
  if (v & 2) {
    if (add_carry(x, N)) return -3;
    if (cmp(x, P) >= 0) return -3;
  }
  // y^2 = x^3 + 7
  U256 seven{{7, 0, 0, 0}};
  U256 ysq = FP_.add(FP_.mul(FP_.sqr(x), x), seven);
  // y = ysq^((P+1)/4)
  U256 e = P;
  add_carry(e, ONE);  // overflow: P+1 = 2^256 - delta + 1; carry handling:
  // (P+1)/4: compute via byte math instead
  // P + 1 = 0xFFFF...FC30 ; (P+1)/4 = 0x3FFFFFFFBFFFFFFFF... compute shift
  // easier: e = (P + 1) >> 2 done on the non-overflowing sum (P+1 < 2^256)
  e = P;
  U256 one = ONE;
  add_carry(e, one);  // no real overflow: P < 2^256 - 1
  // shift right by 2
  for (int i = 0; i < 4; i++) {
    e.v[i] >>= 2;
    if (i < 3) e.v[i] |= e.v[i + 1] << 62;
  }
  U256 y = FP_.pow(ysq, e);
  if (cmp(FP_.sqr(y), ysq) != 0) return -4;
  if ((y.v[0] & 1) != (v & 1)) {
    U256 t = P;
    sub_borrow(t, y);
    y = t;
  }
  U256 z = from_be(hash32);
  // z mod N
  U512 zw{{z.v[0], z.v[1], z.v[2], z.v[3], 0, 0, 0, 0}};
  z = reduce_wide(zw, N_DELTA, N);
  U256 rinv = FN_.inv(r);
  U256 u1 = FN_.mul(FN_.sub(N, z), rinv);  // -z/r
  if (cmp(z, ZERO) == 0) u1 = ZERO;
  U256 u2 = FN_.mul(s, rinv);
  Pt R{x, y, ONE};
  Pt G{GX, GY, ONE};
  Pt q = pt_add(pt_mul(u1, G), pt_mul(u2, R));
  if (is_zero(q.z)) return -5;
  U256 qx, qy;
  pt_affine(q, qx, qy);
  to_be(qx, pub64);
  to_be(qy, pub64 + 32);
  return 0;
}

// Classic verify of sig64 (r||s, low-s enforced) against pub64. 1 = valid.
int geec_ec_verify(const uint8_t hash32[32], const uint8_t sig64[64],
                   const uint8_t pub64[64]) {
  U256 r = from_be(sig64);
  U256 s = from_be(sig64 + 32);
  if (is_zero(r) || is_zero(s) || cmp(r, N) >= 0) return 0;
  // reject high-s (malleable), like the reference's verify
  U256 half = N;
  // half = N >> 1
  for (int i = 0; i < 4; i++) {
    half.v[i] >>= 1;
    if (i < 3) half.v[i] |= half.v[i + 1] << 63;
  }
  if (cmp(s, half) > 0) return 0;
  U256 qx = from_be(pub64), qy = from_be(pub64 + 32);
  U256 seven{{7, 0, 0, 0}};
  if (cmp(FP_.sqr(qy), FP_.add(FP_.mul(FP_.sqr(qx), qx), seven)) != 0) return 0;
  U256 z = from_be(hash32);
  U512 zw{{z.v[0], z.v[1], z.v[2], z.v[3], 0, 0, 0, 0}};
  z = reduce_wide(zw, N_DELTA, N);
  U256 sinv = FN_.inv(s);
  U256 u1 = FN_.mul(z, sinv);
  U256 u2 = FN_.mul(r, sinv);
  Pt G{GX, GY, ONE};
  Pt q{qx, qy, ONE};
  Pt pt = pt_add(pt_mul(u1, G), pt_mul(u2, q));
  if (is_zero(pt.z)) return 0;
  U256 px, py;
  pt_affine(pt, px, py);
  U512 pw{{px.v[0], px.v[1], px.v[2], px.v[3], 0, 0, 0, 0}};
  U256 pxn = reduce_wide(pw, N_DELTA, N);
  return cmp(pxn, r) == 0 ? 1 : 0;
}

// Deterministic RFC6979 sign; out = r||s||v (65 bytes). Returns 0 on success.
int geec_ec_sign(const uint8_t hash32[32], const uint8_t priv32[32],
                 uint8_t sig65[65]) {
  U256 d = from_be(priv32);
  if (is_zero(d) || cmp(d, N) >= 0) return -1;
  // RFC6979: V=0x01*32, K=0x00*32
  uint8_t V[32], K[32];
  std::memset(V, 0x01, 32);
  std::memset(K, 0x00, 32);
  // K = HMAC(K, V || 0x00 || priv || hash)
  {
    uint8_t m[32 + 1 + 32 + 32];
    std::memcpy(m, V, 32);
    m[32] = 0x00;
    std::memcpy(m + 33, priv32, 32);
    std::memcpy(m + 65, hash32, 32);
    hmac_sha256(K, 32, m, sizeof(m), nullptr, 0, nullptr, 0, K);
  }
  hmac_sha256(K, 32, V, 32, nullptr, 0, nullptr, 0, V);
  {
    uint8_t m[32 + 1 + 32 + 32];
    std::memcpy(m, V, 32);
    m[32] = 0x01;
    std::memcpy(m + 33, priv32, 32);
    std::memcpy(m + 65, hash32, 32);
    hmac_sha256(K, 32, m, sizeof(m), nullptr, 0, nullptr, 0, K);
  }
  hmac_sha256(K, 32, V, 32, nullptr, 0, nullptr, 0, V);

  U256 z = from_be(hash32);
  U512 zw{{z.v[0], z.v[1], z.v[2], z.v[3], 0, 0, 0, 0}};
  U256 zn = reduce_wide(zw, N_DELTA, N);

  for (int attempt = 0; attempt < 64; attempt++) {
    hmac_sha256(K, 32, V, 32, nullptr, 0, nullptr, 0, V);
    U256 k = from_be(V);
    if (!is_zero(k) && cmp(k, N) < 0) {
      Pt G{GX, GY, ONE};
      Pt R = pt_mul(k, G);
      U256 rx, ry;
      pt_affine(R, rx, ry);
      U512 rw{{rx.v[0], rx.v[1], rx.v[2], rx.v[3], 0, 0, 0, 0}};
      U256 r = reduce_wide(rw, N_DELTA, N);
      if (!is_zero(r)) {
        U256 kinv = FN_.inv(k);
        U256 rd = FN_.mul(r, from_be(priv32));
        U256 s = FN_.mul(kinv, FN_.add(zn, rd));
        if (!is_zero(s)) {
          uint8_t v = (uint8_t)((ry.v[0] & 1) | (cmp(rx, N) >= 0 ? 2 : 0));
          // low-s normalization flips recovery parity
          U256 half = N;
          for (int i = 0; i < 4; i++) {
            half.v[i] >>= 1;
            if (i < 3) half.v[i] |= half.v[i + 1] << 63;
          }
          if (cmp(s, half) > 0) {
            U256 t = N;
            sub_borrow(t, s);
            s = t;
            v ^= 1;
          }
          to_be(r, sig65);
          to_be(s, sig65 + 32);
          sig65[64] = v;
          return 0;
        }
      }
    }
    // K = HMAC(K, V || 0x00); V = HMAC(K, V)
    uint8_t m[33];
    std::memcpy(m, V, 32);
    m[32] = 0x00;
    hmac_sha256(K, 32, m, 33, nullptr, 0, nullptr, 0, K);
    hmac_sha256(K, 32, V, 32, nullptr, 0, nullptr, 0, V);
  }
  return -2;
}

// priv -> uncompressed 64-byte pubkey. Returns 0 on success.
int geec_ec_pubkey(const uint8_t priv32[32], uint8_t pub64[64]) {
  U256 d = from_be(priv32);
  if (is_zero(d) || cmp(d, N) >= 0) return -1;
  Pt G{GX, GY, ONE};
  Pt q = pt_mul(d, G);
  U256 x, y;
  pt_affine(q, x, y);
  to_be(x, pub64);
  to_be(y, pub64 + 32);
  return 0;
}

// Batched recover: n rows; ok[i] = 1 on success. Host-parallel loop, on
// a team of no more threads than rows: a window of a few rows (the
// scheduler's host-served consensus windows) wakes only the threads that
// get a row.  A whole team woken for 3 rows leaves a dozen threads
// spinning at the barrier on every core, and whichever thread the kernel
// then displaces, the caller among them, waits a scheduler slice behind a
// spinner (PERF.md, PR 37: 6 ms in one such window in nine).
void geec_ec_recover_batch(const uint8_t* hashes /* n*32 */,
                           const uint8_t* sigs /* n*65 */, uint64_t n,
                           uint8_t* pubs /* n*64 */, uint8_t* ok /* n */) {
  int team = 1;
#ifdef _OPENMP
  team = omp_get_max_threads();
  if ((uint64_t)team > n) team = n ? (int)n : 1;
#endif
#pragma omp parallel for schedule(static) num_threads(team)
  for (uint64_t i = 0; i < n; i++)
    ok[i] = geec_ec_recover(hashes + 32 * i, sigs + 65 * i, pubs + 64 * i) == 0;
}

}  // extern "C"
