// Keccak-256 (legacy pre-NIST padding) — native host implementation.
//
// Role parity: the reference computes Keccak-256 in amd64 assembly
// (crypto/sha3/keccakf_amd64.s) behind crypto.Keccak256
// (crypto/crypto.go:43).  This C++ core serves the host control plane
// (header/txn hashing, address derivation) when the shared library is
// built; the pure-Python implementation remains the golden fallback.

#include <cstdint>
#include <cstring>

namespace {

constexpr uint64_t RC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL,
};

constexpr int ROT[5][5] = {
    {0, 36, 3, 41, 18},
    {1, 44, 10, 45, 2},
    {62, 6, 43, 15, 61},
    {28, 55, 25, 21, 56},
    {27, 20, 39, 8, 14},
};

inline uint64_t rotl(uint64_t x, int r) {
  return r == 0 ? x : (x << r) | (x >> (64 - r));
}

void keccak_f(uint64_t a[25]) {
  uint64_t b[25], c[5], d[5];
  for (int rnd = 0; rnd < 24; rnd++) {
    for (int x = 0; x < 5; x++)
      c[x] = a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20];
    for (int x = 0; x < 5; x++) {
      d[x] = c[(x + 4) % 5] ^ rotl(c[(x + 1) % 5], 1);
      for (int y = 0; y < 5; y++) a[x + 5 * y] ^= d[x];
    }
    for (int x = 0; x < 5; x++)
      for (int y = 0; y < 5; y++)
        b[y + 5 * ((2 * x + 3 * y) % 5)] = rotl(a[x + 5 * y], ROT[x][y]);
    for (int y = 0; y < 5; y++)
      for (int x = 0; x < 5; x++)
        a[x + 5 * y] = b[x + 5 * y] ^ (~b[(x + 1) % 5 + 5 * y] & b[(x + 2) % 5 + 5 * y]);
    a[0] ^= RC[rnd];
  }
}

}  // namespace

extern "C" {

// Legacy Keccak-256: rate 136, domain byte 0x01.
void geec_keccak256(const uint8_t* data, uint64_t len, uint8_t out[32]) {
  constexpr uint64_t RATE = 136;
  uint64_t a[25];
  std::memset(a, 0, sizeof(a));

  while (len >= RATE) {
    for (uint64_t i = 0; i < RATE / 8; i++) {
      uint64_t lane;
      std::memcpy(&lane, data + 8 * i, 8);  // little-endian hosts only
      a[i] ^= lane;
    }
    keccak_f(a);
    data += RATE;
    len -= RATE;
  }
  uint8_t block[RATE];
  std::memset(block, 0, RATE);
  std::memcpy(block, data, len);
  block[len] = 0x01;
  block[RATE - 1] |= 0x80;
  for (uint64_t i = 0; i < RATE / 8; i++) {
    uint64_t lane;
    std::memcpy(&lane, block + 8 * i, 8);
    a[i] ^= lane;
  }
  keccak_f(a);
  std::memcpy(out, a, 32);
}

// Batched convenience: n messages of fixed stride.
void geec_keccak256_batch(const uint8_t* data, uint64_t n, uint64_t msg_len,
                          uint8_t* out /* n*32 */) {
  for (uint64_t i = 0; i < n; i++)
    geec_keccak256(data + i * msg_len, msg_len, out + i * 32);
}

}  // extern "C"
