// Columnar txn ingest — one gossip window decoded in one native call.
//
// Role parity: the reference decodes each wire transaction in Go
// (core/types/transaction.go DecodeRLP) and hashes it in amd64 assembly;
// here the whole window's canonical-RLP scan, the v/r/s rules and both
// Keccak-256 digests of every row run behind ONE ctypes call, which
// holds no GIL, and write straight into the numpy columns of
// eges_tpu/ingress/columnar.py TxColumns.  The rules are those of
// columnar.py _scan_txn_frame and _decode_frames, which stay the
// oracle: tests/test_columnar_ingest.py holds the two byte-identical.
//
// Every byte read here is attacker-controlled.  A length taken from the
// wire is compared against what is left of the frame (`n > flen - ps`)
// and never added to a position first.  Single-threaded on purpose: the
// callers' worker threads are the parallelism, and the answer must not
// depend on a thread count.

#include <cstdint>
#include <cstring>
#include <memory>
#include <new>

extern "C" void geec_keccak256(const uint8_t* data, uint64_t len,
                               uint8_t out[32]);

namespace {

struct Field {
  uint64_t enc;  // start of the field's encoding (header included)
  uint64_t ps;   // payload start
  uint64_t pe;   // payload end == end of the encoding
};

inline uint64_t read_be(const uint8_t* p, uint64_t ln) {
  uint64_t v = 0;
  for (uint64_t k = 0; k < ln; k++) v = (v << 8) | p[k];
  return v;
}

// A wire length of `ln` (1..8) bytes; false on a leading zero (a
// non-canonical length).
inline bool read_len(const uint8_t* p, uint64_t ln, uint64_t* out) {
  *out = read_be(p, ln);
  return p[0] != 0;
}

// min(value, 2^64-1) of a canonical big-endian integer field: with no
// leading zero, more than eight bytes is at least 2^64.
inline uint64_t clamp_u64(const uint8_t* p, uint64_t ln) {
  return ln > 8 ? UINT64_MAX : read_be(p, ln);
}

// _scan_txn_frame's rules over one non-empty frame: a list whose
// payload is exactly ten canonical string items and nothing after.
bool scan(const uint8_t* f, uint64_t flen, Field fld[10]) {
  uint64_t pos;
  uint8_t b0 = f[0];
  if (b0 < 0xC0) return false;  // not a list
  if (b0 < 0xF8) {
    pos = 1;
    if (uint64_t(b0 - 0xC0) != flen - 1) return false;  // short or trailing
  } else {
    uint64_t ln = b0 - 0xF7, n;
    if (ln > flen - 1) return false;  // truncated length
    if (!read_len(f + 1, ln, &n) || n < 56) return false;
    pos = 1 + ln;
    if (n != flen - pos) return false;  // short or trailing bytes
  }
  // from here the list's payload ends where the frame does
  for (int k = 0; k < 10; k++) {
    if (pos >= flen) return false;  // fewer than 10 fields
    uint64_t ps, pe;
    b0 = f[pos];
    if (b0 < 0x80) {
      ps = pos;
      pe = pos + 1;
    } else if (b0 < 0xB8) {  // short string
      uint64_t n = b0 - 0x80;
      ps = pos + 1;
      if (n > flen - ps) return false;
      pe = ps + n;
      if (n == 1 && f[ps] < 0x80) return false;  // non-canonical byte
    } else if (b0 < 0xC0) {  // long string
      uint64_t ln = b0 - 0xB7, n;
      if (ln > flen - (pos + 1)) return false;
      ps = pos + 1 + ln;
      if (!read_len(f + pos + 1, ln, &n) || n < 56) return false;
      if (n > flen - ps) return false;
      pe = ps + n;
    } else {
      return false;  // a nested list is no txn field
    }
    fld[k] = {pos, ps, pe};
    pos = pe;
  }
  if (pos != flen) return false;  // an 11th field, or slack
  // the from_rlp guards: r/s fit 256 bits, v 64, `to` is empty or an
  // address, no integer field carries a leading zero
  if (fld[8].pe - fld[8].ps > 32 || fld[9].pe - fld[9].ps > 32) return false;
  if (fld[7].pe - fld[7].ps > 8) return false;
  uint64_t to = fld[3].pe - fld[3].ps;
  if (to != 0 && to != 20) return false;
  static const int kInts[8] = {0, 1, 2, 4, 6, 7, 8, 9};
  for (int k : kInts)
    if (fld[k].pe > fld[k].ps && f[fld[k].ps] == 0) return false;
  return true;
}

// `tag + k` then x in its k minimal big-endian bytes (x != 0): the long
// form of a header and the encoding of an integer; returns 1 + k.
inline uint64_t put_be(uint8_t* out, uint8_t tag, uint64_t x) {
  uint64_t k = 0;
  for (uint64_t t = x; t; t >>= 8) k++;
  out[0] = uint8_t(tag + k);
  for (uint64_t j = 0; j < k; j++) out[1 + j] = uint8_t(x >> (8 * (k - 1 - j)));
  return 1 + k;
}

// RLP list header for an n-byte payload; returns its length (1..9).
inline uint64_t put_list_header(uint8_t* out, uint64_t n) {
  if (n >= 56) return put_be(out, 0xF7, n);
  out[0] = uint8_t(0xC0 + n);
  return 1;
}

// RLP of an unsigned integer; returns its length (1..9).
inline uint64_t put_uint(uint8_t* out, uint64_t x) {
  if (x >= 0x80) return put_be(out, 0x80, x);
  out[0] = x ? uint8_t(x) : 0x80;
  return 1;
}

constexpr uint64_t kHeadRoom = 9;   // longest list header
constexpr uint64_t kTailRoom = 11;  // rlp(cid) of a 63-bit cid + 0x80 0x80

}  // namespace

extern "C" {

// Frames packed back to back in `data`, frame i at offsets[i]..offsets[i+1]
// (n+1 offsets); an empty span is a frame the caller's gate killed.
// Mask, never raise: a frame that fails the scan leaves decoded[i] = 0
// and costs no digest; a decoded row whose v/r/s form no wire signature
// leaves valid[i] = 0 and pays no sighash digest.  `spans` takes the ten
// payload spans (start, end), relative to the frame.  The outputs of a
// row that fails are left as they came (the caller zeroes them).
// Returns 0, or -1 when the scratch buffer could not be allocated
// (nothing written).
int geec_decode_txn_window(const uint8_t* data, const uint64_t* offsets,
                           uint64_t n, uint8_t* decoded, uint8_t* valid,
                           uint8_t* txhash /* n*32 */,
                           uint8_t* sighash /* n*32 */,
                           uint8_t* sig /* n*65 */, uint64_t* nonce,
                           uint64_t* gas_price,
                           uint32_t* spans /* n*10*2 */) {
  uint64_t longest = 0;
  for (uint64_t i = 0; i < n; i++)
    if (offsets[i + 1] > offsets[i] && offsets[i + 1] - offsets[i] > longest)
      longest = offsets[i + 1] - offsets[i];
  // the sighash preimage is a slice of its frame under a new header
  std::unique_ptr<uint8_t[]> scratch(
      new (std::nothrow) uint8_t[kHeadRoom + longest + kTailRoom]);
  if (!scratch) return -1;

  for (uint64_t i = 0; i < n; i++) {
    if (offsets[i + 1] <= offsets[i]) continue;
    const uint8_t* f = data + offsets[i];
    const uint64_t flen = offsets[i + 1] - offsets[i];
    Field fld[10];
    if (flen > UINT32_MAX || !scan(f, flen, fld)) continue;

    decoded[i] = 1;
    geec_keccak256(f, flen, txhash + 32 * i);
    nonce[i] = clamp_u64(f + fld[0].ps, fld[0].pe - fld[0].ps);
    gas_price[i] = clamp_u64(f + fld[1].ps, fld[1].pe - fld[1].ps);
    for (int k = 0; k < 10; k++) {
      spans[20 * i + 2 * k] = uint32_t(fld[k].ps);
      spans[20 * i + 2 * k + 1] = uint32_t(fld[k].pe);
    }

    // signature_parts()'s v/r/s rules
    const uint64_t v = read_be(f + fld[7].ps, fld[7].pe - fld[7].ps);
    const bool prot = v != 27 && v != 28 && v != 0;
    if (prot && v < 35) continue;  // 29..34 (and 1..26) name no chain
    if (v == 0) continue;          // recid would be -27
    const uint64_t cid = prot ? (v - 35) / 2 : 0;
    const uint8_t recid = uint8_t(prot ? (v - 35) & 1 : v - 27);
    const uint64_t rl = fld[8].pe - fld[8].ps, sl = fld[9].pe - fld[9].ps;
    if (rl == 0 || sl == 0) continue;  // zero; canonical, so non-empty is non-zero

    valid[i] = 1;
    uint8_t* g = sig + 65 * i;
    std::memset(g, 0, 64);
    std::memcpy(g + 32 - rl, f + fld[8].ps, rl);
    std::memcpy(g + 64 - sl, f + fld[9].ps, sl);
    g[64] = recid;

    // list header + the first six encodings as they stand in the frame
    // (+ rlp(cid) 0x80 0x80 where protected), built behind room for the
    // longest header so the body is copied once
    uint8_t* body = scratch.get() + kHeadRoom;
    uint64_t blen = fld[5].pe - fld[0].enc;
    std::memcpy(body, f + fld[0].enc, blen);
    if (prot) {
      blen += put_uint(body + blen, cid);
      body[blen++] = 0x80;
      body[blen++] = 0x80;
    }
    uint8_t head[kHeadRoom];
    const uint64_t hlen = put_list_header(head, blen);
    std::memcpy(body - hlen, head, hlen);
    geec_keccak256(body - hlen, hlen + blen, sighash + 32 * i);
  }
  return 0;
}

}  // extern "C"
