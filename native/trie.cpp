// Merkle-Patricia trie nodes, encoded AND hashed: one native call a root.
//
// Role parity: the reference's trie hasher (trie/hasher.go) encodes a
// node and hashes it in Go and assembly.  Here a root pays ONE ctypes
// call, which holds no GIL: geec_derive_sha builds the whole trie of a
// block's transactions or receipts (keys rlp(index), ref:
// core/types/derive_sha.go), geec_trie_hash_nodes takes the nodes of a
// persistent trie that have no reference yet, flattened by
// eges_tpu/core/trie.py.  The rules (hex-prefix paths, a node under 32
// bytes embedded raw in its parent, the rest referred to by Keccak-256)
// are those of trie.py's Python rung, which stays the oracle:
// tests/test_trie_native.py holds the two byte-identical.
//
// Single-threaded on purpose: a root is small work, and a team woken
// for it would cost the callers' Python threads their cores.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <new>
#include <vector>

extern "C" void geec_keccak256(const uint8_t* data, uint64_t len,
                               uint8_t out[32]);

namespace {

constexpr uint64_t kHeadRoom = 9;  // longest list header
constexpr uint64_t kRefMax = 33;   // 0xA0 and a hash; an embedded node is shorter

// A node's payload behind room for its list header, so the encoding is
// contiguous for the hash without a second copy.
struct Node {
  std::vector<uint8_t> buf;

  void clear() { buf.assign(kHeadRoom, 0); }
  void put(const uint8_t* p, uint64_t n) { buf.insert(buf.end(), p, p + n); }
  void put(uint8_t b) { buf.push_back(b); }

  // `tag + k` then n in its k minimal big-endian bytes (n >= 56)
  void put_long(uint8_t tag, uint64_t n) {
    uint8_t be[8];
    uint64_t k = 0;
    for (uint64_t t = n; t; t >>= 8) k++;
    for (uint64_t j = 0; j < k; j++) be[j] = uint8_t(n >> (8 * (k - 1 - j)));
    put(uint8_t(tag + k));
    put(be, k);
  }

  void put_string_head(uint64_t n) {
    if (n < 56) put(uint8_t(0x80 + n));
    else put_long(0xB7, n);
  }

  void put_string(const uint8_t* p, uint64_t n) {
    if (n == 1 && p[0] < 0x80) return put(p[0]);
    put_string_head(n);
    put(p, n);
  }

  // hex-prefix (compact) form of a nibble path, as an RLP string
  void put_path(const uint8_t* nib, uint64_t n, bool terminal) {
    const uint8_t flag = terminal ? 2 : 0;
    const uint8_t head = n % 2 ? uint8_t(((flag + 1) << 4) | nib[0])
                               : uint8_t(flag << 4);
    const uint64_t k = 1 + n / 2;
    if (k > 1) put_string_head(k);  // a lone head byte (under 0x40) stands for itself
    put(head);
    for (uint64_t i = n % 2; i < n; i += 2)
      put(uint8_t((nib[i] << 4) | nib[i + 1]));
  }

  // Close the list: the header goes in front of the payload.  Returns
  // where the node's encoding starts; `*len` is its length.
  const uint8_t* seal(uint64_t* len) {
    const uint64_t n = buf.size() - kHeadRoom;
    uint64_t h = 1;
    if (n >= 56)
      for (uint64_t t = n; t; t >>= 8) h++;
    uint8_t* head = buf.data() + kHeadRoom - h;
    if (n < 56) {
      head[0] = uint8_t(0xC0 + n);
    } else {
      head[0] = uint8_t(0xF7 + h - 1);
      for (uint64_t j = 1; j < h; j++) head[j] = uint8_t(n >> (8 * (h - 1 - j)));
    }
    *len = h + n;
    return head;
  }
};

// A node as its parent holds it: the encoding itself where that is
// under 32 bytes, else 0xA0 and its hash.  Returns the length.
uint64_t reference(const uint8_t* enc, uint64_t len, uint8_t ref[kRefMax]) {
  if (len < 32) {
    std::memcpy(ref, enc, len);
    return len;
  }
  ref[0] = 0xA0;
  geec_keccak256(enc, len, ref + 1);
  return kRefMax;
}

// --- derive_sha: the trie of items keyed by rlp(index), built whole ---

struct Key {
  uint8_t nib[18];  // rlp of a 64-bit index: at most 9 bytes
  uint8_t n;
  uint64_t index;
};

struct Deriver {
  const uint8_t* data;
  const uint64_t* offsets;
  std::vector<Key> keys;  // sorted
  Node level[19];         // a node is built at the depth its path starts
  uint64_t nodes = 0;

  // The node over keys[lo, hi), which share their first `depth` nibbles.
  // rlp(index) keys are prefix-free: no key ends inside another's path,
  // so a branch never carries a value.
  const uint8_t* build(uint64_t lo, uint64_t hi, uint64_t depth,
                       uint64_t* len) {
    Node& node = level[depth];
    node.clear();
    nodes++;
    const Key& first = keys[lo];
    if (hi - lo == 1) {
      node.put_path(first.nib + depth, first.n - depth, true);
      node.put_string(data + offsets[first.index],
                      offsets[first.index + 1] - offsets[first.index]);
      return node.seal(len);
    }
    // sorted, so what the first and the last key share, all share
    const Key& last = keys[hi - 1];
    uint64_t lcp = depth;
    while (first.nib[lcp] == last.nib[lcp]) lcp++;
    uint8_t ref[kRefMax];
    uint64_t clen;
    if (lcp > depth) {  // extension
      const uint8_t* child = build(lo, hi, lcp, &clen);
      node.put_path(first.nib + depth, lcp - depth, false);
      node.put(ref, reference(child, clen, ref));
      return node.seal(len);
    }
    for (uint8_t v = 0; v < 16; v++) {
      uint64_t end = lo;
      while (end < hi && keys[end].nib[depth] == v) end++;
      if (end == lo) {
        node.put(0x80);
        continue;
      }
      const uint8_t* child = build(lo, end, depth + 1, &clen);
      node.put(ref, reference(child, clen, ref));
      lo = end;
    }
    node.put(0x80);  // no value
    return node.seal(len);
  }
};

// --- hash_nodes: a persistent trie's nodes, flattened by trie.py ---

struct Reader {
  const uint8_t* p;
  uint64_t left;

  const uint8_t* take(uint64_t n) {
    if (n > left) return nullptr;
    const uint8_t* at = p;
    p += n;
    left -= n;
    return at;
  }
  bool u32(uint64_t* out) {
    const uint8_t* at = take(4);
    if (at)
      *out = uint64_t(at[0]) | uint64_t(at[1]) << 8 | uint64_t(at[2]) << 16 |
             uint64_t(at[3]) << 24;
    return at != nullptr;
  }
};

bool put_path(Reader& in, Node& node, uint64_t n, bool terminal) {
  const uint8_t* nib = in.take(n);
  if (!nib) return false;
  for (uint64_t k = 0; k < n; k++)
    if (nib[k] > 15) return false;
  node.put_path(nib, n, terminal);
  return true;
}

bool put_value(Reader& in, Node& node, uint64_t n) {
  const uint8_t* v = in.take(n);
  if (v) node.put_string(v, n);
  return v != nullptr;
}

// One child as the record carries it: 0x80 (no child, a branch's slot
// alone), a kept reference as it stands in the parent (0xA0 and a
// hash, or an embedded node's own short list), or 0x00 and the index
// of a node earlier in this batch.
bool put_child(Reader& in, Node& node, bool may_be_empty, uint64_t i,
               const uint8_t* refs, const uint8_t* lens) {
  const uint8_t* b = in.take(1);
  if (!b) return false;
  if (*b == 0x80 && may_be_empty) {
    node.put(0x80);
    return true;
  }
  if (*b == 0x00) {
    uint64_t at;
    if (!in.u32(&at) || at >= i) return false;
    node.put(refs + kRefMax * at, lens[at]);
    return true;
  }
  uint64_t n;
  if (*b == 0xA0) n = 32;
  else if (*b >= 0xC0 && *b <= 0xC0 + 30) n = *b - 0xC0;
  else return false;
  const uint8_t* rest = in.take(n);
  if (!rest) return false;
  node.put(*b);
  node.put(rest, n);
  return true;
}

}  // namespace

extern "C" {

// Items packed back to back in `data`, item i at offsets[i]..offsets[i+1]
// (n+1 offsets, ascending).  Writes the root of the trie that holds item
// i under the key rlp(i) (the empty trie's root for n == 0) and the
// number of nodes it encoded.  Returns 0, or -2 when memory ran out
// (nothing written).
int geec_derive_sha(const uint8_t* data, const uint64_t* offsets, uint64_t n,
                    uint8_t root[32], uint64_t* nodes) {
  try {
    Deriver d{data, offsets};
    d.keys.resize(n);
    for (uint64_t i = 0; i < n; i++) {
      uint8_t key[9];
      uint64_t k = 1;
      if (i == 0) {
        key[0] = 0x80;
      } else if (i < 0x80) {
        key[0] = uint8_t(i);
      } else {
        for (uint64_t t = i; t; t >>= 8) k++;
        key[0] = uint8_t(0x80 + k - 1);
        for (uint64_t j = 1; j < k; j++) key[j] = uint8_t(i >> (8 * (k - 1 - j)));
      }
      Key& out = d.keys[i];
      std::memset(out.nib, 0, sizeof(out.nib));
      for (uint64_t j = 0; j < k; j++) {
        out.nib[2 * j] = key[j] >> 4;
        out.nib[2 * j + 1] = key[j] & 15;
      }
      out.n = uint8_t(2 * k);
      out.index = i;
    }
    std::sort(d.keys.begin(), d.keys.end(), [](const Key& a, const Key& b) {
      return std::memcmp(a.nib, b.nib, sizeof(a.nib)) < 0;
    });
    uint64_t len = 1;
    const uint8_t empty = 0x80;  // rlp(b"")
    const uint8_t* enc = n ? d.build(0, n, 0, &len) : &empty;
    geec_keccak256(enc, len, root);
    *nodes = d.nodes;
    return 0;
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

// `n` node records back to back in `recs` (`len` bytes), children before
// their parents.  A record is its kind and then, little-endian:
//   0 leaf:      u32 path nibbles, u32 value bytes, the nibbles, the value
//   1 extension: u32 path nibbles, the nibbles, the child
//   2 branch:    sixteen children, u32 value bytes, the value
// with a child as put_child has it.  Writes node i's reference to
// refs[33 * i ..] and its length (under 32: the node's own encoding;
// 33: 0xA0 and its hash) to lens[i].  Returns 0; -1 on a record that is
// not of this form or does not end with the buffer; -2 when memory ran
// out.  The outputs of the nodes before a fault are written.
int geec_trie_hash_nodes(const uint8_t* recs, uint64_t len, uint64_t n,
                         uint8_t* refs /* n*33 */, uint8_t* lens /* n */) {
  try {
    Reader in{recs, len};
    Node node;
    for (uint64_t i = 0; i < n; i++) {
      node.clear();
      const uint8_t* kind = in.take(1);
      uint64_t np, nv;
      if (!kind) return -1;
      if (*kind == 0) {
        if (!in.u32(&np) || !in.u32(&nv) || !put_path(in, node, np, true) ||
            !put_value(in, node, nv))
          return -1;
      } else if (*kind == 1) {
        if (!in.u32(&np) || !put_path(in, node, np, false) ||
            !put_child(in, node, false, i, refs, lens))
          return -1;
      } else if (*kind == 2) {
        for (int slot = 0; slot < 16; slot++)
          if (!put_child(in, node, true, i, refs, lens)) return -1;
        if (!in.u32(&nv) || !put_value(in, node, nv)) return -1;
      } else {
        return -1;
      }
      uint64_t elen;
      const uint8_t* enc = node.seal(&elen);
      lens[i] = uint8_t(reference(enc, elen, refs + kRefMax * i));
    }
    return in.left == 0 ? 0 : -1;
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

}  // extern "C"
