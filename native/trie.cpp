// Merkle-Patricia tries: built, KEPT, encoded and hashed in the library.
//
// Role parity: the reference's trie (trie/trie.go insert and delete,
// trie/hasher.go) keeps its nodes, encodes and hashes them in Go and
// assembly.  Here a root pays ONE ctypes call, which holds no GIL:
// geec_derive_sha builds the whole trie of a block's transactions or
// receipts (keys rlp(index), ref: core/types/derive_sha.go) and forgets
// it; geec_trie_update_many puts a batch of keys into a PERSISTENT trie
// whose nodes live here, immutable, shared between the roots that hold
// them and counted, so a state root is a handle in Python and a height
// costs its dirty paths (eges_tpu/core/trie.py has the handles).  The
// rules (hex-prefix paths, a node under 32 bytes embedded raw in its
// parent, the rest referred to by Keccak-256, the canonical shapes
// after an insert and a delete) are those of trie.py's Python rung,
// which stays the oracle: tests/test_trie_native.py holds the two
// byte-identical.
//
// Single-threaded on purpose: a root is small work, and a team woken
// for it would cost the callers' Python threads their cores.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <new>
#include <utility>
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

// --- the node store: a persistent trie's nodes, kept here ---
//
// The shapes are trie.py's _insert / _delete / _make_ext, node for node:
// an extension over a shared prefix, never over an extension or a leaf
// (the paths merge), a branch with one child and no value never left
// standing.  A node is never changed once made, and holds its reference
// from the end of the batch that made it; until then only that batch
// can reach it, under the store's lock.

enum : uint8_t { kLeaf = 0, kExt = 1, kBranch = 2 };

struct alignas(8) Stored {
  uint32_t refs;    // the parents and the handles that hold it
  uint8_t kind;
  uint8_t ref_len;  // 0 until the node is encoded
  uint8_t ref[kRefMax];
  uint32_t npath;   // nibbles (leaf, extension)
  uint32_t nvalue;  // bytes (leaf, branch)
  // then its children (an extension's one, a branch's sixteen), the
  // path a nibble a byte, the value

  static uint64_t kids_of(uint8_t kind) {
    return kind == kLeaf ? 0 : kind == kExt ? 1 : 16;
  }
  uint64_t nkids() const { return kids_of(kind); }
  Stored** kids() { return reinterpret_cast<Stored**>(this + 1); }
  uint8_t* path() { return reinterpret_cast<uint8_t*>(kids() + nkids()); }
  uint8_t* value() { return path() + npath; }
};

uint64_t g_nodes = 0;  // live, under the store's lock

Stored* alloc(uint8_t kind, uint64_t npath, uint64_t nvalue) {
  const uint64_t kids = Stored::kids_of(kind);
  auto* node = static_cast<Stored*>(std::malloc(
      sizeof(Stored) + kids * sizeof(Stored*) + npath + nvalue));
  if (!node) throw std::bad_alloc();
  node->refs = 1;
  node->kind = kind;
  node->ref_len = 0;
  node->npath = uint32_t(npath);
  node->nvalue = uint32_t(nvalue);
  std::fill_n(node->kids(), kids, nullptr);
  g_nodes++;
  return node;
}

// Give one reference back.  What nothing holds any more is freed with
// everything only it held, along a list threaded through the dead
// nodes' own `ref` bytes: no recursion and no allocation, however deep
// or long the chain of heights that goes.
void drop(Stored* node) {
  if (!node || --node->refs) return;
  Stored* dead = nullptr;
  std::memcpy(node->ref, &dead, sizeof dead);
  dead = node;
  while (dead) {
    Stored* gone = dead;
    std::memcpy(&dead, gone->ref, sizeof dead);
    Stored** kids = gone->kids();
    for (uint64_t k = 0, n = gone->nkids(); k < n; k++) {
      Stored* kid = kids[k];
      if (kid && --kid->refs == 0) {
        std::memcpy(kid->ref, &dead, sizeof dead);
        dead = kid;
      }
    }
    std::free(gone);
    g_nodes--;
  }
}

// One counted reference to a node, or to none (the empty trie).
class Ref {
 public:
  Ref() = default;
  explicit Ref(Stored* counted) : node_(counted) {}
  Ref(Ref&& o) noexcept : node_(o.give()) {}
  Ref& operator=(Ref&& o) noexcept {
    Stored* old = node_;
    node_ = o.give();
    drop(old);  // after the new one stands: they may share
    return *this;
  }
  ~Ref() { drop(node_); }
  static Ref share(Stored* node) {
    if (node) node->refs++;
    return Ref(node);
  }
  Stored* get() const { return node_; }
  Stored* operator->() const { return node_; }
  explicit operator bool() const { return node_ != nullptr; }
  Stored* give() {
    Stored* node = node_;
    node_ = nullptr;
    return node;
  }

 private:
  Stored* node_ = nullptr;
};

void copy(uint8_t* to, const uint8_t* from, uint64_t n) {
  if (n) std::memcpy(to, from, n);  // `from` may be null where n is 0
}

Ref leaf(const uint8_t* a, uint64_t na, const uint8_t* b, uint64_t nb,
         const uint8_t* val, uint64_t nv) {
  Ref node(alloc(kLeaf, na + nb, nv));
  copy(node->path(), a, na);
  copy(node->path() + na, b, nb);
  copy(node->value(), val, nv);
  return node;
}

Ref leaf(const uint8_t* path, uint64_t n, const uint8_t* val, uint64_t nv) {
  return leaf(path, n, nullptr, 0, val, nv);
}

// A branch with `like`'s children (none where it is null) and the value
// given; its maker may still set a child.
Ref branch(Stored* like, const uint8_t* val, uint64_t nv) {
  Ref node(alloc(kBranch, 0, nv));
  copy(node->value(), val, nv);
  if (like)
    for (int k = 0; k < 16; k++)
      node->kids()[k] = Ref::share(like->kids()[k]).give();
  return node;
}

void set_kid(Ref& fresh, uint8_t slot, Ref kid) {
  Ref old(fresh->kids()[slot]);
  fresh->kids()[slot] = kid.give();
}

// trie.py's _make_ext: an extension over `child`, or what it collapses to
Ref make_ext(const uint8_t* path, uint64_t n, Ref child) {
  if (n == 0) return child;
  if (child->kind == kLeaf)
    return leaf(path, n, child->path(), child->npath, child->value(),
                child->nvalue);
  const bool merge = child->kind == kExt;
  Ref node(alloc(kExt, n + (merge ? child->npath : 0), 0));
  copy(node->path(), path, n);
  if (merge) {
    copy(node->path() + n, child->path(), child->npath);
    node->kids()[0] = Ref::share(child->kids()[0]).give();
  } else {
    node->kids()[0] = child.give();
  }
  return node;
}

uint64_t common(const uint8_t* a, uint64_t na, const uint8_t* b, uint64_t nb) {
  const uint64_t m = std::min(na, nb);
  uint64_t n = 0;
  while (n < m && a[n] == b[n]) n++;
  return n;
}

struct Step {
  Stored* node;  // an extension, or a branch
  uint8_t slot;  // and the child of it the walk went into
};

// What a branch collapses to when a delete has taken a child or the
// value from it (trie.py's _delete): itself while two things stand in
// it, else its one child under the child's nibble, or a leaf of its
// value, or nothing.
Ref settled(Ref fork) {
  int live = 0, last = 0;
  for (int k = 0; k < 16; k++)
    if (fork->kids()[k]) live++, last = k;
  if (live > 1 || (live == 1 && fork->nvalue)) return fork;
  if (live == 1) {
    const uint8_t nib = uint8_t(last);
    return make_ext(&nib, 1, Ref::share(fork->kids()[last]));
  }
  if (fork->nvalue) return leaf(nullptr, 0, fork->value(), fork->nvalue);
  return Ref();
}

// The nodes above the place a walk changed, made again from it up to
// the root, each with the changed child in the old one's place (`cur`
// none: a delete left nothing there).
Ref rebuild(const std::vector<Step>& steps, Ref cur) {
  for (uint64_t i = steps.size(); i-- > 0;) {
    Stored* up = steps[i].node;
    const bool took = !cur;
    if (up->kind == kExt) {
      if (!took) cur = make_ext(up->path(), up->npath, std::move(cur));
      continue;
    }
    Ref copy = branch(up, up->value(), up->nvalue);
    set_kid(copy, steps[i].slot, std::move(cur));
    cur = took ? settled(std::move(copy)) : std::move(copy);
  }
  return cur;
}

// trie.py's _insert, without its recursion: down to where the key
// parts from what is there, the new bottom, then the path above it.
Ref insert(Stored* node, const uint8_t* nib, uint64_t n, const uint8_t* val,
           uint64_t nv, std::vector<Step>& steps) {
  steps.clear();
  Ref cur;
  for (;;) {
    if (!node) {
      cur = leaf(nib, n, val, nv);
      break;
    }
    const uint8_t* p = node->path();
    const uint64_t np = node->npath;
    if (node->kind == kLeaf) {
      const uint64_t c = common(p, np, nib, n);
      if (c == np && c == n) {
        cur = leaf(nib, n, val, nv);
        break;
      }
      // a branch where they part, an extension over what they share
      Ref fork = np == c  ? branch(nullptr, node->value(), node->nvalue)
                 : n == c ? branch(nullptr, val, nv)
                          : branch(nullptr, nullptr, 0);
      if (np > c)
        set_kid(fork, p[c],
                leaf(p + c + 1, np - c - 1, node->value(), node->nvalue));
      if (n > c) set_kid(fork, nib[c], leaf(nib + c + 1, n - c - 1, val, nv));
      cur = make_ext(nib, c, std::move(fork));
      break;
    }
    if (node->kind == kExt) {
      const uint64_t c = common(p, np, nib, n);
      if (c == np) {
        steps.push_back({node, 0});
        node = node->kids()[0];
        nib += c, n -= c;
        continue;
      }
      // split the extension at c
      Ref below = Ref::share(node->kids()[0]);
      if (np > c + 1)
        below = make_ext(p + c + 1, np - c - 1, std::move(below));
      Ref fork =
          n == c ? branch(nullptr, val, nv) : branch(nullptr, nullptr, 0);
      set_kid(fork, p[c], std::move(below));
      if (n > c) set_kid(fork, nib[c], leaf(nib + c + 1, n - c - 1, val, nv));
      cur = make_ext(nib, c, std::move(fork));
      break;
    }
    if (n == 0) {
      cur = branch(node, val, nv);
      break;
    }
    steps.push_back({node, nib[0]});
    node = node->kids()[nib[0]];
    nib++, n--;
  }
  return rebuild(steps, std::move(cur));
}

// trie.py's _delete likewise.  False where the key is not there: the
// trie stands as it was.
bool erase(Stored* node, const uint8_t* nib, uint64_t n,
           std::vector<Step>& steps, Ref* out) {
  steps.clear();
  for (;;) {
    if (!node) return false;
    const uint64_t np = node->npath;
    if (node->kind == kLeaf) {
      if (common(node->path(), np, nib, n) != std::max(np, n)) return false;
      break;
    }
    if (node->kind == kExt) {
      if (common(node->path(), np, nib, n) != np) return false;
      steps.push_back({node, 0});
      node = node->kids()[0];
      nib += np, n -= np;
      continue;
    }
    if (n == 0) {
      if (!node->nvalue) return false;
      *out = rebuild(steps, settled(branch(node, nullptr, 0)));
      return true;
    }
    steps.push_back({node, nib[0]});
    node = node->kids()[nib[0]];
    nib++, n--;
  }
  *out = rebuild(steps, Ref());
  return true;
}

// Give every node under `root` that has none its reference, every
// child before its parent (trie.py's _unreferenced and _refer_py: a
// node that has one hides its whole subtree).  Returns how many.
uint64_t refer(Stored* root) {
  if (!root || root->ref_len) return 0;
  std::vector<Stored*> order{root};
  for (uint64_t at = 0; at < order.size(); at++) {
    Stored* node = order[at];
    for (uint64_t k = 0, n = node->nkids(); k < n; k++) {
      Stored* kid = node->kids()[k];
      if (kid && !kid->ref_len) order.push_back(kid);
    }
  }
  Node enc;
  for (uint64_t at = order.size(); at-- > 0;) {  // parents came first
    Stored* node = order[at];
    enc.clear();
    if (node->kind != kBranch)
      enc.put_path(node->path(), node->npath, node->kind == kLeaf);
    for (uint64_t k = 0, n = node->nkids(); k < n; k++) {
      const Stored* kid = node->kids()[k];
      if (kid) enc.put(kid->ref, kid->ref_len);
      else enc.put(0x80);
    }
    if (node->kind != kExt) enc.put_string(node->value(), node->nvalue);
    uint64_t len;
    const uint8_t* at_enc = enc.seal(&len);
    node->ref_len = uint8_t(reference(at_enc, len, node->ref));
  }
  return order.size();
}

// The root is referred to by hash whatever its size.
void root_hash(const Stored* root, uint8_t out[32]) {
  const uint8_t empty = 0x80;  // rlp(b"")
  if (!root) geec_keccak256(&empty, 1, out);
  else if (root->ref_len == kRefMax) std::memcpy(out, root->ref + 1, 32);
  else geec_keccak256(root->ref, root->ref_len, out);
}

// A root as Python holds it: a slot and the slot's generation, so an id
// that was released, or never issued, names nothing.  0 is the empty
// trie, which needs no slot.
struct Slot {
  Stored* root = nullptr;  // null: free
  uint32_t gen = 1;
};

struct Store {
  std::mutex lock;
  std::vector<Slot> slots;
  std::vector<uint32_t> free_slots;
  std::vector<Step> steps;       // scratch of a walk
  std::vector<uint8_t> nibbles;  // and of a key
};

Store& store() {
  static Store* s = new Store;  // never destroyed: handles die at exit too
  return *s;
}

bool find(Store& s, uint64_t id, Stored** root) {
  if (id == 0) {
    *root = nullptr;
    return true;
  }
  const uint64_t at = id & 0xFFFFFFFFu;
  if (at >= s.slots.size() || !s.slots[at].root ||
      s.slots[at].gen != id >> 32)
    return false;
  *root = s.slots[at].root;
  return true;
}

uint64_t issue(Store& s, Ref root) {
  if (!root) return 0;
  if (s.free_slots.empty()) {
    s.free_slots.reserve(s.slots.size() + 1);  // room for its release
    s.slots.emplace_back();
    s.free_slots.push_back(uint32_t(s.slots.size() - 1));
  }
  const uint32_t at = s.free_slots.back();
  s.free_slots.pop_back();
  s.slots[at].root = root.give();
  return uint64_t(s.slots[at].gen) << 32 | at;
}

// n+1 offsets that span `len` bytes, ascending, no span over 2^31
bool spans(const uint64_t* off, uint64_t n, uint64_t len) {
  if (off[0] != 0 || off[n] != len) return false;
  for (uint64_t i = 0; i < n; i++)
    if (off[i + 1] < off[i] || (off[i + 1] - off[i]) >> 31) return false;
  return true;
}

// The key's nibbles, of its Keccak-256 where `secure`, in `s.nibbles`.
void nibbles_of(Store& s, const uint8_t* key, uint64_t n, bool secure) {
  uint8_t hashed[32];
  if (secure) {
    geec_keccak256(key, n, hashed);
    key = hashed, n = 32;
  }
  s.nibbles.resize(2 * n);
  for (uint64_t i = 0; i < n; i++) {
    s.nibbles[2 * i] = key[i] >> 4;
    s.nibbles[2 * i + 1] = key[i] & 15;
  }
}

struct Leaves {  // a walk's leaves, into the caller's buffers or counted
  uint8_t* keys;
  uint8_t* vals;
  uint64_t* key_off;
  uint64_t* val_off;
  uint64_t n = 0, key_len = 0, val_len = 0;

  void add(const std::vector<uint8_t>& nib, const uint8_t* val, uint64_t nv) {
    if (keys) {
      for (uint64_t i = 0; i + 1 < nib.size(); i += 2)
        keys[key_len + i / 2] = uint8_t(nib[i] << 4 | nib[i + 1]);
      std::memcpy(vals + val_len, val, nv);
      key_off[n + 1] = key_len + nib.size() / 2;
      val_off[n + 1] = val_len + nv;
    }
    n++, key_len += nib.size() / 2, val_len += nv;
  }
};

// Every leaf under `root` in key order (trie.py's items: a branch's own
// value before its children's).
void walk(Stored* root, Leaves& out) {
  struct At {
    Stored* node;
    uint32_t depth;  // nibbles above it
    int nib;         // the nibble its parent branch holds it under
  };
  std::vector<At> todo;
  std::vector<uint8_t> path;
  if (root) todo.push_back({root, 0, -1});
  while (!todo.empty()) {
    const At at = todo.back();
    todo.pop_back();
    path.resize(at.depth);
    if (at.nib >= 0) path.push_back(uint8_t(at.nib));
    Stored* node = at.node;
    path.insert(path.end(), node->path(), node->path() + node->npath);
    if (node->kind == kExt) {
      todo.push_back({node->kids()[0], uint32_t(path.size()), -1});
    } else if (node->kind == kLeaf) {
      out.add(path, node->value(), node->nvalue);
    } else {
      if (node->nvalue) out.add(path, node->value(), node->nvalue);
      for (int k = 16; k-- > 0;)
        if (node->kids()[k])
          todo.push_back({node->kids()[k], uint32_t(path.size()), k});
    }
  }
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

// --- the node store's entry points ---
//
// Each takes the store's lock for its whole length and holds no GIL (a
// ctypes call), so a handle may be released on any thread while
// another thread's batch runs.  Each returns 0, or -1 for an argument
// that is not of its form (a root id that was never issued or is
// released, offsets that do not span their buffer), or -2 when memory
// ran out; after either the store is as it was.

// `n` pairs, key i at keys[key_off[i]..key_off[i+1]] and its value
// likewise (n+1 offsets each, from 0 up to keys_len / vals_len), put
// into the trie under `root` (0: the empty trie) in the order given:
// the key hashed first where `secure`, inserted where the value has
// bytes and deleted where it has none.  Then every node made and still
// standing is encoded and hashed.  `root` stands as it was; the new
// trie is `*new_root` (0 where nothing is left in it), to be released
// once, its root hash `hash`, and `*nodes` the nodes encoded.
int geec_trie_update_many(uint64_t root, const uint8_t* keys,
                          const uint64_t* key_off, uint64_t keys_len,
                          const uint8_t* vals, const uint64_t* val_off,
                          uint64_t vals_len, uint64_t n, int secure,
                          uint64_t* new_root, uint8_t hash[32],
                          uint64_t* nodes) {
  Store& s = store();
  std::lock_guard<std::mutex> hold(s.lock);
  try {
    Stored* old;
    if (!find(s, root, &old) || !spans(key_off, n, keys_len) ||
        !spans(val_off, n, vals_len))
      return -1;
    Ref cur = Ref::share(old);
    for (uint64_t i = 0; i < n; i++) {
      nibbles_of(s, keys + key_off[i], key_off[i + 1] - key_off[i], secure);
      const uint64_t nv = val_off[i + 1] - val_off[i];
      if (nv) {
        cur = insert(cur.get(), s.nibbles.data(), s.nibbles.size(),
                     vals + val_off[i], nv, s.steps);
      } else {
        Ref left;
        if (erase(cur.get(), s.nibbles.data(), s.nibbles.size(), s.steps,
                  &left))
          cur = std::move(left);
      }
    }
    const uint64_t encoded = refer(cur.get());
    root_hash(cur.get(), hash);
    *new_root = issue(s, std::move(cur));
    *nodes = encoded;
    return 0;
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

// Give the root `id` back: the nodes nothing else holds are freed.
int geec_trie_release(uint64_t id) {
  Store& s = store();
  std::lock_guard<std::mutex> hold(s.lock);
  Stored* root;
  if (id == 0 || !find(s, id, &root)) return -1;
  Slot& slot = s.slots[id & 0xFFFFFFFFu];
  slot.root = nullptr;
  if (++slot.gen == 0) slot.gen = 1;
  s.free_slots.push_back(uint32_t(id & 0xFFFFFFFFu));  // reserved at issue
  drop(root);
  return 0;
}

// The value under `key` (hashed first where `secure`): its length in
// `*len`, 0 where the key is not there, and its bytes in `out` where
// `cap` has room for them (the caller comes again with more if not).
int geec_trie_get(uint64_t root, const uint8_t* key, uint64_t key_len,
                  int secure, uint8_t* out, uint64_t cap, uint64_t* len) {
  Store& s = store();
  std::lock_guard<std::mutex> hold(s.lock);
  try {
    Stored* node;
    if (!find(s, root, &node) || key_len >> 31) return -1;
    nibbles_of(s, key, key_len, secure);
    const uint8_t* nib = s.nibbles.data();
    uint64_t n = s.nibbles.size();
    *len = 0;
    while (node) {
      const uint64_t np = node->npath;
      if (node->kind == kBranch && n) {
        node = node->kids()[nib[0]];
        nib++, n--;
        continue;
      }
      if (node->kind == kExt) {
        if (common(node->path(), np, nib, n) != np) break;
        node = node->kids()[0];
        nib += np, n -= np;
        continue;
      }
      // a leaf, or the branch the key ends at
      if (common(node->path(), np, nib, n) == std::max(np, n)) {
        *len = node->nvalue;
        if (node->nvalue <= cap) copy(out, node->value(), node->nvalue);
      }
      break;
    }
    return 0;
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

// Every (key, value) under `root` in key order, the keys packed back
// from their nibbles.  Always writes the count and the two byte totals;
// fills keys / vals and the n+1 offsets of each only where all three
// capacities (`max_n` leaves, `keys_cap`, `vals_cap` bytes) have room,
// so a first call with none sizes the second's buffers.
int geec_trie_items(uint64_t root, uint8_t* keys, uint64_t keys_cap,
                    uint64_t* key_off, uint8_t* vals, uint64_t vals_cap,
                    uint64_t* val_off, uint64_t max_n, uint64_t* n,
                    uint64_t* keys_len, uint64_t* vals_len) {
  Store& s = store();
  std::lock_guard<std::mutex> hold(s.lock);
  try {
    Stored* node;
    if (!find(s, root, &node)) return -1;
    Leaves sizes{nullptr, nullptr, nullptr, nullptr};
    walk(node, sizes);
    *n = sizes.n, *keys_len = sizes.key_len, *vals_len = sizes.val_len;
    if (sizes.n <= max_n && sizes.key_len <= keys_cap &&
        sizes.val_len <= vals_cap && keys && vals && key_off && val_off) {
      Leaves fill{keys, vals, key_off, val_off};
      key_off[0] = val_off[0] = 0;
      walk(node, fill);
    }
    return 0;
  } catch (const std::bad_alloc&) {
    return -2;
  }
}

// The store's live nodes.
uint64_t geec_trie_store_nodes(void) {
  Store& s = store();
  std::lock_guard<std::mutex> hold(s.lock);
  return g_nodes;
}

}  // extern "C"
