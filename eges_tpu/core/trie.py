"""Merkle-Patricia trie: root hashing and key/value proofs-of-inclusion.

Fills the role of the reference's ``trie/`` package for the paths the
consensus capability set needs, in two forms.  ``derive_sha`` (ref:
core/types/derive_sha.go) and ``trie_root`` build a trie WHOLE from a
key set and fold it into its root: a block's transactions and receipts
are keyed by index and never updated.  :class:`IncrementalTrie` (ref:
trie/trie.go, trie/secure_trie.go) is the persistent, structure-sharing
trie of the state and of contract storage: an update returns a new
handle that shares every untouched node with the old one, so a chain
snapshot is a root pointer and a block's root costs its dirty paths.

A root is the block path's largest cost (three of them a height, 4000
items and 5,000 dirty accounts each at the 1024-validator operating
point), and nearly all of it is walking, copying and ENCODING nodes,
not hashing them.  So a root is one library call where
``native/trie.cpp`` is built in: ``derive_sha`` builds its trie there
and forgets it, and the persistent trie's NODES LIVE THERE, in a
reference-counted store: ``update_many`` hands a batch of keys over
(hashed there, where the trie is secure), the library copies the paths,
encodes and hashes the nodes it made and answers with the new root's id
and hash, and an :class:`IncrementalTrie` is that id, given back when
the handle dies.  No trie node is a Python object then.  The Python
nodes below are the golden model of both and run where the library
lacks the entry points (one test, :func:`native.has_trie`; a handle
stays on the rung that made it); the counters ``trie.nodes`` /
``trie.native_nodes`` / ``trie.native_updates`` say which ran.
"""

from __future__ import annotations

from eges_tpu.core import rlp
from eges_tpu.crypto import native
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.utils.metrics import DEFAULT as metrics

EMPTY_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421"
)  # keccak256(rlp(b''))


def _nibbles(key: bytes) -> list[int]:
    out = []
    for b in key:
        out.append(b >> 4)
        out.append(b & 0xF)
    return out


def _hp_encode(nibbles: list[int], terminal: bool) -> bytes:
    """Hex-prefix encoding (ref: trie/encoding.go hexToCompact)."""
    flag = 2 if terminal else 0
    if len(nibbles) % 2:
        head = [flag + 1] + nibbles
    else:
        head = [flag, 0] + nibbles
    return bytes(
        (head[i] << 4) | head[i + 1] for i in range(0, len(head), 2)
    )


def _node_ref(encoded: bytes):
    """Nodes < 32 bytes embed in the parent; otherwise refer by hash."""
    if len(encoded) < 32:
        return rlp.decode(encoded)
    return keccak256(encoded)


def _lcp_below(items, depth: int) -> int:
    """Longest common nibble prefix of ``items`` at/below ``depth``."""
    first = items[0][0]
    lcp = len(first)
    for nib, _ in items[1:]:
        i = depth
        limit = min(len(first), len(nib))
        while i < limit and nib[i] == first[i]:
            i += 1
        lcp = min(lcp, i)
    return lcp


def _build(items: list[tuple[list[int], bytes]], depth: int, tally=None):
    """Build the node for items sharing a prefix of length ``depth``.

    Returns the RLP *structure* of the node (to be encoded / hashed by
    the caller).  ``items`` must be sorted and have distinct keys.
    ``tally[0]`` goes up by one a node built, where a list is given.
    """
    if not items:
        return b""
    if tally is not None:
        tally[0] += 1
    if len(items) == 1:
        nib, val = items[0]
        return [_hp_encode(nib[depth:], True), val]

    # longest common prefix below depth
    first = items[0][0]
    lcp = _lcp_below(items, depth)
    if lcp > depth:
        child = _build(items, lcp, tally)
        return [_hp_encode(first[depth:lcp], False), _node_ref(rlp.encode(child))]

    # branch node
    children = [b""] * 16
    value = b""
    buckets: dict[int, list] = {}
    for nib, val in items:
        if len(nib) == depth:
            value = val
        else:
            buckets.setdefault(nib[depth], []).append((nib, val))
    for idx, bucket in buckets.items():
        child = _build(bucket, depth + 1, tally)
        children[idx] = _node_ref(rlp.encode(child))
    return children + [value]


def trie_root(pairs: dict[bytes, bytes], tally=None) -> bytes:
    """Root hash of the MPT holding ``pairs`` (raw keys); ``tally`` as
    :func:`_build` has it."""
    if not pairs:
        return EMPTY_ROOT
    items = sorted((_nibbles(k), v) for k, v in pairs.items())
    node = _build(items, 0, tally)
    return keccak256(rlp.encode(node))


def secure_trie_root(pairs: dict[bytes, bytes]) -> bytes:
    """Root with keccak-hashed keys (ref: trie/secure_trie.go)."""
    return trie_root({keccak256(k): v for k, v in pairs.items()})


def _count(nodes: int, native_did: bool) -> None:
    """One root's nodes, encoded and hashed, into the counters."""
    metrics.counter("trie.nodes").inc(nodes)
    if native_did:
        metrics.counter("trie.native_nodes").inc(nodes)


def derive_sha(encoded_items: list[bytes]) -> bytes:
    """Tx/receipt root: trie keyed by rlp(index) (ref:
    core/types/derive_sha.go:30).  Built, encoded and hashed in one
    library call where the library has it; else by :func:`trie_root`."""
    if native.has_trie():
        root, nodes = native.derive_sha(encoded_items)
        _count(nodes, True)
        return root
    tally = [0]
    root = trie_root({rlp.encode(i): item
                      for i, item in enumerate(encoded_items)}, tally)
    _count(tally[0], False)
    return root


# ---------------------------------------------------------------------------
# proofs of inclusion / exclusion (ref: trie/proof.go Prove/VerifyProof)
# ---------------------------------------------------------------------------

def _hp_decode(data: bytes) -> tuple[list[int], bool]:
    nibs = _nibbles(data)
    flag = nibs[0]
    terminal = flag >= 2
    skip = 1 if flag % 2 else 2
    return nibs[skip:], terminal


def trie_prove(pairs: dict[bytes, bytes], key: bytes) -> list[bytes]:
    """Merkle proof for ``key`` against ``trie_root(pairs)``: the encoded
    nodes on the key's path that are referenced by hash (embedded short
    nodes travel inside their parent, as in the reference's proof lists).
    Valid for absent keys too (an exclusion proof)."""
    if not pairs:
        return []
    nib = _nibbles(key)
    items = sorted((_nibbles(k), v) for k, v in pairs.items())
    depth = 0
    proof: list[bytes] = []
    enc = rlp.encode(_build(items, depth))  # root node
    hashed = True  # the root is always by-hash
    while True:
        if hashed:
            proof.append(enc)
        if len(items) == 1:
            return proof
        lcp = _lcp_below(items, depth)
        if lcp > depth:  # extension node
            if nib[depth:lcp] != items[0][0][depth:lcp]:
                return proof  # diverges here: exclusion proven
            depth = lcp
            enc = rlp.encode(_build(items, depth))
            hashed = len(enc) >= 32
            continue
        # branch node
        if len(nib) == depth:
            return proof  # value (or absence) sits in this branch
        bucket = [(n, v) for n, v in items
                  if len(n) > depth and n[depth] == nib[depth]]
        if not bucket:
            return proof  # empty child slot: exclusion proven
        items = bucket
        depth += 1
        enc = rlp.encode(_build(items, depth))
        hashed = len(enc) >= 32


def verify_proof(root: bytes, key: bytes, proof: list[bytes]):
    """Walk ``proof`` from ``root``; returns the proven value, or None
    when the proof shows the key absent.  Raises ValueError on any
    inconsistency (a forged proof)."""
    if root == EMPTY_ROOT:
        if proof:
            raise ValueError("non-empty proof for the empty trie")
        return None
    nib = _nibbles(key)
    it = iter(proof)

    def load(ref):
        if isinstance(ref, (bytes, bytearray)) and len(ref) == 32:
            enc = next(it, None)
            if enc is None:
                raise ValueError("proof truncated")
            if keccak256(enc) != bytes(ref):
                raise ValueError("proof node hash mismatch")
            return rlp.decode(enc)
        return ref  # embedded node (list) or empty slot (b"")

    node = load(root)
    i = 0
    while True:
        if isinstance(node, (bytes, bytearray)):
            if len(node) == 0:
                return None  # empty slot: key absent
            raise ValueError("malformed proof node")
        if len(node) == 17:  # branch
            if i == len(nib):
                val = bytes(node[16])
                return val if val else None
            node = load(node[nib[i]])
            i += 1
            continue
        if len(node) != 2:
            raise ValueError("malformed proof node")
        path, terminal = _hp_decode(bytes(node[0]))
        if terminal:
            return bytes(node[1]) if nib[i:] == path else None
        if nib[i:i + len(path)] != path:
            return None  # extension diverges: key absent
        i += len(path)
        node = load(node[1])


def secure_trie_prove(pairs: dict[bytes, bytes], key: bytes) -> list[bytes]:
    """Proof against :func:`secure_trie_root` (keccak-hashed keys)."""
    return trie_prove({keccak256(k): v for k, v in pairs.items()},
                      keccak256(key))


def verify_secure_proof(root: bytes, key: bytes, proof: list[bytes]):
    return verify_proof(root, keccak256(key), proof)


# ---------------------------------------------------------------------------
# persistent incremental trie (ref: trie/trie.go insert/delete — redesigned
# as an immutable structure-sharing tree instead of geth's mutable nodes +
# journal, so every chain snapshot holds a root pointer and per-block cost
# is O(dirty keys x depth), round-2 verdict item 10)
# ---------------------------------------------------------------------------

# The golden model's nodes (the library's store keeps the same shapes
# and the same memo, native/trie.cpp).  A node's ``_ref`` is its
# REFERENCE, what a parent's encoding holds for it: the node's own
# encoding where that is under 32 bytes, else 0xa0 and its Keccak-256
# (the RLP of the hash).  None until a root() reaches the node; then
# kept for the node's life, which nothing mutates, so no parent and no
# later height encodes or hashes the node again.

class _Leaf:
    __slots__ = ("path", "value", "_ref")

    def __init__(self, path: tuple[int, ...], value: bytes):
        self.path = path
        self.value = value
        self._ref = None


class _Ext:
    __slots__ = ("path", "child", "_ref")

    def __init__(self, path: tuple[int, ...], child):
        self.path = path
        self.child = child
        self._ref = None


class _Branch:
    __slots__ = ("children", "value", "_ref")

    def __init__(self, children: tuple, value: bytes):
        self.children = children  # 16-tuple of nodes | None
        self.value = value
        self._ref = None


def _unreferenced(root) -> list:
    """The nodes under ``root`` that have no reference yet, every child
    before its parent.  A node that has one hides its whole subtree."""
    order = []
    stack = [root]
    while stack:
        node = stack.pop()
        order.append(node)
        if type(node) is _Branch:
            stack.extend([c for c in node.children
                          if c is not None and c._ref is None])
        elif type(node) is _Ext and node.child._ref is None:
            stack.append(node.child)
    order.reverse()  # parents came first
    return order


def _held(ref: bytes):
    """A child's reference as ``rlp.encode`` is to be handed it."""
    return ref[1:] if len(ref) == 33 else rlp.decode(ref)


def _refer_py(order: list) -> None:
    """The golden model: one ``rlp.encode`` and one Keccak a node."""
    for node in order:
        if type(node) is _Leaf:
            s = [_hp_encode(list(node.path), True), node.value]
        elif type(node) is _Ext:
            s = [_hp_encode(list(node.path), False), _held(node.child._ref)]
        else:
            s = [b"" if c is None else _held(c._ref)
                 for c in node.children] + [node.value]
        enc = rlp.encode(s)
        node._ref = enc if len(enc) < 32 else b"\xa0" + keccak256(enc)


def _refer(root) -> None:
    """Give every node under ``root`` that has none its reference."""
    order = _unreferenced(root)
    _refer_py(order)
    _count(len(order), False)


def _insert(node, nibs: tuple[int, ...], value: bytes):
    """Insert/overwrite; returns the new node (shares unchanged subtrees)."""
    if node is None:
        return _Leaf(nibs, value)
    if isinstance(node, _Leaf):
        if node.path == nibs:
            return _Leaf(nibs, value)
        # branch at the divergence point, extension over the shared
        # prefix (a chain of single-child branches would hash to a
        # non-canonical root)
        n = _common_len(node.path, nibs)
        children: list = [None] * 16
        bval = b""
        for path, val in ((node.path, node.value), (nibs, value)):
            if len(path) == n:
                bval = val
            else:
                children[path[n]] = _Leaf(path[n + 1:], val)
        return _make_ext(node.path[:n], _Branch(tuple(children), bval))
    if isinstance(node, _Ext):
        p = node.path
        n = _common_len(p, nibs)
        if n == len(p):
            return _make_ext(p, _insert(node.child, nibs[n:], value))
        # split the extension at n
        below = node.child if len(p) == n + 1 else _Ext(p[n + 1:], node.child)
        children: list = [None] * 16
        children[p[n]] = below
        branch = _Branch(tuple(children), b"")
        branch = _insert(branch, nibs[n:], value)
        return _make_ext(p[:n], branch) if n else branch
    # branch
    if not nibs:
        return _Branch(node.children, value)
    i = nibs[0]
    new_child = _insert(node.children[i], nibs[1:], value)
    ch = list(node.children)
    ch[i] = new_child
    return _Branch(tuple(ch), node.value)


def _common_len(a, b) -> int:
    n = 0
    m = min(len(a), len(b))
    while n < m and a[n] == b[n]:
        n += 1
    return n


def _make_ext(path: tuple[int, ...], child):
    """Extension constructor that collapses degenerate shapes."""
    if not path:
        return child
    if isinstance(child, _Ext):
        return _Ext(path + child.path, child.child)
    if isinstance(child, _Leaf):
        return _Leaf(path + child.path, child.value)
    return _Ext(path, child)


def _delete(node, nibs: tuple[int, ...]):
    """Delete; returns the new node or None.  Missing keys are a no-op."""
    if node is None:
        return None
    if isinstance(node, _Leaf):
        return None if node.path == nibs else node
    if isinstance(node, _Ext):
        n = _common_len(node.path, nibs)
        if n != len(node.path):
            return node  # key not present
        child = _delete(node.child, nibs[n:])
        if child is node.child:
            return node
        if child is None:
            return None
        return _make_ext(node.path, child)
    # branch
    if not nibs:
        if not node.value:
            return node
        new = _Branch(node.children, b"")
    else:
        i = nibs[0]
        child = _delete(node.children[i], nibs[1:])
        if child is node.children[i]:
            return node
        ch = list(node.children)
        ch[i] = child
        new = _Branch(tuple(ch), node.value)
    # collapse if degenerate
    live = [(i, c) for i, c in enumerate(new.children) if c is not None]
    if new.value and not live:
        return _Leaf((), new.value)
    if not new.value and len(live) == 1:
        i, c = live[0]
        return _make_ext((i,), c)
    if not new.value and not live:
        return None
    return new


def _get(node, nibs: tuple[int, ...]):
    while node is not None:
        if isinstance(node, _Leaf):
            return node.value if node.path == nibs else None
        if isinstance(node, _Ext):
            n = _common_len(node.path, nibs)
            if n != len(node.path):
                return None
            node, nibs = node.child, nibs[n:]
            continue
        if not nibs:
            return node.value or None
        node, nibs = node.children[nibs[0]], nibs[1:]
    return None


def _packed(nibs) -> bytes:
    return bytes((nibs[i] << 4) | nibs[i + 1]
                 for i in range(0, len(nibs), 2))


def _walk(node, path):
    """``(nibble path, value)`` over every leaf under ``node``."""
    if node is None:
        return
    if isinstance(node, _Leaf):
        yield path + node.path, node.value
    elif isinstance(node, _Ext):
        yield from _walk(node.child, path + node.path)
    else:  # _Branch
        if node.value:
            yield path, node.value
        for i, ch in enumerate(node.children):
            if ch is not None:
                yield from _walk(ch, path + (i,))


class IncrementalTrie:
    """Immutable MPT handle: ``update``/``delete``/``update_many``
    return NEW handles that share structure with the old one, so chain
    snapshots are cheap and a block's root costs O(dirty keys x depth).

    On the library's rung the handle is a root id of the node store
    (``_id``; given back when the handle dies, on whichever thread) and
    the root hash its batch came back with: ``update_many`` is ONE call
    that walks, copies, encodes and hashes, ``get`` and ``items`` one
    read.  On the golden rung it is the Python root node (``_root``) and
    ``root()`` encodes and hashes the nodes made since.  An empty handle
    is neither; its first update takes the rung the loaded library
    allows, and every handle derived from it stays there."""

    __slots__ = ("_root", "_id", "_hash")

    def __init__(self, _root=None, _id: int = 0, _hash=None):
        self._root = _root
        self._id = _id
        self._hash = _hash

    def __del__(self):
        if self._id:
            native.trie_release(self._id)

    @classmethod
    def from_pairs(cls, pairs: dict[bytes, bytes]) -> "IncrementalTrie":
        return cls().update_many(list(pairs), list(pairs.values()))

    def update_many(self, keys, values,
                    secure: bool = False) -> "IncrementalTrie":
        """``keys[i] -> values[i]`` in the order given, an empty value a
        delete (of an absent key, a no-op); the keys hashed first where
        ``secure``."""
        if self._id or (self._root is None and native.has_trie()):
            new_id, root, nodes = native.trie_update_many(
                self._id, keys, values, secure)
            _count(nodes, True)
            metrics.counter("trie.native_updates").inc(len(keys))
            return IncrementalTrie(_id=new_id, _hash=root)
        node = self._root
        for key, value in zip(keys, values, strict=True):
            nibs = tuple(_nibbles(keccak256(key) if secure else key))
            node = _insert(node, nibs, value) if value \
                else _delete(node, nibs)
        return IncrementalTrie(node)

    def update(self, key: bytes, value: bytes) -> "IncrementalTrie":
        return self.update_many((key,), (value,))

    def delete(self, key: bytes) -> "IncrementalTrie":
        return self.update_many((key,), (b"",))

    def get(self, key: bytes, secure: bool = False):
        if self._id:
            return native.trie_get(self._id, key, secure)
        return _get(self._root,
                    tuple(_nibbles(keccak256(key) if secure else key)))

    def items(self):
        """``(key, value)`` over every leaf in key order, keys re-packed
        from nibble paths.  This is the state-sync SERVING walk (ref
        role: trie.Iterator in eth/downloader/statesync.go's source
        side); on a secure trie the keys that come back are the hashed
        ones."""
        if self._id:
            return iter(native.trie_items(self._id))
        return ((_packed(nibs), val) for nibs, val in _walk(self._root, ()))

    def root(self) -> bytes:
        if self._id:
            return self._hash
        node = self._root
        if node is None:
            return EMPTY_ROOT
        if node._ref is None:
            _refer(node)
        ref = node._ref
        # the root is referred to by hash whatever its size
        return ref[1:] if len(ref) == 33 else keccak256(ref)


class SecureIncrementalTrie:
    """Secure-keyed wrapper (keys pre-hashed, ref: trie/secure_trie.go;
    by the library where its store holds the trie)."""

    __slots__ = ("_t",)

    def __init__(self, _t: IncrementalTrie | None = None):
        self._t = _t if _t is not None else IncrementalTrie()

    def update_many(self, keys, values) -> "SecureIncrementalTrie":
        return SecureIncrementalTrie(self._t.update_many(keys, values, True))

    def update(self, key: bytes, value: bytes) -> "SecureIncrementalTrie":
        return self.update_many((key,), (value,))

    def delete(self, key: bytes) -> "SecureIncrementalTrie":
        return self.update_many((key,), (b"",))

    def get(self, key: bytes):
        return self._t.get(key, True)

    def items(self):
        """(hashed_key, value) pairs — see IncrementalTrie.items."""
        return self._t.items()

    @classmethod
    def from_hashed_pairs(cls, pairs) -> "SecureIncrementalTrie":
        """Rebuild from ``(hashed_key, value)`` pairs as served by
        ``items()`` — the state-sync RECEIVING side.  The caller proves
        integrity by comparing ``root()`` against a certified
        commitment; nothing here trusts the pairs."""
        return cls(IncrementalTrie.from_pairs(dict(pairs)))

    def root(self) -> bytes:
        return self._t.root()
