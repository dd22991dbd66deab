"""A trie's root from ONE library call (``native/trie.cpp``) against the
Python rung of ``eges_tpu/core/trie.py``, byte for byte.

A root is consensus: a node that encodes or hashes a trie node otherwise
refuses every sound block.  So the library's two entry points
(``derive_sha``: a block's transactions or receipts, built whole;
``IncrementalTrie.root``: a persistent trie's nodes that have no reference
yet, flattened and handed over once) are held to the golden model case by
case: item counts and sizes on both sides of every RLP boundary, embedded
nodes, branches with values, extensions that merge after a delete, the
empty trie; and the reference memo is counted: a node that has its
reference is never encoded or hashed again, through any parent.
"""

import contextlib
import random

import pytest

from eges_tpu.core import rlp, trie
from eges_tpu.core.trie import (IncrementalTrie, derive_sha, trie_prove,
                                trie_root, verify_proof)
from eges_tpu.crypto import native
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.utils.metrics import DEFAULT as metrics

pytestmark = pytest.mark.skipif(not native.has_trie(),
                                reason="native lib lacks the trie entries")


class _BuiltBeforeTheTrie:
    """The loaded library as an older build of it: every symbol but
    ``native/trie.cpp``'s."""

    def __init__(self, lib):
        self._lib = lib

    def __getattr__(self, name):
        if name in ("geec_derive_sha", "geec_trie_hash_nodes"):
            raise AttributeError(name)
        return getattr(self._lib, name)


@contextlib.contextmanager
def old_library(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(native, "_LIB", _BuiltBeforeTheTrie(native._load()))
        assert native.available() and not native.has_trie()
        yield


class Counted:
    """What ``trie.nodes`` and ``trie.native_nodes`` rose by inside."""

    def __enter__(self):
        self._before = self._read()
        return self

    def __exit__(self, *exc):
        self.nodes, self.by_library = (
            a - b for a, b in zip(self._read(), self._before))

    @staticmethod
    def _read():
        return (metrics.counter("trie.nodes").value,
                metrics.counter("trie.native_nodes").value)


def _items(n: int, size: int) -> list:
    rng = random.Random(n * 1009 + size)
    return [rng.randbytes(size) for _ in range(n)]


@pytest.mark.parametrize("size", [1, 5, 31, 32, 33, 199, 267, 600])
@pytest.mark.parametrize("n", [0, 1, 2, 15, 16, 17, 127, 128, 129, 1000, 4000])
def test_derive_sha_is_the_python_rungs(n, size):
    """rlp(index) keys of one byte, of two (from 128) and of three (from
    256); items that embed in their parent (a leaf under 32 bytes) and
    items that take a long-string header (from 56 bytes)."""
    items = _items(n, size)
    with Counted() as c:
        root = derive_sha(items)
    tally = [0]
    assert root == trie_root({rlp.encode(i): item
                              for i, item in enumerate(items)}, tally)
    assert c.nodes == c.by_library == tally[0] >= n
    assert (root == trie.EMPTY_ROOT) == (n == 0)


def test_derive_sha_on_a_library_without_the_entry_points(monkeypatch):
    items = _items(300, 40)
    with Counted() as c:
        root = derive_sha(items)
    with old_library(monkeypatch), Counted() as py:
        assert derive_sha(items) == root
        assert derive_sha([]) == trie.EMPTY_ROOT
    assert (py.nodes, py.by_library) == (c.nodes, 0) and c.by_library > 300


def _random_key(rng, key_bytes: int) -> bytes:
    # few distinct nibbles: shared prefixes, keys that end inside
    # another's path (a branch with a value), the empty key
    return bytes(rng.choice((0x00, 0x01, 0x10, 0x11, 0xAB))
                 for _ in range(rng.randrange(key_bytes + 1)))


@pytest.mark.parametrize("key_bytes", [1, 2, 4, 32])
@pytest.mark.parametrize("seed", range(5))
def test_incremental_trie_rungs_agree_after_every_root(seed, key_bytes,
                                                       monkeypatch):
    """Update, overwrite and delete at random, 1-40 byte values (most
    leaves embed): the library's rung, the Python rung and ``trie_root``
    of the same pairs after every ``root()``, each rung on its own
    handles, so each hashes what IT left unhashed."""
    rng = random.Random(seed * 37 + key_bytes)
    by_library = by_python = IncrementalTrie()
    pairs: dict = {}
    for _ in range(40):
        for _ in range(rng.randrange(1, 10)):
            roll = rng.random()
            if roll < 0.55 or not pairs:
                key = _random_key(rng, key_bytes)
            else:
                key = rng.choice(sorted(pairs))
            value = b"" if roll > 0.8 else rng.randbytes(rng.randrange(1, 41))
            if value:
                pairs[key] = value
            else:
                pairs.pop(key, None)
            # an empty value is a delete; of an absent key, a no-op
            by_library = by_library.update(key, value)
            by_python = by_python.update(key, value)
        with Counted() as c:
            root = by_library.root()
        with old_library(monkeypatch), Counted() as py:
            assert by_python.root() == root
        assert root == trie_root(pairs)
        assert c.nodes == c.by_library == py.nodes and py.by_library == 0
        assert dict(by_library.items()) == pairs


def _state_like(n: int) -> dict:
    return {keccak256(i.to_bytes(4, "big")):
            rlp.encode([i, 10**18 + i, trie.EMPTY_ROOT, keccak256(b"")])
            for i in range(n)}


@pytest.mark.parametrize("first", ["old", "new"])
def test_an_old_handles_root_stands_when_a_newer_one_is_hashed(first):
    """Two handles share most nodes; hashing either leaves references on
    the shared ones, and the other's root is what it would have been."""
    pairs = _state_like(600)
    keys = sorted(pairs)
    old = IncrementalTrie.from_pairs(pairs)
    new, changed = old, dict(pairs)
    for k in keys[::7]:
        new = new.update(k, b"changed" + k)
        changed[k] = b"changed" + k
    for k in keys[3::11]:
        new = new.delete(k)
        changed.pop(k, None)
    want = {"old": trie_root(pairs), "new": trie_root(changed)}
    handles = {"old": old, "new": new}
    second = "new" if first == "old" else "old"
    assert handles[first].root() == want[first]
    assert handles[second].root() == want[second]
    with Counted() as c:  # both again: nothing is left to hash
        assert (old.root(), new.root()) == (want["old"], want["new"])
    assert c.nodes == 0


def test_proofs_verify_against_the_librarys_roots():
    """``trie_prove`` builds its nodes from the batch builder; they have
    to hash up to the roots the library gives."""
    pairs = {bytes([i, i * 3 % 251]) + b"key-%d" % i: b"value-%d" % (i * i)
             for i in range(60)}
    root = IncrementalTrie.from_pairs(pairs).root()
    for k, v in pairs.items():
        assert verify_proof(root, k, trie_prove(pairs, k)) == v
    assert verify_proof(root, b"absent", trie_prove(pairs, b"absent")) is None
    items = _items(300, 120)
    keyed = {rlp.encode(i): item for i, item in enumerate(items)}
    root = derive_sha(items)
    for i in (0, 1, 127, 128, 255, 256, 299):
        assert verify_proof(root, rlp.encode(i),
                            trie_prove(keyed, rlp.encode(i))) == items[i]


def _on_path(root, key: bytes) -> list:
    """The nodes a lookup of ``key`` passes, the one it ends at among
    them."""
    nibs, node, out = tuple(trie._nibbles(key)), root, []
    while node is not None:
        out.append(node)
        if isinstance(node, trie._Leaf):
            break
        if isinstance(node, trie._Ext):
            if nibs[:len(node.path)] != node.path:
                break
            node, nibs = node.child, nibs[len(node.path):]
        elif nibs:
            node, nibs = node.children[nibs[0]], nibs[1:]
        else:
            break
    return out


def _all_nodes(root) -> dict:
    out, stack = {}, [root]
    while stack:
        node = stack.pop()
        out[id(node)] = node
        if isinstance(node, trie._Ext):
            stack.append(node.child)
        elif isinstance(node, trie._Branch):
            stack.extend(c for c in node.children if c is not None)
    return out


@pytest.mark.parametrize("rung", ["library", "python"])
def test_a_root_hashes_its_dirty_paths_and_no_sibling(rung, monkeypatch):
    """The memo is the REFERENCE: after k updates on a hashed trie of
    16,384 leaves a root encodes and hashes the nodes on the k paths,
    once each, and none of the clean children beside them (a memo of the
    encoding alone hashed each clean child again through its re-encoded
    parent: 17.8k hashes for 9.4k dirty nodes at 5,000 updates)."""
    pairs = _state_like(16384)
    keys = sorted(pairs)
    touched = random.Random(46).sample(keys, 1500)
    patch = old_library(monkeypatch) if rung == "python" \
        else contextlib.nullcontext()
    with patch:
        base = IncrementalTrie.from_pairs(pairs)
        with Counted() as whole:
            root = base.root()
        assert whole.nodes == len(_all_nodes(base._root)) > 16384

        t = base
        for k in touched[:1200]:
            t = t.update(k, pairs[k] + b"!")
        on_paths = {id(n) for k in touched[:1200]
                    for n in _on_path(t._root, k)}
        with Counted() as c:
            changed = t.root()
        assert c.nodes == len(on_paths) < whole.nodes // 4
        assert c.by_library == (c.nodes if rung == "library" else 0)

        # deletes merge what they leave and an insert may split a leaf:
        # the nodes MADE, wherever they stand, and again none beside them
        t2 = t
        for k in touched[1200:]:
            t2 = t2.delete(k)
        for i in range(100):
            t2 = t2.update(keccak256(b"fresh%d" % i), b"a new account")
        made = _all_nodes(t2._root).keys() - _all_nodes(t._root).keys()
        with Counted() as c2:
            assert t2.root() not in (changed, root)
        assert c2.nodes == len(made) < whole.nodes // 8

        with Counted() as again:
            assert (t.root(), base.root()) == (changed, root)
        assert again.nodes == 0
    assert changed != root
