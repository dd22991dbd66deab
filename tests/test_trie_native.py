"""The library's tries (``native/trie.cpp``) against the Python rung of
``eges_tpu/core/trie.py``, byte for byte.

A root is consensus: a node that encodes or hashes a trie node otherwise
refuses every sound block.  So the library's entry points
(``derive_sha``: a block's transactions or receipts, built whole and
forgotten; the node STORE behind ``IncrementalTrie``: a persistent
trie's nodes kept, shared and counted in the library, a batch of keys
one call) are held to the golden model case by case: item counts and
sizes on both sides of every RLP boundary, embedded nodes, branches with
values, extensions that merge after a delete, the empty trie; the
reference memo is counted: a node that has its reference is never
encoded or hashed again, through any parent; and so is the store: what a
dropped fork or a pruned height held alone is freed, on whichever thread
the handle dies.
"""

import contextlib
import gc
import random
import threading

import pytest

from eges_tpu.core import rlp, trie
from eges_tpu.core.trie import (IncrementalTrie, SecureIncrementalTrie,
                                derive_sha, secure_trie_root, trie_prove,
                                trie_root, verify_proof)
from eges_tpu.crypto import native
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.utils.metrics import DEFAULT as metrics

pytestmark = pytest.mark.skipif(not native.has_trie(),
                                reason="native lib lacks the trie entries")


class _BuiltBeforeTheTrie:
    """The loaded library as an older build of it: no ``derive_sha`` and
    no way INTO the node store (the handles the store has issued still
    read and release through it)."""

    def __init__(self, lib):
        self._lib = lib

    def __getattr__(self, name):
        if name in ("geec_derive_sha", "geec_trie_update_many"):
            raise AttributeError(name)
        return getattr(self._lib, name)


@contextlib.contextmanager
def old_library(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(native, "_LIB", _BuiltBeforeTheTrie(native._load()))
        assert native.available() and not native.has_trie()
        yield


@pytest.fixture(params=["library", "golden"])
def trie_rung(request, monkeypatch):
    """Both rungs of the persistent trie, for the files whose cases
    build states and storage (``pytestmark = usefixtures``): the
    library's node store, and the Python nodes a library without it
    leaves the work to."""
    patch = old_library(monkeypatch) if request.param == "golden" \
        else contextlib.nullcontext()
    with patch:
        yield request.param


class Counted:
    """What ``trie.nodes``, ``trie.native_nodes`` and
    ``trie.native_updates`` rose by inside."""

    def __enter__(self):
        self._before = self._read()
        return self

    def __exit__(self, *exc):
        self.nodes, self.by_library, self.updates = (
            a - b for a, b in zip(self._read(), self._before))

    @staticmethod
    def _read():
        return tuple(metrics.counter(name).value for name in (
            "trie.nodes", "trie.native_nodes", "trie.native_updates"))


def store_nodes() -> int:
    """The gauge ``trie.store_nodes``, read as the registry reads it."""
    native.read_trie_store(metrics)
    return metrics.gauge("trie.store_nodes").value


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


@pytest.mark.parametrize("secure", [False, True], ids=["plain", "secure"])
@pytest.mark.parametrize("how", ["singly", "update_many"])
@pytest.mark.parametrize("key_bytes", [1, 2, 4, 32])
@pytest.mark.parametrize("seed", range(5))
def test_incremental_trie_rungs_agree_after_every_batch(seed, key_bytes, how,
                                                        secure, monkeypatch):
    """Update, overwrite and delete at random, 1-40 byte values (most
    leaves embed): the store, the Python rung and ``trie_root`` of the
    same pairs after EVERY batch, with ``get`` of every present key and
    of absent ones and ``items()``; each rung on its own handles.  A
    batch through ``update_many`` encodes and hashes what the golden
    model's ``root()`` does for the same operations, node for node."""
    rng = random.Random(seed * 37 + key_bytes)
    kind = SecureIncrementalTrie if secure else IncrementalTrie
    whole = secure_trie_root if secure else trie_root
    by_library = by_python = kind()
    pairs: dict = {}
    for _ in range(40):
        keys, values = [], []
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
                # an empty value is a delete; of an absent key, a no-op
                pairs.pop(key, None)
            keys.append(key)
            values.append(value)
        with Counted() as c:
            if how == "singly":
                for key, value in zip(keys, values):
                    by_library = by_library.update(key, value)
            else:
                by_library = by_library.update_many(keys, values)
            root = by_library.root()
        with old_library(monkeypatch), Counted() as py:
            if how == "singly":
                for key, value in zip(keys, values):
                    by_python = by_python.update(key, value)
            else:
                by_python = by_python.update_many(keys, values)
            assert by_python.root() == root
        assert root == whole(pairs)
        assert c.updates == len(keys) and py.updates == py.by_library == 0
        assert c.nodes == c.by_library
        if how == "update_many":
            assert c.nodes == py.nodes
        held = dict(by_library.items())
        assert held == dict(by_python.items())
        assert set(pairs.values()) == set(held.values()) \
            and len(held) == len(pairs)
        if not secure:
            assert held == pairs
        for key in sorted(pairs) + [_random_key(rng, key_bytes + 1)
                                    for _ in range(4)]:
            assert by_library.get(key) == by_python.get(key) \
                == pairs.get(key)
    before = store_nodes()
    del by_library
    assert store_nodes() < before or not pairs


def _state_like(n: int) -> dict:
    return {keccak256(i.to_bytes(4, "big")):
            rlp.encode([i, 10**18 + i, trie.EMPTY_ROOT, keccak256(b"")])
            for i in range(n)}


def _changed(old, pairs: dict):
    """A handle some updates and deletes after ``old``, and its pairs."""
    keys = sorted(pairs)
    new, changed = old, dict(pairs)
    for k in keys[::7]:
        new = new.update(k, b"changed" + k)
        changed[k] = b"changed" + k
    for k in keys[3::11]:
        new = new.delete(k)
        changed.pop(k, None)
    return new, changed


@pytest.mark.parametrize("first", ["old", "new"])
def test_an_old_handles_root_stands_when_a_newer_one_is_hashed(first):
    """Two handles share most nodes; hashing either leaves references on
    the shared ones, and the other's root is what it would have been."""
    pairs = _state_like(600)
    old = IncrementalTrie.from_pairs(pairs)
    new, changed = _changed(old, pairs)
    want = {"old": trie_root(pairs), "new": trie_root(changed)}
    handles = {"old": old, "new": new}
    second = "new" if first == "old" else "old"
    assert handles[first].root() == want[first]
    assert handles[second].root() == want[second]
    with Counted() as c:  # both again: nothing is left to hash
        assert (old.root(), new.root()) == (want["old"], want["new"])
    assert c.nodes == 0


def test_an_old_handle_stands_through_1000_later_batches_and_their_release():
    """The store shares nodes between roots: an old handle's root, gets
    and items are what they were after 1,000 batches on handles derived
    from it, and after every newer handle is dropped; the store is then
    back at the old handle's own nodes."""
    pairs = _state_like(600)
    keys = sorted(pairs)
    old = IncrementalTrie.from_pairs(pairs)
    want, alone = trie_root(pairs), store_nodes()
    rng = random.Random(50)
    new = old
    for i in range(1000):
        batch = rng.sample(keys, 5)
        new = new.update_many(
            batch + [b"fresh-%d" % i],
            [b"" if rng.random() < 0.3 else b"later" + k for k in batch]
            + [b"a new account"])
    assert new.root() != want and store_nodes() > alone

    def stands():
        assert old.root() == want and dict(old.items()) == pairs
        assert all(old.get(k) == pairs[k] for k in keys[::13])
        assert old.get(b"fresh-7") is None

    stands()
    del new
    stands()
    assert store_nodes() == alone


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
    parent: 17.8k hashes for 9.4k dirty nodes at 5,000 updates).  The
    nodes are counted on the golden model's own (``model``); the rung
    under test does the same batches and has to count the same."""
    pairs = _state_like(16384)
    keys = sorted(pairs)
    touched = random.Random(46).sample(keys, 1500)
    fresh = [keccak256(b"fresh%d" % i) for i in range(100)]
    batches = [
        (list(pairs), list(pairs.values())),
        (touched[:1200], [pairs[k] + b"!" for k in touched[:1200]]),
        # deletes merge what they leave and an insert may split a leaf:
        # the nodes MADE, wherever they stand, and again none beside them
        (touched[1200:] + fresh, [b""] * 300 + [b"a new account"] * 100),
    ]
    with old_library(monkeypatch):
        model = [IncrementalTrie()]
        for ks, vs in batches:
            model.append(model[-1].update_many(ks, vs))
        want = [m.root() for m in model]
    made = [_all_nodes(after._root).keys() - (
        _all_nodes(before._root).keys() if before._root else set())
        for before, after in zip(model, model[1:])]
    assert made[1] == {id(n) for k in touched[:1200]
                       for n in _on_path(model[2]._root, k)}
    assert len(made[0]) > 16384 > 4 * len(made[1]) > 8 * len(made[2]) > 0

    patch = old_library(monkeypatch) if rung == "python" \
        else contextlib.nullcontext()
    with patch:
        handles = [IncrementalTrie()]
        for (ks, vs), nodes in zip(batches, made):
            with Counted() as c:
                handles.append(handles[-1].update_many(ks, vs))
                handles[-1].root()
            assert c.nodes == len(nodes)
            assert c.by_library == (c.nodes if rung == "library" else 0)
            assert c.updates == (len(ks) if rung == "library" else 0)
        with Counted() as again:
            assert [h.root() for h in handles] == want
        assert again.nodes == 0
    assert len(set(want)) == 4


# --- the store: what is freed, and on which thread ---

def test_a_fork_built_on_a_base_and_dropped_leaves_the_store_as_it_was():
    pairs = _state_like(2000)
    base = IncrementalTrie.from_pairs(pairs)
    before = store_nodes()
    fork, _ = _changed(base, pairs)
    second = fork.update_many([b"on the fork"], [b"too"])
    assert store_nodes() > before
    del fork  # the second fork still holds what they share
    assert second.get(b"on the fork") == b"too"
    assert store_nodes() > before
    del second
    assert store_nodes() == before
    del base
    assert store_nodes() == before - len(
        _all_nodes_of(pairs)), "the base's own nodes, all of them"


def _all_nodes_of(pairs: dict) -> dict:
    """The golden model's nodes for ``pairs``, built apart."""
    node = None
    for k, v in pairs.items():
        node = trie._insert(node, tuple(trie._nibbles(k)), v)
    return _all_nodes(node)


def test_a_chain_past_its_keep_prunes_its_heights_nodes():
    """``_remember_state``'s floor: a chain of ``_STATE_KEEP`` + 80 tiny
    heights holds the nodes of the heights it keeps and no more, and
    gives them all back when it goes."""
    from eges_tpu.core.chain import BlockChain, make_genesis
    from eges_tpu.core.types import Header, Transaction, new_block
    from eges_tpu.crypto import secp256k1 as secp

    priv = bytes([5]) * 32
    addr = secp.pubkey_to_address(secp.privkey_to_pubkey(priv))
    alloc = {bytes([i]) * 20: 10**18 for i in range(1, 40)}
    alloc[addr] = 10**21
    gc.collect()
    before = store_nodes()
    chain = BlockChain(genesis=make_genesis(alloc=alloc), alloc=alloc)
    at_genesis = store_nodes()
    assert at_genesis > before
    keep, size = chain._STATE_KEEP, {0: at_genesis}
    for n in range(1, keep + 81):
        t = Transaction(nonce=n - 1, gas_price=0, to=bytes([n % 39 + 1]) * 20,
                        value=1).signed(priv)
        kept, root, rroot, gas, bloom = chain.execute_preview([t])
        parent = chain.head()
        blk = new_block(Header(parent_hash=parent.hash, number=n,
                               time=parent.header.time + 1, root=root,
                               receipt_hash=rroot, gas_used=gas,
                               bloom=bloom), txs=kept)
        assert chain.offer(blk), chain.last_error
        size[n] = store_nodes()
    # every height makes a few nodes and none goes while all are kept;
    # the first prune (the dict past keep + 64 entries) gives back the
    # heights under its floor, but for the last few that a kept
    # overlay's bases still reach (StateDB._MAX_DEPTH)
    a_height = (size[keep] - at_genesis) / keep
    assert 2 <= a_height <= 12
    assert all(size[n] > size[n - 1] for n in range(1, keep + 64))
    assert size[keep + 64] < size[keep + 63] - 16 * a_height
    assert size[keep + 80] < size[keep + 63]
    del chain, parent, blk, kept, t
    gc.collect()
    assert store_nodes() == before


def _forks_of(base, pairs: dict, seed: int, rounds: int) -> list:
    """Build a fork of ``base`` and drop it, ``rounds`` times over; the
    roots the forks had."""
    rng = random.Random(seed)
    keys = sorted(pairs)
    roots = []
    for i in range(rounds):
        batch = rng.sample(keys, 40)
        fork = base.update_many(
            batch, [b"" if j % 4 == 0 else b"fork-%d-%d" % (seed, i)
                    for j in range(40)])
        fork = fork.update(b"fork's own", b"%d" % i)
        roots.append(fork.root())
    return roots


def test_threads_that_fork_one_base_read_a_serial_runs_roots():
    """Two threads build and drop forks of one base while a third reads
    it: every root is the serial run's, the base stands, and the store
    is back at the base's nodes (a lost update of a count would leave
    it above, or free a node the base still needs)."""
    import sys

    pairs = _state_like(3000)
    base = IncrementalTrie.from_pairs(pairs)
    want, alone = base.root(), store_nodes()
    serial = {seed: _forks_of(base, pairs, seed, 150) for seed in (1, 2)}
    assert store_nodes() == alone
    got, read = {}, []

    def fork(seed):
        got[seed] = _forks_of(base, pairs, seed, 150)

    def reader():
        for k in sorted(pairs)[::3]:
            read.append((base.get(k) == pairs[k], base.root() == want))

    threads = [threading.Thread(target=fork, args=(seed,), daemon=True)
               for seed in (1, 2)]
    threads.append(threading.Thread(target=reader, daemon=True))
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert got == serial
    assert len(read) == 1000 and all(a and b for a, b in read)
    assert dict(base.items()) == pairs
    assert store_nodes() == alone


def test_a_handle_the_collector_finalises_on_another_thread_releases_once(
        monkeypatch):
    released = []
    release = native.trie_release
    monkeypatch.setattr(native, "trie_release",
                        lambda root: (released.append(root), release(root)))
    before = store_nodes()

    class Holder:
        pass

    holder = Holder()
    holder.me = holder  # a cycle: only the collector frees it
    holder.trie = IncrementalTrie.from_pairs(_state_like(50))
    root_id = holder.trie._id
    assert root_id and store_nodes() > before
    gc.collect()  # nothing of ours is garbage yet
    del holder
    assert released == []
    t = threading.Thread(target=gc.collect, daemon=True)
    t.start()
    t.join(60)
    assert not t.is_alive()
    assert released == [root_id] and store_nodes() == before
    with pytest.raises(ValueError):
        release(root_id)  # the id is dead: a second release is refused
    assert store_nodes() == before


# --- StateDB.root(): one batch ---

def _a_state_roots_counts(monkeypatch, golden: bool):
    from eges_tpu.core.state import Account, StateDB

    patch = old_library(monkeypatch) if golden else contextlib.nullcontext()
    roots, rose = [], []
    with patch:
        state = StateDB({bytes([i]) * 20: Account(balance=i)
                         for i in range(1, 200)})
        for step in range(3):
            accounts = metrics.counter("state.root_accounts").value
            with Counted() as c:
                roots.append(state.root())
            rose.append((metrics.counter("state.root_accounts").value
                         - accounts, c.updates))
            state = state.copy()
            for i in range(1, 60, step + 1):
                state.add_balance(bytes([i]) * 20, 5)
            state.set_account(bytes([150 + step]) * 20, Account())  # emptied
            state.set_storage_many(bytes([7]) * 20, {1: step + 1, 2: 0})
    return roots, rose


def test_a_state_root_is_one_batch_of_its_dirty_accounts(monkeypatch):
    """``trie.native_updates`` rises by what ``state.root_accounts``
    does, a call (the storage writes went in their own batch before);
    under a library without the store by 0, and the roots are the same."""
    roots, rose = _a_state_roots_counts(monkeypatch, golden=False)
    assert [a for a, _ in rose] == [199, 60, 31]
    assert all(a == u for a, u in rose)
    golden_roots, golden_rose = _a_state_roots_counts(monkeypatch, golden=True)
    assert golden_roots == roots and len(set(roots)) == 3
    assert [a for a, _ in golden_rose] == [199, 60, 31]
    assert all(u == 0 for _, u in golden_rose)
