"""Cross-checks: native C++ crypto vs the pure-Python golden model.

Mirrors the role of libsecp256k1's own test harness
(crypto/secp256k1/libsecp256k1/src/tests.c) for this build's native lib.
Skipped when the library is not built (`make -C native`).
"""

import secrets

import pytest

from eges_tpu.crypto import native
from eges_tpu.crypto import secp256k1 as s
from eges_tpu.crypto.keccak import keccak256_py

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native lib not built")


def test_keccak_matches_python():
    for n in (0, 1, 135, 136, 137, 1000):
        data = secrets.token_bytes(n)
        assert native.keccak256(data) == keccak256_py(data)


def test_sign_recover_verify_roundtrip_matches_golden():
    for _ in range(8):
        priv = secrets.token_bytes(32)
        msg = secrets.token_bytes(32)
        sig_n = native.ec_sign(msg, priv)
        sig_p = s.ecdsa_sign_py(msg, priv)
        assert sig_n == sig_p, "deterministic RFC6979 signatures must agree"
        pub = s.privkey_to_pubkey_py(priv)
        assert native.ec_pubkey(priv) == pub
        assert native.ec_recover(msg, sig_n) == pub
        assert native.ec_verify(msg, sig_n[:64], pub)
        # wrong message fails
        assert not native.ec_verify(secrets.token_bytes(32), sig_n[:64], pub)


def test_recover_rejects_invalid():
    with pytest.raises(ValueError):
        native.ec_recover(bytes(32), bytes(64) + b"\x09")  # bad recid
    with pytest.raises(ValueError):
        native.ec_recover(bytes(32), bytes(65))  # r = s = 0


def test_batch_recover():
    import numpy as np

    n = 16
    hashes = b"".join(secrets.token_bytes(32) for _ in range(n))
    privs = [secrets.token_bytes(32) for _ in range(n)]
    sigs = b"".join(s.ecdsa_sign_py(hashes[32 * i:32 * i + 32], privs[i])
                    for i in range(n))
    pubs, ok = native.ec_recover_batch(hashes, sigs, n)
    assert all(ok)
    for i in range(n):
        assert pubs[64 * i:64 * i + 64] == s.privkey_to_pubkey_py(privs[i])


def test_trie_entry_points_on_the_plain_references_roots():
    """``native/trie.cpp``'s entry points on fixed vectors: the empty
    root, and roots that the benchmark's plain reference
    (``perfbench/ref/state.py``, which ``tests/test_state_reference.py``
    holds the whole program to) gives for the same items and pairs."""
    from perfbench.ref import state as ref

    assert native.has_trie()
    assert native.derive_sha([]) == (ref.EMPTY_ROOT, 0)
    assert ref.EMPTY_ROOT == native.EMPTY_TRIE_ROOT
    assert ref.derive_sha(native.DERIVE_SHA_ITEMS) == native.DERIVE_SHA_ROOT
    root, nodes = native.derive_sha(native.DERIVE_SHA_ITEMS)
    assert root == native.DERIVE_SHA_ROOT and nodes > 200
    assert ref.trie_root(list(native.TRIE_PAIRS)) == native.TRIE_NODES_ROOT
    keys, values = zip(*native.TRIE_PAIRS)
    held, root, nodes = native.trie_update_many(0, keys, values, False)
    assert (root, nodes) == (native.TRIE_NODES_ROOT, 4)
    native.trie_release(held)
    assert native.trie_update_many(0, [], [], True) == (0, ref.EMPTY_ROOT, 0)
    native.self_check()


def _raw_update(root, keys: bytes, key_off, values: bytes, val_off, n=None):
    """``geec_trie_update_many`` with spans the caller made up (the
    binding makes them from the items themselves)."""
    import ctypes

    import numpy as np

    koff = np.asarray(key_off, np.uint64)
    voff = np.asarray(val_off, np.uint64)
    out = ctypes.c_uint64(), ctypes.create_string_buffer(32), \
        ctypes.c_uint64()
    native._trie_rc(native._load().geec_trie_update_many(
        root, keys, koff.ctypes.data, len(keys), values, voff.ctypes.data,
        len(values), len(koff) - 1 if n is None else n, False,
        ctypes.byref(out[0]), out[1], ctypes.byref(out[2])))
    return out[0].value


def _released(live):
    root = native.trie_update_many(live, [b"gone"], [b"soon"], False)[0]
    native.trie_release(root)
    return root


@pytest.mark.parametrize("call", [
    # a root id that was never issued
    lambda live: native.trie_update_many(live ^ 1 << 40, [b"k"], [b"v"], True),
    lambda live: native.trie_get((7 << 32) | 0xFFFFFF, b"k", False),
    lambda live: native.trie_items(live + 1),
    lambda live: native.trie_release(0),
    # one that was released already, through each entry
    lambda live: native.trie_update_many(_released(live), [b"k"], [b"v"],
                                         False),
    lambda live: native.trie_get(_released(live), b"k", True),
    lambda live: native.trie_items(_released(live)),
    lambda live: native.trie_release(_released(live)),
    # offsets past the buffers, not from 0, not ascending
    lambda live: _raw_update(live, b"key", [0, 4], b"v", [0, 1]),
    lambda live: _raw_update(live, b"key", [0, 3], b"v", [0, 2**40]),
    lambda live: _raw_update(live, b"key", [1, 3], b"v", [0, 1]),
    lambda live: _raw_update(live, b"keys", [0, 3, 2, 4], b"abc",
                             [0, 1, 2, 3]),
    # lengths that disagree: buffers longer than their spans, fewer
    # values than keys
    lambda live: _raw_update(live, b"key", [0, 2], b"v", [0, 1]),
    lambda live: _raw_update(live, b"key", [0, 3], b"value", [0, 1]),
    lambda live: native.trie_update_many(live, [b"k", b"l"], [b"v"], False),
], ids=["update-unissued", "get-unissued", "items-unissued", "release-empty",
        "update-released", "get-released", "items-released",
        "release-released", "keys-past-the-end", "values-past-the-end",
        "keys-not-from-0", "keys-descending", "keys-slack", "values-slack",
        "fewer-values"])
def test_trie_store_refuses_what_is_not_of_its_form(call):
    """ValueError, and the store as it was: its live nodes, and a live
    root's hash, reads and items."""
    keys, values = zip(*native.TRIE_PAIRS)
    live = native.trie_update_many(0, keys, values, False)[0]
    try:
        nodes = native._load().geec_trie_store_nodes()
        with pytest.raises(ValueError):
            call(live)
        assert native._load().geec_trie_store_nodes() == nodes
        assert native.trie_items(live) == list(native.TRIE_PAIRS)
        assert native.trie_get(live, keys[0], False) == values[0]
        same, root, made = native.trie_update_many(live, [], [], False)
        assert (root, made) == (native.TRIE_NODES_ROOT, 0)
        native.trie_release(same)
    finally:
        native.trie_release(live)


def test_the_empty_key_is_a_key_of_the_plain_trie():
    """Not refused: the golden model holds it as the root branch's
    value (its keys need not be prefix-free), so the store does."""
    from eges_tpu.core.trie import trie_root

    pairs = [(b"", b"at the root"), (b"\x01", b"one"), (b"\x10", b"two")]
    held, root, _ = native.trie_update_many(0, *zip(*pairs), False)
    assert root == trie_root(dict(pairs))
    assert native.trie_get(held, b"", False) == b"at the root"
    assert native.trie_items(held) == pairs
    native.trie_release(held)
