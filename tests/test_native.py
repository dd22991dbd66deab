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
    """``native/trie.cpp``'s two entry points on fixed vectors: the empty
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
    pairs = [(bytes.fromhex("0123"), b"v"),
             (bytes.fromhex("0145"), bytes(range(40)))]
    assert ref.trie_root(pairs) == native.TRIE_NODES_ROOT
    refs, lens = native.trie_hash_nodes(native.TRIE_NODE_RECORDS, 4)
    assert list(lens) == [3, 33, 33, 33]
    assert refs[33 * 3 + 1:] == native.TRIE_NODES_ROOT
    native.self_check()


@pytest.mark.parametrize("records, n", [
    (native.TRIE_NODE_RECORDS[:-1], 4),            # cut short
    (native.TRIE_NODE_RECORDS + b"\x00", 4),       # slack after the last
    (native.TRIE_NODE_RECORDS, 3),                 # fewer nodes than records
    (b"\x03", 1),                                  # no such kind
    (b"\x01" + bytes(4) + b"\x00" + bytes(4), 1),  # a child not before it
    (b"\x01" + bytes(4) + b"\x80", 1),             # an extension over nothing
    (b"\x00" + (1).to_bytes(4, "little") + bytes(4) + b"\x10", 1),  # nibble 16
    (b"\x00" + b"\xff" * 8, 1),                    # lengths past the end
])
def test_trie_hash_nodes_refuses_malformed_records(records, n):
    with pytest.raises(ValueError):
        native.trie_hash_nodes(records, n)
