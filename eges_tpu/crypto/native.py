"""ctypes bindings for the native C++ crypto library.

The reference reaches its C crypto through cgo
(crypto/secp256k1/secp256.go:70,105,126); here the boundary is ctypes
over a plain C ABI (``native/libgeec_native.so``).  The library is
optional: :func:`available` gates use, and the pure-Python golden model
stays authoritative for tests.  Build with ``make -C native``.
"""

from __future__ import annotations

import ctypes
import os

_LIB = None
_TRIED = False


def _lib_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "native", "libgeec_native.so")


def ensure_built() -> str:
    """Build ``native/libgeec_native.so`` if it is missing or older
    than its sources (it is git-ignored, so a fresh checkout has none)
    and return its path.  Raises when ``make`` fails: without the
    library the pure-Python model carries signing and the host
    comparisons, silently and ~10x slower — launchers that need it
    (the test suite, ``chip_smoke.py``) fail loudly instead."""
    import subprocess

    lib = _lib_path()
    native = os.path.dirname(lib)
    srcs = [os.path.join(native, f) for f in (
        "secp256k1.cpp", "keccak.cpp", "election.cpp", "ingress.cpp",
        "trie.cpp", "Makefile")]
    if os.path.exists(lib) and all(
            os.path.getmtime(lib) >= os.path.getmtime(s) for s in srcs):
        return lib
    proc = subprocess.run(["make", "-C", native], capture_output=True,
                          text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native lib build failed:\n{proc.stdout}\n{proc.stderr}")
    return lib


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = _lib_path()
    if not os.path.exists(path):
        return None
    lib = ctypes.CDLL(path)
    lib.geec_keccak256.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_char_p]
    lib.geec_ec_recover.argtypes = [ctypes.c_char_p] * 3
    lib.geec_ec_recover.restype = ctypes.c_int
    lib.geec_ec_verify.argtypes = [ctypes.c_char_p] * 3
    lib.geec_ec_verify.restype = ctypes.c_int
    lib.geec_ec_sign.argtypes = [ctypes.c_char_p] * 3
    lib.geec_ec_sign.restype = ctypes.c_int
    lib.geec_ec_pubkey.argtypes = [ctypes.c_char_p] * 2
    lib.geec_ec_pubkey.restype = ctypes.c_int
    lib.geec_ec_recover_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_char_p]
    try:  # window decoder (native/ingress.cpp); absent in old builds
        lib.geec_decode_txn_window.argtypes = (
            [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint64]
            + [ctypes.c_void_p] * 8)
        lib.geec_decode_txn_window.restype = ctypes.c_int
    except AttributeError:
        pass
    try:  # trie roots (native/trie.cpp); absent in old builds
        lib.geec_derive_sha.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.geec_derive_sha.restype = ctypes.c_int
        lib.geec_trie_hash_nodes.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_char_p]
        lib.geec_trie_hash_nodes.restype = ctypes.c_int
    except AttributeError:
        pass
    try:  # election component (native/election.cpp); absent in old builds
        lib.geec_window_check.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_char_p]
        lib.geec_window_check.restype = ctypes.c_int
        lib.geec_elect_winner.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.geec_elect_winner.restype = ctypes.c_int64
    except AttributeError:
        pass
    _LIB = lib
    return _LIB


def available() -> bool:
    return _load() is not None


def keccak256(data: bytes) -> bytes:
    lib = _load()
    out = ctypes.create_string_buffer(32)
    lib.geec_keccak256(data, len(data), out)
    return out.raw


# (argument, dtype, shape of a row) of geec_decode_txn_window's outputs,
# in the call's order
_WINDOW_COLUMNS = (("decoded", "bool", ()), ("valid", "bool", ()),
                   ("txhash", "uint8", (32,)), ("sighash", "uint8", (32,)),
                   ("sig", "uint8", (65,)), ("nonce", "uint64", ()),
                   ("gas_price", "uint64", ()), ("spans", "uint32", (10, 2)))


def has_decode_window() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "geec_decode_txn_window")


def decode_txn_window(data: bytes, offsets, **columns) -> None:
    """One gossip window of txn frames, packed back to back in ``data``
    (frame ``i`` spans ``offsets[i]..offsets[i+1]``; ``offsets`` is a
    uint64 numpy array of n+1 entries, an empty span a dead frame),
    scanned, ruled on and digested in ONE library call that holds no
    GIL and writes the rows straight into ``columns``: the zeroed,
    C-contiguous numpy arrays :data:`_WINDOW_COLUMNS` names (rows that
    fail are left as they came).  ``native/ingress.cpp`` has the rules;
    raises AttributeError on a library built before the entry existed."""
    lib = _load()
    n = len(offsets) - 1
    # the pointers are only as good as these checks: the library trusts
    # the spans and the row counts it is given
    if (offsets.dtype != "uint64" or not offsets.flags.c_contiguous
            or n < 0 or int(offsets[0]) != 0
            or int(offsets[-1]) != len(data)
            or (offsets[1:] < offsets[:-1]).any()):
        raise ValueError("offsets do not span the window's bytes")
    ptrs = []
    for name, dtype, row in _WINDOW_COLUMNS:
        col = columns[name]
        if (col.dtype != dtype or col.shape != (n, *row)
                or not col.flags.c_contiguous
                or not col.flags.writeable):
            raise ValueError(f"column {name!r} is not {(n, *row)} of "
                             f"{dtype}")
        ptrs.append(col.ctypes.data)
    if lib.geec_decode_txn_window(data, offsets.ctypes.data, n, *ptrs):
        raise MemoryError("native window decoder found no scratch memory")


def window_columns(n: int) -> dict:
    """The zeroed arrays :data:`_WINDOW_COLUMNS` names, for ``n`` rows:
    what :func:`decode_txn_window` fills and ``core.txcolumns.TxColumns``
    holds."""
    import numpy as np

    return {name: np.zeros((n, *row), dtype)
            for name, dtype, row in _WINDOW_COLUMNS}


def pack_txn_frames(frames) -> tuple:
    """``(data, offsets, columns)`` of a window's frames: the frames back
    to back (one join; an empty frame an empty span, a dead row), where
    each begins and ends (one cumsum) and the zeroed columns.  What the
    library call takes, made from the frames themselves, so the spans
    are what it trusts them to be."""
    import numpy as np

    n = len(frames)
    data = b"".join(frames)
    offsets = np.zeros((n + 1,), np.uint64)
    np.cumsum(np.fromiter(map(len, frames), np.uint64, n), out=offsets[1:])
    if int(offsets[-1]) != len(data):
        raise ValueError("a frame's len() is not its size in bytes")
    return data, offsets, window_columns(n)


def decode_txn_frames(frames) -> tuple:
    """:func:`pack_txn_frames`, with the columns filled by ONE
    :func:`decode_txn_window` call: the one way a window of frames
    reaches the library (``ingress.columnar.decode_window`` behind its
    byte gate, ``core.state`` for a block body's wire bytes)."""
    data, offsets, columns = pack_txn_frames(frames)
    decode_txn_window(data, offsets, **columns)
    return data, offsets, columns


def has_trie() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "geec_trie_hash_nodes")


def derive_sha(items) -> tuple[bytes, int]:
    """Root of the trie that holds ``items[i]`` under the key
    ``rlp(i)``, and the number of nodes it took: built, encoded and
    hashed in ONE library call that holds no GIL (``native/trie.cpp``).
    Raises AttributeError on a library built before the entry existed."""
    import numpy as np

    lib = _load()
    n = len(items)
    # the spans are made here from the items themselves, so they are
    # what the library trusts them to be
    offsets = np.zeros((n + 1,), np.uint64)
    np.cumsum(np.fromiter(map(len, items), np.uint64, n), out=offsets[1:])
    root = ctypes.create_string_buffer(32)
    nodes = ctypes.c_uint64()
    if lib.geec_derive_sha(b"".join(items), offsets.ctypes.data, n, root,
                           ctypes.byref(nodes)):
        raise MemoryError("native derive_sha found no scratch memory")
    return root.raw, nodes.value


def trie_hash_nodes(records: bytes, n: int) -> tuple[bytes, bytes]:
    """``n`` trie nodes, flattened children first into ``records`` (the
    form ``native/trie.cpp geec_trie_hash_nodes`` documents and
    ``core/trie.py`` writes), encoded and hashed in ONE library call
    that holds no GIL.  Returns node ``i``'s reference in
    ``refs[33 * i:][:lens[i]]``: the node's own encoding where that is
    under 32 bytes, else ``0xa0`` and its hash.  The library checks
    every length against what is left of ``records``."""
    lib = _load()
    refs = ctypes.create_string_buffer(33 * n)
    lens = ctypes.create_string_buffer(n)
    rc = lib.geec_trie_hash_nodes(records, len(records), n, refs, lens)
    if rc == -1:
        raise ValueError("node records are not of the library's form")
    if rc:
        raise MemoryError("native trie hasher found no scratch memory")
    return refs.raw, lens.raw


def ec_recover(msg_hash: bytes, sig: bytes) -> bytes:
    """65-byte sig -> 64-byte pubkey; raises ValueError on invalid input."""
    lib = _load()
    out = ctypes.create_string_buffer(64)
    rc = lib.geec_ec_recover(msg_hash, sig, out)
    if rc != 0:
        raise ValueError(f"invalid signature (native rc={rc})")
    return out.raw


def ec_verify(msg_hash: bytes, sig_rs: bytes, pub: bytes) -> bool:
    lib = _load()
    return bool(lib.geec_ec_verify(msg_hash, sig_rs[:64], pub))


def ec_sign(msg_hash: bytes, priv: bytes) -> bytes:
    lib = _load()
    out = ctypes.create_string_buffer(65)
    rc = lib.geec_ec_sign(msg_hash, priv, out)
    if rc != 0:
        raise ValueError(f"sign failed (native rc={rc})")
    return out.raw


def ec_pubkey(priv: bytes) -> bytes:
    lib = _load()
    out = ctypes.create_string_buffer(64)
    rc = lib.geec_ec_pubkey(priv, out)
    if rc != 0:
        raise ValueError("invalid private key")
    return out.raw


def ec_recover_batch(hashes: bytes, sigs: bytes, n: int) -> tuple[bytes, bytes]:
    """Flat n*32 hashes + n*65 sigs -> (n*64 pubs, n ok-bytes)."""
    lib = _load()
    pubs = ctypes.create_string_buffer(64 * n)
    ok = ctypes.create_string_buffer(n)
    lib.geec_ec_recover_batch(hashes, sigs, n, pubs, ok)
    return pubs.raw, ok.raw


def window_check(flat_sorted_addrs: bytes, size: int, start: int, n: int,
                 addr: bytes) -> bool:
    """Native committee/acceptor window membership (election.cpp)."""
    lib = _load()
    return bool(lib.geec_window_check(flat_sorted_addrs, size, start, n,
                                      addr))


def elect_winner(records: bytes, m: int) -> int:
    """Winner index among ``m`` 28-byte (addr20 || rand8be) records."""
    lib = _load()
    return int(lib.geec_elect_winner(records, m))


def has_election() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "geec_window_check")


def self_check() -> None:
    """Cross-check native vs the Python golden model."""
    from eges_tpu.crypto import keccak as pk
    from eges_tpu.crypto import secp256k1 as ps

    assert keccak256(b"") == pk.keccak256(b"")
    assert keccak256(b"abc" * 100) == pk.keccak256(b"abc" * 100)
    priv = bytes(range(1, 33))
    msg = pk.keccak256(b"native self check")
    assert ec_pubkey(priv) == ps.privkey_to_pubkey(priv)
    sig = ec_sign(msg, priv)
    assert sig == ps.ecdsa_sign(msg, priv), "sign mismatch vs golden model"
    assert ec_recover(msg, sig) == ps.privkey_to_pubkey(priv)
    assert ec_verify(msg, sig[:64], ps.privkey_to_pubkey(priv))
    _check_decode_window()
    _check_trie()


# roots the benchmark's plain reference gives (perfbench/ref/state.py
# derive_sha / trie_root; tests/test_native.py computes them again)
EMPTY_TRIE_ROOT = bytes.fromhex(
    "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421")
DERIVE_SHA_ITEMS = [bytes([i % 251]) * (i % 40 + 1) for i in range(200)]
DERIVE_SHA_ROOT = bytes.fromhex(
    "09f3ef3772261d6fa788bf21d351a88d96c9ca902badf5a0931af21df2bb16cd")
# the trie of 0x0123 -> "v" and 0x0145 -> bytes(range(40)), children
# first: a leaf short enough to be embedded, a leaf that is hashed, the
# branch over both at nibble 2, the extension (0, 1) above it
TRIE_NODE_RECORDS = (
    b"\x00" + (1).to_bytes(4, "little") + (1).to_bytes(4, "little")
    + b"\x03" + b"v"
    + b"\x00" + (1).to_bytes(4, "little") + (40).to_bytes(4, "little")
    + b"\x05" + bytes(range(40))
    + b"\x02" + b"\x80" * 2 + b"\x00" + (0).to_bytes(4, "little")
    + b"\x80" + b"\x00" + (1).to_bytes(4, "little") + b"\x80" * 11
    + (0).to_bytes(4, "little")
    + b"\x01" + (2).to_bytes(4, "little") + b"\x00\x01"
    + b"\x00" + (2).to_bytes(4, "little"))
TRIE_NODES_ROOT = bytes.fromhex(
    "2da65d865a48d4e29a9d050e1f962d087aa06e920436f481529c55c20bf9b6d3")


def _check_trie() -> None:
    """The two entry points of ``native/trie.cpp`` on fixed vectors."""
    assert derive_sha([]) == (EMPTY_TRIE_ROOT, 0)
    assert derive_sha(DERIVE_SHA_ITEMS)[0] == DERIVE_SHA_ROOT
    refs, lens = trie_hash_nodes(TRIE_NODE_RECORDS, 4)
    assert list(lens) == [3, 33, 33, 33], "embedded leaf, three hashes"
    assert refs[:3] == b"\xc2\x33v" and refs[99] == 0xa0
    assert refs[100:132] == TRIE_NODES_ROOT


def _check_decode_window() -> None:
    """``geec_decode_txn_window`` on a few fixed frames, valid and
    malformed, against answers the golden Keccak gives (this module
    sits below the decoder's Python oracle, which the tier-1
    differential test holds it to case by case)."""
    from eges_tpu.crypto import keccak as pk

    # nonce 9, gas price 2**70, gas 21000, to 00..13, value 7, "chk"
    body = bytes.fromhex("0989400000000000000000825208940001020304050607"
                         "08090a0b0c0d0e0f10111213078363686b")
    r, s = pk.keccak256(b"r"), pk.keccak256(b"s")

    def frame(tail: bytes) -> bytes:
        p = body + tail
        return (bytes([0xC0 + len(p)]) if len(p) < 56
                else b"\xf8" + bytes([len(p)])) + p

    good = frame(b"\x80\x81\xbe\xa0" + r + b"\xa0" + s)  # v 190: chain 77
    frames = [good, frame(b"\x80\x1c\xa0" + r + b"\xa0" + s),  # v 28
              frame(b"\x80\x80\x80\x80"),   # unsigned
              frame(b"\x80\x1e\x01\x01"),   # v 30 names no chain
              b"", good[:-1], good + b"\x00", b"\xc0",
              b"\xff" * 9 + good, good[:3] + b"\xbf" + good[4:]]
    pre = [b"\xeb" + body + b"\x4d\x80\x80", b"\xe8" + body]
    n = len(frames)
    _, _, cols = decode_txn_frames(frames)
    assert cols["decoded"].tolist() == [True] * 4 + [False] * 6
    assert cols["valid"].tolist() == [True] * 2 + [False] * 8
    for i in range(n):
        ok = i < 4
        assert bytes(cols["txhash"][i]) == (
            pk.keccak256(frames[i]) if ok else bytes(32)), "txhash"
        assert cols["nonce"][i] == (9 if ok else 0)
        assert cols["gas_price"][i] == (2**64 - 1 if ok else 0)
        assert bytes(cols["sig"][i]) == (
            r + s + b"\x01" if i < 2 else bytes(65)), "sig"
        assert bytes(cols["sighash"][i]) == (
            pk.keccak256(pre[i]) if i < 2 else bytes(32)), "sighash"
    # the payload spans of row 0: the ten fields back out of the frame
    fields = [good[a:b] for a, b in cols["spans"][0].tolist()]
    assert fields == [b"\x09", bytes.fromhex("400000000000000000"),
                      b"\x52\x08", bytes(range(20)), b"\x07", b"chk", b"",
                      b"\xbe", r, s], "spans"
