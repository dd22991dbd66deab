"""ctypes bindings for the native C++ crypto library.

The reference reaches its C crypto through cgo
(crypto/secp256k1/secp256.go:70,105,126); here the boundary is ctypes
over a plain C ABI (``native/libgeec_native.so``).  The library is
optional: :func:`available` gates use, and the pure-Python golden model
stays authoritative for tests.  Build with ``make -C native``.
"""

from __future__ import annotations

import array
import ctypes
import itertools
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
        u64, out64 = ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)
        lib.geec_trie_update_many.argtypes = [
            u64, ctypes.c_char_p, ctypes.c_void_p, u64,
            ctypes.c_char_p, ctypes.c_void_p, u64, u64, ctypes.c_int,
            out64, ctypes.c_char_p, out64]
        lib.geec_trie_update_many.restype = ctypes.c_int
        lib.geec_trie_release.argtypes = [u64]
        lib.geec_trie_release.restype = ctypes.c_int
        lib.geec_trie_get.argtypes = [
            u64, ctypes.c_char_p, u64, ctypes.c_int, ctypes.c_char_p,
            u64, out64]
        lib.geec_trie_get.restype = ctypes.c_int
        lib.geec_trie_items.argtypes = [
            u64, ctypes.c_char_p, u64, ctypes.c_void_p, ctypes.c_char_p,
            u64, ctypes.c_void_p, u64, out64, out64, out64]
        lib.geec_trie_items.restype = ctypes.c_int
        lib.geec_trie_store_nodes.argtypes = []
        lib.geec_trie_store_nodes.restype = u64
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
    """Whether the loaded library has ``native/trie.cpp``'s entry points
    (the node store's among them: they came last)."""
    lib = _load()
    return lib is not None and hasattr(lib, "geec_trie_update_many")


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


def _spans(items) -> tuple:
    """``(data, offsets)``: the items back to back and where each begins
    and ends (n+1 uint64), made here from the items themselves, so the
    spans are what the library takes them to be (the store checks them
    all the same).  An ``array``: a batch of one, a contract's few
    slots, pays a microsecond for it."""
    return b"".join(items), array.array(
        "Q", itertools.accumulate(map(len, items), initial=0))


def _trie_rc(rc: int) -> None:
    if rc == -1:
        raise ValueError("not a live root of the trie store, or spans "
                         "that do not fit their buffers")
    if rc:
        raise MemoryError("native trie store found no memory")


def trie_update_many(root: int, keys, values,
                     secure: bool) -> tuple[int, bytes, int]:
    """``keys[i] -> values[i]`` put, in the order given, into the trie
    the store holds under ``root`` (0: the empty trie): a key hashed
    first where ``secure``, inserted where its value has bytes, deleted
    where it is empty; then every node made is encoded and hashed.  ONE
    library call that holds no GIL.  ``root`` stands as it was; returns
    the new trie's id (0 where it is empty; else to be given back once
    through :func:`trie_release`), its root hash and the number of nodes
    encoded.  ValueError for a root that is not live."""
    lib = _load()
    if len(keys) != len(values):
        raise ValueError("as many values as keys")
    kdata, koff = _spans(keys)
    vdata, voff = _spans(values)
    new_root, nodes = ctypes.c_uint64(), ctypes.c_uint64()
    root_hash = ctypes.create_string_buffer(32)
    _trie_rc(lib.geec_trie_update_many(
        root, kdata, koff.buffer_info()[0], len(kdata), vdata,
        voff.buffer_info()[0], len(vdata), len(keys), bool(secure),
        ctypes.byref(new_root), root_hash, ctypes.byref(nodes)))
    return new_root.value, root_hash.raw, nodes.value


def trie_release(root: int) -> None:
    """Give a root id back to the store, once: the nodes nothing else
    holds are freed.  Any thread may."""
    _trie_rc(_load().geec_trie_release(root))


def trie_get(root: int, key: bytes, secure: bool):
    """The value under ``key`` (hashed first where ``secure``) in the
    trie under ``root``, or None."""
    lib = _load()
    n = ctypes.c_uint64()
    cap = 128  # an account's RLP fits; a longer value comes again
    while True:
        out = ctypes.create_string_buffer(cap)
        _trie_rc(lib.geec_trie_get(root, key, len(key), bool(secure), out,
                                   cap, ctypes.byref(n)))
        if n.value <= cap:
            return out.raw[:n.value] or None
        cap = n.value


def trie_items(root: int) -> list:
    """Every ``(key, value)`` of the trie under ``root`` in key order
    (the keys as the trie holds them: hashed, in a secure trie): one
    call that sizes the buffers and one that fills them."""
    lib = _load()
    n, nk, nv = ctypes.c_uint64(), ctypes.c_uint64(), ctypes.c_uint64()
    sizes = (ctypes.byref(n), ctypes.byref(nk), ctypes.byref(nv))
    _trie_rc(lib.geec_trie_items(root, None, 0, None, None, 0, None, 0,
                                 *sizes))
    count = n.value
    keys = ctypes.create_string_buffer(nk.value)
    vals = ctypes.create_string_buffer(nv.value)
    koff = array.array("Q", bytes(8 * (count + 1)))
    voff = array.array("Q", bytes(8 * (count + 1)))
    _trie_rc(lib.geec_trie_items(
        root, keys, nk.value, koff.buffer_info()[0], vals, nv.value,
        voff.buffer_info()[0], count, *sizes))
    kraw, vraw = keys.raw, vals.raw
    return [(kraw[ka:kb], vraw[va:vb]) for ka, kb, va, vb in zip(
        koff, koff[1:], voff, voff[1:])]


def read_trie_store(metrics) -> None:
    """Set the gauge ``trie.store_nodes``, the store's live nodes, in
    the registry ``metrics``.  Called when the DEFAULT registry is read,
    as ``profiler.read_cpu`` is: a handle is released wherever it dies
    (the collector's thread among them), so nothing on that path writes
    a metric.  Absent where the library has no store."""
    if has_trie():
        metrics.gauge("trie.store_nodes").set(
            _load().geec_trie_store_nodes())


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
# 0x0123 -> "v" and 0x0145 -> bytes(range(40)): a leaf short enough to
# be embedded, a leaf that is hashed, the branch over both at nibble 2,
# the extension (0, 1) above it
TRIE_PAIRS = ((bytes.fromhex("0123"), b"v"),
              (bytes.fromhex("0145"), bytes(range(40))))
TRIE_NODES_ROOT = bytes.fromhex(
    "2da65d865a48d4e29a9d050e1f962d087aa06e920436f481529c55c20bf9b6d3")


def _check_trie() -> None:
    """The entry points of ``native/trie.cpp`` on fixed vectors: the
    whole-built trie, and the store through a batch, a delete that
    merges what it leaves, reads, and the release that frees."""
    assert derive_sha([]) == (EMPTY_TRIE_ROOT, 0)
    assert derive_sha(DERIVE_SHA_ITEMS)[0] == DERIVE_SHA_ROOT
    lib = _load()
    before = lib.geec_trie_store_nodes()
    keys, values = zip(*TRIE_PAIRS)
    root, root_hash, nodes = trie_update_many(0, keys, values, False)
    assert (root_hash, nodes) == (TRIE_NODES_ROOT, 4)
    assert trie_items(root) == list(TRIE_PAIRS)
    assert trie_get(root, keys[1], False) == values[1]
    assert trie_get(root, b"\x01", False) is None
    # without the second key the first is one leaf, hashed as the root
    one, one_hash, nodes = trie_update_many(root, keys[1:], [b""], False)
    assert nodes == 1 and trie_items(one) == [TRIE_PAIRS[0]]
    assert one_hash == keccak256(b"\xc5\x83\x20" + keys[0] + values[0])
    assert trie_update_many(one, keys[:1], [b""], False)[:2] == (
        0, EMPTY_TRIE_ROOT)
    assert lib.geec_trie_store_nodes() == before + 5
    trie_release(root)
    trie_release(one)
    assert lib.geec_trie_store_nodes() == before, "the store leaked"


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
