"""Wire-speed columnar ingest tests for tier-1.

Covers: the vectorized window decoder (``eges_tpu/ingress/columnar.py``)
against ``Transaction.decode`` — per-field columns, malformed and
non-canonical frame rejection, the native window decoder against its
Python oracle case by case, the fallback without it, the one packing
helper under both the window decoder and a block body's
``core.state._signature_rows`` — and the pool's one way in: a window of
frames (``TxPool.add_remotes_window``) against the same transactions as
objects (``TxPool.add_remotes``, the window of their columns) over the
same stream (identical stats, admission order and ledger billing), in
chunks of 1, 13 and 45; ``add_remotes`` over more than a window's rows
and over a repeated transaction; the scheduler's window submit; the
invalid-signature flood (billed to the flooder, no recovery a row);
and two same-seed 4-node sims, one fed 12-transaction gossip bundles
and one the same stream a transaction a message: equal pool stats,
billing and ``commit_anatomy`` pool stages on every node.
"""

import dataclasses
import json
import os
import random
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from eges_tpu.core import rlp
from eges_tpu.core.txpool import TxPool
from eges_tpu.core.rlp import RLPError
from eges_tpu.core.types import Transaction
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.ingress import (admit_remotes, admit_remotes_window,
                              decode_txn_window)
from eges_tpu.ingress import columnar
from eges_tpu.utils import ledger as LG

PRIV_A = bytes(range(1, 33))
PRIV_B = bytes(range(2, 34))


def _mixed_stream(n: int = 45) -> list[Transaction]:
    """Deterministic admission-exercising stream: signed (legacy and
    EIP-155), unsigned, structurally-valid-but-unrecoverable, and
    invalid-signature rows, with nonce collisions driving price-bump
    replacements."""
    out = []
    for i in range(n):
        t = Transaction(nonce=i % 9, gas_price=1 + i, gas_limit=21000,
                        to=bytes(20) if i % 7 else None, value=i,
                        payload=b"x" * (i % 11))
        if i % 6 == 5:
            out.append(dataclasses.replace(t, v=27, r=0, s=1))  # invalid
        elif i % 6 == 4:
            out.append(t.signed(PRIV_B, chain_id=77))
        elif i % 6 == 3:
            out.append(t)  # unsigned: no signature_parts, rejected
        else:
            out.append(t.signed(PRIV_A))
    return out


class _WallClock:
    """Pool clock whose window timer never fires: flushes in these
    tests happen only on the max_batch threshold or an explicit
    ``_on_window`` — keeps the flush cadence test-controlled."""

    @staticmethod
    def now() -> float:
        return 100.0

    @staticmethod
    def call_later(delay, fn):
        class _Never:
            @staticmethod
            def cancel() -> None:
                pass
        return _Never()


# -- decoder vs the scalar oracle -----------------------------------------

def test_decode_window_matches_scalar_decode_column_for_column():
    txns = _mixed_stream(40)
    frames = [t.encode() for t in txns]
    frames += [b"\xff\x01\x02", frames[0][:10], b""]  # undecodable tail

    ref = columnar.columns_from_txns(
        [Transaction.decode(f) for f in frames[:40]])
    # an object window's signature columns wait for the flush
    assert not ref.valid.any() and not ref.sig.any()
    assert ref.signed() is ref and ref.signed().valid.any()
    got = decode_txn_window(frames)

    assert got.n == len(frames)
    assert not got.decoded[40:].any()
    for name in ("sighash", "sig", "txhash", "gas_price", "nonce",
                 "decoded", "valid"):
        assert np.array_equal(getattr(got, name)[:40], getattr(ref, name)), \
            name
    for i in range(40):
        assert got.hashes[i] == txns[i].hash
        # direct-construction txn() must equal the full scalar decoder
        assert got.txn(i) == Transaction.decode(frames[i])
        assert got.txn(i).hash == txns[i].hash


def test_decode_window_rejects_exactly_what_scalar_decode_rejects():
    good = _mixed_stream(6)[0].encode()
    bad = [
        b"",                          # empty
        good[:-3],                    # truncated payload
        b"\x85abc",                   # truncated string header
        bytes([good[0] + 1]) + good[1:] + b"\x00",  # list overrun
        good.replace(b"\x82\x52\x08", b"\x83\x00\x52\x08", 1),  # 0-pad int
    ]
    cols = decode_txn_window([good] + bad)
    assert cols.decoded[0] and not cols.decoded[1:].any()
    for i, frame in enumerate(bad):
        try:
            Transaction.decode(frame)
        except (RLPError, ValueError, IndexError):
            continue
        raise AssertionError(
            f"scalar decoder accepted frame {i} the window decoder "
            f"dropped: {frame.hex()}")


def test_decode_window_without_the_native_decoder_is_identical(monkeypatch):
    from eges_tpu.utils.metrics import DEFAULT as metrics

    frames = [t.encode() for t in _mixed_stream(20)]
    ref = decode_txn_window(frames)
    # force the Python rung, as a library without the entry would
    monkeypatch.setattr(columnar, "_DECODE", columnar._decode_frames)
    rows = metrics.counter("ingress.decode_rows").value
    native_rows = metrics.counter("ingress.decode_native_rows").value
    got = decode_txn_window(frames)
    assert columnar._same_columns(got, ref)
    assert metrics.counter("ingress.decode_rows").value == rows + 20
    assert metrics.counter("ingress.decode_native_rows").value == \
        native_rows


# -- the native window decoder vs its oracle, a case per kind -------------

_R = int.from_bytes(bytes(range(7, 39)), "big")
_S = int.from_bytes(bytes(range(9, 41)), "big")


def _tx(**kw) -> Transaction:
    base = dict(nonce=3, gas_price=7, gas_limit=21000, to=bytes(20),
                value=5, payload=b"p" * 100, v=27, r=_R, s=_S)
    base.update(kw)
    return Transaction(**base)


def _raw(fields: list) -> bytes:
    """A list frame around field ENCODINGS given as they should stand
    on the wire, canonical or not."""
    body = b"".join(fields)
    return rlp._encode_length(len(body), 0xC0) + body


def _enc_fields(t: Transaction) -> list:
    return [rlp.encode(x) for x in t.to_rlp()]


def _with_field(k: int, enc: bytes) -> bytes:
    fields = _enc_fields(_tx())
    fields[k] = enc
    return _raw(fields)


def _case_generator():
    from perfbench import gen

    x = gen.Transfers(2**31 + 7, accounts=4, count=40, payload_bytes=100,
                      gas_limit=29000)
    return list(x.frames) + [t.encode() for t in _mixed_stream(30)]


def _case_eip155_v():
    return [_tx(v=v).encode()
            for v in (35, 36, 37, 38, 2**63, 2**63 + 1, 2**64 - 1)]


def _case_v_unassigned():
    return [_tx(v=v).encode() for v in [*range(1, 27), *range(29, 35)]]


def _case_recid_out_of_range():
    # an unprotected v of 0 reads as recid -27; 27 and 28 are the two
    # that stand
    return [_tx(v=v).encode() for v in (0, 27, 28)]


def _case_r_s():
    return [_tx(r=0).encode(), _tx(s=0).encode(), _tx(r=0, s=0).encode(),
            _tx(r=1 << 256).encode(), _tx(s=(1 << 256) + 9).encode(),
            _tx(r=1, s=1).encode(), _tx(r=(1 << 256) - 1).encode(),
            _with_field(8, b"\x82\x00\x05"), _with_field(9, b"\x00"),
            _with_field(9, b"\xa1\x00" + bytes(range(1, 33)))]


def _case_wide_nonce_and_price():
    return [_tx(nonce=n, gas_price=g).encode()
            for n, g in ((2**64 - 1, 2**64 - 1), (2**64, 1), (1, 2**64),
                         (2**200 + 3, 2**64 + 5), (0, 0), (127, 128),
                         (2**900, 2**56))]


def _case_to_width():
    return [_tx(to=bytes(n)).encode() for n in (19, 21, 1, 32)] + \
        [_tx(to=None).encode(), _tx(to=b"\x00" * 19 + b"\x01").encode()]


def _case_field_count():
    f = _enc_fields(_tx())
    return [_raw(f[:9]), _raw(f + [b"\x01"]), _raw(f[:1]), _raw([]),
            _raw(f + f)]


def _case_nested_list():
    return [_with_field(k, b"\xc1\x05") for k in range(10)] + \
        [_with_field(5, b"\xc0"), _with_field(0, b"\xf8\x38" + b"\x01" * 56)]


def _case_trailing_bytes():
    good = _tx().encode()
    short = _tx(payload=b"", r=1, s=2).encode()  # a one-byte list header
    return [good + b"\x00", good + good, short + b"\x80",
            # the header claims one byte more than the frame has, and
            # one fewer
            bytes([short[0] + 1]) + short[1:],
            bytes([short[0] - 1]) + short[1:],
            good[:2] + bytes([good[2] + 1]) + good[3:] + b"\x00"]


def _case_truncations():
    out = []
    for good in (_tx().encode(), _tx(payload=b"", r=1, s=2).encode(),
                 _tx(v=2**63, payload=b"q" * 300).encode()):
        out += [good[:k] for k in range(len(good) + 1)]
    return out


def _case_non_canonical_lengths():
    sixty = b"z" * 60
    return [
        _with_field(0, b"\x81\x05"),            # a byte under 0x80, headed
        _with_field(5, b"\x81\x7f"),
        _with_field(5, b"\x81\x80"),            # canonical: stands
        _with_field(5, b"\xb8\x05hello"),        # long form under 56
        _with_field(5, b"\xb8\x37" + b"z" * 55),
        _with_field(5, b"\xb8\x38" + b"z" * 56),  # canonical: stands
        _with_field(5, b"\xb9\x00\x3c" + sixty),  # zero-led length
        _with_field(5, b"\xba\x00\x00\x3c" + sixty),
        b"\xf8\x20" + b"\x01" * 0x20,           # long list under 56
        b"\xf9\x00\x80" + b"\x01" * 0x80,       # zero-led list length
        b"\xf8",                                 # a length with no bytes
        b"\xb8\x38" + b"z" * 56,                 # a string, not a list
    ]


def _case_length_overflow():
    eight = lambda x: x.to_bytes(8, "big")  # noqa: E731
    out = [b"\xff" + eight(n) + b"\x01" * 64
           for n in (2**64 - 1, 2**64 - 9, 2**63, 56, 64)]
    for n in (2**64 - 1, 2**64 - 2, 2**64 - 150, 2**63, 2**32):
        for k in (0, 5, 9):
            out.append(_with_field(k, b"\xbf" + eight(n) + b"\x01" * 8))
    out.append(_with_field(5, b"\xbb" + (2**32 - 1).to_bytes(4, "big")))
    return out


def _case_gate_and_buffer_kinds():
    good = _tx().encode()
    big = _tx(payload=b"b" * (columnar.FRAME_MAX_BYTES - 200)).encode()
    over = _tx(payload=b"b" * columnar.FRAME_MAX_BYTES).encode()
    assert len(big) <= columnar.FRAME_MAX_BYTES < len(over)
    return [b"", good, over, bytearray(good), memoryview(good), big,
            bytearray(), memoryview(bytearray(over)), good]


def _case_mutations():
    rng = random.Random(20260928)
    bases = [_tx().encode(), _tx(v=37, payload=b"").encode(),
             _tx(to=None, v=28, nonce=2**64, payload=b"m" * 70).encode(),
             _tx(r=1, s=2, payload=b"").encode()]
    out = []
    for _ in range(2000):
        f = bytearray(rng.choice(bases))
        f[rng.randrange(len(f))] = rng.randrange(256)
        out.append(bytes(f))
    return out


_CASES = {name[len("_case_"):]: fn for name, fn in sorted(globals().items())
          if name.startswith("_case_")}


@pytest.mark.parametrize("kind", sorted(_CASES))
def test_native_window_decoder_matches_the_oracle(kind):
    """Three readings of one window: the rung ``decode_window`` runs
    (the native decoder, where the library has it), the Python oracle
    a frame at a time, and the scalar ``Transaction.decode``."""
    frames = _CASES[kind]()
    want = columnar._decode_frames(list(frames))
    got = decode_txn_window(frames)
    assert got.n == want.n == len(frames)
    for name in ("decoded", "valid", "txhash", "sighash", "sig", "nonce",
                 "gas_price", "_spans"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), (kind, name)
    assert got.hashes == want.hashes
    assert not (got.valid & ~got.decoded).any()
    for i, frame in enumerate(frames):
        frame = bytes(frame)
        if got.decoded[i]:
            ref = Transaction.decode(frame)
            t = got.txn(i)
            assert t == ref == want.txn(i), (kind, i)
            assert t.hash == got.hashes[i] == bytes(got.txhash[i]) == \
                keccak256(frame)
            # one field is lossy: any non-zero `is_geec` reads True and
            # re-encodes as 1, so only such a frame is not its own
            # re-encoding (``Transaction.decode`` then hashes another string)
            assert (ref.hash == t.hash) == (ref.encode() == frame)
            assert ref.encode() == frame or rlp.decode(frame)[6] != b"\x01"
            parts = ref.signature_parts()
            assert bool(got.valid[i]) == (parts is not None), (kind, i)
            if parts is not None:
                assert (bytes(got.sig[i]), bytes(got.sighash[i])) == parts
            assert got.nonce[i] == min(ref.nonce, 2**64 - 1)
            assert got.gas_price[i] == min(ref.gas_price, 2**64 - 1)
            continue
        # a dead row leaves nothing behind, and the scalar decoder
        # refuses whatever the gate let through
        assert got.hashes[i] is None and not got.txhash[i].any()
        assert not got.sig[i].any() and not got.sighash[i].any()
        if not 0 < len(frame) <= columnar.FRAME_MAX_BYTES:
            continue
        try:
            items = rlp.decode(frame)
        except RLPError:
            continue
        # a list where a field should be is the scalar decoder's blind
        # spot (an empty one reads as the integer 0, any other trips a
        # TypeError); the window decoders refuse both
        if isinstance(items, list) and any(isinstance(x, list)
                                           for x in items):
            continue
        with pytest.raises((RLPError, ValueError, IndexError)):
            Transaction.from_rlp(items)
    # non-vacuous: each kind but the all-dead ones decodes something
    if kind not in ("field_count", "nested_list", "trailing_bytes",
                    "length_overflow"):
        assert got.decoded.any(), kind
    if kind not in ("generator", "eip155_v", "wide_nonce_and_price"):
        assert not (got.decoded & got.valid).all(), kind


def test_the_native_decoder_is_the_rung_that_runs():
    """``tests/conftest.py`` built the library, so the probe at import
    must have chosen the native call; a silent fall to the Python rung
    would pass every differential case and lose the whole gain."""
    from eges_tpu.crypto import native
    from eges_tpu.utils.metrics import DEFAULT as metrics

    assert native.has_decode_window()
    assert columnar._DECODE is columnar._decode_native
    rows = metrics.counter("ingress.decode_rows").value
    native_rows = metrics.counter("ingress.decode_native_rows").value
    decode_txn_window([_tx().encode(), b"", b"\xc0"])
    assert metrics.counter("ingress.decode_rows").value == rows + 3
    assert metrics.counter("ingress.decode_native_rows").value == \
        native_rows + 3


def test_native_self_check_holds_the_window_decoder_to_fixed_answers():
    """What ``make -C native test`` runs: the library against the golden
    model, the window decoder's fixed frames among it."""
    from eges_tpu.crypto import native

    native.self_check()


def test_native_wrapper_refuses_columns_that_do_not_fit():
    """The library trusts its pointers: the wrapper checks every size,
    dtype and the offsets before it hands them over."""
    from eges_tpu.crypto import native

    frames = [_tx().encode()] * 3

    def call(**swap):
        data, offsets, kw = native.pack_txn_frames(frames)
        offsets = swap.pop("offsets", offsets)
        kw.update(swap)
        native.decode_txn_window(data, offsets, **kw)
        return kw

    assert call()["decoded"].all()
    bad = [dict(sig=np.zeros((3, 64), np.uint8)),
           dict(txhash=np.zeros((2, 32), np.uint8)),
           dict(nonce=np.zeros((3,), np.int32)),
           dict(decoded=np.zeros((6,), bool)[::2]),
           dict(spans=np.zeros((3, 10, 2), np.uint64)),
           dict(offsets=np.array([0, 5, 3, 3 * len(frames[0])], np.uint64)),
           dict(offsets=np.array([0, 1, 2, 3], np.uint64)),
           dict(offsets=np.array([0, 1, 2, 3 * len(frames[0])], np.int64))]
    for swap in bad:
        with pytest.raises(ValueError):
            call(**swap)


def test_two_threads_decoding_at_once_each_get_their_own_window():
    """The native call holds no GIL, so two workers are inside it at
    once: nothing in it may be shared between calls."""
    windows = [[_tx(nonce=1000 * w + i, payload=bytes([w]) * (40 + i % 90),
                    v=27 + (i + w) % 2 if i % 5 else 0).encode()
                for i in range(250)] for w in range(2)]
    want = [columnar._decode_frames(w) for w in windows]
    assert want[0].hashes != want[1].hashes
    wrong: list = []
    start = threading.Barrier(2)

    def work(k: int) -> None:
        start.wait(timeout=30)
        for _ in range(60):
            got = decode_txn_window(windows[k])
            if not columnar._same_columns(got, want[k]) or \
                    got.txn(7) != want[k].txn(7):
                wrong.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


# -- the one packing helper under both of its callers ---------------------

def test_block_body_and_window_decoder_fill_the_same_columns(monkeypatch):
    """``core.state._signature_rows`` (a block body's wire bytes) and
    ``decode_window`` (a gossip window) reach the library through ONE
    helper, ``native.decode_txn_frames``: the same frames give the same
    signatures, signing hashes and transaction hashes."""
    from eges_tpu.core.state import _signature_rows
    from eges_tpu.crypto import native

    signed = [t for t in _mixed_stream(30) if t.signature_parts()]
    frames = [t.encode() for t in signed]
    body = [Transaction.decode(f) for f in frames]   # keeps the wire bytes
    assert all("wire" in t._SENDER_CACHE and "hash" not in t._SENDER_CACHE
               for t in body)

    calls = []
    real = native.decode_txn_frames

    def counted(fs):
        calls.append(len(fs))
        return real(fs)

    monkeypatch.setattr(native, "decode_txn_frames", counted)
    cols = decode_txn_window(frames)
    sigs, sighashes, filled = _signature_rows(body)
    assert calls == [len(frames)] * 2
    assert filled == len(frames) and cols.valid.all()
    assert np.array_equal(sigs, cols.sig)
    assert np.array_equal(sighashes, cols.sighash)
    # the pass left each row's transaction hash in its memo
    assert [t._SENDER_CACHE["hash"] for t in body] == cols.hashes


# -- pool admission: frames by window, Transactions by add_remotes --------

def _run_pool(frames: list[bytes], *, as_frames: bool, chunk: int):
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    led = LG.IngressLedger(lambda: 100.0)
    pool = TxPool(_WallClock(), verifier=NativeBatchVerifier(),
                  max_batch=16)
    with LG.bind(led, "peer:src"):
        for w in range(0, len(frames), chunk):
            part = frames[w:w + chunk]
            if as_frames:
                admit_remotes_window(pool, decode_txn_window(part))
            else:
                admit_remotes(pool, [Transaction.decode(f) for f in part])
        pool._on_window()  # window timer fires: tail flush
    order = [(s, t.hash) for s, t in pool._order
             if t.hash not in pool._dead]
    return dict(pool.stats), order, led.snapshot()


@pytest.mark.parametrize("chunk", [1, 13, 45])
def test_frames_by_window_and_transactions_by_add_remotes_admit_alike(chunk):
    stream = _mixed_stream(45)
    frames = [t.encode() for t in stream] + \
        [t.encode() for t in stream[:7]]  # re-delivered duplicates

    sc, oc, lc = _run_pool(frames, as_frames=True, chunk=chunk)
    sl, ol, ll = _run_pool(frames, as_frames=False, chunk=chunk)
    assert sc == sl
    assert oc == ol                    # same rows, same arrival order
    assert lc == ll                    # billing to the cent
    # non-vacuous: every outcome class fired
    assert sc["admitted"] and sc["rejected"] and sc["duplicate"] \
        and sc["replaced"]
    # and the arrival's size changes nothing but the number of flushes
    s13 = _run_pool(frames, as_frames=False, chunk=13)
    assert {k: v for k, v in sl.items() if k != "batches"} == \
        {k: v for k, v in s13[0].items() if k != "batches"}
    assert ol == s13[1]


def test_add_remotes_over_a_window_s_rows_chunks_and_admits_them_all(
        monkeypatch):
    """More transactions than one window may hold: ``add_remotes`` makes
    a window a chunk of ``WINDOW_MAX_ROWS`` and every row is admitted."""
    from eges_tpu.core import txpool as txpool_mod
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    monkeypatch.setattr(txpool_mod, "WINDOW_MAX_ROWS", 8)
    txns = [Transaction(nonce=i, gas_price=1, gas_limit=21000,
                        to=bytes(20), value=i).signed(PRIV_A)
            for i in range(21)]
    windows = []
    pool = TxPool(_WallClock(), verifier=NativeBatchVerifier(),
                  max_batch=64)
    real = pool.add_remotes_window

    def counted(cols):
        windows.append(cols.n)
        real(cols)

    pool.add_remotes_window = counted
    pool.add_remotes(iter(txns))       # any iterable, as before
    pool._on_window()
    assert windows == [8, 8, 5]
    assert pool.stats["admitted"] == 21 and pool.stats["batches"] == 1
    assert [t.hash for _, t in pool._order] == [t.hash for t in txns]
    # what the chunking is for: a window over the real cap is refused
    with pytest.raises(ValueError):
        columnar.columns_from_txns(
            txns[:1] * (columnar.WINDOW_MAX_ROWS + 1))


def test_a_repeated_transaction_in_one_list_counts_one_duplicate():
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    a, b = [Transaction(nonce=i, gas_price=1, gas_limit=21000,
                        to=bytes(20), value=i).signed(PRIV_A)
            for i in range(2)]
    led = LG.IngressLedger(lambda: 100.0)
    pool = TxPool(_WallClock(), verifier=NativeBatchVerifier())
    with LG.bind(led, "peer:src"):
        pool.add_remotes([a, b, a])
        pool._on_window()
    assert pool.stats == {"admitted": 2, "rejected": 0, "duplicate": 1,
                          "batches": 1, "replaced": 0}
    (origin,) = led.snapshot()["origins"]
    assert (origin["admits"], origin["drops"]) == (2.0, 1.0)


@pytest.mark.parametrize("as_frames", [True, False],
                         ids=["window", "add_remotes"])
def test_invalid_sig_flood_billed_without_scalar_fallback(monkeypatch,
                                                          as_frames):
    """A whole-window invalid-signature flood rides the batched reject
    path end to end, as frames and as ``Transaction``s alike: the
    per-entry recovery helper must never run (it is monkeypatched to a
    tripwire), and every reject bills the flooder's ledger origin."""
    from eges_tpu.crypto import verify_host

    def _tripwire(entries, verifier, priority="bulk"):
        raise AssertionError("recover_signers used on the pool's "
                             "flood path")

    monkeypatch.setattr(verify_host, "recover_signers", _tripwire)

    n = 32
    flood = [Transaction(nonce=i, gas_price=1, gas_limit=21000,
                         to=bytes(20), value=0, v=27, r=0, s=1)
             for i in range(n)]
    led = LG.IngressLedger(lambda: 100.0)
    pool = TxPool(_WallClock(), verifier=None, max_batch=16)
    with LG.bind(led, "peer:flooder"):
        if as_frames:
            admit_remotes_window(pool, decode_txn_window(
                [t.encode() for t in flood]))
        else:
            admit_remotes(pool, flood)
        pool._on_window()
    assert pool.stats["rejected"] == n and pool.stats["admitted"] == 0
    snap = led.snapshot()
    assert [r["origin"] for r in snap["origins"]] == ["peer:flooder"]
    assert snap["origins"][0]["rejects"] == float(n)


# -- scheduler window submit ----------------------------------------------

def test_scheduler_submit_window_recovers_against_host_oracle():
    from eges_tpu.crypto import keccak as K
    from eges_tpu.crypto import secp256k1 as ec
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import (NativeBatchVerifier,
                                             recover_signers_window)

    cols = decode_txn_window([t.encode() for t in _mixed_stream(24)])
    rows = np.nonzero(cols.valid)[0]
    assert rows.size > 4
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=1.0,
                              max_batch=64)
    try:
        rec = recover_signers_window(cols.sighash[rows], cols.sig[rows],
                                     sched)
    finally:
        sched.close()
    for k, i in enumerate(rows.tolist()):
        pub = ec.ecdsa_recover(bytes(cols.sighash[i]), bytes(cols.sig[i]))
        assert rec[k] == K.keccak256(pub)[-20:]


# -- a 4-node sim fed bundles against one fed singletons ------------------

def _gossip_cluster(bundle: int):
    """4-node txpool sim with an injected flooder peer bursting the
    mixed stream (valid + invalid sigs + a duplicate tail) as gossip
    messages of ``bundle`` transactions each."""
    import eges_tpu.consensus.messages as M
    from eges_tpu.crypto import secp256k1 as secp
    from eges_tpu.sim.cluster import SimCluster

    # fund the flood senders so admitted txns become EXECUTABLE and
    # blocks include them — that's what emits the commit_anatomy
    # stage="pool" events the comparison reads
    alloc = {secp.pubkey_to_address(secp.privkey_to_pubkey(p)): 10 ** 18
             for p in (PRIV_A, PRIV_B)}
    cluster = SimCluster(4, seed=0, txn_per_block=4, txpool=True,
                         alloc=alloc)
    cluster.net.join("flooder", "10.0.0.99", 9999,
                     lambda d: None, lambda d: None)
    # no jitter: what differs between the two sims is the size of the
    # flooder's messages, not the order the wire leaves them in
    cluster.net.jitter_s = 0.0
    stream = _mixed_stream(30)
    stream += stream[:5]
    fired = [False]

    def burst():
        fired[0] = True
        for w in range(0, len(stream), bundle):
            cluster.net.deliver_gossip("flooder", M.pack_gossip(
                M.GOSSIP_TXNS, M.TxnsMsg(txns=tuple(stream[w:w + bundle]))))

    cluster.clock.call_later(0.01, burst)
    return cluster, fired


def _run_differential(bundle: int):
    cluster, fired = _gossip_cluster(bundle)
    cluster.start()
    cluster.run(600.0, stop_condition=lambda: fired[0]
                and cluster.min_height() >= 6)
    for sn in cluster.nodes:
        sn.node.stop()
    stats = {sn.name: dict(sn.node.txpool.stats) for sn in cluster.nodes}
    return cluster.journals(), stats, cluster.heights()


def test_sim_fed_bundles_and_sim_fed_singletons_admit_alike():
    """However the stream is cut into messages, every row is a row of a
    window: the two sims' pools, ledgers and journals are the same."""
    from harness.chaos import canonical_dump

    jb, sb, hb = _run_differential(12)
    js, ss, hs = _run_differential(1)

    assert hb == hs and min(hb) >= 6
    assert sb == ss                    # pool stats on every node
    # non-vacuous: the flood admitted, rejected and replaced on every node
    assert all(s["admitted"] and s["rejected"] and s["replaced"]
               for s in sb.values())
    # commit anatomy pool stages (ingest->admit legs on the virtual
    # clock) are present and equal, node for node
    for name in jb:
        stages = [[e for e in j[name] if e.get("type") == "commit_anatomy"
                   and e.get("stage") == "pool"] for j in (jb, js)]
        assert stages[0] == stages[1]
        assert stages[0] or name not in sb
    # billing straight off the journal stream, node for node
    led = [json.dumps({n: [{k: v for k, v in e.items() if k != "costs"}
                           for e in evs if e.get("type") == "ingress_ledger"]
                       for n, evs in j.items()}, sort_keys=True)
           for j in (jb, js)]
    assert led[0] == led[1] and "peer:flooder" in led[0]
    # and the repo's own determinism criterion: canonical journal dumps
    # (volatile wall-clock fields stripped, everything protocol kept)
    # match BYTE FOR BYTE
    assert canonical_dump(jb) == canonical_dump(js)
