"""Columnar txn ingest: a gossip window's frames to the pool's columns.

A whole window of raw txn frames is decoded ONCE into
``core.txcolumns.TxColumns`` (the format lives there, below the pool;
its names are re-exported here) instead of a per-datagram RLP decode
into a ``Transaction`` and a per-tx ``signature_parts()`` re-encode.
This module is what is ingress about that: the per-frame byte gate, the
one native call behind it (:func:`decode_window`), and the same decoder
in Python, a frame at a time, that the native one is held to.

Byte-identity contract: for every frame the per-row results here equal
what ``Transaction.decode(frame)`` gives —

* ``txhash`` is ``keccak256(frame)``.  ``core/rlp.py`` rejects every
  non-canonical encoding, so a frame that decodes at all re-encodes to
  itself and this equals ``Transaction.decode(frame).hash``.
* ``sighash`` is built by slicing the first six field encodings
  straight out of the frame (one list header + optional EIP155
  suffix), which equals ``Transaction.sighash(chain_id)`` for the same
  canonicality reason — no re-encode, no Transaction.
* the ``valid`` mask applies the same v/r/s rules as
  ``Transaction.signature_parts()`` (mask-don't-raise), and the
  ``decoded`` mask the same width guards as ``Transaction.from_rlp``.

``tests/test_columnar_ingest.py`` holds the decoders to each other and
to ``Transaction.decode`` case by case.
"""

from __future__ import annotations

import numpy as np

from eges_tpu.core import rlp
from eges_tpu.core.txcolumns import (U64_MAX, WINDOW_MAX_ROWS,  # noqa: F401
                                     TxColumns, columns_from_txns)
from eges_tpu.core.types import Transaction
from eges_tpu.crypto import native
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.utils import metrics, tracing

# Hard per-frame byte gate, applied BEFORE any parsing: an oversized
# frame must die without costing a decode or even a hash (the node's
# datagram path already enforces its own INGRESS_MAX_BYTES on the whole
# message; this is the per-row second fence for direct window callers).
FRAME_MAX_BYTES = 128 * 1024

_SECP_MAX = 1 << 256


def _scan_txn_frame(frame: bytes) -> tuple[list, list]:
    """Parse one canonical txn frame WITHOUT building a Transaction:
    returns ``(items, spans)`` where ``items[i]`` is field ``i``'s raw
    byte-string payload and ``spans[i] = (enc_start, enc_end)`` is the
    field's FULL encoding span inside ``frame`` (header included) —
    what the sighash preimage is sliced from.  Raises RLPError on
    anything ``Transaction.decode`` would reject."""
    if not frame:
        raise rlp.RLPError("empty frame")
    b0 = frame[0]
    if b0 < 0xC0:
        raise rlp.RLPError("txn frame must be a list")
    if b0 < 0xF8:
        pos, end = 1, 1 + (b0 - 0xC0)
    else:
        ln = b0 - 0xF7
        if 1 + ln > len(frame):
            raise rlp.RLPError("truncated length")
        lb = frame[1:1 + ln]
        if lb[:1] == b"\x00":
            raise rlp.RLPError("non-canonical length")
        n = int.from_bytes(lb, "big")
        if n < 56:
            raise rlp.RLPError("non-canonical long list")
        pos, end = 1 + ln, 1 + ln + n
    if end != len(frame):
        raise rlp.RLPError("trailing bytes")
    items, spans = [], []
    push_item, push_span = items.append, spans.append
    flen = len(frame)
    for _ in range(10):
        if pos >= end:
            raise rlp.RLPError("txn frame needs 10 fields")
        enc_start = pos
        # _scan_string_item's exact rules, inlined: ten calls per frame
        # is the decode loop's hottest edge
        b0 = frame[pos]
        if b0 < 0x80:
            ps, pe = pos, pos + 1
            pos += 1
        elif b0 < 0xB8:  # short string
            n = b0 - 0x80
            ps = pos + 1
            pe = ps + n
            if pe > flen:
                raise rlp.RLPError("truncated string")
            if n == 1 and frame[ps] < 0x80:
                raise rlp.RLPError("non-canonical single byte")
            pos = pe
        elif b0 < 0xC0:  # long string
            ln = b0 - 0xB7
            ps = pos + 1 + ln
            if ps > flen:
                raise rlp.RLPError("truncated length")
            lb = frame[pos + 1:ps]
            if lb[:1] == b"\x00":
                raise rlp.RLPError("non-canonical length")
            n = int.from_bytes(lb, "big")
            if n < 56:
                raise rlp.RLPError("non-canonical long string")
            pe = ps + n
            if pe > flen:
                raise rlp.RLPError("truncated string")
            pos = pe
        else:
            raise rlp.RLPError("txn field must be a string item")
        if pos > end:
            raise rlp.RLPError("list payload overrun")
        push_item(frame[ps:pe])
        push_span((enc_start, pos))
    if pos != end:
        raise rlp.RLPError("txn frame needs exactly 10 fields")
    # the from_rlp guards: r/s fit 256 bits, v fits 64 bits, `to` is
    # empty or a 20-byte address, uint fields carry no leading zero —
    # every frame that decodes here must also survive from_rlp, so a
    # deferred txn() at admission time can never raise
    if len(items[8]) > 32 or len(items[9]) > 32:
        raise rlp.RLPError("signature scalar wider than 256 bits")
    if len(items[7]) > 8:
        raise rlp.RLPError("v wider than 64 bits")
    if len(items[3]) not in (0, 20):
        raise rlp.RLPError("to must be empty or a 20-byte address")
    for idx in (0, 1, 2, 4, 6, 7, 8, 9):  # all but to(3)/payload(5)
        if items[idx][:1] == b"\x00":
            raise rlp.RLPError("non-canonical integer (leading zero)")
    return items, spans


def _list_header(n: int) -> bytes:
    """RLP list header for an ``n``-byte payload (encode-side mirror of
    the scanner above; kept local so no private reach into rlp)."""
    if n < 56:
        return bytes([0xC0 + n])
    lb = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([0xC0 + 55 + len(lb)]) + lb


def _gated(frames: list) -> list:
    """The per-frame byte gate, BEFORE any copy: an empty or oversized
    frame contributes an empty span, so it dies without a parse or a
    hash."""
    return [f if 0 < len(f) <= FRAME_MAX_BYTES else b"" for f in frames]


def decode_window(frames) -> TxColumns:  # ingress-entry:bounded
    """Vectorized envelope/signature extraction: a whole window of raw
    txn frames (length-capped by the transport) into one
    :class:`TxColumns` — O(1) Python-level transitions per window, here
    and on the downstream path, instead of O(rows).

    The byte gate runs first, in Python and before any copy (oversized
    frames die pre-decode, pre-hash).  Then ONE native call
    (``native/ingress.cpp``), which holds no GIL, does per frame what
    :func:`_decode_frames` does: one canonical scan recording field
    spans, ``signature_parts``'s exact v/r/s rules, the sighash
    preimage sliced straight out of the frame (list header + first six
    field encodings + EIP155 suffix; no re-encode, no ``Transaction``)
    and both digests, written into the columns as they stand.  Decode
    or signature failures mask the row out instead of raising
    (mask-don't-raise, the batch contract); invalid-signature rows
    never pay a sighash keccak."""
    frames = list(frames)
    if len(frames) > WINDOW_MAX_ROWS:
        raise ValueError("window exceeds %d rows — chunk the caller"
                         % WINDOW_MAX_ROWS)
    with tracing.DEFAULT.span("ingress.decode", rows=len(frames)):
        cols = _DECODE(frames)
    metrics.DEFAULT.counter("ingress.decode_rows").inc(len(frames))
    if _DECODE is _decode_native:
        metrics.DEFAULT.counter("ingress.decode_native_rows").inc(
            len(frames))
    return cols


def _decode_native(frames: list) -> TxColumns:
    """:func:`decode_window`'s one library call over a window it has
    capped."""
    cols = TxColumns(len(frames), *native.decode_txn_frames(_gated(frames)))
    th = cols.txhash.tobytes()
    cols.hashes = [th[32 * i:32 * i + 32] if ok else None
                   for i, ok in enumerate(cols.decoded.tolist())]
    return cols


def _decode_frames(frames: list) -> TxColumns:
    """The same window in Python, a frame at a time: the oracle the
    native decoder is held to (tests/test_columnar_ingest.py), and the
    fallback for a checkout whose library lacks it."""
    cols = TxColumns(len(frames), *native.pack_txn_frames(_gated(frames)))
    data, offsets = cols._data, cols._offsets.tolist()
    for i in range(cols.n):  # bounded-by: WINDOW_MAX_ROWS (row cap in decode_window)
        frame = data[offsets[i]:offsets[i + 1]]
        if not frame:
            continue  # oversized/empty: dead before any parse
        try:
            items, spans = _scan_txn_frame(frame)
        except rlp.RLPError:
            continue
        cols.decoded[i] = True
        cols.hashes[i] = h = keccak256(frame)
        cols.txhash[i] = np.frombuffer(h, np.uint8)
        # a payload ends where its encoding does
        cols._spans[i] = [(end - len(it), end)
                          for it, (_, end) in zip(items, spans)]
        cols.nonce[i] = min(int.from_bytes(items[0], "big"), U64_MAX)
        cols.gas_price[i] = min(int.from_bytes(items[1], "big"), U64_MAX)
        # signature_parts()'s exact v/r/s rules, span-sliced
        v = int.from_bytes(items[7], "big")
        protected = v not in (27, 28) and v != 0
        if protected and v < 35:
            continue  # the chain_id ValueError branch: 29..34 unassigned
        cid = (v - 35) // 2 if protected else None
        recid = v - 27 if cid is None else v - 35 - 2 * cid
        r = int.from_bytes(items[8], "big")
        s = int.from_bytes(items[9], "big")
        if not (0 <= recid <= 3 and 0 < r < _SECP_MAX
                and 0 < s < _SECP_MAX):
            continue
        cols.valid[i] = True
        cols.sig[i] = np.frombuffer(
            r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([recid]),
            np.uint8)
        body = frame[spans[0][0]:spans[5][1]]
        if cid is not None:
            body = body + rlp.encode(cid) + b"\x80\x80"
        cols.sighash[i] = np.frombuffer(
            keccak256(_list_header(len(body)) + body), np.uint8)
    return cols


def _same_columns(a: TxColumns, b: TxColumns) -> bool:
    return a.hashes == b.hashes and all(
        np.array_equal(getattr(a, name), getattr(b, name))
        for name in ("decoded", "valid", "txhash", "sighash", "sig",
                     "nonce", "gas_price", "_spans"))


def _dispatch_decode():
    """Which rung :func:`decode_window` runs, decided once: the native
    window decoder where the library has it and it answers a known
    window (a protected row, an unsigned one, a malformed one) as the
    oracle does, else the oracle itself."""
    try:
        probe = [Transaction(nonce=1, gas_price=2, gas_limit=21000,
                             to=bytes(20), value=3, payload=b"probe",
                             v=37, r=5, s=6).encode(),
                 Transaction(nonce=1 << 70).encode(), b"\xc1\x80"]
        if native.has_decode_window() and _same_columns(
                _decode_native(probe), _decode_frames(probe)):
            return _decode_native
    # analysis: allow-swallow(optional native-accel probe; falls back to python)
    except Exception:
        pass
    return _decode_frames


_DECODE = _dispatch_decode()
