"""Columnar txn ingest — the wire-speed front half (ROADMAP item 5).

The back half of the pipeline is batched to the hilt (one device call
per verify window); before this module, every row still paid per-tx
Python on the way in: a per-datagram RLP decode into a ``Transaction``
object, a per-tx ``signature_parts()`` re-encode, a per-tx cache probe
and ``Future`` in the scheduler, per-tx dict bookkeeping in the pool.
Here a whole gossip window of txn frames is decoded ONCE into columnar
numpy arrays — ``sighash32`` / ``sig65`` / ``txhash`` / ``gas_price`` /
``nonce`` columns plus validity masks — shaped exactly like the verify
path's staging buffers, so the window lands in the device staging pool
(``verifier.recover_addresses`` / ``scheduler.submit_window``) without
any per-row conversion.  ``Transaction`` object construction is
deferred to admission time (:meth:`TxColumns.txns`, one pass over the
columns for a flushed slice's admitted rows): rejected rows —
the flood case — never materialize an object at all, keeping the
cheap-reject path cheap at wire rate (arXiv 1808.02252's DoS contract;
arXiv 2112.02229's never-touch-a-scalar-path discipline).

Byte-identity contract: for every frame the per-row results here equal
the legacy scalar path exactly —

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

The tier-1 differential test (tests/test_columnar_ingest.py) holds the
two paths byte-identical end to end: admissions, stats, ledger
billing, journal dumps.
"""

from __future__ import annotations

import numpy as np

from eges_tpu.core import rlp
from eges_tpu.core.types import Transaction
from eges_tpu.crypto import native
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.utils import metrics, tracing

# Hard per-frame byte gate, applied BEFORE any parsing: an oversized
# frame must die without costing a decode or even a hash (the node's
# datagram path already enforces its own INGRESS_MAX_BYTES on the whole
# message; this is the per-row second fence for direct window callers).
FRAME_MAX_BYTES = 128 * 1024

# Hard row cap per window — the largest window the scheduler's staging
# pool is sized for; decode callers chunk above it.
WINDOW_MAX_ROWS = 16384

_SECP_MAX = 1 << 256
_U64_MAX = (1 << 64) - 1  # where the nonce / gas_price columns clip


class TxColumns:
    """One decoded gossip window in columnar form.

    Arrays are row-aligned: row ``i`` of every column describes frame
    (or txn) ``i`` of the input.  ``decoded[i]`` is False when the
    frame failed the size gate or canonical decode (no identity — the
    row is untouchable); ``valid[i]`` is False when the row decoded
    but its v/r/s cannot form a wire signature (the cheap-reject rows
    the pool bills without ever building a ``Transaction``).
    """

    __slots__ = ("n", "sighash", "sig", "txhash", "gas_price", "nonce",
                 "decoded", "valid", "hashes", "_data", "_offsets",
                 "_spans", "_txns")

    def __init__(self, n: int):
        self.n = n
        self.sighash = np.zeros((n, 32), np.uint8)
        self.sig = np.zeros((n, 65), np.uint8)
        self.txhash = np.zeros((n, 32), np.uint8)
        self.gas_price = np.zeros((n,), np.uint64)
        self.nonce = np.zeros((n,), np.uint64)
        self.decoded = np.zeros((n,), bool)
        self.valid = np.zeros((n,), bool)
        # python-object mirror of ``txhash`` for set-based dedup (the
        # pool's ``_known`` difference is one C-level set op over these)
        self.hashes: list[bytes | None] = [None] * n
        # decode path only (_pack): the window's frames packed back to
        # back (frame i at _offsets[i].._offsets[i+1], a dead frame an
        # empty span) and each decoded row's ten payload spans (start,
        # end), relative to its frame — all txn() needs of the wire
        self._data = self._offsets = self._spans = None
        self._txns: list = [None] * n   # materialized / original txns

    def txn(self, i: int) -> Transaction:
        """Materialize row ``i``'s ``Transaction``: the one-row case
        of :meth:`txns`."""
        return self.txns((i,))[0]

    def txns(self, rows) -> list[Transaction]:
        """Materialize ``rows``' ``Transaction``s in ONE pass over the
        columns — admission time only; rejected rows never pay this.
        A row already materialized (or kept from ``columns_from_txns``,
        which has no wire bytes at all) is returned as it stands."""
        have = self._txns
        need = [i for i in rows if have[i] is None]
        if need:
            # direct field construction instead of from_rlp: the scan
            # already enforced every from_rlp guard (canonical uints,
            # r/s/v widths, `to` length), so int.from_bytes over the
            # raw payloads builds the identical object without a
            # second decode pass — and without the frozen dataclass's
            # __init__ (eleven object.__setattr__ a row): the instance
            # dict is set whole, the memoized hash seeded from the wire
            # frame's keccak (canonical RLP: keccak256(frame) ==
            # keccak256(t.encode())), so admission never re-encodes
            idx = np.asarray(need, np.int64)
            spans = (self._spans[idx].astype(np.int64)
                     + self._offsets[idx].astype(np.int64)[:, None, None])
            data, hashes = self._data, self.hashes
            new, put, num = object.__new__, object.__setattr__, \
                int.from_bytes
            # the spans as 20 columns, so that a row is the loop's own
            # names and allocates nothing
            for (i, nonce, price, a0, b0, a1, b1, a2, b2, a3, b3, a4, b4,
                 a5, b5, a6, b6, a7, b7, a8, b8, a9, b9) in zip(
                    need, self.nonce[idx].tolist(),
                    self.gas_price[idx].tolist(),
                    *spans.reshape(len(need), 20).T.tolist()):
                # the two uint64 columns clip: a wider field is re-read
                if nonce == _U64_MAX:
                    nonce = num(data[a0:b0], "big")
                if price == _U64_MAX:
                    price = num(data[a1:b1], "big")
                t = new(Transaction)
                put(t, "__dict__", {
                    "nonce": nonce, "gas_price": price,
                    "gas_limit": num(data[a2:b2], "big"),
                    "to": data[a3:b3] or None,
                    "value": num(data[a4:b4], "big"),
                    "payload": data[a5:b5],
                    "is_geec": num(data[a6:b6], "big") != 0,
                    "v": num(data[a7:b7], "big"),
                    "r": num(data[a8:b8], "big"),
                    "s": num(data[a9:b9], "big"),
                    "_SENDER_CACHE": {"hash": hashes[i]}})
                have[i] = t  # bounded-by: self.n, the window's rows: a slot of a list sized once (an index past it raises)
        return [have[i] for i in rows]

    def gather(self, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(sighash32, sig65) sub-arrays for ``rows`` — contiguous
        uint8 blocks that drop straight into the verifier's staging
        buffers (one fancy-index copy, zero per-row conversion)."""
        return self.sighash[rows], self.sig[rows]


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


def _pack(frames: list) -> TxColumns:
    """A window's columns, empty but for its bytes: the per-frame byte
    gate BEFORE any copy (an empty or oversized frame contributes an
    empty span, so it dies without a parse or a hash), then one join
    and the offsets from one cumsum."""
    n = len(frames)
    cols = TxColumns(n)
    kept = [f if 0 < len(f) <= FRAME_MAX_BYTES else b"" for f in frames]
    cols._data = b"".join(kept)  # bounded-by: WINDOW_MAX_ROWS * FRAME_MAX_BYTES (gate above, row cap in decode_window)
    cols._offsets = np.zeros((n + 1,), np.uint64)
    np.cumsum([len(f) for f in kept], dtype=np.uint64,
              out=cols._offsets[1:])
    cols._spans = np.zeros((n, 10, 2), np.uint32)
    if int(cols._offsets[-1]) != len(cols._data):
        raise ValueError("a frame's len() is not its size in bytes")
    return cols


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
    cols = _pack(frames)
    native.decode_txn_window(
        cols._data, cols._offsets, decoded=cols.decoded, valid=cols.valid,
        txhash=cols.txhash, sighash=cols.sighash, sig=cols.sig,
        nonce=cols.nonce, gas_price=cols.gas_price, spans=cols._spans)
    th = cols.txhash.tobytes()
    cols.hashes = [th[32 * i:32 * i + 32] if ok else None
                   for i, ok in enumerate(cols.decoded.tolist())]
    return cols


def _decode_frames(frames: list) -> TxColumns:
    """The same window in Python, a frame at a time: the oracle the
    native decoder is held to (tests/test_columnar_ingest.py), and the
    fallback for a checkout whose library lacks it."""
    cols = _pack(frames)
    data, offsets = cols._data, cols._offsets.tolist()
    for i in range(cols.n):
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
        cols.nonce[i] = min(int.from_bytes(items[0], "big"), _U64_MAX)
        cols.gas_price[i] = min(int.from_bytes(items[1], "big"), _U64_MAX)
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


def columns_from_txns(txns) -> TxColumns:  # ingress-entry:bounded
    """Columns for already-decoded ``Transaction`` objects (the gossip
    path hands the pool decoded txns): extraction only — the original
    objects are kept and returned by :meth:`TxColumns.txn`, so
    admission admits the exact objects the legacy path would."""
    txns = list(txns)
    if len(txns) > WINDOW_MAX_ROWS:
        raise ValueError("window exceeds %d rows — chunk the caller"
                         % WINDOW_MAX_ROWS)
    cols = TxColumns(len(txns))
    for i, t in enumerate(txns):
        h = t.hash
        cols.decoded[i] = True
        cols.hashes[i] = h
        cols.txhash[i] = np.frombuffer(h, np.uint8)
        cols._txns[i] = t
        cols.nonce[i] = min(t.nonce, _U64_MAX)
        cols.gas_price[i] = min(t.gas_price, _U64_MAX)
        parts = t.signature_parts()
        if parts is not None:
            sig, sighash = parts
            cols.sig[i] = np.frombuffer(sig, np.uint8)
            cols.sighash[i] = np.frombuffer(sighash, np.uint8)
            cols.valid[i] = True
    return cols
