"""The plain reference of a validator's sender work: from a block body's
bytes to the list of its senders or ``REFUSE``, and a plain model of the
pool's content.  Its own RLP reading, ``keccak`` and ``secp.recover``;
nothing of the program.

A block body is the RLP list of the block's transactions, each the
10-field frame the gossip plane carries (nonce, gas price, gas limit, to,
value, payload, the geec flag, v, r, s), signed the Homestead way
(``v`` 27 or 28): what ``perfbench/gen_zipf.py`` makes.
"""

from __future__ import annotations

from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256_many

REFUSE = "refuse"
PRICE_BUMP_PCT = 10  # a replacement bids this much more, or is dropped


def _item(data: bytes, pos: int):
    """The RLP item at ``pos``: ``(value, next position)``; a byte string
    as ``bytes``, a list as a list of items."""
    b = data[pos]
    if b < 0x80:
        return data[pos:pos + 1], pos + 1
    if b < 0xC0:
        if b < 0xB8:
            start, n = pos + 1, b - 0x80
        else:
            ln = b - 0xB7
            start = pos + 1 + ln
            n = int.from_bytes(data[pos + 1:start], "big")
        return data[start:start + n], start + n
    if b < 0xF8:
        start, n = pos + 1, b - 0xC0
    else:
        ln = b - 0xF7
        start = pos + 1 + ln
        n = int.from_bytes(data[pos + 1:start], "big")
    out, at = [], start
    while at < start + n:
        value, at = _item(data, at)
        out.append(value)
    return out, start + n


def read(data: bytes):
    """The one RLP item that ``data`` holds."""
    value, end = _item(data, 0)
    if end != len(data):
        raise ValueError("bytes after the item")
    return value


def row_parts(fields: list):
    """``(sighash, sig65 or None)`` of one transaction's ten fields: the
    Keccak-256 of the first six as a list, and r || s || recovery id, or
    None where ``v`` names no recovery id."""
    body = b"".join(rlp.encode(x) for x in fields[:6])
    h = keccak256_many([rlp.length_prefix(len(body), 0xC0) + body])[0]
    v = int.from_bytes(fields[7], "big")
    if v not in (27, 28):
        return h, None
    return h, (fields[8].rjust(32, b"\0") + fields[9].rjust(32, b"\0")
               + bytes([v - 27]))


def row_sender(fields: list):
    """The address that signed one transaction, or None."""
    h, sig = row_parts(fields)
    return secp.recover(h, sig) if sig else None


def frame_sender(frame: bytes):
    """The same for a gossip frame's bytes."""
    return row_sender(read(frame))


def block_senders(body: bytes, rows=None):
    """The senders of a block body's transactions in order, or ``REFUSE``
    where one of them names no sender (the block is invalid whole).
    ``rows`` keeps the work to those indices: the answer is then the
    senders of those rows alone, or ``REFUSE`` if one of THEM has none."""
    txns = read(body)
    out = []
    for i in (range(len(txns)) if rows is None else rows):
        sender = row_sender(txns[i])
        if sender is None:
            return REFUSE
        out.append(sender)
    return out


class PoolModel:
    """What a stream of gossip frames leaves admitted: a dict by
    ``(sender, nonce)``, first come unless the price is bumped by 10%; a
    frame seen before is a copy and changes nothing; a frame without a
    sender is refused.  ``commit`` frees the slots of a final block's
    transactions."""

    def __init__(self):
        self.slots: dict = {}      # (sender, nonce) -> (hash, gas price)
        self.seen: set = set()
        self.admitted: list = []   # (frame hash, sender), as admitted
        self.refused: list = []    # frame hashes

    def offer(self, frame: bytes) -> None:
        h = keccak256_many([frame])[0]
        if h in self.seen:
            return
        self.seen.add(h)
        fields = read(frame)
        sender = row_sender(fields)
        if sender is None:
            self.refused.append(h)
            return
        slot = (sender, int.from_bytes(fields[0], "big"))
        price = int.from_bytes(fields[1], "big")
        old = self.slots.get(slot)
        if old is not None and price * 100 < old[1] * (100 + PRICE_BUMP_PCT):
            return
        self.slots[slot] = (h, price)
        self.admitted.append((h, sender))

    def commit(self, hashes) -> None:
        final = set(hashes)
        for slot in [s for s, (h, _p) in self.slots.items() if h in final]:
            del self.slots[slot]
