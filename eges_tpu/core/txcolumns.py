"""The pool's column format: one window of transactions as row-aligned
numpy arrays.

A window is what the pool admits at once: a gossip window's frames as
``ingress.columnar.decode_window`` read them off the wire, or a list of
``Transaction`` objects (:func:`columns_from_txns`).  The columns are
shaped like the verify path's staging buffers (``sighash`` n x 32,
``sig`` n x 65), so a flushed slice reaches ``scheduler.submit_window``
with no conversion a row, and a ``Transaction`` is built only for a row
that admits (:meth:`TxColumns.txns`): rejected rows, the flood case
(arXiv 1808.02252's DoS contract), never become an object.
"""

from __future__ import annotations

import numpy as np

from eges_tpu.core.types import Transaction
from eges_tpu.crypto import native

# Hard row cap per window — the largest window the scheduler's staging
# pool is sized for; callers chunk above it.
WINDOW_MAX_ROWS = 16384
U64_MAX = (1 << 64) - 1  # where the nonce / gas_price columns clip


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
                 "_spans", "_txns", "_unsigned")

    def __init__(self, n: int, data=None, offsets=None, columns=None):
        """``n`` rows, zeroed; a decoder hands over what
        ``native.pack_txn_frames`` made of the window's frames."""
        self.n = n
        c = native.window_columns(n) if columns is None else columns
        self.sighash, self.sig, self.txhash = \
            c["sighash"], c["sig"], c["txhash"]
        self.gas_price, self.nonce = c["gas_price"], c["nonce"]
        self.decoded, self.valid = c["decoded"], c["valid"]
        # python-object mirror of ``txhash`` for set-based dedup (the
        # pool's ``_known`` difference is one C-level set op over these)
        self.hashes: list[bytes | None] = [None] * n
        # decode path only: the window's frames packed back to back
        # (frame i at _offsets[i].._offsets[i+1], a dead frame an empty
        # span) and each decoded row's ten payload spans (start, end),
        # relative to its frame — all txns() needs of the wire
        self._data, self._offsets, self._spans = data, offsets, c["spans"]
        self._txns: list = [None] * n   # materialized / original txns
        self._unsigned = False  # columns_from_txns: signed() fills them

    def signed(self) -> "TxColumns":
        """The window with ``valid`` / ``sig`` / ``sighash`` filled.  A
        decoder filled them; ``columns_from_txns`` leaves them to the
        first call, the pool's flush: ``signature_parts()`` is a Keccak
        a row in the library, which lets go of the GIL, and a caller of
        ``add_remotes`` that holds the node's lock (an RPC worker beside
        a busy event loop) would wait to get it back, a row."""
        if self._unsigned:
            self._unsigned = False
            for i, t in enumerate(self._txns):
                parts = t.signature_parts()
                if parts is not None:
                    self.sig[i] = np.frombuffer(parts[0], np.uint8)
                    self.sighash[i] = np.frombuffer(parts[1], np.uint8)
                    self.valid[i] = True
        return self

    def txn(self, i: int) -> Transaction:
        """Row ``i``'s ``Transaction``: the one-row case of :meth:`txns`."""
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
                if nonce == U64_MAX:
                    nonce = num(data[a0:b0], "big")
                if price == U64_MAX:
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


def columns_from_txns(txns) -> TxColumns:
    """Columns for ``Transaction`` objects (what ``TxPool.add_remotes``
    is handed): the objects are kept and returned by :meth:`TxColumns.txns`,
    so admission admits the very objects it was given.  The signature
    columns wait for :meth:`TxColumns.signed`."""
    txns = list(txns)
    if len(txns) > WINDOW_MAX_ROWS:
        raise ValueError("window exceeds %d rows — chunk the caller"
                         % WINDOW_MAX_ROWS)
    cols = TxColumns(len(txns))
    cols._txns, cols._unsigned = txns, True
    for i, t in enumerate(txns):
        cols.hashes[i] = h = t.hash
        cols.decoded[i] = True
        cols.txhash[i] = np.frombuffer(h, np.uint8)
        cols.nonce[i] = min(t.nonce, U64_MAX)
        cols.gas_price[i] = min(t.gas_price, U64_MAX)
    return cols
