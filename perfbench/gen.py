"""Traffic generation: everything a run sends is made here from ``--seed``
with the plain reference's own keys, signatures, hashes and RLP.

Two shapes, one for each kind of deployment:

* ``Transfers``: signed transfers from seeded accounts, each carrying the
  source's call data, for a client of a cluster (``drivers/cluster.py``);
* ``NodeFeed``: what ONE node of a large committee receives for a block:
  gossip windows of raw transaction frames (a share of them re-gossiped
  duplicates), election votes, ACK replies and a header signature, with one
  row in ``invalid_every`` invalid, four kinds in turn
  (``drivers/node.py``).

Every seed gives the same counts, sizes and arrival times; the seed moves
the keys, the payloads and which rows are the invalid and duplicated ones.
"""

from __future__ import annotations

import random

from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256_many

KINDS = ("s_out_of_range", "bad_recid", "flipped_message", "r_off_curve")


def _off_curve_x(rng) -> int:
    while True:
        x = rng.randrange(1, secp.N)
        if pow((pow(x, 3, secp.P) + 7) % secp.P, (secp.P - 1) // 2,
               secp.P) != 1:
            return x


def _key_base(rng) -> int:
    return rng.randrange(1 << 200, 1 << 250)


def _frame(body: bytes, sig: bytes, recid_v: int | None = None) -> bytes:
    """The 10-field transaction frame around an encoded 6-field body."""
    v = 27 + sig[64] if recid_v is None else recid_v
    tail = (b"\x80" + rlp.encode(v) + rlp.encode(int.from_bytes(sig[:32], "big"))
            + rlp.encode(int.from_bytes(sig[32:64], "big")))
    return rlp.length_prefix(len(body) + len(tail), 0xC0) + body + tail


def _body(nonce: int, gas_limit: int, to: bytes, payload: bytes) -> bytes:
    """nonce, gas price 0, gas limit, to, value 0, payload."""
    return (rlp.encode(nonce) + b"\x80" + rlp.encode(gas_limit)
            + rlp.encode(to) + b"\x80" + rlp.encode(payload))


def _sighash_input(body: bytes) -> bytes:
    return rlp.length_prefix(len(body), 0xC0) + body


def _sign_bodies(bodies: list, privs: list, rng) -> list:
    """One signature over each transaction body's signing hash."""
    hashes = keccak256_many(_sighash_input(b) for b in bodies)
    return secp.sign_rows(privs, hashes, _key_base(rng))


def _spoil(kind, sig: bytes, rng) -> bytes:
    """The two kinds of invalid row that live in the signature alone."""
    if kind == "s_out_of_range":
        return sig[:32] + (secp.N + 1 + rng.randrange(1 << 64)) \
            .to_bytes(32, "big") + sig[64:]
    if kind == "r_off_curve":
        return _off_curve_x(rng).to_bytes(32, "big") + sig[32:]
    return sig


class Transfers:
    """``count`` signed transfers, account after account in nonce rounds:
    transfer k is account ``k % accounts`` at nonce ``k // accounts``."""

    def __init__(self, seed: int, *, accounts: int, count: int,
                 payload_bytes: int, gas_limit: int):
        rng = random.Random(seed)
        self.privs, self.senders = secp.keys(_key_base(rng), accounts)
        order = list(range(accounts))
        rng.shuffle(order)  # which account is bound to which ingress node
        self.order = order
        bodies, privs = [], []
        for k in range(count):
            a = k % accounts
            bodies.append(_body(k // accounts, gas_limit,
                                self.senders[(a + 1) % accounts],
                                rng.randbytes(payload_bytes)))
            privs.append(self.privs[a])
        sigs = _sign_bodies(bodies, privs, rng)
        self.frames = [_frame(b, s) for b, s in zip(bodies, sigs)]
        self.hashes = keccak256_many(self.frames)
        self.account = [k % accounts for k in range(count)]


class NodeFeed:
    """One node's share of a committee's traffic, as a pool of blocks that
    the run cycles through.  ``pool_blocks`` distinct blocks of frames and
    ``vote_pool_blocks`` of vote rows: long enough cycles that neither the
    pool's dedup history nor the scheduler's recovery cache ever sees a row
    again while it still remembers it, so each pass costs what fresh rows
    would."""

    def __init__(self, seed: int, d: dict):
        rng = random.Random(seed)
        self.d = d
        n_acc, per_blk = d["accounts"], d["txn_per_block"]
        self.dups = int(per_blk * d["duplicate_share"])
        self.uniq = per_blk - self.dups
        self.acc_privs, self.acc_addrs = secp.keys(_key_base(rng), n_acc)
        self.val_privs, self.val_addrs = secp.keys(_key_base(rng),
                                                   d["validators"])
        self.to_index = {a: i for i, a in enumerate(self.acc_addrs)}
        every = d["invalid_every"]
        phase = rng.randrange(every)

        def kind_of(i: int):
            return (KINDS[(i // every) % 4] if i % every == phase else None)

        # -- transaction frames ----------------------------------------
        total = d["pool_blocks"] * self.uniq
        bodies, privs = [], []
        for k in range(total):
            a = k % n_acc
            bodies.append(_body(k // n_acc, d["gas_limit"],
                                self.acc_addrs[(a + 1) % n_acc],
                                rng.randbytes(d["payload_bytes"])))
            privs.append(self.acc_privs[a])
        sigs = _sign_bodies(bodies, privs, rng)
        self.frame_kind = [kind_of(k) for k in range(total)]
        self.frames = []
        for k, (body, sig) in enumerate(zip(bodies, sigs)):
            kind, v = self.frame_kind[k], None
            if kind == "bad_recid":
                v = 27 + 5
            elif kind == "flipped_message":
                body = body[:-1] + bytes([body[-1] ^ 0x40])
            self.frames.append(_frame(body, _spoil(kind, sig, rng), v))
        # a block's arrival order: its first frames once, then the rest
        # mixed with re-gossiped copies of the first ones, cut into
        # gossip windows
        head = min(self.uniq, self.dups + d["gossip_window"])
        marks = [False] * (self.uniq - head) + [True] * self.dups
        self.blocks = []
        for b in range(d["pool_blocks"]):
            lo = b * self.uniq
            rng.shuffle(marks)
            again = list(range(lo, lo + self.dups))
            rng.shuffle(again)
            seq, nxt = list(range(lo, lo + head)), lo + head
            for is_dup in marks:
                if is_dup:
                    seq.append(again.pop())
                else:
                    seq.append(nxt)
                    nxt += 1
            w = d["gossip_window"]
            self.blocks.append([seq[i:i + w] for i in range(0, len(seq), w)])

        # -- vote rows -----------------------------------------------------
        n_val, n_el = d["validators"], d["committee"]
        rows_blk = n_el + n_val + d["header_sigs"]
        n_rows = d["vote_pool_blocks"] * rows_blk
        signer = []
        for _ in range(d["vote_pool_blocks"]):
            signer += rng.sample(range(n_val), n_el)       # election votes
            signer += list(range(n_val))                   # ACK replies
            signer += [rng.randrange(n_val)
                       for _ in range(d["header_sigs"])]  # the proposer
        msgs = [rng.randbytes(32) for _ in range(n_rows)]
        vsigs = secp.sign_rows([self.val_privs[s] for s in signer], msgs,
                               _key_base(rng))
        self.vote_kind = [kind_of(i) for i in range(n_rows)]
        self.vote_entries, self.vote_expect = [], []
        for i, (h, sig) in enumerate(zip(msgs, vsigs)):
            kind = self.vote_kind[i]
            if kind == "bad_recid":
                sig = sig[:64] + b"\x05"
            elif kind == "flipped_message":
                h = bytes([h[0] ^ 0x40]) + h[1:]
            self.vote_entries.append((h, _spoil(kind, sig, rng)))
            self.vote_expect.append(self.val_addrs[signer[i]])
        self.rows_per_vote_block = rows_blk

    # what the run asks for ---------------------------------------------
    def windows(self, block: int) -> list:
        """Block ``block``'s gossip windows, each a list of frame indices."""
        return self.blocks[block % len(self.blocks)]

    def votes(self, block: int):
        """``(election, header, ack)`` slices of vote-row indices."""
        lo = (block % self.d["vote_pool_blocks"]) * self.rows_per_vote_block
        n_el, n_val = self.d["committee"], self.d["validators"]
        # the rows were made as election votes, ACK replies, header
        return (range(lo, lo + n_el),
                range(lo + n_el + n_val, lo + self.rows_per_vote_block),
                range(lo + n_el, lo + n_el + n_val))

    def rows_per_block(self) -> int:
        return self.d["txn_per_block"] + self.rows_per_vote_block

    def frame_expect(self, k: int):
        """What the pool must do with a fresh frame k: ``("admit",
        sender)``, ``("admit_other", signer)`` where the message was
        altered after signing, or ``("reject", None)``."""
        kind = self.frame_kind[k]
        addr = self.acc_addrs[k % self.d["accounts"]]
        if kind is None:
            return "admit", addr
        if kind == "flipped_message":
            return "admit_other", addr
        return "reject", None

    def frame_parts(self, k: int):
        """``(sighash, sig65 or None)`` of frame k, worked out from the
        frame's bytes by the reference's own RLP reading."""
        items = _decode_list(self.frames[k])
        v = int.from_bytes(items[7], "big")
        body = b"".join(rlp.encode(x) for x in items[:6])
        h = keccak256_many([_sighash_input(body)])[0]
        if not 27 <= v <= 30:
            return h, None
        return h, (items[8].rjust(32, b"\0") + items[9].rjust(32, b"\0")
                   + bytes([v - 27]))


def _decode_list(frame: bytes) -> list:
    """The byte strings of a one-level RLP list (no nesting)."""
    b0 = frame[0]
    pos = 1 if b0 < 0xF8 else 1 + (b0 - 0xF7)
    out = []
    while pos < len(frame):
        b = frame[pos]
        if b < 0x80:
            out.append(frame[pos:pos + 1])
            pos += 1
        elif b < 0xB8:
            out.append(frame[pos + 1:pos + 1 + b - 0x80])
            pos += 1 + b - 0x80
        else:
            ln = b - 0xB7
            n = int.from_bytes(frame[pos + 1:pos + 1 + ln], "big")
            out.append(frame[pos + 1 + ln:pos + 1 + ln + n])
            pos += 1 + ln + n
    return out
