"""What ONE validator of a small committee receives, block after block,
when the chain's work is transaction-sender recovery: every transaction
comes as gossip, some of it again as copies, and then once more in the
proposer's block (``drivers/validator.py``).  Everything is made from
``--seed`` with the plain reference's own keys, signatures, hashes and RLP
(``perfbench/ref/``), through the frame helpers of ``gen.py``; the run is
held against ``ref/senders.py`` and against what this module knows by
construction.

A block of ``txn_per_block`` transfers (1000):

* its senders are drawn from ``accounts`` by a Zipfian of ``zipf_theta``
  (a seeded permutation says which account has which rank), each sender's
  nonces running on from block to block; the block body lists them as a
  proposer's selection would, senders in the order they first appear,
  each sender's nonces ascending;
* ``unseen_share`` of them (100) reach this validator by gossip only
  AFTER the block: they come in the next block's windows;
* its gossip stream is ``txn_per_block / (1 - duplicate_share)`` frames
  (1333) in windows of ``gossip_window``: its own transactions that gossip
  brings in time (900) and the previous block's late ones (100), in a
  seeded random order, so a hot sender's nonces arrive out of order; and
  333 copies of frames that came earlier in the stream, never in the
  window of their original.  One gossip frame in ``invalid_every`` (21 of
  the copies) comes spoiled, the four kinds of ``gen.KINDS`` in turn;
* one block in ``bad_block_every`` carries one transaction whose
  signature is none (s out of range, r off the curve, in turn) in the
  place of a sound one; of two such blocks, one's bad transaction came as
  gossip before, so the scheduler's cache holds its ``None``.  The block
  must be refused whole, and is followed by the same block without it.

Every seed gives the same counts, sizes and order of frames, windows and
blocks; the seed moves the keys, the payloads, who sends what and which
rows are late, copied and spoiled.
"""

from __future__ import annotations

import itertools
import random

from perfbench.gen import (KINDS, _body, _frame, _key_base, _sign_bodies,
                           _spoil)
from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256_many

BLOCK_KINDS = ("s_out_of_range", "r_off_curve")  # what a bad block carries


def zipf_cum_weights(n: int, theta: float) -> list:
    """Cumulative weights of ranks 1..n under a Zipfian: rank r is drawn
    with probability proportional to ``1 / r ** theta`` (YCSB's)."""
    return list(itertools.accumulate(1.0 / (r ** theta)
                                     for r in range(1, n + 1)))


class ZipfFeed:
    """``pool_blocks`` distinct blocks that the run cycles through: more
    transactions than the pool's hash history holds, so a second pass
    costs what fresh rows would, while inside a few blocks the scheduler's
    recovery cache is meant to answer."""

    def __init__(self, seed: int, d: dict):
        rng = random.Random(seed)
        self.d = d
        n_acc, per_blk = d["accounts"], d["txn_per_block"]
        n_blk, win = d["pool_blocks"], d["gossip_window"]
        self.late = int(per_blk * d["unseen_share"])
        self.gossip_frames = round(per_blk / (1.0 - d["duplicate_share"]))
        self.copies = self.gossip_frames - per_blk
        self.spoiled = round(self.gossip_frames / d["invalid_every"])
        privs, self.addrs = secp.keys(_key_base(rng), n_acc)
        self.by_rank = list(range(n_acc))
        rng.shuffle(self.by_rank)  # which account has which rank

        # -- the blocks' transactions, in body order ---------------------
        cum = zipf_cum_weights(n_acc, d["zipf_theta"])
        self.ranks = rng.choices(range(n_acc), cum_weights=cum,
                                 k=n_blk * per_blk)
        nonce = [0] * n_acc
        self.account, bodies = [], []
        self.block_nonces = []  # per block: (address, its next nonce)
        for b in range(n_blk):
            drawn = [self.by_rank[r] for r in
                     self.ranks[b * per_blk:(b + 1) * per_blk]]
            count: dict = {}
            for a in drawn:  # a dict keeps the order of first appearance
                count[a] = count.get(a, 0) + 1
            for a, n in count.items():
                for _ in range(n):
                    bodies.append(_body(nonce[a], d["gas_limit"],
                                        self.addrs[(a + 1) % n_acc],
                                        rng.randbytes(d["payload_bytes"])))
                    self.account.append(a)
                    nonce[a] += 1
            self.block_nonces.append([(self.addrs[a], nonce[a])
                                      for a in count])
        sigs = _sign_bodies(bodies, [privs[a] for a in self.account], rng)
        self.frames = [_frame(body, sig) for body, sig in zip(bodies, sigs)]
        self.n_valid = len(self.frames)
        self.kind = [None] * self.n_valid      # of every frame
        self.origin = list(range(self.n_valid))  # the sound frame under it

        def spoil(k: int, kind: str) -> int:
            """A spoiled variant of sound frame ``k``; its index."""
            body, sig, v = bodies[k], sigs[k], None
            if kind == "bad_recid":
                v = 27 + 5
            elif kind == "flipped_message":
                body = body[:-1] + bytes([body[-1] ^ 0x40])
            self.frames.append(_frame(body, _spoil(kind, sig, rng), v))
            self.kind.append(kind)
            self.origin.append(k)
            return len(self.frames) - 1

        # -- what gossip brings late, and each block's stream ------------
        unseen = [set(rng.sample(range(b * per_blk, (b + 1) * per_blk),
                                 self.late)) for b in range(n_blk)]
        self.unseen = unseen
        head = min(per_blk, self.copies + win)
        every = d["bad_block_every"]
        self.blocks, self.body_rows, self.repaired_rows = [], [], []
        self.bad = {}  # block -> (bad frame's index, came as gossip)
        for b in range(n_blk):
            own = range(b * per_blk, (b + 1) * per_blk)
            fresh = [k for k in own if k not in unseen[b]] \
                + sorted(unseen[(b - 1) % n_blk])
            rng.shuffle(fresh)
            marks = [False] * (per_blk - head) + [True] * self.copies
            rng.shuffle(marks)
            again = fresh[:self.copies]
            rng.shuffle(again)
            seq, nxt, slots = fresh[:head], head, []
            for is_copy in marks:
                if is_copy:
                    slots.append(len(seq))
                    seq.append(again.pop())
                else:
                    seq.append(fresh[nxt])
                    nxt += 1
            first = b * self.spoiled  # the four kinds in turn, all blocks
            kinds = [KINDS[(first + i) % 4] for i in range(self.spoiled)]
            spoiled_at = rng.sample(slots, self.spoiled)
            bad_kind, gossiped = None, False
            if b % every == every // 2:
                o = b // every
                bad_kind = BLOCK_KINDS[o % 2]
                gossiped = (o // 2) % 2 == 0 and bad_kind in kinds
            if gossiped:
                # the bad transaction comes as gossip first: the spoiled
                # copy of its kind is a copy of one of the block's own
                i = kinds.index(bad_kind)
                if seq[spoiled_at[i]] not in own:
                    spoiled_at[i] = next(at for at in slots
                                         if at not in spoiled_at
                                         and seq[at] in own)
            for at, kind in zip(spoiled_at, kinds):
                seq[at] = spoil(seq[at], kind)
            rows = list(own)
            if bad_kind:
                if gossiped:
                    bad = seq[spoiled_at[kinds.index(bad_kind)]]
                else:
                    bad = spoil(rng.choice(
                        [k for k in own if k not in unseen[b]]), bad_kind)
                self.bad[b] = (bad, gossiped)
                rows[self.origin[bad] - b * per_blk] = bad
            self.body_rows.append(rows)
            self.repaired_rows.append([k for k in rows
                                       if self.kind[k] is None])
            self.blocks.append([seq[i:i + win]
                                for i in range(0, len(seq), win)])
        self.hashes = keccak256_many(self.frames)
        self.index_of = {h: k for k, h in enumerate(self.hashes)}

    # what the run asks for ---------------------------------------------
    def windows(self, block: int) -> list:
        """Block ``block``'s gossip windows, each a list of frame indices."""
        return self.blocks[block % len(self.blocks)]

    def is_bad(self, block: int) -> bool:
        return block % len(self.blocks) in self.bad

    def rows_of(self, block: int, repaired: bool = False) -> list:
        """The frame indices of the proposer's block, in body order; the
        block without its bad transaction where ``repaired``."""
        p = block % len(self.blocks)
        return self.repaired_rows[p] if repaired else self.body_rows[p]

    def body(self, block: int, repaired: bool = False) -> bytes:
        """The block body's bytes: the RLP list of its transactions."""
        payload = b"".join([self.frames[k]
                            for k in self.rows_of(block, repaired)])
        return rlp.length_prefix(len(payload), 0xC0) + payload

    def signer(self, k: int) -> bytes:
        """The account that signed the sound frame under frame ``k``."""
        return self.addrs[self.account[self.origin[k]]]

    def frame_expect(self, k: int):
        """What the pool must do with a fresh frame k: ``("admit",
        sender)``, ``("admit_other", signer)`` where the message was
        altered after signing, or ``("reject", None)``."""
        kind = self.kind[k]
        if kind is None:
            return "admit", self.signer(k)
        if kind == "flipped_message":
            return "admit_other", self.signer(k)
        return "reject", None

    def construction(self, block: int) -> dict:
        """A block's rows by construction, given that the block before it
        was validated first: gossip frames by what becomes of them, the
        rows that enter the scheduler (a frame whose ``v`` names no
        recovery id never does) and those of them that the recovery cache
        or a window in flight answers."""
        p = block % len(self.blocks)
        seq = [k for w in self.blocks[p] for k in w]
        spoiled = [k for k in seq if self.kind[k] is not None]
        enter = sum(1 for k in spoiled if self.kind[k] != "bad_recid")
        per_blk = self.d["txn_per_block"]
        out = {"gossip_frames": len(seq), "fresh": per_blk,
               "copies": len(seq) - per_blk - len(spoiled),
               "spoiled": len(spoiled), "block_rows": per_blk,
               "gossip_scheduler_rows": per_blk + enter,
               "scheduler_rows": 2 * per_blk + enter,
               # the late rows of the block before, then this block's own
               "hits": self.late + (per_blk - self.late)}
        if p in self.bad:
            # the bad row stands where a row that gossip brought stood;
            # it is a hit only if its own bytes came as gossip; the second
            # pass is the cache's, row for row
            out["hits"] += (per_blk - 1) - (0 if self.bad[p][1] else 1)
            out["block_rows"] += per_blk - 1
            out["scheduler_rows"] += per_blk - 1
        return out
