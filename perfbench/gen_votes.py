"""What the proposer of a 256-validator committee receives for a block
(``drivers/proposer.py``), everything from ``--seed``:

* the bulk rows, ``gen.NodeFeed``'s unchanged: the block's 1250 gossip
  frames (its 1000 transactions and a quarter of them re-gossiped, 1 in
  ``invalid_every`` invalid) in windows of ``gossip_window``, the
  election's ``election_rows`` vote rows and the header's row, as
  ``(sighash, signature)`` pairs;
* the ACK stream, this module's: one ``ValidateReply`` datagram from each
  of the other 255 validators, wire-encoded as
  ``eges_tpu/consensus/messages.py`` encodes it (by the plain
  reference's RLP, not the program's) and signed with that validator's
  seeded key, in the order they arrive.

Of a block's 255 replies, ``validators // forged_every`` (4) are FORGED:
they pass every check in front of the verifier and fall only there (a
signature by another validator's key, s out of range, r off the curve,
in turn), and ``validators // foreign_every`` (2) are FOREIGN: they must
fall before the verifier (a sound signature over an ACK for another
block hash; a sound ACK by a key outside the membership), one of each a
block.  The rest are sound.

Where the bad ones stand is fixed, the order of everything else seeded
(:meth:`VotesFeed.construction`): all but one of the forged stand among
the first ``need`` arrivals (``need`` the threshold, 169), the last
forged and the foreign ones after arrival ``late_from`` (200, the
deployment's).  A tally
that collects to the threshold, verifies everything collected, prunes
and goes on a reply at a time therefore takes every block the same two
attempts: ``need`` rows that the cache has never seen, 3 pruned; then,
at arrival ``need + 3``, ``need`` rows of which the cache answers all
but 3.  Every seed gives the same counts and positions' ranges; the
seed moves the keys, the hashes, who proposes, who misbehaves and the
order.
"""

from __future__ import annotations

import random

from perfbench import gen
from perfbench.gen import _key_base, _spoil
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256_many

FORGED = ("other_key", "s_out_of_range", "r_off_curve")
FOREIGN = ("foreign_hash", "non_member")


def bulk_keys(d: dict) -> dict:
    """The deployment as ``gen.NodeFeed`` reads it: a block's gossip
    stream is its transactions and ``duplicate_share`` of them again (so
    1250 frames of which a fifth are copies), the election's rows take
    the place of its committee, and its own ACK rows go unused."""
    frames = round(d["txn_per_block"] * (1 + d["duplicate_share"]))
    return {**d, "txn_per_block": frames,
            "duplicate_share": 1 - d["txn_per_block"] / frames,
            "committee": d["election_rows"], "header_sigs": 1}


def datagram(block_num: int, author: bytes, block_hash: bytes,
             sig: bytes, accepted: int = 1) -> bytes:
    """A validate reply on the direct plane: the envelope ``[code,
    author, payload]`` around ``[block_num, author, accepted, retry 0,
    no fill blocks, block_hash, sig]``."""
    payload = rlp.encode([block_num, author, accepted, 0, [], block_hash,
                          sig])
    return rlp.encode([ref_quorum.VALIDATE_REPLY, author, payload])


class VoteBlock:
    """One block's ACK stream."""

    def __init__(self, number, proposer, block_hash, seed):
        self.number, self.proposer = number, proposer
        self.hash, self.seed = block_hash, seed
        self.datagrams: list = []  # bytes, in arrival order
        self.kinds: list = []      # None (sound) or the kind of bad reply
        self.authors: list = []    # the claimed author of each
        self.sigs: list = []       # the signature each carries


class VotesFeed:
    def __init__(self, seed: int, d: dict):
        self.d = d
        self.bulk = gen.NodeFeed(seed, bulk_keys(d))
        n = d["validators"]
        self.members = list(self.bulk.val_addrs)
        self.need = ref_quorum.need(d["validate_threshold"], d["acceptors"])
        self.n_forged = n // d["forged_every"]
        self.n_foreign = n // d["foreign_every"]
        rng = random.Random(seed ^ 0xACC5)
        pool = d["vote_pool_blocks"]
        # keys outside the membership, one a block
        out_privs, out_addrs = secp.keys(_key_base(rng), pool)
        forged_kinds = [FORGED[i % len(FORGED)]
                        for i in range(pool * self.n_forged)]
        msgs, rows = [], []  # every signature of the pool in one batch
        self.blocks: list = []
        for p in range(pool):
            proposer = rng.randrange(n)
            blk = VoteBlock(number=p + 1, proposer=proposer,
                            block_hash=rng.randbytes(32),
                            seed=rng.getrandbits(63))
            others = [i for i in range(n) if i != proposer]
            bad = rng.sample(others, self.n_forged + self.n_foreign)
            kind_of = dict(zip(bad, forged_kinds[p * self.n_forged:
                                                 (p + 1) * self.n_forged]
                               + [FOREIGN[i % len(FOREIGN)]
                                  for i in range(self.n_foreign)]))
            # the arrival order: the sound ones shuffled, then the bad
            # ones put where construction() says they stand
            sound = [i for i in others if i not in kind_of]
            rng.shuffle(sound)
            early = bad[:self.n_forged - 1]
            late = bad[self.n_forged - 1:]
            order = sound
            for i in late:  # among the arrivals from ``late_from`` on
                order.insert(rng.randrange(
                    d["late_from"] - len(early), len(order) + 1), i)
            for i in early:  # among the first ``need`` arrivals
                order.insert(rng.randrange(self.need - len(early) + 1), i)
            for i in order:
                kind = kind_of.get(i)
                author, priv, h = self.members[i], \
                    self.bulk.val_privs[i], blk.hash
                if kind == "other_key":
                    priv = self.bulk.val_privs[(i + 1) % n]
                elif kind == "foreign_hash":
                    h = bytes(x ^ 0xFF for x in blk.hash)
                elif kind == "non_member":
                    author, priv = out_addrs[p], out_privs[p]
                blk.kinds.append(kind)
                blk.authors.append(author)
                msgs.append(b"geec/ack" + rlp.encode(
                    [blk.number, author, 1, h]))
                rows.append((blk, priv, author, h, kind))
            self.blocks.append(blk)
        sigs = secp.sign_rows([r[1] for r in rows], keccak256_many(msgs),
                              _key_base(rng))
        for (blk, _priv, author, h, kind), sig in zip(rows, sigs):
            sig = _spoil(kind, sig, rng)
            blk.sigs.append(sig)
            blk.datagrams.append(datagram(blk.number, author, h, sig))

    # what the run asks for ---------------------------------------------
    def block(self, b: int) -> VoteBlock:
        """Block ``b``'s ACK stream (the pool cycles)."""
        return self.blocks[b % len(self.blocks)]

    def sound(self, b: int, k: int | None = None) -> set:
        """By construction, the sound supporters among block ``b``'s
        first ``k`` arrivals (all of them by default)."""
        blk = self.block(b)
        return {a for a, kind in zip(blk.authors[:k], blk.kinds[:k])
                if kind is None}

    def judged(self, b: int, k: int | None = None) -> dict:
        """By construction, what ``ref.quorum.tally`` answers for block
        ``b``'s first ``k`` arrivals (all of them by default)."""
        blk = self.block(b)
        sound = [a if kind is None else None
                 for a, kind in zip(blk.authors[:k], blk.kinds[:k])]
        return {"sound": sound, "need": self.need,
                "stands_from": ref_quorum.stands_from(sound, self.need)}

    def construction(self, b: int) -> dict:
        """What block ``b``'s stream is by construction, and what the
        collect, verify, prune and go-on tally makes of it."""
        blk = self.block(b)
        forged = [k for k, kind in enumerate(blk.kinds) if kind in FORGED]
        foreign = [k for k, kind in enumerate(blk.kinds)
                   if kind in FOREIGN]
        early = sum(1 for k in forged if k < self.need)
        return {"replies": len(blk.datagrams),
                "sound": sum(1 for kind in blk.kinds if kind is None),
                "forged": [blk.kinds[k] for k in forged],
                "foreign": sorted(blk.kinds[k] for k in foreign),
                "forged_among_first_need": early,
                "first_late_arrival": min(forged[early:] + foreign),
                "need": self.need,
                # the tally as it is today
                "attempts": 2, "attempt_rows": [self.need, self.need],
                "pruned": early, "certified_at": self.need + early,
                "cache_hits": self.need - early,
                "device_rows": [self.need, early]}
