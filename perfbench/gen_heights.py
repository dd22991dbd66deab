"""What the PROPOSER of the 1024-validator chain receives, height after
height, while it builds, gets certified and seals its own blocks
(``drivers/block_proposer.py``): the clients' transactions as gossip, its
committee's election votes, and the acceptors' ACKs of the block it has
just built.  Everything is made from ``--seed`` with the plain reference's
own keys, signatures, hashes, RLP and membership windows
(``perfbench/ref/``).  There is no chain here: the chain is the node's own,
and what it built is held to the reference after the run.

The generator is also the chain's trusted random source (Geec's THW,
:class:`Thw`): the seed it hands the node for each header's ``trust_rand``
puts the node into the next height's version-0 committee, so the node
stands for EVERY height (a validator of this chain stands for one in 32).

A height ``h`` (stream ``p = h - 1``):

* **gossip**: ``txn_per_block / (1 - duplicate_share)`` frames (5333) in
  windows of ``gossip_window`` (21 of 256): ``txn_per_block`` (4000) fresh
  signed transfers of ``value_wei`` (1) at gas price 0 with
  ``payload_bytes`` (100) of call data (senders drawn uniformly from the
  ``senders`` (2048) sending accounts, recipients from all ``accounts``
  (16,384, every one funded), each sender's nonces ascending across the
  whole stream) and 1333 copies of frames that came earlier in the
  height's stream, never in the window of their original, of which one
  gossip frame in ``invalid_every`` (83) comes spoiled, the four kinds of
  ``gen.KINDS`` in turn; all in a seeded order.  One height in
  ``unexecutable_every`` (16) the stream carries BESIDES ``unexecutable``
  (8) soundly signed transfers that cannot execute (``nonce_gap``: nonce 1
  of an account that never sent; ``over_balance``: twice the genesis
  balance; in turn), each from an account of its own among those that
  never send: the pool admits them, a block must leave them out;
* **election**: the signed votes of the other ``committee - 1`` (31)
  members of the height's committee (``ref/membership.py`` over the THW's
  seed), in a seeded order, ``forged_votes`` (1) of them forged and
  standing among the first ``election_threshold`` (16) arrivals;
* **ACKs**: one ``ValidateReply`` datagram from each of the other
  ``validators - 1`` (1023) validators over the hash of the block the node
  BUILT, so signed at run time (``ref/late_sign.py``: the nonce points are
  laid here, ``s`` is finished when the hash is known): ``forged_acks``
  (16) forged (:data:`FORGED` in turn; they pass every check in front of
  the verifier and fall only there), ``forged_acks_early`` (12) of them
  among the first ``need`` (513) arrivals that count and none among the
  next 12, and ``foreign_acks`` (8) from keys outside the membership in
  the place of 8 validators who stay silent (they fall before the tally);
  a tally that collects to the threshold, verifies everything, prunes and
  goes on a reply at a time takes every height the same 2 attempts
  (:meth:`HeightsFeed.construction`).

Every seed gives the same counts and positions' ranges; the seed moves the
keys, the payloads, who sends to whom, who votes, who misbehaves and the
order.
"""

from __future__ import annotations

import random

from perfbench.gen import (KINDS, _decode_list, _frame, _key_base,
                           _off_curve_x, _sign_bodies, _spoil)
from perfbench.gen_chain import transfer_body
from perfbench.gen_votes import FORGED, datagram
from perfbench.ref import membership as ref_members
from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256_many
from perfbench.ref.late_sign import LateSigner
from perfbench.ref.senders import _item

UNEXECUTABLE = ("nonce_gap", "over_balance")
MSG_VOTE, UDP_ELECT = 0x02, 0x02  # an election vote on the direct plane
_TAIL = 32 + 2 + 65  # a reply's block hash, the signature's prefix, it


def election_threshold(committee: int) -> int:
    """Upstream's: the majority of the committee less the candidate's own
    implicit vote (``election_go.go``)."""
    return -(-(committee + 1) // 2) - 1


def _height_frames(job) -> tuple:
    """One height's fresh transfers, signed and framed (in a worker or in
    line): ``(frames, their Keccaks)``."""
    chunk_seed, rows, gas_limit, value, payload_bytes = job
    rng = random.Random(chunk_seed)
    bodies = [transfer_body(nonce, gas_limit, to, value,
                            rng.randbytes(payload_bytes))
              for nonce, to, _priv in rows]
    sigs = _sign_bodies(bodies, [priv for _n, _to, priv in rows], rng)
    frames = [_frame(b, s) for b, s in zip(bodies, sigs)]
    return frames, keccak256_many(frames)


def _respoiled(frame: bytes, kind: str, rng) -> bytes:
    """A spoiled variant of a sound frame, from its bytes."""
    items = _decode_list(frame)
    body = b"".join(rlp.encode(x) for x in items[:6])
    sig = (items[8].rjust(32, b"\0") + items[9].rjust(32, b"\0")
           + bytes([items[7][0] - 27]))
    v = None
    if kind == "bad_recid":
        v = 27 + 5
    elif kind == "flipped_message":
        body = body[:-1] + bytes([body[-1] ^ 0x40])
    return _frame(body, _spoil(kind, sig, rng), v)


def request_block(data: bytes) -> tuple:
    """From a validate request's bytes, without reading its 4000
    transactions: ``(height, the block's hash)``.  The request is
    ``[0x11, [height, author, [header, ...], ...]]`` and a block's hash is
    the Keccak of its header's RLP."""
    def enter(pos: int) -> int:  # a list's first item
        b = data[pos]
        if b < 0xC0:
            raise ValueError("not a list")
        return pos + 1 if b < 0xF8 else pos + 1 + (b - 0xF7)

    pos = enter(0)
    code, pos = _item(data, pos)
    if code != b"\x11":
        raise ValueError("not a validate request")
    pos = enter(pos)
    height, pos = _item(data, pos)
    _author, pos = _item(data, pos)
    start = enter(pos)  # the block's first item: its header
    _header, end = _item(data, start)
    return (int.from_bytes(height, "big"),
            keccak256_many([data[start:end]])[0])


class Thw:
    """The trusted random source a node of this cell is GIVEN
    (``GeecNode(rand_source=...)``): block ``n``'s ``trust_rand`` is the
    generator's seed of height ``n + 1``."""

    def __init__(self, seeds: list, seed: int):
        self.seeds = seeds
        self._rng = random.Random(seed)

    def my_rand(self, blk_num: int) -> int:
        return self._rng.getrandbits(64)

    def trust_rand(self, blk_num: int) -> int:
        return self.seeds[blk_num + 1]


class HeightsFeed:
    def __init__(self, seed: int, d: dict, *, executor=None,
                 first_unexecutable: int | None = None):
        """``executor`` (a ``concurrent.futures`` one, of spawned
        processes where the caller holds a chip) signs the heights'
        transfers side by side; the feed is the same without one."""
        rng = random.Random(seed)
        self.d = d
        n_acc, n_send, n_val = d["accounts"], d["senders"], d["validators"]
        per_blk, n_h = d["txn_per_block"], d["stream_heights"]
        win, every = d["gossip_window"], d["unexecutable_every"]
        self.gossip_frames = round(per_blk / (1.0 - d["duplicate_share"]))
        self.copies = self.gossip_frames - per_blk
        self.spoiled = round(self.gossip_frames / d["invalid_every"])
        self.heights = n_h

        # -- who is who ----------------------------------------------------
        acc_privs, self.addrs = secp.keys(_key_base(rng), n_acc)
        val_privs, val_addrs = secp.keys(_key_base(rng), n_val)
        out_privs, self.outsiders = secp.keys(_key_base(rng),
                                              d["foreign_acks"])
        self.validators = [(a, "10.%d.%d.%d" % (i >> 16, i >> 8 & 255,
                                                i & 255), 8100 + i)
                           for i, a in enumerate(val_addrs)]
        at_of = {a: (ip, port) for a, ip, port in self.validators}
        priv_of = self.priv_of = dict(zip(val_addrs, val_privs))
        members = self.members = sorted(val_addrs)
        n_com = min(d["committee"], n_val)
        # height 1's seed is the genesis header's trust_rand, 0: its
        # committee is the window from the first member on
        me = rng.randrange(n_com)
        self.node_addr = members[me]
        self.node_priv = priv_of[self.node_addr]
        self.need = ref_members.majority(d["acceptors"], n_val)
        self.vote_threshold = election_threshold(n_com)
        self.balance = d["balance_wei"]
        self.senders = rng.sample(range(n_acc), n_send)
        idle = [a for a in range(n_acc) if a not in set(self.senders)]
        rng.shuffle(idle)

        # -- the THW's seeds: the node in every height's committee --------
        self.seeds = [None, 0]
        for _h in range(n_h + 1):
            r = rng.getrandbits(64)
            self.seeds.append(r - r % n_val
                              + (me - rng.randrange(n_com)) % n_val)
        self.thw = Thw(self.seeds, rng.getrandbits(64))

        # -- the fresh transfers, height by height ---------------------------
        nonce = [0] * n_acc
        self.account: list = []
        jobs = []
        for _p in range(n_h):
            rows = []
            for _k in range(per_blk):
                a = self.senders[rng.randrange(n_send)]
                rows.append((nonce[a], self.addrs[rng.randrange(n_acc)],
                             acc_privs[a]))
                self.account.append(a)
                nonce[a] += 1
            jobs.append((rng.getrandbits(64), rows, d["gas_limit"],
                         d["value_wei"], d["payload_bytes"]))
        made = list(executor.map(_height_frames, jobs)) if executor \
            else [_height_frames(j) for j in jobs]
        self.frames = [f for frames, _h in made for f in frames]
        self.hashes = [h for _f, hashes in made for h in hashes]
        self.n_valid = len(self.frames)
        self.kind = [None] * self.n_valid
        self.origin = list(range(self.n_valid))

        def extra(frame: bytes, kind: str, origin: int) -> int:
            self.frames.append(frame)
            self.kind.append(kind)
            self.origin.append(origin)
            return len(self.frames) - 1

        # -- each height's gossip stream (gen_chain's construction) -------
        first_at = every // 2 if first_unexecutable is None \
            else first_unexecutable
        self.unexecutable: dict = {}  # stream -> its frames' indices
        self._account_of: dict = {}   # such a frame -> its account
        self.streams = []
        for p in range(n_h):
            fresh = list(range(p * per_blk, (p + 1) * per_blk))
            if p % every == first_at % every:
                bodies, privs, mine = [], [], []
                for i in range(d["unexecutable"]):
                    a = idle.pop()
                    kind = UNEXECUTABLE[i % len(UNEXECUTABLE)]
                    gap = kind == "nonce_gap"
                    bodies.append(transfer_body(
                        1 if gap else 0, d["gas_limit"],
                        self.addrs[rng.randrange(n_acc)],
                        d["value_wei"] if gap else 2 * self.balance,
                        rng.randbytes(d["payload_bytes"])))
                    privs.append(acc_privs[a])
                    mine.append((kind, a))
                sigs = _sign_bodies(bodies, privs, rng)
                self.unexecutable[p] = []
                for b, s, (kind, a) in zip(bodies, sigs, mine):
                    k = extra(_frame(b, s), kind, len(self.frames))
                    self._account_of[k] = a
                    self.unexecutable[p].append(k)
                fresh += self.unexecutable[p]
            rng.shuffle(fresh)
            head = min(len(fresh), self.copies + win)
            marks = [False] * (len(fresh) - head) + [True] * self.copies
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
            at0 = p * self.spoiled  # the four kinds in turn, all heights
            for i, at in enumerate(rng.sample(slots, self.spoiled)):
                kind = KINDS[(at0 + i) % 4]
                seq[at] = extra(_respoiled(self.frames[seq[at]], kind, rng),
                                kind, self.origin[seq[at]])
            self.streams.append([seq[i:i + win]
                                 for i in range(0, len(seq), win)])
        self.hashes += keccak256_many(self.frames[self.n_valid:])
        self.index_of = {h: k for k, h in enumerate(self.hashes)}

        # -- the election votes, every height's ------------------------------
        n_fv = d["forged_votes"]
        rows, msgs, privs = [], [], []
        self.votes: list = []  # per stream: [(datagram, kind, author)]
        for p in range(n_h):
            h = p + 1
            voters = [a for a in ref_members.committee(
                members, self.seeds[h], 0, d["committee"])
                if a != self.node_addr]
            rng.shuffle(voters)
            forged = voters[:n_fv]
            order = voters[n_fv:]
            for a in forged:  # among the first ``threshold`` arrivals
                order.insert(rng.randrange(self.vote_threshold - n_fv + 1),
                             a)
            for a in order:
                kind = FORGED[(p + len(rows)) % len(FORGED)] \
                    if a in forged else None
                priv = priv_of[a]
                if kind == "other_key":
                    priv = priv_of[members[(members.index(a) + 1) % n_val]]
                msgs.append(b"geec/elect" + rlp.encode(
                    [MSG_VOTE, h, a, 0, 0]))
                privs.append(priv)
                rows.append((p, a, kind))
            self.votes.append([])
        sigs = secp.sign_rows(privs, keccak256_many(msgs), _key_base(rng))
        for (p, a, kind), sig in zip(rows, sigs):
            ip, port = at_of[a]
            self.votes[p].append((rlp.encode([UDP_ELECT, a, rlp.encode(
                [MSG_VOTE, p + 1, a, 0, 0, 0, ip.encode(), port,
                 _spoil(kind, sig, rng)])]), kind, a))

        # -- the ACKs: everything but the block's hash -------------------
        n_fa, n_early = d["forged_acks"], d["forged_acks_early"]
        n_out = d["foreign_acks"]
        others = [a for a in members if a != self.node_addr]
        self.replies = len(others)
        late_privs: list = []
        self.ack_plan: list = []  # per stream: [(author, kind, spoil)]
        self._ack_pre: list = []  # per stream: [(message's, datagram's)]
        for p in range(n_h):
            h = p + 1
            bad = rng.sample(others, n_fa + n_out)
            forged, silent = bad[:n_fa], set(bad[n_fa:])
            kind_of = {a: FORGED[(p * n_fa + i) % len(FORGED)]
                       for i, a in enumerate(forged)}
            sound = [a for a in others if a not in kind_of
                     and a not in silent]
            rng.shuffle(sound)
            early, late = forged[:n_early], forged[n_early:]
            # the arrivals that COUNT: ``need`` of which ``n_early``
            # forged, then ``n_early`` sound ones, then the rest
            first = sound[:self.need - n_early]
            for a in early:
                first.insert(rng.randrange(len(first) + 1), a)
            rest = sound[self.need:] + late
            rng.shuffle(rest)
            order = first + sound[self.need - n_early:self.need] + rest
            for a in self.outsiders:  # anywhere: they never count
                order.insert(rng.randrange(len(order) + 1), a)
            plan, pre = [], []
            for a in order:
                kind, spoil, priv = kind_of.get(a), None, priv_of.get(a)
                if priv is None:
                    kind = "non_member"
                    priv = out_privs[self.outsiders.index(a)]
                elif kind == "other_key":
                    priv = priv_of[members[(members.index(a) + 1) % n_val]]
                elif kind == "s_out_of_range":
                    spoil = (secp.N + 1 + rng.randrange(1 << 64)) \
                        .to_bytes(32, "big")
                elif kind == "r_off_curve":
                    spoil = _off_curve_x(rng).to_bytes(32, "big")
                late_privs.append(priv)
                plan.append((a, kind, spoil))
                pre.append((b"geec/ack" + rlp.encode(
                    [h, a, 1, bytes(32)])[:-32],
                    datagram(h, a, bytes(32), bytes(65))[:-_TAIL]))
            self.ack_plan.append(plan)
            self._ack_pre.append(pre)
        self._late = LateSigner(late_privs, _key_base(rng))

    # what the run asks for ---------------------------------------------
    def windows(self, stream: int) -> list:
        """Stream ``stream``'s gossip windows, each a list of frame
        indices."""
        return self.streams[stream]

    def acks(self, stream: int, block_hash: bytes) -> list:
        """Height ``stream + 1``'s ``ValidateReply`` datagrams over
        ``block_hash``, in arrival order."""
        pre = self._ack_pre[stream]
        base = stream * len(pre)
        hashes = keccak256_many(m + block_hash for m, _dg in pre)
        out = []
        for j, ((_a, kind, spoil), (_m, dg)) in enumerate(
                zip(self.ack_plan[stream], pre)):
            sig = self._late.finish(base + j, hashes[j])
            if kind == "s_out_of_range":
                sig = sig[:32] + spoil + sig[64:]
            elif kind == "r_off_curve":
                sig = spoil + sig[32:]
            out.append(dg + block_hash + b"\xb8\x41" + sig)
        return out

    def signer(self, k: int) -> bytes:
        """The account that signed the sound frame under frame ``k``."""
        k = self.origin[k]
        return self.addrs[self.account[k] if k < self.n_valid
                          else self._account_of[k]]

    def frame_expect(self, k: int):
        """What the pool must do with a fresh frame k: ``("admit",
        sender)``, ``("admit_other", signer)`` where the message was
        altered after signing, or ``("reject", None)``.  A transfer that
        cannot execute is soundly signed: the pool admits it."""
        kind = self.kind[k]
        if kind is None or kind in UNEXECUTABLE:
            return "admit", self.signer(k)
        if kind == "flipped_message":
            return "admit_other", self.signer(k)
        return "reject", None

    def construction(self, stream: int) -> dict:
        """A height's rows by construction, and what the collect, verify,
        prune and go-on tally makes of its votes and its ACKs."""
        d = self.d
        seq = [k for w in self.streams[stream] for k in w]
        kinds = [kind for _a, kind, _s in self.ack_plan[stream]]
        counts = [i for i, kind in enumerate(kinds) if kind != "non_member"]
        forged = [i for i in counts if kinds[i] is not None]
        early = [i for i in forged if counts.index(i) < self.need]
        n_early = len(early)
        after = [kinds[i] for i in counts[self.need:self.need + n_early]]
        votes = [kind for _dg, kind, _a in self.votes[stream]]
        n_fv = sum(1 for kind in votes[:self.vote_threshold]
                   if kind is not None)
        to_elect = self.vote_threshold + n_fv
        to_certify = self.need + n_early
        return {"gossip_frames": len(seq),
                "fresh": sum(1 for k in set(seq) if self.kind[k] is None),
                "copies": len(seq) - len(set(seq)),
                "spoiled": sum(1 for k in seq if self.kind[k] in KINDS),
                "unexecutable": [self.kind[k] for k in
                                 self.unexecutable.get(stream, ())],
                "windows": len(self.streams[stream]),
                "votes": len(votes),
                "forged_votes_among_first_threshold": n_fv,
                "votes_to_elect": to_elect,
                "replies": len(kinds),
                "sound": sum(1 for kind in kinds if kind is None),
                "forged": [kinds[i] for i in forged],
                "forged_among_first_need": n_early,
                "forged_among_the_next": sum(
                    1 for kind in after if kind is not None),
                "foreign": sum(1 for kind in kinds if kind == "non_member"),
                "need": self.need,
                # the tally as it is today
                "attempts": 2, "attempt_rows": [self.need, self.need],
                "certified_at_counted": to_certify,
                "certified_at": counts[to_certify - 1] + 1,
                # gossip + the proposal's transactions once + the votes
                # up to the one that elected + the ACKs that counted up
                # to the one that certified
                "rows": len(seq) + d["txn_per_block"] + to_elect
                + to_certify}
