"""The plain reference of a proposer's quorum: WHO may be counted among a
block's supporters and WHEN a quorum may and must stand, from the bytes
of the ACK datagrams in arrival order, the height's acceptors and the
chain's ``validate_threshold``; and the check of a finished certificate.
Its own RLP reading, ``keccak`` and ``secp.recover``; nothing of the
program.

It says nothing of HOW a tally gets there: at which reply signatures are
verified, in how many attempts, or at which reply the program noticed
that its quorum stood.  A program that verifies stragglers together, or
every reply as it comes, is held to the same answers.

The wire (``eges_tpu/consensus/messages.py`` writes the same): a
datagram of the direct plane is the RLP list ``[code, author, payload]``,
``code`` 1 for a validate reply; the payload is the RLP list
``[block_num, author, accepted, retry, fill_blocks, block_hash, sig]``;
an ACK's signature is over ``keccak256(b"geec/ack" + rlp([block_num,
author, accepted, block_hash]))``, 65 bytes r || s || recovery id.
"""

from __future__ import annotations

from fractions import Fraction

from perfbench.ref import rlp, secp
from perfbench.ref.keccak import keccak256
from perfbench.ref.senders import read

VALIDATE_REPLY = 0x01


def need(fraction, acceptors: int) -> int:
    """The supporters a quorum takes: ``ceil(fraction * acceptors)``
    with the fraction read as the decimal it is written as (0.66 of 256
    is 169, of 100 is 66); None is upstream's majority,
    ``ceil((acceptors + 1) / 2)``."""
    if fraction is None:
        return -(-(acceptors + 1) // 2)
    f = Fraction(str(fraction))
    return -(-f.numerator * acceptors // f.denominator)


def ack_sighash(block_num: int, author: bytes, accepted: int,
                block_hash: bytes) -> bytes:
    return keccak256(b"geec/ack" + rlp.encode(
        [block_num, author, accepted, block_hash]))


def read_ack(datagram: bytes):
    """The fields of a validate reply's datagram, or None where the
    bytes are none: ``(author, block_num, accepted, block_hash, sig)``.
    The envelope's author is not believed; the payload's is what the
    signature binds."""
    try:
        code, _env_author, payload = read(datagram)
        if int.from_bytes(code, "big") != VALIDATE_REPLY:
            return None
        f = read(payload)
        return (f[1], int.from_bytes(f[0], "big"),
                int.from_bytes(f[2], "big"), f[5], f[6])
    except (IndexError, ValueError, TypeError):
        return None


def sound_author(datagram: bytes, members, block_num: int,
                 block_hash: bytes):
    """The acceptor whose sound ACK this datagram is, or None: a reply
    for THIS height that accepts THIS block hash, from a member of the
    height's acceptors, with a signature that recovers its author."""
    ack = read_ack(datagram)
    if ack is None:
        return None
    author, num, accepted, h, sig = ack
    if (num != block_num or accepted != 1 or h != block_hash
            or author not in members):
        return None
    if secp.recover(ack_sighash(num, author, accepted, h), sig) != author:
        return None
    return author


def stands_from(sound: list, want: int):
    """The shortest prefix of ``sound`` (an acceptor or None an arrival)
    that holds ``want`` distinct acceptors, or None."""
    seen: set = set()
    for i, a in enumerate(sound):
        if a is not None:
            seen.add(a)
            if len(seen) >= want:
                return i + 1
    return None


def tally(datagrams, members, fraction, block_num: int,
          block_hash: bytes) -> dict:
    """A block's datagrams in arrival order, judged: ``sound[i]`` is the
    acceptor whose sound ACK datagram i is, or None (an acceptor's
    second sound ACK counts once: it is in the set already);
    ``supporters(k)`` the set of sound supporters among the first k;
    ``need`` the threshold; ``stands_from`` the shortest prefix on which
    a quorum MAY stand (its sound supporters number ``need``), or None
    where the whole stream never gets there.  A program's quorum is
    sound if it stood on some prefix no shorter than that, counted no
    one outside that prefix's set, and counted ``need`` at the least;
    and where ``stands_from`` is not None the program MUST have
    certified a quorum by the stream's end."""
    members = set(members)
    sound = [sound_author(d, members, block_num, block_hash)
             for d in datagrams]
    want = need(fraction, len(members))
    return {"sound": sound, "need": want,
            "stands_from": stands_from(sound, want)}


def supporters(judged: dict, k: int) -> set:
    """The sound supporters among the first ``k`` datagrams."""
    return {a for a in judged["sound"][:k] if a is not None}


def judge_quorum(judged: dict, certified_at, counted, kept=None) -> dict:
    """A program's quorum against :func:`tally`'s answer.
    ``certified_at`` is how many datagrams had been handed over when the
    program's quorum stood (None: it never did), ``counted`` the
    supporters it certified, ``kept`` the authors it still held then
    (default: the supporters).  Counts of what is wrong, each 0 for a
    sound quorum:

    * ``forged``: supporters that are no sound supporter of that prefix
      (a forged, foreign, non-member or refusing ACK that counted);
    * ``under``: how far the supporters fall short of ``need``;
    * ``pruned``: sound supporters of that prefix the program dropped;
    * ``missed``: 1 where a quorum had to stand by the stream's end and
      none did."""
    if certified_at is None:
        return {"forged": 0, "under": 0, "pruned": 0,
                "missed": int(judged["stands_from"] is not None)}
    may = supporters(judged, certified_at)
    counted = set(counted)
    kept = counted if kept is None else set(kept)
    return {"forged": len(counted - may),
            "under": max(0, judged["need"] - len(counted)),
            "pruned": len(may - kept), "missed": 0}


def check_certificate(sups, sigs, members, fraction, block_num: int,
                      block_hash: bytes):
    """A finished certificate, signature by signature: None where it
    proves a quorum, else the reason it does not.  ``need`` distinct
    supporters at the least, each an acceptor of the height, each with
    a signature over the ACK of THIS block hash that recovers it."""
    members = set(members)
    if len(sups) != len(sigs):
        return "supporters and signatures differ in number"
    if len(set(sups)) != len(sups):
        return "a supporter counted twice"
    if len(sups) < need(fraction, len(members)):
        return "fewer supporters than the threshold"
    for a, s in zip(sups, sigs):
        if a not in members:
            return "a supporter outside the acceptors"
        if secp.recover(ack_sighash(block_num, a, 1, block_hash), s) != a:
            return "a signature that does not recover its supporter"
    return None
