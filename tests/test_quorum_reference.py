"""A proposer's ACK tally against the benchmark's plain reference: what
``c256.votes-steady`` holds the program to on the chip, at a size a test
can hold (12 validators, ``validate_threshold`` 0.66, so 8 ACKs).

Seeded ACK streams (``perfbench/gen_votes.datagram``, the reference's own
RLP and keys) with forged, foreign-hash, non-member, refusing and
twice-sent replies go, as bytes, through the program's own path
(``consensus.quorum.handle_direct`` -> ``QuorumTally.ack`` on a
``WorkingBlock``: what ``consensus/node.py`` runs) and through
``perfbench/ref/quorum.py``: the program's quorum counts nobody the
reference does not, stands no sooner than it may, drops no sound ACK, and
its certificate passes both checks, on the host C++ verifier and on the
jax verifier (CPU backend, the 16-row bucket alone).  Then the threshold
itself: the configured fraction, upstream's majority where it is absent,
the genesis round trip, and a confirm one signature short.
"""

import dataclasses
import random
import threading
import time

import pytest

from eges_tpu.consensus import messages as M
from eges_tpu.consensus.config import (BootstrapNode, ChainGeecConfig,
                                       NodeConfig)
from eges_tpu.consensus.membership import Member, Membership
from eges_tpu.consensus.node import GeecNode
from eges_tpu.consensus.quorum import QuorumTally, handle_direct
from eges_tpu.consensus.working_block import WorkingBlock
from eges_tpu.core.chain import BlockChain, make_genesis
from eges_tpu.core.types import ConfirmBlockMsg, Header, new_block
from eges_tpu.crypto import secp256k1 as host
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.sim.simnet import SimClock
from eges_tpu.utils import ledger, tracing
from eges_tpu.utils.metrics import DEFAULT as metrics
from perfbench import gen, gen_votes
from perfbench.ref import quorum as ref
from perfbench.ref import secp
from perfbench.ref.keccak import keccak256_many

N, FRACTION, NEED = 12, 0.66, 8
MAX_BATCH = 16  # one bucket: the jax verifier compiles no other
BLOCK_NUM = 7


class Stream:
    """One block's replies, seeded: of the 11 other validators eight send
    a sound ACK (one of them twice), one a forged one (a kind by the
    seed), one an ACK for another block hash, one refuses; an outsider
    sends a sound-looking one; and one more forged reply squats on a
    sound validator's address.  The order is the seed's."""

    def __init__(self, seed: int):
        rng = random.Random(seed)
        self.privs, self.members = secp.keys(rng.randrange(1 << 200,
                                                           1 << 250), N)
        out_priv, out_addr = (x[0] for x in secp.keys(
            rng.randrange(1 << 200, 1 << 250), 1))
        self.hash = rng.randbytes(32)
        self.block_seed = rng.getrandbits(63)
        proposer = rng.randrange(N)
        others = [i for i in range(N) if i != proposer]
        bad = rng.sample(others, 3)
        forged, squat = rng.sample(gen_votes.FORGED, 2)
        kinds = dict(zip(bad, [forged, "foreign_hash", "refusing"]))
        rows = []  # (author, key, block hash, accepted, kind, how spoiled)
        for i in others:
            kind = kinds.get(i)
            priv, h, acc = self.privs[i], self.hash, 1
            if kind == "other_key":
                priv = self.privs[(i + 1) % N]
            elif kind == "foreign_hash":
                h = bytes(x ^ 0xFF for x in self.hash)
            elif kind == "refusing":
                acc = 0
            rows.append((self.members[i], priv, h, acc, kind, kind))
        rows.append((out_addr, out_priv, self.hash, 1, "non_member", None))
        sound = [r for r in rows if r[4] is None]
        rows.append(rng.choice(sound))  # sent twice
        victim = rng.choice(sound)
        rows.append((victim[0], out_priv if squat == "other_key"
                     else victim[1], self.hash, 1, "squat", squat))
        rng.shuffle(rows)
        hashes = keccak256_many(
            b"geec/ack" + ref.rlp.encode([BLOCK_NUM, r[0], r[3], r[2]])
            for r in rows)
        sigs = secp.sign_rows([r[1] for r in rows], hashes,
                              rng.randrange(1 << 200, 1 << 250))
        self.kinds = [r[4] for r in rows]
        self.datagrams = [
            gen_votes.datagram(BLOCK_NUM, a, h, gen._spoil(how, sig, rng),
                               accepted=acc)
            for (a, _p, h, acc, _kind, how), sig in zip(rows, sigs)]
        self.sound = [r[0] if r[4] is None else None for r in rows]


def _membership(members, fraction=FRACTION) -> Membership:
    ms = Membership(len(members), len(members), validate_fraction=fraction)
    for a in members:
        ms.add(Member(addr=a, ip="", port=0, ttl=50))
    return ms


def _deliver(data: bytes, dispatch, dropped=None) -> None:
    """One datagram through the node's direct-plane entry."""
    handle_direct(data, dispatch, lock=_LOCK, book=_BOOK, max_bytes=1 << 20,
                  log=None if dropped is None
                  else lambda what, **kw: dropped.append(what))


_LOCK = threading.RLock()
_BOOK = ledger.IngressLedger(clock=time.monotonic)


def play(stream: Stream, verifier, fraction=FRACTION) -> dict:
    """The stream through the program's own path, a datagram at a time;
    what it certified and at which arrival."""
    sched = VerifierScheduler(verifier, max_batch=MAX_BATCH)
    ms = _membership(stream.members, fraction)
    tally = QuorumTally(ms, sched, signing=True)
    wb = WorkingBlock(stream.members[0])
    wb.advance(BLOCK_NUM)
    wb.validate_threshold = ms.validate_threshold()
    out = {"at": None, "dropped": []}

    def dispatch(code, msg, author):
        assert code == M.UDP_EXAMINE_REPLY
        if tally.ack(wb, msg, seed=stream.block_seed,
                     block_hash=stream.hash, collecting=out["at"] is None):
            out.update(at=k + 1, supporters=tuple(wb.validate_replies),
                       cert=dict(wb.validate_cert))

    try:
        for k, data in enumerate(stream.datagrams):
            _deliver(data, dispatch, out["dropped"])
        out["kept"] = tuple(wb.validate_replies)
        if out["at"] is not None:
            sups = out["supporters"]
            confirm = ConfirmBlockMsg(
                block_number=BLOCK_NUM, hash=stream.hash, confidence=1000,
                supporters=sups,
                supporter_sigs=tuple(out["cert"][a] for a in sups))
            out["cert_ok"] = tally.cert_ok(confirm, stream.block_seed)
            short = dataclasses.replace(
                confirm, supporters=sups[:NEED - 1],
                supporter_sigs=confirm.supporter_sigs[:NEED - 1])
            out["short_ok"] = tally.cert_ok(short, stream.block_seed)
    finally:
        sched.close()
    return out


_JAX = []


def _verifier(name: str):
    if name == "native":
        return NativeBatchVerifier()
    if not _JAX:
        import numpy as np

        from eges_tpu.crypto.verifier import BatchVerifier
        bv = BatchVerifier()
        # the 16-row bucket traces and compiles here (about a minute on
        # the CPU), not inside a wait of the play
        bv.recover_addresses(np.zeros((MAX_BATCH, 65), np.uint8),
                             np.zeros((MAX_BATCH, 32), np.uint8))
        _JAX.append(bv)
    return _JAX[0]


@pytest.mark.parametrize("name,seed", [
    ("native", 2**31 + 1), ("native", 2**31 + 2), ("native", 2**31 + 3),
    ("native", 5), ("native", 6), ("native", 7),
    ("jax", 2**31 + 1), ("jax", 5)])
def test_the_tally_certifies_what_the_plain_reference_allows(name, seed):
    stream = Stream(seed)
    judged = ref.tally(stream.datagrams, stream.members, FRACTION,
                       BLOCK_NUM, stream.hash)
    # the reference reads the bytes as the stream was made
    assert judged["sound"] == stream.sound and judged["need"] == NEED
    assert judged["stands_from"] is not None  # eight sound validators
    got = play(stream, _verifier(name))
    assert got["dropped"] == []
    assert got["at"] is not None and got["at"] >= judged["stands_from"]
    assert ref.judge_quorum(judged, got["at"], got["supporters"],
                            got["kept"]) == {
        "forged": 0, "under": 0, "pruned": 0, "missed": 0}
    assert len(set(got["supporters"])) == len(got["supporters"]) >= NEED
    assert ref.check_certificate(
        got["supporters"], [got["cert"][a] for a in got["supporters"]],
        stream.members, FRACTION, BLOCK_NUM, stream.hash) is None
    assert got["cert_ok"] is True and got["short_ok"] is False


def test_the_reference_refuses_what_no_quorum_is():
    stream = Stream(11)
    judged = ref.tally(stream.datagrams, stream.members, FRACTION,
                       BLOCK_NUM, stream.hash)
    got = play(stream, NativeBatchVerifier())
    sups = list(got["supporters"])
    sigs = [got["cert"][a] for a in sups]
    check = lambda s, g, m=stream.members, f=FRACTION, h=stream.hash: \
        ref.check_certificate(s, g, m, f, BLOCK_NUM, h)  # noqa: E731
    assert check(sups, sigs) is None
    assert "fewer" in check(sups[:NEED - 1], sigs[:NEED - 1])
    assert "twice" in check(sups[:-1] + sups[:1], sigs[:-1] + sigs[:1])
    assert "recover" in check(sups, sigs[1:] + sigs[:1])
    assert "recover" in check(sups, sigs, h=bytes(32))
    assert "outside" in check(sups, sigs, m=[a for a in stream.members
                                             if a != sups[0]], f=0.6)
    # a forged supporter, a quorum one short, a sound ACK dropped, a
    # quorum that never stood: each is counted as what it is
    forged = next(ref.read_ack(d)[0] for d, k in zip(
        stream.datagrams, stream.kinds) if k in gen_votes.FORGED)
    n = len(stream.datagrams)
    assert ref.judge_quorum(judged, n, sups + [forged])["forged"] == 1
    assert ref.judge_quorum(judged, n, sups[:NEED - 1])["under"] == 1
    assert ref.judge_quorum(judged, n, sups, sups[1:])["pruned"] >= 1
    assert ref.judge_quorum(judged, None, ())["missed"] == 1
    # upstream's majority of 12 is 7: a quorum of 7 is under this chain's
    got7 = play(stream, NativeBatchVerifier(), fraction=None)
    assert len(got7["supporters"]) == 7
    assert ref.judge_quorum(judged, got7["at"],
                            got7["supporters"])["under"] == 1


def test_a_forged_ack_costs_a_second_attempt_and_the_counters_say_so():
    """Three sound ACKs and a forged one among the first four of a chain
    that needs four: an attempt of 4 rows prunes one, the next sound
    reply starts a second attempt over 4 rows, of which the cache
    answers 3."""
    rng = random.Random(3)
    privs, members = secp.keys(1 << 210, 6)
    h = rng.randbytes(32)
    msgs = keccak256_many(b"geec/ack" + ref.rlp.encode([BLOCK_NUM, a, 1, h])
                          for a in members)
    sigs = secp.sign_rows(privs[:3] + [privs[0]] + privs[4:], msgs, 1 << 220)
    grams = [gen_votes.datagram(BLOCK_NUM, a, h, s)
             for a, s in zip(members, sigs)][:5]
    ms = _membership(members)  # ceil(0.66 * 6) = 4
    assert ms.validate_threshold() == 4
    names = ("quorum_attempts", "quorum_rows", "quorum_pruned", "quorums")
    before = {n: metrics.counter("consensus." + n).value for n in names}
    hist = metrics.histogram("consensus.quorum_seconds")
    n_hist = hist.count
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=MAX_BATCH)
    tally = QuorumTally(ms, sched)
    wb = WorkingBlock(members[5])
    wb.advance(BLOCK_NUM)
    wb.validate_threshold = 4
    stood = []
    try:
        for data in grams:
            _deliver(data, lambda c, m, a: stood.append(tally.ack(
                wb, m, seed=1, block_hash=h, collecting=True)))
        hits = sched.stats()["cache_hits"]
    finally:
        sched.close()
    assert stood == [False, False, False, False, True]
    assert set(wb.validate_replies) == set(members[:3]) | {members[4]}
    assert set(wb.validate_cert) == set(wb.validate_replies)
    after = {n: metrics.counter("consensus." + n).value for n in names}
    assert {n: after[n] - before[n] for n in names} == {
        "quorum_attempts": 2, "quorum_rows": 8, "quorum_pruned": 1,
        "quorums": 1}
    assert hist.count == n_hist + 1 and hits == 3
    spans = [s for s in tracing.DEFAULT.finished()
             if s["name"] == "consensus.verify_quorum"][-2:]
    assert [(s["attrs"]["attempt"], s["attrs"]["need"], s["attrs"]["rows"])
            for s in spans] == [(1, 4, 4), (2, 4, 4)]
    handled = [s for s in tracing.DEFAULT.finished()
               if s["name"] == "consensus.handle"][-5:]
    assert {s["attrs"]["kind"] for s in handled} == {"validate_reply"}


@pytest.mark.parametrize("n,fraction,want", [
    (256, 0.66, 169), (12, 0.66, 8), (100, 0.66, 66), (3, 0.66, 2),
    (4, 0.75, 3), (256, 1.0, 256), (256, 0.51, 131),
    (256, None, 129), (12, None, 7), (3, None, 2), (4, None, 3),
    (2, None, 2)])
def test_the_threshold_follows_the_configured_fraction(n, fraction, want):
    ms = _membership([i.to_bytes(20, "big") for i in range(n)], fraction)
    assert ms.validate_threshold() == want == ref.need(fraction, n)
    if fraction is None:  # upstream's rule, as it always was
        assert want == -(-(n + 1) // 2)


def test_the_genesis_key_round_trips_and_is_absent_by_default():
    boot = (BootstrapNode(account=bytes(20), ip="10.0.0.1", port=8100),)
    plain = ChainGeecConfig(bootstrap=boot)
    assert plain.validate_threshold is None
    assert "validate_threshold" not in plain.to_json()
    assert ChainGeecConfig.from_json(plain.to_json()) == plain
    cfg = ChainGeecConfig(bootstrap=boot, validate_threshold=0.66)
    assert cfg.to_json()["validate_threshold"] == 0.66
    assert ChainGeecConfig.from_json(cfg.to_json()) == cfg
    assert ChainGeecConfig.from_json(
        {"validate_threshold": None}).validate_threshold is None
    for bad in (0.5, 0.0, 1.01, -1):
        with pytest.raises(ValueError):
            ChainGeecConfig(validate_threshold=bad)


class _Transport:
    def gossip(self, data):
        pass

    def send_direct(self, ip, port, data):
        pass


def _node(fraction):
    privs = [bytes([i + 1]) * 32 for i in range(N)]
    addrs = [host.pubkey_to_address(host.privkey_to_pubkey(p))
             for p in privs]
    boot = tuple(BootstrapNode(account=a, ip=f"10.0.0.{i + 1}",
                               port=8100 + i) for i, a in enumerate(addrs))
    ccfg = ChainGeecConfig(bootstrap=boot, signed_votes=True,
                           validate_threshold=fraction)
    ncfg = NodeConfig(coinbase=addrs[0], consensus_ip="10.0.0.1",
                      consensus_port=8100, n_candidates=N, n_acceptors=N,
                      txn_per_block=4, total_nodes=N, privkey=privs[0])
    node = GeecNode(BlockChain(genesis=make_genesis()), SimClock(),
                    _Transport(), ncfg, ccfg, mine=True)
    return node, privs, addrs


@pytest.mark.parametrize("fraction,need", [(0.66, 8), (None, 7)])
def test_a_confirm_one_signature_short_is_refused(fraction, need):
    node, privs, addrs = _node(fraction)
    assert node.membership.validate_threshold() == need
    blk = new_block(Header(parent_hash=node.chain.head().hash, number=1,
                           coinbase=addrs[1], time=1, trust_rand=5))

    def confirm(n_sups: int) -> ConfirmBlockMsg:
        sups = tuple(addrs[1:n_sups + 1])
        sigs = tuple(host.ecdsa_sign(M.ValidateReply(
            block_num=1, author=a, accepted=True,
            block_hash=blk.hash).signing_hash(), privs[i + 1])
            for i, a in enumerate(sups))
        c = ConfirmBlockMsg(block_number=1, hash=blk.hash, confidence=1000,
                            supporters=sups, supporter_sigs=sigs)
        return dataclasses.replace(
            c, sig=host.ecdsa_sign(c.signing_hash(), privs[1]))

    assert node._confirm_ok(confirm(need - 1)) is False
    assert node._confirm_ok(confirm(need)) is True
    assert node._confirm_ok(confirm(need + 1)) is True
    # the tally holds the proposer to the same number
    node._phase, node._proposal = 2, blk  # VALIDATING
    node.wb.validate_threshold = node.membership.validate_threshold()
    for i in range(1, need):
        r = M.ValidateReply(block_num=1, author=addrs[i],
                            block_hash=blk.hash)
        node._handle_validate_reply(dataclasses.replace(
            r, sig=host.ecdsa_sign(r.signing_hash(), privs[i])))
    assert node._phase == 2 and not node.wb.validate_succeeded
    r = M.ValidateReply(block_num=1, author=addrs[need],
                        block_hash=blk.hash)
    node._handle_validate_reply(dataclasses.replace(
        r, sig=host.ecdsa_sign(r.signing_hash(), privs[need])))
    assert node.wb.validate_succeeded and node._phase != 2
    assert len(node.wb.validate_cert) == need
