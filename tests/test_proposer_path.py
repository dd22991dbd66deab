"""The PROPOSER's half of ``consensus/node.py`` on the wall clock, with no
socket: what ``c1024p.heights-backlog`` drives on the chip, at a size a
test can hold (16 validators, 32 transfers a block).

A ``GeecNode`` with ``mine=True`` built as the benchmark's driver builds it
(``perfbench/drivers/block_proposer.py build_node``: a recording transport,
the generator as its trusted random source) is fed by
``perfbench/gen_heights.py`` through the driver's own two threads: gossip
one height ahead, the committee's votes, the acceptors' ACKs over the hash
of the block the node built.  Everything the node gossiped is then read
back from its bytes and judged by the plain reference alone
(``judge_heights``): every block executes in ``ref/state.py`` to its
header's roots, every certificate holds under ``ref/secp.py``, the forged
and foreign replies and the unexecutable transfers are nowhere.
"""

import concurrent.futures
import random
import threading

import pytest

from eges_tpu.core.chain import BlockChain
from eges_tpu.crypto.scheduler import VerifierScheduler
from perfbench import control_propose, gen_heights
from perfbench.drivers import block_proposer as bp
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import secp
from perfbench.ref import senders as ref_senders

DEPLOY = {"validators": 16, "committee": 4, "acceptors": 16,
          "txn_per_block": 32, "gossip_window": 8, "duplicate_share": 0.25,
          "invalid_every": 8, "accounts": 64, "senders": 16,
          "payload_bytes": 100, "gas_limit": 29000, "value_wei": 1,
          "balance_wei": 10**18, "forged_votes": 1, "forged_acks": 3,
          "forged_acks_early": 2, "foreign_acks": 1,
          "unexecutable_every": 2, "unexecutable": 2, "stream_heights": 8,
          "roots_every": 1, "cert_reference_rows": 2}
MAX_BATCH = 16  # one bucket: the jax verifier compiles no other
ZERO = ("heights_out_of_order", "sealed_not_the_head",
        "requests_not_the_sealed_block", "blocks_not_full",
        "unsound_txns_in_blocks", "txns_in_two_blocks",
        "blocks_not_executable", "commitments_wrong", "forged_supporters",
        "supporters_under_threshold", "certificates_malformed",
        "elections_under_threshold", "reference_signatures_wrong")


def _verifier(name: str):
    from tests.test_state_reference import _verifier as make

    return make(name)


class Rig:
    """One proposer on a recording transport, and the driver's threads."""

    def __init__(self, d, seed, verifier="native", control=None):
        self.d = d
        self.feed = feed = gen_heights.HeightsFeed(
            seed, d,
            first_unexecutable=control_propose.FIRST_UNEXECUTABLE[control])
        self.sched = VerifierScheduler(_verifier(verifier),
                                       max_batch=MAX_BATCH)
        self.transport = bp.Transport()
        self.chain = BlockChain(verifier=self.sched, alloc={
            a: feed.balance for a in feed.addrs})
        self.node = node = bp.build_node(
            feed, d, self.chain, self.sched, self.transport,
            control_propose.node_class(control))
        node.quorum = control_propose.quorum_of(control, node)
        self.tally = tally = bp.Tally(feed)
        tally.node = node
        node.journal.on_record = tally.on_event
        self.chain.add_listener(tally.on_block)
        self.pool = control_propose.pool_class(control)(
            node.clock, verifier=self.sched, on_admitted=tally.on_admitted)
        node.txpool = self.pool
        self.run = bp.Heights(feed, node, self.pool, self.chain,
                              self.transport, tally, bp._no_span)
        self.threads = []

    def seal(self, heights: int) -> None:
        """Both threads, until ``heights`` heights are sealed."""
        run = self.run
        run.go.set()
        self.threads = [threading.Thread(target=run.feeder),
                        threading.Thread(target=run.block_path)]
        for t in self.threads:
            t.start()
        self.node.start()
        with run.cv:
            while run.done < heights and not run.closed.is_set():
                run.cv.wait(0.25)
        run.closing.set()
        run.closed.wait(30.0)
        with run.cv:
            run.stop.set()
            run.cv.notify_all()
        for t in self.threads:
            t.join()
        assert run.failed is None, [
            (e["type"], e.get("blk"), e["ts"])
            for e in self.node.journal.events()[-12:]]

    def sent(self) -> tuple:
        """``(requests by height, confirms by height)`` off the
        transport's bytes."""
        return bp.sent_by_height(self.transport)

    def judge(self) -> dict:
        requests, confirms = self.sent()
        with concurrent.futures.ThreadPoolExecutor(1) as ex:
            return bp.judge_heights(self.feed, self.tally, requests,
                                    confirms, self.d, ex, random.Random(5))

    def close(self) -> None:
        self.node.stop()
        self.sched.close()


def _executed() -> tuple[int, int]:
    from eges_tpu.utils.metrics import DEFAULT as metrics

    return (metrics.counter("chain.executions").value,
            metrics.counter("chain.insert_previewed").value)


@pytest.mark.parametrize("verifier", ["native", "jax"])
def test_six_heights_built_certified_sealed_and_held_to_the_reference(
        verifier):
    rig = Rig(DEPLOY, 2**31 + 29, verifier)
    try:
        before = _executed()
        rig.seal(6)
        feed, tally = rig.feed, rig.tally
        assert [n for n, _h, _head, _t in tally.inserted][:6] == \
            [1, 2, 3, 4, 5, 6]
        # a height is executed ONCE, by its preview: the seal's insert
        # takes that state (the run closes with the height in hand sealed)
        heights = len(tally.inserted)
        assert _executed() == tuple(n + heights for n in before)
        got = rig.judge()
        assert {k: got[k] for k in ZERO} == dict.fromkeys(ZERO, 0)
        assert got["roots_compared"] >= 6
        # after the last height every account is the reference's
        state = rig.chain.head_state()
        assert {a: [state.nonce(a), state.balance(a)]
                for a in feed.addrs} == got["state"]
        requests, confirms = rig.sent()
        bad = {k for ks in feed.unexecutable.values() for k in ks}
        for n, bhash, _head, _t in tally.inserted[:6]:
            # the request (a slow verifier's 500 ms retries gossip it
            # again): the node's, a full block, the block that was sealed
            assert {gen_heights.request_block(r)[1]
                    for r in requests[n]} == {bhash}
            req = bp.read_request(requests[n][-1])
            assert (req["author"], req["hash"], req["fakes"]) == (
                feed.node_addr, bhash, 0)
            ks = [feed.index_of[h] for h in req["tx_hashes"]]
            assert len(ks) == 32 and not bad & set(ks)
            assert all(feed.kind[k] is None for k in ks)
            # the certificate, every signature through the reference
            c = ref_senders.read(confirms[n])[1]
            sups, sigs = c[3], c[7]
            kinds = {a: kind for a, kind, _s in feed.ack_plan[n - 1]}
            assert len(set(sups)) == len(sups) >= feed.need == 9
            for a, s in zip(sups, sigs):
                assert kinds[a] is None  # no forged, no foreign reply
                assert secp.recover(ref_quorum.ack_sighash(n, a, 1, bhash),
                                    s) == a
            # by construction: elected on the third vote, certified on
            # the eleventh reply that counts, two attempts each
            assert tally.elected[n][0] == 3
            assert tally.certified[n][1] == 11
            assert tally.built[n] == 32
        # the transfers that cannot execute were admitted and stay behind
        left = {t.hash for by in rig.pool.pending.values()
                for t in by.values()}
        handed = {k for _p, idx in tally.handed for k in idx}
        assert {feed.hashes[k] for k in bad & handed} <= left
    finally:
        rig.close()


@pytest.mark.parametrize("control, check", [
    ("accept_all", "forged_supporters"),
    ("unfiltered_pool", "blocks_not_full"),
])
def test_a_control_fails_the_check_that_is_its_own(control, check):
    rig = Rig(DEPLOY, 2**31 + 29, control=control)
    try:
        rig.seal(6)
        got = rig.judge()
        assert [k for k in ZERO if got[k]] == [check]
    finally:
        rig.close()


def test_an_aborted_proposals_preview_is_not_anothers_block():
    """The node builds height 1 (its preview is kept), then ANOTHER
    proposer's block of that height, the same transfers under another
    coinbase, is inserted: it is verified and executed in full, and the
    proposal is aborted."""
    import dataclasses

    from eges_tpu.consensus import messages as M
    from eges_tpu.core.evm import BlockCtx
    from eges_tpu.core.types import ConfirmBlockMsg, new_block
    from eges_tpu.ingress import admit_remotes_window, decode_txn_window
    from tests.test_validated_insert import last_insert_span

    rig = Rig(DEPLOY, 2**31 + 29)
    try:
        feed, node, chain, run = rig.feed, rig.node, rig.chain, rig.run
        handed = 0
        for idx in feed.windows(0):
            admit_remotes_window(rig.pool, decode_txn_window(
                [feed.frames[k] for k in idx]))
            handed += len(idx)
        assert _wait(lambda: sum(rig.pool.stats[k] for k in (
            "admitted", "rejected", "duplicate")) >= handed)
        before = _executed()
        node.start()
        assert _wait(lambda: len(rig.transport.direct) >= len(feed.votes[0]))
        for dg, _kind, _a in feed.votes[0]:
            node.on_direct(dg)
        at = run._sent(rig.transport.gossiped, 0,
                       lambda g: bp._code(g[1]) == bp.VALIDATE_REQ)
        assert at >= 0 and _executed() == (before[0] + 1, before[1])
        mine = M.unpack_gossip(rig.transport.gossiped[at][1])[1].block
        assert chain._previewed is not None and chain.height() == 0
        # the other proposer's block, from a chain of its own
        other = bytes([0xD7]) * 20
        twin = BlockChain(verifier=rig.sched, alloc={
            a: feed.balance for a in feed.addrs})
        h = mine.header
        kept, root, rroot, gas, bloom = twin.execute_preview(
            list(mine.transactions), other, ctx=BlockCtx(
                coinbase=other, number=1, time=h.time,
                difficulty=h.difficulty))
        assert len(kept) == 32
        theirs = new_block(dataclasses.replace(
            h, coinbase=other, root=root, receipt_hash=rroot, gas_used=gas,
            bloom=bloom), txs=kept)
        theirs = theirs.with_confirm(ConfirmBlockMsg(
            block_number=1, hash=theirs.hash, confidence=1000))
        assert chain.offer(theirs) == [theirs]
        assert _executed() == (before[0] + 3, before[1])  # twin's, the full
        assert last_insert_span()["reused"] == 0
        assert chain._previewed is None
        assert chain.head_state().root() == root and chain.height() == 1
        assert any(e["type"] == "proposal_aborted" and e["blk"] == 1
                   for e in node.journal.events())
    finally:
        rig.close()


def _wait(cond, seconds: float = 30.0) -> bool:
    import time

    deadline = time.monotonic() + seconds
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.002)
    return True


def test_pad_upstream_puts_fakes_beside_a_full_block():
    rig = Rig(DEPLOY, 2**31 + 29, control="pad_upstream")
    try:
        rig.seal(3)
        requests, _confirms = rig.sent()
        sound = Rig(DEPLOY, 2**31 + 29)
        try:
            sound.seal(3)
            theirs, _c = sound.sent()
        finally:
            sound.close()
        for n in (1, 2, 3):
            req = bp.read_request(requests[n][-1])
            assert (len(req["tx_hashes"]), req["fakes"]) == (32, 32)
            assert len(requests[n][-1]) > len(theirs[n][-1]) + 32 * 100
        got = rig.judge()
        assert [k for k in ZERO if got[k]] == []  # the size is the check
    finally:
        rig.close()


def test_a_timer_cancelled_while_it_waited_for_the_lock_does_not_run():
    """On a clock whose timers are threads, a timer that fired while a
    handler held the node's lock cannot be reached by ``cancel``: found
    with the slow jax verifier, where an election's 1 s re-send fired
    inside the attempt that won it, armed itself again and, a height
    later, aborted that height's proposal."""
    class Handle:
        def __init__(self, fn):
            self.fn, self.cancelled = fn, False

        def cancel(self):
            self.cancelled = True

    class Clock:
        def __init__(self):
            self.handles = []

        def now(self):
            return 0.0

        def call_later(self, _delay, fn):
            self.handles.append(Handle(fn))
            return self.handles[-1]

    rig = Rig(DEPLOY, 2**31 + 29)
    try:
        node = rig.node
        node.clock, ran = Clock(), []
        node._set_timer("election", 1.0, lambda: ran.append("first"))
        first = node.clock.handles[-1]
        node._cancel_timer("election")
        first.fn()  # it had fired already and was waiting for the lock
        assert ran == [] and first.cancelled
        node._set_timer("election", 1.0, lambda: ran.append("second"))
        first.fn()  # nor once its name is armed anew
        assert ran == []
        node.clock.handles[-1].fn()
        assert ran == ["second"]
    finally:
        rig.close()
