"""A validator's sender work against the benchmark's plain reference, row
for row: what ``c64.zipf-backlog`` holds the program to on the chip, at a
size a test can hold (8 blocks of 32 transactions over 16 accounts).

The same seeded stream (``perfbench/gen_zipf.py``) goes through the
program (``decode_txn_window`` -> ``TxPool`` -> scheduler, then each block
body through ``core.state.recover_senders``) and through
``perfbench/ref/senders.py``: the same senders, the same admitted set, the
same refusals, the same refused blocks, on the host C++ verifier and on
the jax verifier (CPU backend, the 16-row bucket alone).  Then the program
against itself: no cache, the default cache, and a block handed over while
its gossip window is held in flight give identical answers.
"""

import random
import threading
import time

import pytest

from eges_tpu.core import rlp
from eges_tpu.core.state import StateError, recover_senders
from eges_tpu.core.txpool import TxPool
from eges_tpu.core.types import Transaction
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.ingress import admit_remotes_window, decode_txn_window
from eges_tpu.utils.metrics import DEFAULT as metrics
from perfbench import gen, gen_zipf
from perfbench.clock import ThreadClock
from perfbench.ref import senders as ref

DEPLOY = {"accounts": 16, "txn_per_block": 32, "pool_blocks": 8,
          "gossip_window": 8, "zipf_theta": 0.99, "duplicate_share": 0.25,
          "unseen_share": 0.10, "invalid_every": 8, "bad_block_every": 4,
          "gas_limit": 29000, "payload_bytes": 100}
SEED = 2**31 + 64
MAX_BATCH = 16  # one bucket: the jax verifier compiles no other


@pytest.fixture(scope="module")
def feed():
    return gen_zipf.ZipfFeed(SEED, DEPLOY)


def _counters() -> dict:
    return {n: metrics.counter("chain." + n).value for n in (
        "sender_rows", "sender_cached_rows", "sender_coalesced_rows",
        "blocks_refused")}


class Gate:
    """A verifier that can be told to keep a window on its way: the call
    that carries the window waits inside it until the gate opens."""

    def __init__(self, inner):
        self._inner = inner
        self.open = threading.Event()
        self.open.set()
        self.entered = threading.Event()

    def recover_addresses(self, sigs, hashes):
        self.entered.set()
        self.open.wait(30.0)
        return self._inner.recover_addresses(sigs, hashes)


def play(feed, verifier, *, hold_block=None, **sched_kw) -> dict:
    """The stream through the program, a block at a time: its gossip
    windows, then its body (the repaired one after a refusal), then the
    commit.  With ``hold_block`` that block's body is handed over while
    the block's last gossip rows are still inside the verifier."""
    gate = Gate(verifier) if hold_block is not None else None
    sched = VerifierScheduler(gate or verifier, max_batch=MAX_BATCH,
                              **sched_kw)
    admits, bodies = [], []
    pool = TxPool(ThreadClock(), verifier=sched,
                  on_admitted=lambda t, s: admits.append((t.hash, s)))
    handed, before = 0, _counters()

    def body(b: int, repaired: bool):
        txns = [Transaction.from_rlp(t)
                for t in rlp.decode(feed.body(b, repaired))]
        try:
            got = recover_senders(txns, sched)
        except StateError:
            got = None
        bodies.append((b, repaired, got))
        return txns if got is not None else None

    def settle(n: int) -> None:
        """Wait until the pool has an outcome for ``n`` frames: gossip
        first, then the block."""
        deadline = time.monotonic() + 30.0
        while sum(pool.stats[k] for k in ("admitted", "rejected",
                                          "duplicate")) < n:
            assert time.monotonic() < deadline
            time.sleep(0.002)

    try:
        for b in range(DEPLOY["pool_blocks"]):
            held = b == hold_block
            if held:
                gate.entered.clear()
                gate.open.clear()
            for idx in feed.windows(b):
                admit_remotes_window(pool, decode_txn_window(
                    [feed.frames[k] for k in idx]))
                handed += len(idx)
            if held:
                # the pool's timer has flushed, the window is inside the
                # verifier: now the block comes, from a thread of its own
                assert gate.entered.wait(10.0)
                caller = threading.Thread(target=body, args=(b, False))
                caller.start()
                deadline = time.monotonic() + 10.0
                while sched.stats()["pending"] == 0:
                    assert time.monotonic() < deadline
                    time.sleep(0.002)
                gate.open.set()
                caller.join(30.0)
                assert not caller.is_alive()
                txns = [Transaction.from_rlp(t)
                        for t in rlp.decode(feed.body(b))]
            settle(handed)  # with a block held, once the gate is open
            if not held:
                txns = body(b, False) or body(b, True)
            pool.remove_included(txns, block=b)
        after, stats = _counters(), sched.stats()
    finally:
        sched.close()
    return {"admits": admits, "bodies": bodies, "pool": dict(pool.stats),
            "sched": stats,
            "chain": {k: after[k] - before[k] for k in after}}


def by_reference(feed) -> dict:
    model, bodies = ref.PoolModel(), []
    for b in range(DEPLOY["pool_blocks"]):
        for idx in feed.windows(b):
            for k in idx:
                model.offer(feed.frames[k])
        got = ref.block_senders(feed.body(b))
        bodies.append((b, False, got))
        if got == ref.REFUSE:
            bodies.append((b, True, ref.block_senders(feed.body(b, True))))
        model.commit(feed.hashes[k] for k in feed.rows_of(b, True))
    return {"admitted": set(model.admitted), "refused": len(model.refused),
            "bodies": bodies}


@pytest.fixture(scope="module")
def want(feed):
    return by_reference(feed)


def _verifier(name: str):
    if name == "native":
        return NativeBatchVerifier()
    import numpy as np

    from eges_tpu.crypto.verifier import BatchVerifier
    bv = BatchVerifier()
    # the 16-row bucket traces and compiles here (about a minute on the
    # CPU), not inside a wait of the play
    bv.recover_addresses(np.zeros((MAX_BATCH, 65), np.uint8),
                         np.zeros((MAX_BATCH, 32), np.uint8))
    return bv


def _same_as_reference(got: dict, want: dict, feed) -> None:
    assert set(got["admits"]) == want["admitted"]
    assert got["pool"]["rejected"] == want["refused"]
    assert [(b, rep, ref.REFUSE if s is None else s)
            for b, rep, s in got["bodies"]] == want["bodies"]
    # and both are what the generator made: the bad blocks refused, every
    # other row its signer's
    assert sorted(b for b, rep, s in got["bodies"] if s is None) == \
        sorted(feed.bad) and len(feed.bad) == 2
    for b, rep, s in got["bodies"]:
        if s is not None:
            assert s == [feed.signer(k) for k in feed.rows_of(b, rep)]
    assert got["chain"]["blocks_refused"] == len(feed.bad)


@pytest.mark.parametrize("name", ["native", "jax"])
def test_the_program_answers_what_the_plain_reference_answers(name, feed,
                                                              want):
    got = play(feed, _verifier(name))
    _same_as_reference(got, want, feed)
    # the cache did answer: a block's rows came as gossip before it
    chain = got["chain"]
    assert chain["sender_rows"] == sum(
        len(feed.rows_of(b, rep)) for b, rep, _s in got["bodies"])
    assert chain["sender_cached_rows"] > chain["sender_rows"] // 2
    assert got["sched"]["cache_hits"] >= chain["sender_cached_rows"]


@pytest.fixture(scope="module")
def default_cache(feed):
    return play(feed, NativeBatchVerifier())


def test_no_cache_answers_the_same(feed, want, default_cache):
    got = play(feed, NativeBatchVerifier(), cache_size=0)
    _same_as_reference(got, want, feed)
    assert got["sched"]["cache_hits"] == 0
    assert got["chain"]["sender_cached_rows"] == 0
    assert default_cache["sched"]["cache_hits"] > 0
    assert set(got["admits"]) == set(default_cache["admits"])
    assert got["bodies"] == default_cache["bodies"]


@pytest.mark.parametrize("block", [1, 2])  # an ordinary block, a bad one
def test_a_block_that_meets_its_gossip_in_flight_answers_the_same(
        block, feed, want, default_cache):
    assert (block in feed.bad) == (block == 2)
    got = play(feed, NativeBatchVerifier(), hold_block=block)
    if block in feed.bad:
        # the held body was refused; the play went on with the sound one
        held = [s for b, rep, s in got["bodies"] if b == block]
        assert held == [None]
        got["bodies"] = [x for x in got["bodies"] if x[0] != block]
        want = {**want, "bodies": [x for x in want["bodies"]
                                   if x[0] != block]}
        feed_bad = sorted(set(feed.bad) - {block})
        assert sorted(b for b, _r, s in got["bodies"] if s is None) == \
            feed_bad
        assert set(got["admits"]) == want["admitted"]
        assert [(b, rep, ref.REFUSE if s is None else s)
                for b, rep, s in got["bodies"]] == want["bodies"]
    else:
        _same_as_reference(got, want, feed)
        assert got["bodies"] == default_cache["bodies"]
    assert set(got["admits"]) == set(default_cache["admits"])


def test_two_callers_of_one_block_share_its_rows_in_flight(feed):
    """The in-flight dedup as ``recover_senders`` reports it: the second
    caller's rows join the first one's while the verifier is held."""
    gate = Gate(NativeBatchVerifier())
    sched = VerifierScheduler(gate, max_batch=1024, window_ms=10_000.0)
    txns = [Transaction.from_rlp(t) for t in rlp.decode(feed.body(0))]
    want = [feed.signer(k) for k in feed.rows_of(0)]
    out: dict = {}
    before = _counters()
    try:
        gate.open.clear()
        first = threading.Thread(
            target=lambda: out.update(a=recover_senders(txns, sched)))
        first.start()
        assert gate.entered.wait(10.0)  # the first window is on its way
        # a second block's worth enters and waits; a third joins it
        others = [threading.Thread(target=lambda i=i: out.update(
            {i: recover_senders(txns[:16], sched)})) for i in (1, 2)]
        for t in others:
            t.start()
            deadline = time.monotonic() + 10.0
            while sched.stats()["window_submits"] < 2 + others.index(t):
                assert time.monotonic() < deadline
                time.sleep(0.002)
        gate.open.set()
        for t in [first, *others]:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        gate.open.set()
        sched.close()
    assert out["a"] == want and out[1] == out[2] == want[:16]
    chain = {k: v - before[k] for k, v in _counters().items()}
    assert chain["sender_rows"] == len(txns) + 32
    assert chain["sender_coalesced_rows"] == 16
    assert chain["sender_cached_rows"] == 0


def test_a_recovery_id_that_is_none_refuses_the_block_before_the_scheduler(
        feed):
    """``bad_recid`` in a block: ``signature_parts`` is None, the block is
    refused (and counted) without a row entering the scheduler; the
    reference refuses it too."""
    rows = feed.rows_of(0)
    bad = next(k for k in range(feed.n_valid, len(feed.frames))
               if feed.kind[k] == "bad_recid")
    frames = [feed.frames[k] for k in rows[:5]] + [feed.frames[bad]]
    payload = b"".join(frames)
    body = ref.rlp.length_prefix(len(payload), 0xC0) + payload
    assert ref.block_senders(body) == ref.REFUSE
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=MAX_BATCH)
    before = _counters()
    try:
        with pytest.raises(StateError):
            recover_senders([Transaction.from_rlp(t)
                             for t in rlp.decode(body)], sched)
        assert sched.stats()["window_submits"] == 0
    finally:
        sched.close()
    chain = {k: v - before[k] for k, v in _counters().items()}
    assert chain == {"sender_rows": 0, "sender_cached_rows": 0,
                     "sender_coalesced_rows": 0, "blocks_refused": 1}


def test_the_pool_model_takes_a_replacement_as_the_pool_does(feed):
    """Two transactions of one sender and nonce: first come unless the
    price is bumped by a tenth, in the model as in the pool."""
    rng = random.Random(7)
    privs, addrs = gen.secp.keys(gen._key_base(rng), 2)
    bodies = [gen.rlp.encode(0) + gen.rlp.encode(price)
              + gen.rlp.encode(29000) + gen.rlp.encode(addrs[1]) + b"\x80"
              + gen.rlp.encode(bytes([i]) * 8)
              for i, price in enumerate((100, 109, 110, 100))]
    sigs = gen._sign_bodies(bodies, [privs[0]] * 4, rng)
    frames = [gen._frame(b, s) for b, s in zip(bodies, sigs)]
    model = ref.PoolModel()
    for f in frames:
        model.offer(f)
    # 109 is under the bump and dropped, 110 replaces, 100 is dropped
    assert [h for h, _s in model.admitted] == [
        ref.keccak256_many([frames[i]])[0] for i in (0, 2)]
    admits = []
    pool = TxPool(ThreadClock(), verifier=NativeBatchVerifier(),
                  on_admitted=lambda t, s: admits.append((t.hash, s)))
    for i, f in enumerate(frames):
        admit_remotes_window(pool, decode_txn_window([f]))
        deadline = time.monotonic() + 10.0
        while sum(pool.stats[k] for k in ("admitted", "rejected",
                                          "duplicate")) <= i:
            assert time.monotonic() < deadline
            time.sleep(0.002)
    assert admits == model.admitted
    assert pool.stats["replaced"] == 1 and pool.stats["duplicate"] == 2
