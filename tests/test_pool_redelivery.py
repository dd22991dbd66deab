"""The same transaction delivered to the pool AGAIN, after the dedup
history's coarse clear (``TxPool._KNOWN_CAP``: 65,536 hashes, twelve
heights of the 1024-validator chain's gossip), is a duplicate: it must not
be taken for a price-bump replacement of itself.

Found on the chip by ``c1024p.heights-backlog`` (PR 48), the first cell
whose node calls ``pending_txns``: at gas price 0 the copy passed the
price test (``0 < 0`` is false), "replaced" its original, and put the hash
they share among the tombstones; the next compaction of ``_order`` dropped
BOTH entries, the sender left a proposer's sight with its transactions
still pending, and one block in thirty went out short of a full pool.
"""

from eges_tpu.core.txpool import TxPool
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.ingress import admit_remotes_window, decode_txn_window
from perfbench import gen_heights
from perfbench.clock import ThreadClock
from tests.test_proposer_path import DEPLOY


def _pool():
    feed = gen_heights.HeightsFeed(2**31 + 43, {**DEPLOY,
                                                "stream_heights": 1})
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    return feed, sched, TxPool(ThreadClock(), verifier=sched)


def _hand(pool, frames) -> None:
    admit_remotes_window(pool, decode_txn_window(frames))
    with pool._lock:
        pool._flush()


def test_a_copy_after_the_historys_clear_is_a_duplicate_not_a_replacement():
    feed, sched, pool = _pool()
    try:
        frames = feed.frames[:32]
        _hand(pool, frames)
        before = [t.hash for t in pool.pending_txns()]
        assert len(before) == 32 and pool.stats["admitted"] == 32
        pool._known.clear()  # what the coarse clear at the cap does
        _hand(pool, frames[:20])
        assert (pool.stats["admitted"], pool.stats["replaced"],
                pool.stats["duplicate"]) == (32, 0, 20)
        assert not pool._dead
        # whatever a compaction does then, a proposer sees every sender
        with pool._lock:
            pool._order = [(s, t) for s, t in pool._order
                           if t.hash not in pool._dead]
        assert [t.hash for t in pool.pending_txns()] == before
        assert len(pool) == 32
    finally:
        sched.close()


def test_a_different_transaction_at_a_higher_price_still_replaces():
    from eges_tpu.core.types import Transaction

    _feed, sched, pool = _pool()
    try:
        priv = (77).to_bytes(32, "big")
        to = bytes(range(20))

        def signed(price: int, payload: bytes) -> Transaction:
            return Transaction(nonce=0, gas_price=price, gas_limit=21000,
                               to=to, value=1, payload=payload).signed(priv)

        first, same_price, dearer = (signed(10, b"a"), signed(10, b"b"),
                                     signed(11, b"c"))
        pool.add_remotes([first])
        pool.add_remotes([same_price])
        pool.add_remotes([dearer])
        with pool._lock:
            pool._flush()
        assert [t.hash for t in pool.pending_txns()] == [dearer.hash]
        assert (pool.stats["admitted"], pool.stats["replaced"],
                pool.stats["duplicate"]) == (2, 1, 1)
    finally:
        sched.close()
