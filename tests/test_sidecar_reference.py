"""Three node processes' worth of askers on ONE verify sidecar against the
benchmark's plain reference, row for row: what ``c1024s3.shared-backlog``
holds the program to on the chip, at a size a test can hold.

One sidecar (``crypto/sidecar.serve`` over a real Unix socket) on the host
C++ verifier and on the jax verifier (CPU backend, the 16-row bucket
alone); three ``SidecarClient``s, each on a thread of its own; seeded rows
with all four kinds of invalid signature; the clients ask overlapping
slices through every entry a scheduler's holders use (``recover_signers``,
``recover_window``, ``recover_addresses``, ``submit``), bulk and consensus
calls interleaved.  Every answer equals ``perfbench/ref/secp.py``'s, which
knows nothing of sockets, caches or processes; the counts add up; and a
pool and ``recover_senders`` on a client give what they give on a
scheduler in process (``tests/test_senders_reference.py``'s rows and its
own ``play``).
"""

import random
import threading
import time

import numpy as np
import pytest

from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.sidecar import SidecarClient, serve
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from perfbench import gen
from perfbench.ref import secp
from tests import test_senders_reference as senders
from tests.test_scheduler import _arrays

DEPLOY = {"validators": 12, "committee": 4, "header_sigs": 1,
          "txn_per_block": 8, "duplicate_share": 0.25, "gossip_window": 8,
          "invalid_every": 5, "accounts": 8, "payload_bytes": 20,
          "gas_limit": 29000, "pool_blocks": 1, "vote_pool_blocks": 8}
SEED = 2**31 + 42
MAX_BATCH = 16  # one bucket: the jax verifier compiles no other
CLIENTS = 3
JOIN_S = 120.0


@pytest.fixture(scope="module")
def rows():
    """``(entries, kinds, the reference's answers)``: 136 vote rows, one
    in five invalid, the four kinds in turn."""
    feed = gen.NodeFeed(SEED, DEPLOY)
    entries = feed.vote_entries
    assert {k for k in feed.vote_kind if k} == set(gen.KINDS)
    want = [secp.recover(h, sig) for h, sig in entries]
    # the reference refuses three kinds and answers another signer for
    # the message altered after signing
    for kind, got, signer in zip(feed.vote_kind, want, feed.vote_expect):
        if kind is None:
            assert got == signer
        elif kind == "flipped_message":
            assert got is not None and got != signer
        else:
            assert got is None
    return entries, feed.vote_kind, want


_JAX = []


def _verifier(name: str):
    if name == "native":
        return NativeBatchVerifier()
    if not _JAX:
        from eges_tpu.crypto.verifier import BatchVerifier
        bv = BatchVerifier()
        # the 16-row bucket traces and compiles here (about a minute on
        # the CPU), not inside a wait of a client
        bv.recover_addresses(np.zeros((MAX_BATCH, 65), np.uint8),
                             np.zeros((MAX_BATCH, 32), np.uint8))
        _JAX.append(bv)
    return _JAX[0]


def _ask(client, rng, entries) -> tuple:
    """One call of a seeded kind over ``entries``: ``(answers, cached +
    coalesced as the call reported them, or None)``."""
    how = rng.choice(["signers", "signers_consensus", "window",
                      "addresses", "submit"])
    h, s = _arrays(entries)
    if how == "signers":
        got = client.recover_signers(entries)
    elif how == "signers_consensus":
        got = client.recover_signers(entries, priority="consensus")
    elif how == "window":
        got = client.recover_window(h, s, priority=rng.choice(
            ["bulk", "consensus"]))
    elif how == "addresses":
        pair = client.recover_addresses(s, h)
        addrs, ok = pair
        return ([bytes(a) if good else None
                 for a, good in zip(addrs, ok)],
                pair.cached + pair.coalesced)
    else:
        futs = [client.submit(*e, priority="consensus") for e in entries]
        return [f.result(JOIN_S) for f in futs], None
    return list(got), got.cached + got.coalesced


@pytest.mark.parametrize("name", ["native", "jax"])
def test_three_clients_get_the_references_answers_and_the_counts_add_up(
        name, rows, tmp_path):
    entries, _kinds, want = rows
    sched = VerifierScheduler(_verifier(name), max_batch=MAX_BATCH)
    server = serve(sched, str(tmp_path / "s.sock"))
    clients = [SidecarClient(server.path) for _ in range(CLIENTS)]
    wrong, asked, shared = [], [0] * CLIENTS, [0] * CLIENTS

    def node(i: int) -> None:
        rng = random.Random(SEED + i)
        for _ in range(12):
            # overlapping slices: the clients meet in one cache and one
            # in-flight table
            lo = rng.randrange(0, len(entries) - 8)
            n = rng.choice([1, 3, 8, 20, 40])
            idx = list(range(lo, min(lo + n, len(entries))))
            got, counted = _ask(clients[i], rng, [entries[k] for k in idx])
            asked[i] += len(idx)
            shared[i] += counted or 0
            wrong.extend((i, k) for k, g in zip(idx, got) if g != want[k])

    threads = [threading.Thread(target=node, args=(i,))
               for i in range(CLIENTS)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(JOIN_S)
        assert not any(t.is_alive() for t in threads)
        # a connection's writer counts a window once its reply is out,
        # which the client may have in hand a moment earlier
        deadline = time.monotonic() + JOIN_S
        while server.stats()["rows"] < sum(asked):
            assert time.monotonic() < deadline
            time.sleep(0.002)
        st, sv = sched.stats(), server.stats()
    finally:
        for c in clients:
            c.close()
        server.close()
        sched.close()
    assert wrong == []
    assert sum(asked) > 300
    # every row asked entered the scheduler once, and each was answered
    # by the cache, by a twin in flight, or by a batch of its own
    assert st["window_rows"] == sum(asked) == sv["rows"]
    computed = st["rows"]  # a window the host served by rule among them
    assert st["cache_hits"] + st["coalesced_rows"] + computed == sum(asked)
    assert st["cache_hits"] > 0 and computed < sum(asked)
    # what the calls reported is the scheduler's own count (``submit``
    # reports none)
    assert sum(shared) <= st["cache_hits"] + st["coalesced_rows"]
    assert sv["clients"] == CLIENTS and sv["torn_frames"] == 0
    assert sorted(c["rows"] for c in sv["served"]) == sorted(asked)
    assert all(c.stats()["fallback_rows"] == 0 for c in clients)


def test_the_counts_a_call_reports_are_the_windows_own(rows, tmp_path):
    entries, _kinds, want = rows
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=MAX_BATCH)
    server = serve(sched, str(tmp_path / "s.sock"))
    a, b = SidecarClient(server.path), SidecarClient(server.path)
    try:
        first = a.recover_signers(entries[:10])
        again = b.recover_signers(entries[5:15], priority="consensus")
        h, s = _arrays(entries[:15])
        pair = a.recover_addresses(s, h)
        twice = b.recover_window(*_arrays([entries[20]] * 4))
    finally:
        a.close()
        b.close()
        server.close()
        sched.close()
    assert (first.cached, first.coalesced) == (0, 0)
    assert (again.cached, again.coalesced) == (5, 0)  # another's rows
    assert list(again) == want[5:15]
    assert (pair.cached, pair.coalesced) == (15, 0)
    # one call's own duplicates join the row that carries them
    assert (twice.cached, twice.coalesced) == (0, 3)
    assert list(twice) == [want[20]] * 4


@pytest.mark.parametrize("name", ["native"])
def test_a_pool_and_recover_senders_on_a_client_give_what_they_give_on_a_scheduler(
        name, tmp_path, monkeypatch):
    """``tests/test_senders_reference.py``'s stream and its own ``play``,
    with a sidecar's client where it builds its scheduler."""
    feed = senders.gen_zipf.ZipfFeed(senders.SEED, senders.DEPLOY)
    want = senders.by_reference(feed)
    in_process = senders.play(feed, _verifier(name))
    sched = VerifierScheduler(_verifier(name), max_batch=senders.MAX_BATCH)
    server = serve(sched, str(tmp_path / "s.sock"))
    client = SidecarClient(server.path)
    monkeypatch.setattr(senders, "VerifierScheduler",
                        lambda _verifier, **kw: client)
    try:
        got = senders.play(feed, None)
        st = sched.stats()
    finally:
        server.close()
        sched.close()
    senders._same_as_reference(got, want, feed)
    assert set(got["admits"]) == set(in_process["admits"])
    assert got["bodies"] == in_process["bodies"]
    # how many flushes the pool's timer cut the stream into is real time's
    timed = ("batches",)
    assert {k: v for k, v in got["pool"].items() if k not in timed} == \
        {k: v for k, v in in_process["pool"].items() if k not in timed}
    # recover_senders read the cache's answers through the client
    assert got["chain"]["sender_rows"] == in_process["chain"]["sender_rows"]
    assert got["chain"]["sender_cached_rows"] > \
        got["chain"]["sender_rows"] // 2
    assert st["cache_hits"] >= got["chain"]["sender_cached_rows"]
    assert got["sched"]["fallback_rows"] == 0 and got["sched"]["rows"] > 0
