"""Verifier scheduler: coalescing windows, the sender-recovery cache,
flush ordering, shutdown draining, and the cluster-level invariant that
steady state produces ZERO one-row device batches.

The fast tests run against :class:`NativeBatchVerifier` (no JAX import);
the slow one proves bit-identical results against a real
:class:`BatchVerifier` device path.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from eges_tpu.crypto import secp256k1 as host
from eges_tpu.crypto.scheduler import (
    VerifierScheduler, _bucket16, scheduler_for,
)
from eges_tpu.crypto.verify_host import NativeBatchVerifier


def _sign_entries(n: int, salt: int = 0) -> list[tuple[bytes, bytes]]:
    """n distinct valid ``(sighash, sig)`` entries (native-signed when
    the lib is built, pure-Python otherwise)."""
    from eges_tpu.crypto import native

    out = []
    for i in range(n):
        msg = (salt * 100_000 + i + 1).to_bytes(4, "big") * 8
        priv = bytes([((salt + i) % 200) + 7]) * 32
        sig = (native.ec_sign(msg, priv) if native.available()
               else host.ecdsa_sign(msg, priv))
        out.append((msg, sig))
    return out


def _host_model(entries) -> list:
    out = []
    for h, sig in entries:
        try:
            out.append(host.recover_address(h, sig)
                       if len(sig) == 65 and len(h) == 32 else None)
        except Exception:
            out.append(None)
    return out


def test_concurrent_submitters_match_host_model():
    """N threads submitting overlapping/duplicate/invalid sigs all get
    exactly the host model's answers back."""
    entries = _sign_entries(24)
    entries.append((b"\x01" * 32, b"\x00" * 65))  # valid shape, bad sig
    entries.append((b"\x02" * 32, b"\x00" * 10))  # malformed length
    expect = _host_model(entries)

    sched = scheduler_for(NativeBatchVerifier(), window_ms=2.0)
    results: dict[int, list] = {}
    errs: list = []

    def worker(k: int) -> None:
        try:
            rotated = entries[k:] + entries[:k]  # overlap across threads
            results[k] = sched.recover_signers(rotated)
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs
    for k, got in results.items():
        assert got == expect[k:] + expect[:k], f"thread {k} mismatch"
    st = sched.stats()
    # the 6 threads' overlapping copies were absorbed by the cache and
    # by in-flight row sharing: far fewer rows dispatched than submitted
    submitted = 6 * len(entries)
    assert st["rows"] < submitted, st
    assert st["cache_hits"] + st["coalesced_rows"] > 0, st
    sched.close()


def test_cache_eviction_lru():
    sched = VerifierScheduler(NativeBatchVerifier(), cache_size=8)
    entries = _sign_entries(12, salt=1)
    assert sched.recover_signers(entries) == _host_model(entries)
    assert sched.stats()["cached_entries"] == 8  # first 4 evicted

    st0 = sched.stats()
    # oldest 4 were evicted -> misses again; newest 4 are still hits
    sched.recover_signers(entries[:4])
    st1 = sched.stats()
    assert st1["cache_misses"] - st0["cache_misses"] == 4
    sched.recover_signers(entries[-4:])
    st2 = sched.stats()
    assert st2["cache_hits"] - st1["cache_hits"] == 4
    assert st2["cache_misses"] == st1["cache_misses"]
    sched.close()


def test_bucket_full_flush_beats_deadline():
    """With a long window, a bucket-full batch flushes immediately while
    a lone entry waits out the deadline — and the flush reasons record
    that ordering."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=400.0,
                              max_batch=4)
    entries = _sign_entries(5, salt=2)
    expect = _host_model(entries)

    t0 = time.monotonic()
    futs = [sched.submit(h, s) for h, s in entries[:4]]
    got = [f.result(30) for f in futs]
    full_dt = time.monotonic() - t0
    assert got == expect[:4]
    assert full_dt < 0.35, "bucket-full flush waited for the deadline"
    assert sched.stats()["flush_full"] == 1

    t0 = time.monotonic()
    lone = sched.submit(*entries[4])
    assert lone.result(30) == expect[4]
    lone_dt = time.monotonic() - t0
    assert lone_dt >= 0.35, "deadline flush fired before the window"
    st = sched.stats()
    assert st["flush_deadline"] == 1
    # the lone row was diverted to the host path, not a padded device row
    assert st["host_diverted"] == 1
    sched.close()


def test_kick_skips_deadline():
    """Synchronous callers must not sleep out the micro-window: kick()
    flushes whatever is pending right now."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=2000.0)
    entries = _sign_entries(3, salt=3)
    t0 = time.monotonic()
    assert sched.recover_signers(entries) == _host_model(entries)
    assert time.monotonic() - t0 < 1.5
    assert sched.stats()["flush_kick"] == 1
    sched.close()


def test_inflight_dedup_shares_one_row():
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=200.0)
    (h, s), = _sign_entries(1, salt=4)
    f1 = sched.submit(h, s)
    f2 = sched.submit(h, s)  # identical in-flight key -> same batch row
    sched.kick()
    want = _host_model([(h, s)])[0]
    assert f1.result(30) == want and f2.result(30) == want
    st = sched.stats()
    assert st["coalesced_rows"] == 1 and st["rows"] == 1
    sched.close()


def test_shutdown_drains_every_future_and_joins_thread():
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0)
    entries = _sign_entries(6, salt=5)
    futs = [sched.submit(h, s) for h, s in entries]
    assert not any(f.done() for f in futs)  # deadline is far away
    sched.close()
    # no lost futures...
    assert [f.result(0) for f in futs] == _host_model(entries)
    # ...and no leaked thread
    assert sched._thread is not None and not sched._thread.is_alive()
    # post-close submissions still resolve (inline on the caller)
    f = sched.submit(*entries[0])
    assert f.result(0) == _host_model(entries[:1])[0]


# -- a window of rows enters: the synchronous facades -----------------------

def _mixed_entries(n: int, salt: int) -> list[tuple[bytes, bytes]]:
    """``n`` entries, most valid, with an invalid signature, a malformed
    signature, a malformed hash and a duplicate in every run of ten."""
    entries = _sign_entries(n, salt=salt)
    for i in range(0, n, 10):
        entries[i] = (bytes([i % 250 + 1]) * 32, b"\x00" * 65)   # bad sig
        if i + 3 < n:
            entries[i + 3] = (entries[i + 3][0], b"\x01" * 10)   # short sig
        if i + 5 < n:
            entries[i + 5] = (b"\x02" * 7, entries[i + 5][1])    # short hash
        if i + 7 < n:
            entries[i + 7] = entries[i + 6]                      # duplicate
    return entries


@pytest.mark.parametrize("priority", ["bulk", "consensus"])
def test_window_call_larger_than_max_batch_matches_host_model(priority):
    """One ``recover_signers`` call of more rows than a window holds,
    with invalid, malformed and duplicate entries among them, answers
    row for row what the host model answers; the malformed rows never
    reach the device and are counted as per-row ``submit`` counts them."""
    entries = _mixed_entries(40, salt=20)
    expect = _host_model(entries)
    malformed = sum(1 for h, s in entries if len(s) != 65 or len(h) != 32)
    dups = len(entries) - malformed - len(
        {e for e in entries if len(e[1]) == 65 and len(e[0]) == 32})
    assert malformed >= 8 and dups >= 4

    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0,
                              max_batch=16)
    assert sched.recover_signers(entries, priority=priority) == expect
    st = sched.stats()
    klass, other = (("consensus", "bulk") if priority == "consensus"
                    else ("bulk", "consensus"))
    assert st["invalid"] == malformed
    assert st["coalesced_rows"] == dups
    assert st["rows"] == len(entries) - malformed - dups
    assert st["cache_misses"] == len(entries) - malformed
    assert st["window_submits"] == st["window_submits_" + klass] == 1
    assert st["window_rows"] == st["window_rows_" + klass] == len(entries)
    assert st["window_submits_" + other] == st["window_rows_" + other] == 0
    # cut by the dispatcher alone: full windows, then the kicked rest
    assert [f["rows"] for f in sched.flights()] == [16, 12]
    assert st["flush_full"] == 1 and st["flush_deadline"] == 0
    assert {f["klass"] for f in sched.flights()} == {klass}
    # a second pass is answered by the cache, the malformed rows again
    # by the early-out
    assert sched.recover_signers(entries, priority=priority) == expect
    st2 = sched.stats()
    assert st2["batches"] == st["batches"]
    assert st2["invalid"] == 2 * malformed
    sched.close()


def test_consensus_call_flies_as_one_window_past_pending_bulk_rows():
    """A consensus call of ``max_batch`` rows made while bulk rows are
    pending is ONE consensus-class flight of ``max_batch`` rows: it
    enters under one lock hold, so no deadline can cut it, and it takes
    the window's seats ahead of the older bulk rows."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0,
                              max_batch=32)
    bulk = _sign_entries(5, salt=21)
    votes = _sign_entries(32, salt=22)
    bulk_futs = [sched.submit(h, s) for h, s in bulk]
    assert sched.recover_signers(votes, priority="consensus") == \
        _host_model(votes)
    assert [f.result(30) for f in bulk_futs] == _host_model(bulk)
    flights = sched.flights()
    assert [(f["klass"], f["rows"], f["reason"]) for f in flights] == \
        [("consensus", 32, "full"), ("bulk", 5, "kick")]
    st = sched.stats()
    assert st["flush_deadline"] == 0
    assert (st["window_submits_consensus"], st["window_rows_consensus"]) \
        == (1, 32)
    # each bulk ``submit`` was a one-row window
    assert st["window_submits_bulk"] == st["window_rows_bulk"] == 5
    assert st["window_submits"] == 6 and st["window_rows"] == 37
    sched.close()


@pytest.mark.parametrize("how", ["closed", "device_fails", "torn_down"])
def test_window_call_answers_every_row_on_the_host_path(how):
    """No synchronous call loses a row: against a closed scheduler the
    rows are recovered inline, a window that fails on the device is
    host-diverted, and rows that a torn-down scheduler FAILED are
    recovered on the host by the facade itself."""
    entries = _mixed_entries(12, salt=23)
    expect = _host_model(entries)
    verifier = NativeBatchVerifier()
    sched = VerifierScheduler(verifier, window_ms=10_000.0)
    if how == "closed":
        sched.close()
        assert sched.recover_signers(entries, priority="consensus") == expect
        assert sched.stats()["batches"] == 0
        return
    if how == "device_fails":
        def boom(rows):
            raise RuntimeError("device lost")
        sched.failure_hook = boom
        assert sched.recover_signers(entries, priority="consensus") == expect
        st = sched.stats()
        assert st["device_errors"] == 1 and st["breaker"] == "open"
        sched.close()
        return
    # torn down: the dispatch thread is stuck in a first window, so the
    # call's rows are still pending when close() gives up on it and
    # fails them
    gate, entered = threading.Event(), threading.Event()
    orig = verifier.recover_addresses

    def stuck(sigs, hashes):
        entered.set()
        gate.wait(120)
        return orig(sigs, hashes)

    verifier.recover_addresses = stuck
    first = _sign_entries(3, salt=24)
    got: dict = {}
    t1 = threading.Thread(
        target=lambda: got.update(first=sched.recover_signers(first)))
    t1.start()
    assert entered.wait(60)
    t2 = threading.Thread(target=lambda: got.update(
        second=sched.recover_signers(entries, priority="consensus")))
    t2.start()
    deadline = time.monotonic() + 60.0
    while sched.stats()["pending"] == 0 and time.monotonic() < deadline:
        time.sleep(0.002)
    try:
        sched.close(timeout=0.05)
        t2.join(60)
        assert got["second"] == expect
    finally:
        gate.set()
    t1.join(60)
    assert got["first"] == _host_model(first)


@pytest.mark.parametrize("first", ["row", "window"])
def test_a_row_and_a_window_share_one_computed_row(first):
    """The same signature from two callers, one through per-row
    ``submit`` and one through a window entry, whichever comes first:
    both get the answer, the row is computed once."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0)
    shared, other = _sign_entries(2, salt=25)
    want = _host_model([shared, other])
    if first == "row":
        fut = sched.submit(*shared)
        assert sched.recover_signers([shared, other],
                                     priority="consensus") == want
        assert fut.result(30) == want[0]
    else:
        hashes = np.frombuffer(shared[0] + other[0], np.uint8).reshape(2, 32)
        sigs = np.frombuffer(shared[1] + other[1], np.uint8).reshape(2, 65)
        win = sched.submit_window(hashes, sigs)
        fut = sched.submit(*shared, priority="consensus")
        sched.kick()
        assert fut.result(30) == want[0]
        assert win.result(30) == want
    st = sched.stats()
    assert st["coalesced_rows"] == 1 and st["rows"] == 2
    # the shared row was promoted to the higher of its callers' classes
    assert [f["klass"] for f in sched.flights()] == ["consensus"]
    sched.close()


@pytest.mark.parametrize("case", ["cache_hit", "malformed",
                                  "dedup_promotes", "closed"])
def test_single_row_submit_is_a_one_row_window(case):
    """``submit`` is the window entry with one row behind a future: the
    same row in the same state gets the same answer and moves every
    counter of ``stats()`` as a one-row window does,
    ``window_submits_<class>`` included."""
    row, other = _sign_entries(2, salt=34)
    if case == "malformed":
        row = (row[0], row[1][:10])
    prio = "consensus" if case == "dedup_promotes" else "bulk"

    def one_row(sched, entry):
        if entry == "submit":
            fut = sched.submit(*row, priority=prio)
            sched.kick()
            return fut.result(30)
        if case == "malformed":
            # no array holds a malformed row: the list facade takes it
            return sched.recover_signers([row], priority=prio)[0]
        win = sched.submit_window(*_arrays([row]), priority=prio)
        sched.kick()
        return win.result(30)[0]

    answers, deltas = [], []
    for entry in ("submit", "window"):
        sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0)
        earlier = None
        if case == "cache_hit":
            sched.recover_signers([row, other])
        elif case == "dedup_promotes":
            earlier = sched.submit_window(*_arrays([row, other]))  # bulk
        elif case == "closed":
            sched.close()
        before = sched.stats()
        answers.append(one_row(sched, entry))
        if earlier is not None:
            assert earlier.result(30) == _host_model([row, other])
            assert [f["klass"] for f in sched.flights()] == ["consensus"]
        sched.close()  # the dispatch thread's last counters are in
        after = sched.stats()
        deltas.append({k: after[k] - before[k] for k in before
                       if type(before[k]) is int})
    assert answers[0] == answers[1] == _host_model([row])[0]
    assert deltas[0] == deltas[1]
    moved = {k: v for k, v in deltas[0].items() if v}
    assert moved.pop("window_submits_" + prio) == 1
    assert moved.pop("window_rows_" + prio) == 1
    assert moved.pop("window_submits") == moved.pop("window_rows") == 1
    assert moved == {
        "cache_hit": {"cache_hits": 1, "cache_served_rows": 1},
        "malformed": {"invalid": 1},
        "dedup_promotes": {
            "cache_misses": 1, "coalesced_rows": 1, "kicks": 1,
            "flush_kick": 1, "batches": 1, "rows": 2, "bucket_rows": 16,
            "resolve_holds": 2, "cached_entries": 2, "pending": -2,
            "flight_windows": 1},
        "closed": {"cache_misses": 1, "cached_entries": 1},
    }[case]


def test_rows_and_windows_from_many_threads_lose_no_update():
    """More threads than cores, half of them through window entries and
    half through per-row ``submit``, over overlapping rows, with the
    interpreter switching threads a hundred times as often: every
    caller gets the host model's answers, and every cache miss either
    made a computed row or shared one."""
    import sys

    entries = _mixed_entries(30, salt=27)
    expect = _host_model(entries)
    malformed = sum(1 for h, s in entries if len(s) != 65 or len(h) != 32)
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=1.0,
                              max_batch=16, cache_size=8)
    n_threads, rounds = 12, 6
    errs: list = []
    barrier = threading.Barrier(n_threads)

    def worker(k: int) -> None:
        try:
            barrier.wait(60)
            for r in range(rounds):
                cut = (3 * k + 5 * r) % len(entries)
                rot = entries[cut:] + entries[:cut]
                want = expect[cut:] + expect[:cut]
                if k % 2:
                    got = sched.recover_signers(
                        rot, priority="consensus" if k % 4 == 1 else "bulk")
                else:
                    futs = [sched.submit(h, s) for h, s in rot]
                    sched.kick()
                    got = [f.result(60) for f in futs]
                assert got == want, (k, r)
        except BaseException as e:  # surfaced via errs
            errs.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errs, errs[:1]
    sched.close()
    st = sched.stats()
    calls = n_threads * rounds
    assert st["invalid"] == calls * malformed
    assert st["cache_hits"] + st["cache_misses"] == \
        calls * (len(entries) - malformed)
    assert st["cache_misses"] == st["rows"] + st["coalesced_rows"]
    # half the calls were one window each, the other half one one-row
    # window a ``submit``
    assert st["window_submits"] == calls // 2 * (1 + len(entries))
    assert st["pending"] == 0


def test_recover_addresses_takes_the_window_path():
    """Arrays in, arrays out, through one window entry (block bodies
    and the EVM precompile come this way)."""
    entries = _sign_entries(6, salt=26)
    entries[2] = (b"\x09" * 32, b"\x00" * 65)
    expect = _host_model(entries)
    sigs = np.frombuffer(b"".join(s for _h, s in entries),
                         np.uint8).reshape(6, 65)
    hashes = np.frombuffer(b"".join(h for h, _s in entries),
                           np.uint8).reshape(6, 32)
    sched = VerifierScheduler(NativeBatchVerifier())
    addrs, ok = sched.recover_addresses(sigs, hashes, priority="consensus")
    assert [bytes(addrs[i]) if ok[i] else None for i in range(6)] == expect
    assert not ok[2] and not addrs[2].any()
    st = sched.stats()
    assert (st["window_submits_consensus"], st["window_rows_consensus"]) \
        == (1, 6)
    e_addrs, e_ok = sched.recover_addresses(sigs[:0], hashes[:0])
    assert e_addrs.shape == (0, 20) and e_ok.shape == (0,)
    sched.close()


def _arrays(entries):
    hashes = np.frombuffer(b"".join(h for h, _s in entries),
                           np.uint8).reshape(len(entries), 32)
    sigs = np.frombuffer(b"".join(s for _h, s in entries),
                         np.uint8).reshape(len(entries), 65)
    return hashes, sigs


class _Dead(BaseException):
    """A window's death that is no device error: nothing diverts it."""


@pytest.mark.parametrize("how", ["computed", "diverted", "died"])
def test_a_mixed_batch_answers_every_holder_exactly_once(how):
    """One batch whose rows belong to two windows, to plain ``submit``
    futures (one-row windows), to a row three callers share, to an invalid signature and
    to two holders that a hedge's winner had answered before: every
    holder gets, once, what setting it row by row gave; a window takes
    one hold for all of its rows.  A batch that died hands its error to
    the holders and records nothing."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0)
    e = _sign_entries(7, salt=30)
    bad = (b"\x07" * 32, b"\x00" * 65)
    m = _host_model(e)
    win_a = sched.submit_window(*_arrays([e[0], e[1], e[2], bad]))
    win_b = sched.submit_window(*_arrays([e[3], e[1], e[4]]),
                                priority="consensus")
    futs = [sched.submit(*e[5]), sched.submit(*e[1]), sched.submit(*e[6])]
    # the hedge's winner was here first (its value would be the same
    # bit for bit; another one shows that nothing is written twice)
    won = b"\x5a" * 20
    win_a._set_rows((2,), (won,))
    (win_6, idx_6), = sched._pending[e[6]][0]  # what futs[2] stands for
    win_6._set_rows((idx_6,), (won,))
    with sched._lock:
        batch = [(k, sched._pending.pop(k)) for k in list(sched._pending)]
    assert len(batch) == 8 and sched.stats()["coalesced_rows"] == 2

    def hook(rows):
        raise (_Dead if how == "died" else RuntimeError)("lost")

    if how != "computed":
        sched.failure_hook = hook
    if how == "died":
        with pytest.raises(_Dead):
            sched._run_batch(sched._lanes[0], batch, "kick", time.monotonic())
        dead = [isinstance(v, _Dead) for v in win_a.result(0)]
        assert dead == [True, True, False, True]
        assert all(isinstance(v, _Dead) for v in win_b.result(0))
        for f in futs[:2]:
            with pytest.raises(_Dead):
                f.result(0)
        st = sched.stats()
        assert st["batches"] == st["rows"] == st["resolve_holds"] == 0
        assert st["cached_entries"] == 0 and sched.flights() == []
    else:
        sched._run_batch(sched._lanes[0], batch, "kick", time.monotonic())
        assert win_a.result(0) == [m[0], m[1], won, None]
        assert win_b.result(0) == [m[3], m[1], m[4]]
        assert [f.result(0) for f in futs[:2]] == [m[5], m[1]]
        st = sched.stats()
        # two windows and the three one-row windows behind the plain
        # futures (one had its answer: its hold changed nothing)
        assert st["resolve_holds"] == 5
        assert (st["batches"], st["rows"]) == (1, 8)
        assert st["device_errors"] == (how == "diverted")
        flight = sched.flights()[-1]
        assert flight["rows"] == 8 and flight["klass"] == "consensus"
        assert flight["diverted"] == (how == "diverted")
        assert flight["resolve_ms"] > 0
        assert st["class_wait_ms"]["consensus"]["count"] == 3
        assert st["class_wait_ms"]["bulk"]["count"] == 5
        # the cache holds what the batch computed, not the early value
        assert sched.submit(*e[2]).result(0) == m[2]
        assert sched.submit(*bad).result(0) is None
    assert futs[2].result(0) == won
    assert win_a._remaining == win_b._remaining == 0
    sched.close()


def _targets(kind: str):
    from eges_tpu.crypto.verify_host import (
        NativeMeshVerifier, PipelinedNativeVerifier,
    )
    return {"inline": NativeBatchVerifier,
            "pipelined": PipelinedNativeVerifier,
            "mesh": lambda: NativeMeshVerifier(4)}[kind]()


@pytest.mark.parametrize("target", ["inline", "pipelined", "mesh"])
def test_a_woken_caller_finds_its_rows_cached_and_its_windows_recorded(
        target):
    """The moment a synchronous call returns, with no wait: every one of
    its keys is answered by the cache, and ``stats()`` and the flight
    ring hold its windows (the recording stands in front of the
    holders); on the mesh the call was three chunks on three lanes."""
    sched = VerifierScheduler(_targets(target), window_ms=10_000.0,
                              max_batch=32, min_split=4, hedge=False)
    entries = _sign_entries(24, salt=31)
    want = _host_model(entries)
    assert sched.recover_signers(entries, priority="consensus") == want
    st = sched.stats()
    assert st["rows"] == 24 and st["cache_hits"] == 0
    assert st["batches"] == (3 if target == "mesh" else 1)
    assert sum(f["rows"] for f in sched.flights()) == 24
    again = [sched.submit(h, s) for h, s in entries]
    assert all(f.done() for f in again)
    assert [f.result(0) for f in again] == want
    assert sched.stats()["cache_hits"] == 24
    sched.close()
    st = sched.stats()
    # one window of the caller's in every device window
    assert st["resolve_holds"] == st["batches"] == len(sched.flights())
    assert all(f["resolve_ms"] > 0 for f in sched.flights())


@pytest.mark.parametrize("target", ["inline", "pipelined"])
def test_a_recording_that_raises_costs_no_caller_its_answer(target,
                                                            monkeypatch):
    """The registry raises inside ``_record_window``: the window's
    callers have their answers all the same, ``_finish_batch`` raises
    the error when they are answered, and the lane (or the dispatch
    thread) serves the next window."""
    from eges_tpu.utils.metrics import DEFAULT as registry

    sched = VerifierScheduler(_targets(target), window_ms=10_000.0)
    real, left = registry.histogram, [1]

    def histogram(name):
        if name == "verifier.sched_batch_rows" and left[0]:
            left[0] -= 1
            raise RuntimeError("registry down")
        return real(name)

    monkeypatch.setattr(registry, "histogram", histogram)
    finish, raised = sched._finish_batch, []

    def spy(lane, p):
        try:
            finish(lane, p)
        except BaseException as exc:
            raised.append(str(exc))
            raise

    sched._finish_batch = spy
    first, second = _sign_entries(6, salt=32), _sign_entries(5, salt=33)
    assert sched.recover_signers(first) == _host_model(first)
    assert sched.recover_signers(second) == _host_model(second)
    assert all(sched.submit(h, s).done() for h, s in first + second)
    sched.close()
    assert raised == ["registry down"] and left == [0]
    assert [f["rows"] for f in sched.flights()] == [6, 5]


class _AnswersAtOnce:
    """A pipelined target with nothing to compute: address 01 00.. for
    every row but each 64th, which it calls invalid."""

    def stage_recover(self, sigs, hashes):
        return len(sigs)

    def commit_recover(self, staged):
        return staged

    def collect_recover(self, n):
        addrs = np.zeros((n, 20), np.uint8)
        addrs[:, 0] = 1
        return addrs, np.arange(n) % 64 != 0


def test_a_burst_leaves_the_lane_in_steps_a_window_not_a_row():
    """A 1025-row consensus call on a pipelined lane is one 1024-row
    window and a one-row tail: one hold answers each, the window's
    flight has its ``resolve_ms``, the queue-wait histograms still count
    rows, and the burst's stage and resolve have histograms of their
    own."""
    from eges_tpu.utils.metrics import DEFAULT as registry

    def counts():
        snap = registry.snapshot()
        return {k: snap.get(k, {"count": 0})["count"] for k in (
            "verifier.sched_queue_wait_seconds",
            "verifier.sched_queue_wait_seconds;class=consensus",
            "verifier.window_stage_seconds;class=consensus,size=burst",
            "verifier.window_resolve_seconds;class=consensus,size=burst",
            "verifier.window_resolve_seconds;class=consensus,size=call")}

    sched = VerifierScheduler(_AnswersAtOnce(), window_ms=10_000.0,
                              max_batch=1024)
    entries = [(i.to_bytes(4, "big") * 8, bytes([i % 250 + 1]) * 65)
               for i in range(1025)]
    before = counts()
    out = sched.recover_signers(entries, priority="consensus")
    sched.close()
    assert out[:1024] == [None if i % 64 == 0 else b"\x01" + b"\x00" * 19
                          for i in range(1024)]
    # (the one-row tail is recovered on the dispatch thread, beside
    # the lane: which of the two is recorded first is not fixed)
    tail, burst = sorted(sched.flights(), key=lambda f: f["rows"])
    assert (tail["rows"], tail["reason"]) == (1, "kick")
    assert (burst["rows"], burst["reason"]) == (1024, "full")
    assert burst["pipelined"] and burst["resolve_ms"] > 0
    st = sched.stats()
    assert st["rows"] == 1025 and st["resolve_holds"] == 2
    grown = {k: v - before[k] for k, v in counts().items()}
    assert list(grown.values()) == [1025, 1025, 1, 1, 1]


def test_cluster_sim_no_singleton_batches_and_warm_cache():
    """4-node signed cluster over one shared scheduler: the chain
    advances, no steady-state one-row device batch ever happens, the
    recovery cache absorbs gossip re-verification, and every cached
    answer is bit-identical to a fresh synchronous batch-verifier run."""
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils.metrics import DEFAULT as metrics

    single0 = metrics.counter("verifier.singleton_batches").value
    c = SimCluster(4, txn_per_block=2, seed=3, signed=True,
                   verifier=NativeBatchVerifier())
    c.start()
    c.run(120, stop_condition=lambda: c.min_height() >= 5)
    assert c.min_height() >= 5, c.heights()
    h = c.min_height()
    assert len({sn.chain.get_block_by_number(h).hash
                for sn in c.nodes}) == 1

    st = c.verifier.stats()
    assert metrics.counter("verifier.singleton_batches").value == single0
    assert st["cache_hits"] > 0, st
    assert st["rows"] + st["cache_hits"] >= st["cache_misses"]
    # flush decisions landed in the first node's journal
    flushes = [e for e in c.nodes[0].node.journal.events()
               if e["type"] == "verifier_flush"]
    assert len(flushes) == st["batches"]

    # bit-identical: replay a sample of the scheduler's cached answers
    # through a fresh synchronous verifier
    with c.verifier._lock:
        sample = list(c.verifier._cache.items())[:32]
    entries = [k for k, _ in sample]
    sync = NativeBatchVerifier()
    sigs = np.zeros((len(entries), 65), np.uint8)
    hashes = np.zeros((len(entries), 32), np.uint8)
    for i, (hh, ss) in enumerate(entries):
        sigs[i] = np.frombuffer(ss, np.uint8)
        hashes[i] = np.frombuffer(hh, np.uint8)
    addrs, ok = sync.recover_addresses(sigs, hashes)
    for i, (_, cached) in enumerate(sample):
        assert cached == (bytes(addrs[i]) if ok[i] else None)
    c.verifier.close()


def test_bucket16_model():
    # _bucket16 is the shared crypto/bucketing.bucket_round — the ONE
    # padding model the scheduler and both verifier facades round with
    from eges_tpu.crypto.bucketing import bucket_round

    assert _bucket16 is bucket_round
    assert [_bucket16(n) for n in (1, 15, 16, 17, 129)] == \
        [16, 16, 16, 32, 256]
    # per-device targets pad from their own (smaller) floor
    assert [bucket_round(n, 4) for n in (1, 4, 5, 9)] == [4, 4, 8, 16]


@pytest.mark.slow
def test_scheduler_bit_identical_to_device_batchverifier():
    """The acceptance check on the real device path: scheduler answers
    == synchronous BatchVerifier answers on the same inputs."""
    from eges_tpu.crypto.verifier import BatchVerifier

    bv = BatchVerifier()
    entries = _sign_entries(9, salt=6)
    entries.append((b"\x07" * 32, bytes(64) + b"\x01"))  # invalid row
    sigs = np.zeros((len(entries), 65), np.uint8)
    hashes = np.zeros((len(entries), 32), np.uint8)
    for i, (h, s) in enumerate(entries):
        sigs[i] = np.frombuffer(s, np.uint8)
        hashes[i] = np.frombuffer(h, np.uint8)
    addrs, ok = bv.recover_addresses(sigs, hashes)
    sync = [bytes(addrs[i]) if ok[i] else None for i in range(len(entries))]

    sched = scheduler_for(bv)
    assert sched.recover_signers(entries) == sync
    # second pass never touches the device again
    st0 = sched.stats()
    assert sched.recover_signers(entries) == sync
    st1 = sched.stats()
    assert st1["batches"] == st0["batches"]
    assert st1["cache_hits"] - st0["cache_hits"] == len(entries)
    sched.close()
