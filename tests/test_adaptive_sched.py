"""The scheduler's policy surface (keyword arguments, and nothing
else: no environment variable, no controller), the configurable flight
ring with its ``flight_dropped`` loss signal, the
hedged re-dispatch contract (bit-identical results, loser cancelled or
wasted — never recorded — and exactly-once ledger billing), and
priority-class preemption at placement.

Everything runs against the JAX-free host verifiers, same as
``test_scheduler.py``.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from eges_tpu.crypto import secp256k1 as host
from eges_tpu.crypto.scheduler import VerifierScheduler, scheduler_for
from eges_tpu.crypto.verify_host import (
    NativeBatchVerifier,
    NativeMeshVerifier,
)
from eges_tpu.utils import ledger as ledger_mod


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


# -- the policy surface ---------------------------------------------------

def test_a_keyword_argument_reaches_the_scheduler():
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=3.0,
                              flight_ring=8)
    try:
        st = sched.stats()
        assert st["window_ms"] == 3.0
        assert st["flight_capacity"] == sched._flights.maxlen == 8
        # untouched values keep their defaults
        assert sched.max_batch == 1024
    finally:
        sched.close()


def test_scheduler_reads_no_environment(monkeypatch):
    """A stray variable in an operator's shell changes nothing, and a
    malformed one does not stop the node."""
    monkeypatch.setenv("EGES_SCHED_MAX_BATCH", "lots")
    monkeypatch.setenv("EGES_SCHED_ADAPTIVE", "1")
    monkeypatch.setenv("EGES_SCHED_WINDOW_MS", "7.5")
    sched = VerifierScheduler(NativeBatchVerifier())
    try:
        st = sched.stats()
        assert sched.max_batch == 1024
        assert st["window_ms"] == 2.0
        assert "adaptive" not in st
    finally:
        sched.close()


def test_unknown_policy_keyword_is_refused():
    with pytest.raises(TypeError):
        VerifierScheduler(NativeBatchVerifier(), adaptive=True)
    with pytest.raises(TypeError):
        scheduler_for(NativeBatchVerifier(), slo_p99_ms=20)


# -- flight ring loss signal ----------------------------------------------

def test_flight_ring_size_and_dropped_counter():
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0,
                              flight_ring=4)
    try:
        for k in range(6):
            entries = _sign_entries(3, salt=k + 1)
            futs = [sched.submit(h, s) for h, s in entries]
            sched.kick()
            for f in futs:
                assert f.result(30) is not None
        st = sched.stats()
        assert st["batches"] == 6
        assert len(sched.flights()) == 4      # ring kept the newest 4
        assert st["flight_dropped"] == 2      # ... and counted the loss
        assert st["flight_capacity"] == 4
    finally:
        sched.close()


# -- hedged re-dispatch ---------------------------------------------------

@pytest.mark.parametrize("entry", ["futures", "window"])
def test_hedge_bit_identical_results_and_exactly_once_billing(entry):
    """Rows that came through ``submit`` and rows of one window entry
    alike: the hedge's winner answers them, the healed loser finds
    every holder answered and changes nothing."""
    mesh = NativeMeshVerifier(2)
    sched = VerifierScheduler(mesh, window_ms=10_000.0, hedge=True,
                              hedge_floor_ms=10.0, hedge_poll_ms=2.0)
    release = threading.Event()
    victim = mesh.device_targets()[0]
    orig = victim.recover_addresses

    def _stuck(sigs, hashes):
        release.wait(30)
        return orig(sigs, hashes)

    victim.recover_addresses = _stuck
    entries = _sign_entries(6, salt=3)
    entries.append((b"\x01" * 32, b"\x00" * 65))   # invalid row rides too
    expect = _host_model(entries)
    led = ledger_mod.IngressLedger(clock=time.monotonic)
    try:
        with ledger_mod.bind(led, "peerX"):
            if entry == "window":
                win = sched.submit_window(
                    np.frombuffer(b"".join(h for h, _ in entries),
                                  np.uint8).reshape(-1, 32),
                    np.frombuffer(b"".join(s for _, s in entries),
                                  np.uint8).reshape(-1, 65))
            else:
                futs = [sched.submit(h, s) for h, s in entries]
        sched.kick()
        # lane 0 is stuck: only the hedge on lane 1 can resolve these
        got = (win.result(30) if entry == "window"
               else [f.result(30) for f in futs])
        assert got == expect                       # bit-identical
        st = sched.stats()
        assert st["hedges"] >= 1
        assert st["hedge_wins"] >= 1
        costs = led.snapshot()["costs"]
        billed = dict(costs.get("peerX", {}))
        assert billed.get("device_ms", 0.0) > 0.0  # winner charged

        # heal: the wasted loser finishes, is discarded, and must not
        # touch stats rows, flights, or the ledger a second time
        rows_before = st["rows"]
        flights_before = len(sched.flights())
        release.set()
        sched.close()
        st = sched.stats()
        assert st["rows"] == rows_before
        assert len(sched.flights()) == flights_before
        # one hold for the window, one a ``submit`` (a one-row window);
        # none for the loser
        assert st["resolve_holds"] == (1 if entry == "window"
                                       else len(entries))
        if entry == "window":
            assert win.result(0) == expect and win._remaining == 0
        assert st["hedges"] == (st["hedge_cancelled"]
                                + st["hedge_wasted"])
        # the snapshot applies the ledger's half-life decay at read
        # time: the account may only have shrunk, and by far less than
        # the window's cost that a second charge would add
        after = led.snapshot()["costs"].get("peerX", {})
        assert 0.9 * billed["device_ms"] <= after["device_ms"] \
            <= billed["device_ms"] + 1e-9
        assert after["host_ms"] == billed["host_ms"] == 0.0
    finally:
        release.set()
        sched.close()


def test_hedge_loser_cancelled_before_execution():
    # both lanes stuck: window A blocks lane 0 inflight, B blocks lane 1,
    # C queues behind A.  The hedge thread re-places all three onto their
    # siblings' queues.  Releasing ONLY lane 1 lets it win A and C via
    # their hedge copies (B via its primary); when lane 0 finally wakes
    # it must drop the already-claimed B-hedge and C-primary copies at
    # pop, without dispatching them — the "cancelled" loser outcome.
    mesh = NativeMeshVerifier(2)
    # the straggler monitor reads its floor live: it is held off (an
    # hour) until all three windows are where the scenario needs them,
    # so a slow machine cannot hedge A before B is placed
    sched = VerifierScheduler(mesh, window_ms=10_000.0, hedge=True,
                              hedge_floor_ms=3.6e6, hedge_poll_ms=2.0)
    gates = [threading.Event(), threading.Event()]
    served: list[tuple[int, int]] = []
    for lane_i, tgt in enumerate(mesh.device_targets()):
        orig = tgt.recover_addresses

        def _gate(sigs, hashes, _i=lane_i, _orig=orig,
                  _ev=gates[lane_i]):
            _ev.wait(120)
            served.append((_i, len(sigs)))
            return _orig(sigs, hashes)

        tgt.recover_addresses = _gate

    def _await(cond) -> None:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with sched._lock:
                if cond():
                    return
            time.sleep(0.002)
        raise AssertionError("scheduler never reached expected state")

    # three separate kicked windows — each must land before the next is
    # submitted, or the admission thread coalesces them into one window
    ent_a = _sign_entries(2, salt=40)     # -> lane 0, inflight, stuck
    ent_b = _sign_entries(4, salt=41)     # -> lane 1, inflight, stuck
    ent_c = _sign_entries(2, salt=42)     # -> lane 0 queue, behind A
    expect = _host_model(ent_a + ent_b + ent_c)
    futs = [sched.submit(h, s) for h, s in ent_a]
    sched.kick()
    _await(lambda: sched._lanes[0].inflight_rows == 2)
    futs += [sched.submit(h, s) for h, s in ent_b]
    sched.kick()
    _await(lambda: sched._lanes[1].inflight_rows == 4)
    futs += [sched.submit(h, s) for h, s in ent_c]
    sched.kick()
    _await(lambda: len(sched._lanes[0].queue) == 1)
    try:
        # let the monitor loose, wait for it to copy all three windows
        # onto their siblings' queues, then release lane 1 alone: every
        # future must resolve without lane 0
        with sched._lock:
            sched.hedge_floor_ms = 10.0
        _await(lambda: sched._stats["hedges"] >= 3)
        gates[1].set()
        got = [f.result(30) for f in futs]
        assert got == expect
        assert all(i == 1 for i, _n in served)
        gates[0].set()
        sched.close()
        st = sched.stats()
        assert st["hedges"] == 3
        assert st["hedge_wins"] >= 2           # A and C won by hedges
        assert st["hedge_cancelled"] >= 1      # dropped at pop, unserved
        assert st["hedges"] == (st["hedge_cancelled"]
                                + st["hedge_wasted"])
        # cancelled copies never reached a device: total rows served is
        # submitted rows plus only the WASTED losers' rows
        wasted_rows = sum(n for i, n in served if i == 0)
        assert sum(n for _i, n in served) == 8 + wasted_rows
    finally:
        for ev in gates:
            ev.set()
        sched.close()


# -- priority classes -----------------------------------------------------

def test_consensus_preempts_bulk_at_placement():
    mesh = NativeMeshVerifier(2)
    sched = VerifierScheduler(mesh, window_ms=10_000.0, hedge=False)
    gates = [threading.Event(), threading.Event()]
    for lane_i, tgt in enumerate(mesh.device_targets()):
        orig = tgt.recover_addresses

        def _gate(sigs, hashes, _orig=orig, _ev=gates[lane_i]):
            _ev.wait(30)
            return _orig(sigs, hashes)

        tgt.recover_addresses = _gate

    def window(n: int, salt: int, priority: str) -> list:
        futs = [sched.submit(h, s, priority=priority)
                for h, s in _sign_entries(n, salt=salt)]
        sched.kick()
        return futs

    def _await(cond) -> None:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with sched._lock:
                if cond():
                    return
            time.sleep(0.002)
        raise AssertionError("scheduler never reached expected state")

    # occupy both lanes (4 rows on lane 0, 10 on lane 1), then queue a
    # bulk window on lane 0 — loads stay strictly unequal (8 vs 10) so
    # least-loaded placement is deterministic, no round-robin tie-break;
    # each window must land before the next submit or they coalesce
    futs = window(4, 50, "bulk")
    _await(lambda: sched._lanes[0].inflight_rows == 4)
    futs += window(10, 51, "bulk")
    _await(lambda: sched._lanes[1].inflight_rows == 10)
    futs += window(4, 52, "bulk")
    _await(lambda: len(sched._lanes[0].queue) == 1)
    # a consensus window then lands at the HEAD of that same queue,
    # ahead of the earlier bulk window
    futs += window(2, 53, "consensus")
    _await(lambda: len(sched._lanes[0].queue) == 2)
    with sched._lock:
        queued = [tk.klass for tk in sched._lanes[0].queue]
    try:
        assert queued == ["consensus", "bulk"]
        for ev in gates:
            ev.set()
        for f in futs:
            assert f.result(30) is not None
        st = sched.stats()
        waits = st["class_wait_ms"]
        assert waits["consensus"]["count"] == 2
        assert waits["bulk"]["count"] == 18
        assert waits["consensus"]["p99_ms"] >= 0.0
    finally:
        for ev in gates:
            ev.set()
        sched.close()
