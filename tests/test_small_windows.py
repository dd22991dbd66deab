"""The scheduler's host-by-rule divert: a one-row window, and a
consensus-class window of a few rows on a target that would pad it to a
bucket ``HOST_WINDOW_RATIO`` times its size, are answered inline on the
dispatch thread in ONE native call; everything else keeps its lane.

The target is the host C++ verifier behind the split-phase trio (what the
scheduler's lane worker drives on the chip), reporting the kernel path's
bucket ladder and counting what reaches it; the answers are held to the
benchmark's plain reference (``perfbench/ref/secp.py``), forged rows of
``gen_votes.FORGED``'s three kinds among them.
"""

import functools
import random
import threading
import time

import pytest

from eges_tpu.crypto import native
from eges_tpu.crypto.bucketing import bucket_round
from eges_tpu.crypto.scheduler import (HOST_WINDOW_RATIO, VerifierScheduler,
                                       _key_arrays)
from eges_tpu.crypto.verify_host import (NativeBatchVerifier,
                                         PipelinedNativeVerifier)
from eges_tpu.utils.metrics import DEFAULT as metrics
from perfbench import gen, gen_votes
from perfbench.ref import secp
from perfbench.ref.keccak import keccak256_many

FLOOR = 256  # the kernel path's smallest bucket (LANE_BLOCK)
LAST = FLOOR // HOST_WINDOW_RATIO  # the largest window the rule takes
DISPATCHER = "verifier-scheduler"


class Ladder(PipelinedNativeVerifier):
    """A lane target with a bucket ladder from ``floor`` rows up; notes
    the rows of each window staged on it, and holds a window in
    ``collect_recover`` while ``gate`` is clear."""

    def __init__(self, floor: int = FLOOR):
        super().__init__()
        self.floor = floor
        self.windows: list = []
        self.gate = threading.Event()
        self.gate.set()

    def _pad(self, n: int) -> int:
        return bucket_round(max(n, 1), self.floor)

    def stage_recover(self, sigs, hashes):
        self.windows.append(len(sigs))
        return super().stage_recover(sigs, hashes)

    def collect_recover(self, st):
        assert self.gate.wait(30)
        return super().collect_recover(st)


class TwoChips(NativeBatchVerifier):
    """Two such targets behind ``device_targets()``: a mesh's lanes."""

    def __init__(self):
        super().__init__()
        self.targets = [Ladder(), Ladder()]

    def device_targets(self) -> list:
        return list(self.targets)


class Records:
    """A journal that keeps what it is handed."""

    def __init__(self):
        self.events: list = []

    def record(self, type: str, **fields) -> None:
        self.events.append((type, fields))


@functools.lru_cache(maxsize=None)
def _rows(n: int, salt: int = 0):
    """``n`` seeded ``(hash, sig)`` rows, the first three forged (another
    key, s out of range, r off the curve: they fall, or answer another
    address, only at the verifier), and the reference's answer a row."""
    rng = random.Random(1000 * salt + n)
    privs, _addrs = secp.keys(gen._key_base(rng), n)
    hashes = keccak256_many(rng.randbytes(40) for _ in range(n))
    kinds = [gen_votes.FORGED[i] if i < len(gen_votes.FORGED) else None
             for i in range(n)]
    signers = [privs[(i + 1) % n] if k == "other_key" else privs[i]
               for i, k in enumerate(kinds)]
    sigs = [gen._spoil(k, s, rng) for k, s in zip(
        kinds, secp.sign_rows(signers, hashes, gen._key_base(rng)))]
    rows = tuple(zip(hashes, sigs))
    return rows, [secp.recover(h, s) for h, s in rows]


@pytest.fixture
def native_calls(monkeypatch):
    """Every ``native.ec_recover_batch`` call as (thread name, rows)."""
    calls: list = []
    real = native.ec_recover_batch

    def counted(hashes, sigs, n):
        calls.append((threading.current_thread().name, n))
        return real(hashes, sigs, n)

    monkeypatch.setattr(native, "ec_recover_batch", counted)
    return calls


def _host_rows() -> int:
    return metrics.counter("verifier.host_rows").value


@pytest.mark.parametrize("klass", ["consensus", "bulk"])
@pytest.mark.parametrize("n", [1, 2, 3, LAST, LAST + 1, 169])
def test_where_a_window_runs(n, klass, native_calls):
    rows, expect = _rows(n)
    if n >= 3:  # the forged kinds are among them, and fall as they must
        assert expect[1] is None and expect[2] is None
        assert expect[0] is not None
    hosted = n == 1 or (klass == "consensus" and n <= LAST)
    target = Ladder()
    sched = VerifierScheduler(target)
    host0 = _host_rows()
    try:
        assert list(sched.recover_signers(rows, priority=klass)) == expect
        st = sched.stats()
        flight, = sched.flights()
    finally:
        sched.close()
    on_host = [c for c in native_calls if c[0] == DISPATCHER]
    if hosted:
        assert target.windows == []
        assert on_host == [(DISPATCHER, n)]  # ONE call for the window
        assert (st["host_diverted"], st["host_diverted_rows"]) == (1, n)
        assert _host_rows() - host0 == n
        assert st["bucket_rows"] == n and flight["bucket"] == n
        assert not flight["pipelined"] and sched._lanes[0].thread is None
    else:
        assert target.windows == [n] and on_host == []
        assert (st["host_diverted"], st["host_diverted_rows"]) == (0, 0)
        assert _host_rows() == host0
        assert flight["bucket"] == bucket_round(n, FLOOR)
        assert flight["pipelined"]
    assert (st["batches"], st["rows"]) == (1, n)
    assert flight["rows"] == n and flight["klass"] == klass
    assert not flight["diverted"] and st["breaker"] == "closed"
    assert st["devices"][0]["host_diverted"] == int(hosted)


def test_a_small_window_does_not_wait_for_the_lane(native_calls):
    """A lane window in flight: the small consensus window is answered
    beside it, as a one-row window is."""
    bulk, bulk_expect = _rows(20, salt=1)
    votes, votes_expect = _rows(3, salt=2)
    target = Ladder()
    target.gate.clear()
    sched = VerifierScheduler(target)
    try:
        win = sched.submit_window(*_key_arrays(list(bulk)))
        sched.kick()
        deadline = time.monotonic() + 30
        while target.windows != [20] and time.monotonic() < deadline:
            time.sleep(0.001)  # the lane holds it before the votes enter
        assert target.windows == [20]
        got: list = []
        caller = threading.Thread(target=lambda: got.extend(
            sched.recover_signers(votes, priority="consensus")))
        caller.start()
        caller.join(30)
        assert not caller.is_alive() and got == votes_expect
        assert not win._fut.done()  # the lane's window is still out
        assert sched.stats()["host_diverted_rows"] == 3
        target.gate.set()
        assert win.result(30) == bulk_expect
    finally:
        target.gate.set()
        sched.close()
    assert target.windows == [20]
    assert [c for c in native_calls if c[0] == DISPATCHER] == [
        (DISPATCHER, 3)]


def test_a_mesh_answers_a_small_window_on_no_lane():
    votes, expect = _rows(3, salt=3)
    mesh = TwoChips()
    sched = VerifierScheduler(mesh)
    try:
        assert list(sched.recover_signers(
            votes, priority="consensus")) == expect
        st = sched.stats()
        assert st["lanes"] == 2 and st["host_diverted_rows"] == 3
        assert all(lane.thread is None for lane in sched._lanes)
        assert [t.windows for t in mesh.targets] == [[], []]
    finally:
        sched.close()


@pytest.mark.parametrize("make", [PipelinedNativeVerifier,
                                  NativeBatchVerifier,
                                  lambda: Ladder(16)],
                         ids=["pipelined_native", "native", "ladder16"])
def test_the_rule_does_not_engage(make, native_calls):
    """A target that reports no bucket (the native verifiers, on which
    the sims and the chaos harness run) and one whose ladder starts at
    16 rows: a 3-row consensus window is the target's, and the flight
    and journal records are what they were."""
    votes, expect = _rows(3, salt=4)
    # a deadline far off: on a loaded machine the caller's kick may come
    # later than the default 2 ms after its entry, and the flush would
    # be recorded as ``deadline``
    sched = VerifierScheduler(make(), window_ms=2000.0)
    sched.journal = Records()
    host0 = _host_rows()
    try:
        assert list(sched.recover_signers(
            votes, priority="consensus")) == expect
        st = sched.stats()
        flight, = sched.flights()
    finally:
        sched.close()
    assert (st["host_diverted"], st["host_diverted_rows"]) == (0, 0)
    assert _host_rows() == host0
    assert [n for _t, n in native_calls] == [3]  # the target's own call
    assert (flight["rows"], flight["bucket"], flight["klass"]) == (
        3, 16, "consensus")
    flush, anatomy = sched.journal.events
    assert flush[0] == "verifier_flush" and {
        k: flush[1][k] for k in ("rows", "reason", "occupancy")} == {
        "rows": 3, "reason": "kick", "occupancy": 0.1875}
    assert anatomy[0] == "commit_anatomy" and {
        k: anatomy[1][k] for k in ("stage", "rows", "diverted", "lane")} == {
        "stage": "verify_window", "rows": 3, "diverted": False, "lane": 0}


def test_without_the_native_library_only_one_row_is_the_hosts(monkeypatch):
    votes, expect = _rows(3, salt=5)
    monkeypatch.setattr(native, "available", lambda: False)
    target = Ladder()
    sched = VerifierScheduler(target)
    try:
        assert list(sched.recover_signers(
            votes, priority="consensus")) == expect
        assert list(sched.recover_signers(
            _rows(1, salt=5)[0], priority="consensus")) == _rows(1, salt=5)[1]
        st = sched.stats()
    finally:
        sched.close()
    assert target.windows == [3]
    assert (st["host_diverted"], st["host_diverted_rows"]) == (1, 1)


def test_a_window_that_dies_on_the_host_fails_its_rows(monkeypatch):
    """As a one-row window's death does: the rows take the error as
    their value, and the lane's breaker hears nothing of it."""
    votes, _expect = _rows(3, salt=6)

    def dies(hashes, sigs, n):
        raise MemoryError("no room for the answers")

    monkeypatch.setattr(native, "ec_recover_batch", dies)
    target = Ladder()
    sched = VerifierScheduler(target)
    try:
        win = sched.submit_window(*_key_arrays(list(votes)),
                                  priority="consensus")
        sched.kick()
        assert all(isinstance(v, MemoryError) for v in win.result(30))
        st = sched.stats()
    finally:
        sched.close()
    assert target.windows == [] and st["breaker"] == "closed"
    assert (st["device_errors"], st["breaker_trips"], st["batches"]) == (
        0, 0, 0)
