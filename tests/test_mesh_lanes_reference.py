"""Four scheduler lanes against the benchmark's plain reference, row for
row: what ``c1024x4.mixed-backlog`` holds the program to on the chip, at
the test's ``max_batch``.

The lanes are ``NativeMeshVerifier(4)``: a ``MeshBatchVerifier``'s lanes
each run the recover graph on a device of their own, and a jitted
executable compiles for the device it first runs on, so three of the four
forced host devices would trace and compile the secp256k1 graph anew
(about a minute each on the CPU).  Placement, splitting, the lane workers
and the resolution are the scheduler's and are the same code on both; the
graph itself is held to the host model in ``tests/test_verifier.py`` and,
per lane's device, by ``chip_smoke.py --chips 4``.
"""

import random

import pytest

from eges_tpu.crypto.scheduler import scheduler_for
from eges_tpu.crypto.verify_host import NativeMeshVerifier
from perfbench import gen
from perfbench.ref import secp

LANES, MAX_BATCH = 4, 64
CAP = MAX_BATCH // LANES  # a lane's chunk cap: 16 rows


def _seeded_rows(seed: int, n: int, every: int = 4):
    """``n`` ``(hash32, sig65)`` rows signed by the reference, one in
    ``every`` invalid, the generator's four kinds in turn; and the kinds."""
    rng = random.Random(seed)
    privs, _ = secp.keys(rng.randrange(1 << 200, 1 << 250), n)
    msgs = [rng.randbytes(32) for _ in range(n)]
    sigs = secp.sign_rows(privs, msgs, rng.randrange(1 << 200, 1 << 250))
    rows, kinds = [], []
    for i, (h, sig) in enumerate(zip(msgs, sigs)):
        kind = gen.KINDS[(i // every) % 4] if i % every == 3 else None
        if kind == "bad_recid":
            sig = sig[:64] + b"\x05"
        elif kind == "flipped_message":
            h = bytes([h[0] ^ 0x40]) + h[1:]
        rows.append((h, gen._spoil(kind, sig, rng)))
        kinds.append(kind)
    return rows, kinds


# rows of the call, its class, and by hand: the device windows it flies
# as (rows each, in the order the dispatcher cuts them) and how many of
# them split
CASES = {
    # at most the chunk cap: whole, on one lane
    "fits_one_lane": (CAP, "bulk", [CAP], 0),
    # 2 x cap: two chunks of 16 on two lanes
    "splits_in_two": (2 * CAP, "bulk", [CAP, CAP], 1),
    # a full window: four chunks, one a lane (the ACK burst's 1024 -> 4 x
    # 256 on the chip)
    "splits_in_four": (MAX_BATCH, "bulk", [CAP] * 4, 1),
    # the 1025-row-shaped consensus call: one full window in four
    # chunks, then the one-row tail, which the singleton rule serves on
    # the host from lane 0
    "consensus_call_one_over": (MAX_BATCH + 1, "consensus",
                                [CAP] * 4 + [1], 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_four_lanes_answer_what_the_plain_reference_answers(case):
    n, priority, windows, splits = CASES[case]
    rows, kinds = _seeded_rows(2700 + n, n)
    assert set(kinds) >= set(gen.KINDS)  # every invalid kind is among them
    want = [secp.recover(h, sig) for h, sig in rows]
    for kind, (h, sig), addr in zip(kinds, rows, want):
        # the reference itself, by construction: a spoiled signature is
        # nobody's, an altered message somebody else's
        assert (addr is None) == (kind not in (None, "flipped_message"))

    # hedging off: a loaded machine that keeps a chunk past the 25 ms
    # floor would copy it to a sibling lane, and the per-lane counts
    # below would count the copy's lane
    sched = scheduler_for(NativeMeshVerifier(LANES), max_batch=MAX_BATCH,
                          window_ms=10_000.0, hedge=False)
    try:
        assert sched.stats()["lanes"] == LANES
        assert sched._chunk_cap == CAP
        got = sched.recover_signers(rows, priority=priority)
        assert got == want
        st = sched.stats()
        per_lane = [d["rows"] for d in st["devices"]]
        assert sum(per_lane) == n == st["rows"]
        assert sorted(f["rows"] for f in sched.flights()) == sorted(windows)
        assert st["batches"] == len(windows)
        assert st["window_splits"] == splits
        assert sum(1 for r in per_lane if r) == min(LANES, len(
            [w for w in windows if w > 1]))
        if len(windows) >= LANES:
            assert all(r >= CAP for r in per_lane), per_lane
        assert {f["klass"] for f in sched.flights()} == {
            "consensus" if priority == "consensus" else "bulk"}
        # nothing was rescued or thrown away on the way
        assert st["straggler_diverts"] == st["device_errors"] == 0
        assert st["hedges"] == st["hedge_wasted"] == 0
        assert st["host_diverted"] == windows.count(1)
        # a second pass is the cache's: the same answers, no new window
        assert sched.recover_signers(rows, priority=priority) == want
        assert sched.stats()["batches"] == len(windows)
    finally:
        sched.close()
