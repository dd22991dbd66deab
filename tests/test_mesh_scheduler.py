"""Mesh verifier dispatch: per-device window lanes, least-loaded
placement with window splitting, per-lane circuit breakers (straggler
isolation), deterministic close() draining, and the per-device stats
surface — all over the JAX-free :class:`NativeMeshVerifier` so tier-1
exercises the full mesh machinery without an accelerator.
"""

from __future__ import annotations

import threading

import numpy as np

from eges_tpu.crypto import secp256k1 as host
from eges_tpu.crypto.scheduler import VerifierScheduler, scheduler_for
from eges_tpu.crypto.verify_host import (
    NativeBatchVerifier, NativeMeshVerifier,
)


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


def test_saturated_window_reaches_every_device():
    """One full 192-row window over 8 lanes splits into 8 chunks placed
    on DISTINCT lanes — every virtual device serves exactly rows/8, and
    the answers are bit-identical to the host model."""
    n_dev, rows = 8, 192
    # placement is what this asserts: with the straggler monitor on, a
    # chunk that a loaded machine keeps waiting past the 25 ms hedge
    # floor is served by a sibling lane, and the per-lane counts move
    sched = VerifierScheduler(NativeMeshVerifier(n_dev),
                              window_ms=10_000.0, max_batch=rows,
                              min_split=8, hedge=False)
    entries = _sign_entries(rows, salt=10)
    futs = [sched.submit(h, s) for h, s in entries]  # fills the bucket
    assert [f.result(60) for f in futs] == _host_model(entries)

    st = sched.stats()
    assert st["lanes"] == n_dev
    assert st["flush_full"] == 1
    assert st["window_splits"] == 1
    devs = st["devices"]
    assert [d["device"] for d in devs] == list(range(n_dev))
    for d in devs:
        assert d["rows"] == rows // n_dev, devs
        assert d["batches"] == 1
        assert d["occupancy"] is not None and 0 < d["occupancy"] <= 1.0
        assert d["breaker"] == "closed"
    assert sum(d["rows"] for d in devs) == st["rows"] == rows
    sched.close()


def test_a_split_window_answers_each_call_once_a_chunk():
    """Two window entries fill one 32-row window that splits into four
    chunks on four lanes; the boundary between the two calls falls
    inside the third chunk.  A chunk takes one hold for each call it
    has rows of: five in all, for 32 rows."""
    sched = VerifierScheduler(NativeMeshVerifier(4), window_ms=10_000.0,
                              max_batch=32, min_split=4, hedge=False)
    entries = _sign_entries(32, salt=14)
    expect = _host_model(entries)

    def arrays(part):
        return (np.frombuffer(b"".join(h for h, _ in part),
                              np.uint8).reshape(-1, 32),
                np.frombuffer(b"".join(s for _, s in part),
                              np.uint8).reshape(-1, 65))

    win_a = sched.submit_window(*arrays(entries[:20]))
    win_b = sched.submit_window(*arrays(entries[20:]),
                                priority="consensus")  # fills the bucket
    assert win_a.result(60) + win_b.result(60) == expect
    sched.close()
    st = sched.stats()
    assert st["window_splits"] == 1 and st["batches"] == 4
    assert [d["rows"] for d in st["devices"]] == [8, 8, 8, 8]
    assert st["resolve_holds"] == 5
    assert all(f["resolve_ms"] > 0 for f in sched.flights())
    assert sorted(f["klass"] for f in sched.flights()) == \
        ["bulk", "bulk", "consensus", "consensus"]
    waits = st["class_wait_ms"]
    assert (waits["bulk"]["count"], waits["consensus"]["count"]) == (20, 12)


def test_concurrent_mesh_submitters_bit_identical():
    """8 caller threads over a 4-lane mesh: every caller gets exactly
    the host model's answers, lane row counts account for every
    dispatched row, and load reached more than one device."""
    sched = VerifierScheduler(NativeMeshVerifier(4), window_ms=5.0,
                              max_batch=32, min_split=4)
    entries = _sign_entries(96, salt=11)
    expect = _host_model(entries)
    results: dict[int, list] = {}
    errs: list = []
    barrier = threading.Barrier(8)

    def worker(k: int) -> None:
        try:
            barrier.wait()
            chunk = entries[k * 12:(k + 1) * 12]
            results[k] = sched.recover_signers(chunk)
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs
    for k, got in results.items():
        assert got == expect[k * 12:(k + 1) * 12], f"thread {k} mismatch"

    st = sched.stats()
    assert sum(d["rows"] for d in st["devices"]) == st["rows"]
    assert sum(d["batches"] for d in st["devices"]) == st["batches"]
    assert sum(1 for d in st["devices"] if d["rows"] > 0) >= 2, st
    sched.close()


def test_straggler_lane_diverts_only_its_own_windows():
    """Killing ONE device's dispatch trips only that lane's breaker:
    its chunks host-divert (answers stay correct), the other lanes keep
    the device path with zero errors."""
    n_dev, victim = 4, 1
    mesh = NativeMeshVerifier(n_dev)

    def boom(_rows: int) -> None:
        raise RuntimeError("injected: device lost")

    # the breaker is what this asserts: with the straggler monitor on,
    # a chunk that a loaded machine keeps past the 25 ms hedge floor is
    # copied to a sibling lane, and where the copy beats the victim's
    # host divert the divert is a hedge loser (``hedge_wasted``), which
    # records nothing: ``straggler_diverts`` then rightly reads 0
    sched = VerifierScheduler(mesh, window_ms=10_000.0, max_batch=64,
                              min_split=8, hedge=False)
    sched._lanes[victim].target.failure_hook = boom

    # window 1: 64 rows -> 4 chunks, one per lane; the victim's chunk
    # raises, host-diverts, and trips the per-lane breaker
    entries = _sign_entries(64, salt=12)
    futs = [sched.submit(h, s) for h, s in entries]
    assert [f.result(60) for f in futs] == _host_model(entries)

    st = sched.stats()
    dv = st["devices"][victim]
    assert dv["breaker"] == "open"
    assert dv["device_errors"] == 1
    assert dv["straggler_diverts"] >= 1
    for d in st["devices"]:
        if d["device"] == victim:
            continue
        assert d["breaker"] == "closed", st
        assert d["device_errors"] == 0, st
        assert d["rows"] > 0, st
    assert st["breaker"] == "open"  # any-lane-open aggregate

    # window 2: the victim's chunk breaker-diverts without touching its
    # device; everything still resolves bit-identically
    entries2 = _sign_entries(64, salt=13)
    futs2 = [sched.submit(h, s) for h, s in entries2]
    assert [f.result(60) for f in futs2] == _host_model(entries2)
    st2 = sched.stats()
    assert st2["devices"][victim]["breaker_diverted"] >= 16
    assert st2["devices"][victim]["device_errors"] == 1  # no new error
    sched.close()


def test_close_drains_lanes_then_stops_threads():
    """close() serves a pending window as the final flush_close batch
    (lane workers exit only after the admission front drains), resolves
    every future, and joins every thread."""
    sched = VerifierScheduler(NativeMeshVerifier(4), window_ms=10_000.0,
                              max_batch=256, min_split=4)
    entries = _sign_entries(32, salt=14)
    futs = [sched.submit(h, s) for h, s in entries]
    assert not any(f.done() for f in futs)  # deadline far away
    sched.close()
    assert [f.result(0) for f in futs] == _host_model(entries)
    st = sched.stats()
    assert st["flush_close"] == 1
    assert sched._thread is not None and not sched._thread.is_alive()
    for lane in sched._lanes:
        assert lane.thread is None or not lane.thread.is_alive()
        assert not lane.queue and lane.queued_rows == 0
    # post-close submissions still resolve (inline on the caller)
    f = sched.submit(*entries[0])
    assert f.result(0) == _host_model(entries[:1])[0]


def test_stats_per_device_breakdown_keeps_legacy_keys():
    sched = VerifierScheduler(NativeMeshVerifier(2), window_ms=2.0)
    entries = _sign_entries(8, salt=15)
    assert sched.recover_signers(entries) == _host_model(entries)
    st = sched.stats()
    # the pre-mesh flat surface is intact...
    for k in ("cache_hits", "cache_misses", "coalesced_rows", "batches",
              "rows", "bucket_rows", "host_diverted", "kicks",
              "flush_full", "flush_deadline", "flush_kick",
              "flush_close", "invalid", "device_errors", "breaker_trips",
              "breaker_probes", "breaker_diverted", "cached_entries",
              "pending", "breaker"):
        assert k in st, k
    # ...plus the mesh additions
    assert st["lanes"] == 2
    assert st["window_splits"] >= 0
    assert [d["device"] for d in st["devices"]] == [0, 1]
    for d in st["devices"]:
        for k in ("queue_depth", "max_queue_depth", "inflight_rows",
                  "breaker", "batches", "rows", "bucket_rows",
                  "host_diverted", "straggler_diverts", "device_errors",
                  "breaker_trips", "breaker_probes", "breaker_diverted",
                  "occupancy"):
            assert k in d, k
    sched.close()


def test_single_lane_scheduler_spawns_no_lane_workers():
    """A verifier without device_targets() keeps the pre-mesh shape:
    one lane, dispatched inline by the admission thread."""
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=2.0)
    entries = _sign_entries(4, salt=16)
    assert sched.recover_signers(entries) == _host_model(entries)
    assert sched.stats()["lanes"] == 1
    assert sched._lanes[0].thread is None
    sched.close()


def test_scheduler_for_attaches_mesh_scheduler_once():
    mesh = NativeMeshVerifier(2)
    s1 = scheduler_for(mesh)
    assert s1.stats()["lanes"] == 2
    assert scheduler_for(mesh) is s1
    s1.close()
    s2 = scheduler_for(mesh)  # a closed scheduler is replaced
    assert s2 is not s1 and s2.stats()["lanes"] == 2
    s2.close()


def test_mesh_cluster_sim_advances_and_uses_lanes():
    """4-node signed sim over an 8-lane virtual mesh (the
    ``mesh_devices`` wiring in sim/cluster.py): consensus converges and
    the shared scheduler reports the per-device surface."""
    from eges_tpu.sim.cluster import SimCluster

    c = SimCluster(4, txn_per_block=2, seed=5, signed=True,
                   mesh_devices=8)
    c.start()
    c.run(120, stop_condition=lambda: c.min_height() >= 4)
    assert c.min_height() >= 4, c.heights()
    h = c.min_height()
    assert len({sn.chain.get_block_by_number(h).hash
                for sn in c.nodes}) == 1
    st = c.verifier.stats()
    assert st["lanes"] == 8
    assert sum(d["rows"] for d in st["devices"]) == st["rows"]
    # mesh dispatch decisions landed in the journal stream
    events = [e for sn in c.nodes for e in sn.node.journal.events()
              if e["type"] == "verifier_mesh_dispatch"]
    assert sum(d["batches"] for d in st["devices"]) == st["batches"]
    assert events, "mesh dispatch events missing from the journal"
    assert all(e["rows"] >= 1 and "device" in e for e in events)
    c.verifier.close()
