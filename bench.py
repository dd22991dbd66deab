"""Benchmark: batched secp256k1 ecrecover throughput + latency on one chip.

The BASELINE.json primary metric — secp256k1 verifies/sec/chip — measured
on the TPU, or not at all:

* The parent process never imports JAX (a parent that touched JAX would
  hold the chip and the child would fail or hang).  It starts ONE child
  that owns the device for the whole run.  The child refuses any
  platform but ``tpu`` and the bench then exits non-zero with an
  ``error`` line: there is no CPU fallback, so no host number is ever
  printed under a per-chip metric name.
* The child reports stage results line-by-line as they complete (256-row
  graph first: the known-good compile + correctness gate; then 16384 —
  the throughput point, since per-dispatch overhead amortizes with
  rows; then 1024 with p50/p99 latency).  The parent prints a complete,
  valid bench JSON line after EVERY improvement, so a stall at any
  later stage still leaves a parseable result on stdout.
* On budget exhaustion (``BENCH_BUDGET_S``, default 420 s) the parent
  kills the child and the last line already printed stands.
* Every line names the device: the chip the child reported, and
  ``"device": "host"`` on the host-only stages below (native C++
  verifier, host clock — never a device metric).

The workload is honest: real signatures (so the verifier does full
work) plus a sprinkling of invalid rows (corrupted s, bad recovery id)
whose rejection is asserted against the independent host model.
``vs_baseline`` divides by the *larger* of the measured native-C++
baseline and the 16 k/s reference-class figure (BASELINE.md: the
libsecp256k1 cgo path is ~12-20 k verifies/s/core), so the ratio is
conservative even though our schoolbook C++ recover is slower.

Further independently-gated series ride every round:
``cold_start_seconds`` (child entry to first verified batch — the
number the ``crypto/aotstore.py`` artifact store shrinks by
deserializing stored executables instead of recompiling; gated
lower-is-better), ``pipeline_overlap_ratio`` (the scheduler's
double-buffered lane pipeline measured host-side over
``PipelinedNativeVerifier`` — overlapped windows / pipelined windows),
``slo_compliance_ratio`` / ``slo_false_positive_alerts`` (a calm
sim cluster through the live telemetry collector + burn-rate SLO
engine, ``harness/collector.py`` / ``harness/slo.py`` — any alert
firing on a healthy cluster is a false positive, gated at exactly
zero), and ``commit_p99_ms`` (the commit-anatomy critical-path
assembler over the same calm-sim shape, ``harness/anatomy.py`` —
end-to-end commit p99 plus per-phase shares, gated lower-is-better).

``bench.py mesh`` is a separate stage: it regenerates MESH_SCALING.json
through ``harness/mesh_scaling.run`` (psum/ring A/B, recorded collective
winner, and the mesh scheduler saturation pass with per-device
occupancy, per point) and appends a ``mesh_sharded_rows_per_s`` line to
the same history file, gated independently by
``harness/check_regression.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

REF_CLASS_CPU_PER_S = 16_000.0  # mid of 12-20k/s/core (BASELINE.md)
DEFAULT_BUDGET_S = 420.0


def _git_rev() -> str | None:
    """Current commit hash straight from ``.git`` (no subprocess — the
    bench parent stays import-light and a missing git binary must not
    fail a measurement).  One implementation, shared with every
    profiling artifact header: ``harness.profutil`` is stdlib-only at
    import time."""
    from harness.profutil import git_rev

    return git_rev()


def _provenance() -> dict:
    """Stamp fields for every bench line: platform, git revision, and a
    CALLER-SUPPLIED timestamp (``--timestamp=<v>`` or BENCH_TIMESTAMP
    env — never ambient wall-clock, so re-running a recorded bench
    reproduces the line byte-for-byte)."""
    import platform as _platform

    ts = os.environ.get("BENCH_TIMESTAMP")
    for a in sys.argv[1:]:
        if a.startswith("--timestamp="):
            ts = a[len("--timestamp="):]
    out = {"platform": "%s-%s" % (sys.platform, _platform.machine()),
           "git_rev": _git_rev()}
    if ts is not None:
        out["timestamp"] = ts
    return out


def _append_history(line: dict) -> None:
    """Append the round's final line to ``harness/bench_history.jsonl``
    (BENCH_HISTORY overrides the path; the file is a run-time product,
    created on first use) — the series ``harness/check_regression.py``
    gates on."""
    path = os.environ.get(
        "BENCH_HISTORY", os.path.join(_REPO, "harness",
                                      "bench_history.jsonl"))
    try:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line, sort_keys=True) + "\n")
    except OSError:
        pass  # an unwritable history file must not fail the bench


# ---------------------------------------------------------------------------
# child: runs on one backend, emits "RESULT {...}" lines per stage
# ---------------------------------------------------------------------------

def _child(deadline: float, max_batch: int) -> None:
    t_child0 = time.monotonic()

    def left() -> float:
        return deadline - time.monotonic()

    import jax

    from eges_tpu.crypto.aotstore import default_store, enable_persistent_cache

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        # no fallback: a number from another backend is not this bench's
        print("NODEVICE " + json.dumps(
            {"platform": d0.platform, "device": str(d0)}), flush=True)
        sys.exit(3)
    enable_persistent_cache()
    import jax.numpy as jnp
    import numpy as np
    from jax import export as exp_mod

    from eges_tpu.crypto.verifier import ecrecover_batch
    from eges_tpu.models.flagship import example_batch

    device = str(d0)
    kind = "%s:%s" % (d0.platform,
                      getattr(d0, "device_kind", "") or d0.platform)
    fn = jax.jit(ecrecover_batch)
    # the AOT artifact store (crypto/aotstore.py): a bucket whose
    # serialized executable survives from a previous round deserializes
    # in seconds instead of recompiling in minutes — the bench measures
    # that as cold_start_s and labels each stage load/compile
    store = default_store()

    base_s, base_h, valid, expect = example_batch(max_batch, invalid_every=17)

    def emit(obj: dict) -> None:
        obj["device"] = device
        print("RESULT " + json.dumps(obj), flush=True)

    # Stage order is budget-driven: each batch size is a fresh trace +
    # compile (minutes cold, seconds from the artifact store), so after
    # the 256-row correctness gate the child jumps straight to the
    # biggest batch — throughput grows with rows because per-dispatch
    # overhead amortizes — then backfills the 1024-row p50/p99
    # operating point if budget remains.  Under a TIGHT budget there is
    # no room for a throwaway 256-row gate compile: go straight to the
    # headline batch and run the correctness gate on ITS output — the
    # gate asserts on whichever batch completes first either way.
    tight = left() <= 360
    order = (16384, 1024) if tight else (256, 16384, 1024, 4096)
    # clamp to the caller's cap instead of skipping past it — a tight
    # run with max_batch < 1024 must still measure SOMETHING
    order = tuple(dict.fromkeys(min(b, max_batch) for b in order))
    first = True
    cold_start_s = None
    for batch in order:
        if batch > max_batch:
            continue
        # After the first graph is proven, require slack for a fresh
        # compile + measurement; the first attempt gets all the time.
        if not first and left() < 90:
            break
        sigs, hashes = base_s[:batch], base_h[:batch]
        # per-bucket executable: an AOT artifact (if one is stored for
        # this exact bucket/device-kind/code-rev) beats a fresh trace
        fn_b, aot_src = fn, "jit"
        if store is not None:
            payload = store.load("recover", batch, kind)
            if payload is not None:
                try:
                    fn_b = jax.jit(exp_mod.deserialize(payload).call)
                    aot_src = "load"
                except Exception:
                    fn_b, aot_src = fn, "jit"
        t0 = time.monotonic()
        js, jh = jnp.asarray(sigs), jnp.asarray(hashes)
        out = fn_b(js, jh)
        jax.block_until_ready(out)
        compile_s = time.monotonic() - t0

        if first:
            # correctness gate (includes invalid-row masking)
            addrs = np.asarray(out[0])
            ok = np.asarray(out[2]).astype(bool)
            for i in range(batch):
                if expect[i] is None:
                    continue  # corrupted-s rows recover some other address
                if valid[i]:
                    assert ok[i], f"row {i}: valid signature rejected"
                    assert bytes(addrs[i]) == expect[i], f"row {i}: addr mismatch"
                else:
                    assert not ok[i], f"row {i}: invalid signature accepted"
            first = False
            # cold start: child entry (JAX import + init included) to
            # the first VERIFIED batch on this backend — the number the
            # AOT store exists to shrink
            cold_start_s = round(time.monotonic() - t_child0, 1)

        # Distinct pre-uploaded inputs per call: the runtime memoizes
        # repeat dispatches of (executable, same buffers), so timing a
        # loop over one input set measures nothing.  Iteration count is
        # time-targeted: a fast chip would otherwise finish 6 calls in
        # milliseconds and the number would be dispatch noise.
        n_sets = 8
        sets = [(jnp.asarray(np.roll(sigs, i + 1, axis=0)),
                 jnp.asarray(np.roll(hashes, i + 1, axis=0)))
                for i in range(n_sets)]
        jax.block_until_ready(sets)
        lats = []
        n_iters = 0
        t0 = time.monotonic()
        while True:
            a, b = sets[n_iters % n_sets]
            t1 = time.monotonic()
            jax.block_until_ready(fn_b(a, b))
            lats.append(time.monotonic() - t1)
            n_iters += 1
            el = time.monotonic() - t0
            # a graph that takes seconds per call is measured well
            # enough by 3 calls; don't burn the big-batch budget on
            # statistical overkill
            min_iters = 3 if lats[0] > 5.0 else 6
            if (n_iters >= min_iters and el > 2.0) or n_iters >= 200 \
                    or el > min(30.0, max(left() - 15, 2.0)):
                break
        dt = time.monotonic() - t0
        res = {"batch": batch, "per_sec": batch * n_iters / dt,
               "compile_s": round(compile_s, 1), "aot": aot_src}
        if cold_start_s is not None:
            res["cold_start_s"] = cold_start_s
            cold_start_s = None  # rides the FIRST stage's line only
        # tail latencies for EVERY bucket (matching the runtime
        # verifier.device_seconds histograms), not just the 1024 point —
        # BENCH_*.json consumers get the full batch->tail curve
        from eges_tpu.utils.metrics import percentile
        srt = sorted(lats)
        res["p50_ms"] = round(percentile(srt, 50) * 1e3, 3)
        res["p99_ms"] = round(percentile(srt, 99) * 1e3, 3)
        # emit the throughput result BEFORE the latency extras: on a
        # slow backend the 30-call latency loop can outlive the budget,
        # and being killed mid-latency must not lose the stage
        emit(res)

        if batch == 1024 and left() > 20:
            # p50/p99 at the BASELINE.md 1k-validator operating point;
            # per-iteration deadline check so the loop degrades to
            # fewer samples instead of dying with none.  On a graph
            # that takes seconds per call the timing loop above already
            # sampled enough — extra iterations would eat the budget
            # the 4096/16384 stages need.
            extra = 0 if lats[0] > 2.0 else 24
            for i in range(extra):
                if left() < 10:
                    break
                a = jnp.asarray(np.roll(sigs, i + 10, axis=0))
                b = jnp.asarray(np.roll(hashes, i + 10, axis=0))
                jax.block_until_ready((a, b))
                t1 = time.monotonic()
                jax.block_until_ready(fn_b(a, b))
                lats.append(time.monotonic() - t1)
            lats.sort()
            res["p50_ms"] = round(percentile(lats, 50) * 1e3, 3)
            res["p99_ms"] = round(percentile(lats, 99) * 1e3, 3)
            emit(res)

        if store is not None and aot_src != "load" \
                and left() > max(90.0, compile_s):
            # bank this bucket's executable for the NEXT round: export
            # re-lowers the graph (roughly another compile), so it only
            # runs when the budget clearly survives it
            try:
                exported = exp_mod.export(jax.jit(ecrecover_batch))(js, jh)
                store.save("recover", batch, kind, exported.serialize())
            # analysis: allow-swallow(artifact banking is best-effort; the measurement already emitted)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# parent: baseline + the one device child, print progressive JSON lines
# ---------------------------------------------------------------------------

def _cpu_baseline() -> float | None:
    """Single-threaded native C++ recover rate (the per-call hot path the
    reference serializes through); None when the lib isn't built."""
    try:
        from eges_tpu.crypto import native

        if not native.available():
            return None
        n = 192
        hashes, sigs = [], []
        for i in range(n):
            msg = bytes([(i % 255) + 1]) * 32
            priv = bytes([(i % 200) + 5]) * 32
            sigs.append(native.ec_sign(msg, priv))
            hashes.append(msg)
        t0 = time.perf_counter()
        for h, s in zip(hashes, sigs):
            native.ec_recover(h, s)
        return n / (time.perf_counter() - t0)
    # analysis: allow-swallow(optional probe; a failed leg reports null)
    except Exception:
        return None


def _coalesced_stage() -> dict | None:
    """Coalesced-path stage: 8 concurrent submitters drive the verifier
    scheduler (``crypto/scheduler.py``) over the native host verifier and
    the stage reports the EFFECTIVE occupancy (dispatched rows / padded
    bucket rows) plus the sender-recovery cache hit rate.

    Runs in the PARENT on purpose: the scheduler and
    ``NativeBatchVerifier`` import no JAX, and what this stage measures —
    how well the micro-window turns per-caller single verifies into full
    buckets — is backend-independent.  None when the native lib (or the
    pure-Python fallback it rides on) can't sign the workload."""
    import threading

    try:
        from eges_tpu.crypto import native
        from eges_tpu.crypto import secp256k1 as host
        from eges_tpu.crypto.scheduler import VerifierScheduler
        from eges_tpu.crypto.verify_host import NativeBatchVerifier

        n_threads, uniq, reverify = 8, 48, 16
        entries = []
        for i in range(n_threads * uniq):
            msg = (i + 1).to_bytes(4, "big") * 8
            priv = bytes([(i % 200) + 7]) * 32
            sig = (native.ec_sign(msg, priv) if native.available()
                   else host.ecdsa_sign(msg, priv))
            entries.append((msg, sig))

        sched = VerifierScheduler(NativeBatchVerifier(), window_ms=2.0,
                                  max_batch=256)
        barrier = threading.Barrier(n_threads)
        failures = []
        t0 = time.monotonic()

        def submitter(k: int) -> None:
            barrier.wait()  # all 8 callers hit the window together
            mine = entries[k * uniq:(k + 1) * uniq]
            # second pass re-verifies a slice of the NEIGHBOUR's rows —
            # the gossip pattern the recovery cache exists for
            j = ((k + 1) % n_threads) * uniq
            for part in (mine, entries[j:j + reverify]):
                futs = [sched.submit(h, s) for h, s in part]
                for f in futs:
                    if f.result(60) is None:
                        failures.append(k)

        threads = [threading.Thread(target=submitter, args=(k,),
                                    daemon=True)
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        sched.close()
        dt = time.monotonic() - t0

        st = sched.stats()
        lookups = st["cache_hits"] + st["cache_misses"]
        return {
            "submitters": n_threads,
            "submitted": n_threads * (uniq + reverify),
            "rows": st["rows"],
            "batches": st["batches"],
            "singleton_diverted": st["host_diverted"],
            "effective_occupancy":
                round(st["rows"] / max(st["bucket_rows"], 1), 3),
            "cache_hit_rate":
                round(st["cache_hits"] / max(lookups, 1), 3),
            "verify_failures": len(failures),
            "elapsed_s": round(dt, 2),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _pipeline_stage() -> dict | None:
    """Double-buffered lane pipeline stage: back-to-back multi-row
    windows drive the verifier scheduler over a
    :class:`~eges_tpu.crypto.verify_host.PipelinedNativeVerifier`, so
    each lane stages window N+1 (the H2D analogue) while window N
    computes — the stage reports the scheduler's
    ``pipeline_overlap_ratio`` (overlapped windows / pipelined windows).

    Runs in the PARENT like ``_coalesced_stage``: the split-phase host
    verifier imports no JAX and the overlap mechanics it measures are
    backend-independent.  None when the workload can't be signed."""
    try:
        from eges_tpu.crypto import native
        from eges_tpu.crypto import secp256k1 as host
        from eges_tpu.crypto.scheduler import VerifierScheduler
        from eges_tpu.crypto.verify_host import PipelinedNativeVerifier

        n_windows, rows = 8, 32
        entries = []
        for i in range(n_windows * rows):
            msg = (i + 1).to_bytes(4, "big") * 8
            priv = bytes([(i % 200) + 9]) * 32
            sig = (native.ec_sign(msg, priv) if native.available()
                   else host.ecdsa_sign(msg, priv))
            entries.append((msg, sig))

        sched = VerifierScheduler(PipelinedNativeVerifier(),
                                  window_ms=1.0, max_batch=rows)
        t0 = time.monotonic()
        # all windows submitted up-front: the lane queue stays deep
        # enough that every window after the first has a predecessor
        # still computing when its staging starts
        futs = [sched.submit(h, s) for h, s in entries]
        bad = sum(1 for f in futs if f.result(120) is None)
        sched.close()
        dt = time.monotonic() - t0

        st = sched.stats()
        return {
            "windows": st.get("pipeline_windows", 0),
            "overlapped": st.get("pipeline_overlapped", 0),
            "overlap_ratio": st.get("pipeline_overlap_ratio", 0.0),
            "rows": st["rows"],
            "rows_per_s": round(st["rows"] / max(dt, 1e-9), 1),
            "verify_failures": bad,
            "elapsed_s": round(dt, 2),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _slo_stage() -> dict | None:
    """Telemetry-plane stage: a small calm (fault-free) sim cluster
    runs with the live collector + burn-rate SLO engine attached
    (``harness/collector.py`` / ``harness/slo.py``) and reports the
    engine's compliance ratio and how many alerts fired.  On a healthy
    cluster ANY firing alert is a false positive, so the history series
    ``slo_false_positive_alerts`` is gated at exactly zero and
    ``slo_compliance_ratio`` is gated lower-is-worse by
    ``harness/check_regression.py``.

    Runs in the PARENT like ``_coalesced_stage``: the sim imports no
    JAX and the burn-rate mechanics are backend-independent."""
    try:
        from eges_tpu.sim.cluster import SimCluster
        from harness.collector import ClusterCollector

        t0 = time.monotonic()
        col = ClusterCollector()
        cluster = SimCluster(4, seed=0, txn_per_block=5, txpool=True)
        cluster.enable_telemetry(sink=col.ingest, interval_s=0.5)
        cluster.start()
        cluster.run(600.0,
                    stop_condition=lambda: cluster.min_height() >= 4)
        for sn in cluster.nodes:
            sn.node.stop()
        cluster.flush_telemetry()
        col.finalize()
        return {
            "compliance_ratio": round(col.slo.compliance_ratio, 6),
            "false_positive_alerts": col.slo.fired_total,
            "eval_ticks": col.slo.eval_ticks,
            "envelopes": col.envelopes,
            "heights": cluster.heights(),
            "elapsed_s": round(time.monotonic() - t0, 2),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _anatomy_stage() -> dict | None:
    """Commit-anatomy stage: the same calm sim shape as ``_slo_stage``
    through the live collector, but reporting the critical-path
    assembler's view (``harness/anatomy.py``) — end-to-end commit
    p50/p99 and the per-phase latency shares.  The history series
    ``commit_p99_ms`` is gated lower-is-better by
    ``harness/check_regression.py``, so a commit-latency regression
    fails the round even when steady-state verifies/s holds.

    Runs in the PARENT like ``_slo_stage``: the sim imports no JAX and
    the phase chain is measured on the virtual clock."""
    try:
        from eges_tpu.sim.cluster import SimCluster
        from harness.collector import ClusterCollector

        t0 = time.monotonic()
        col = ClusterCollector()
        cluster = SimCluster(4, seed=0, txn_per_block=5, txpool=True)
        cluster.enable_telemetry(sink=col.ingest, interval_s=0.5)
        cluster.start()
        cluster.run(600.0,
                    stop_condition=lambda: cluster.min_height() >= 4)
        for sn in cluster.nodes:
            sn.node.stop()
        cluster.flush_telemetry()
        col.finalize()
        rep = col.report()["anatomy"]
        if not rep["blocks"] or rep["commit_p99_ms"] is None:
            return None
        dom = rep.get("dominant") or {}
        return {
            "blocks": rep["blocks"],
            "commit_p50_ms": rep["commit_p50_ms"],
            "commit_p99_ms": rep["commit_p99_ms"],
            "phase_shares": {
                k: v["share"] for k, v in rep["phases"].items()},
            "dominant_phase": dom.get("phase"),
            "elapsed_s": round(time.monotonic() - t0, 2),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _rejoin_stage() -> dict | None:
    """Snapshot-rejoin stage: a calm sim with the durable checkpoint
    cadence on; one node crashes, the survivors run ahead, and the
    restart is wall-clock timed.  The restarted node must anchor on the
    newest root-verified checkpoint and replay only the tail, so the
    history series ``rejoin_replayed_blocks`` and ``rejoin_seconds``
    are both gated lower-is-better by ``harness/check_regression.py``
    — a regression back to O(chain) boot replay fails the round.

    Runs in the PARENT like ``_slo_stage``: the sim imports no JAX and
    only the restart itself is measured on the wall clock."""
    try:
        from eges_tpu.sim.cluster import SimCluster
        from eges_tpu.sim.faults import FaultInjector

        t0 = time.monotonic()
        cluster = SimCluster(4, seed=0, txn_per_block=2,
                             checkpoint_every=4)
        inj = FaultInjector(cluster)
        cluster.start()
        cluster.run(900.0,
                    stop_condition=lambda: cluster.min_height() >= 12)
        inj.fire_now("crash", node="node1")
        # survivors extend the chain: the tail the restart must replay
        cluster.run(240.0, stop_condition=lambda: min(
            sn.chain.height() for sn in cluster.live_nodes()) >= 16)
        t_restart = time.monotonic()
        inj.fire_now("restart", node="node1")
        rejoin_s = time.monotonic() - t_restart
        evs = cluster.journals().get("node1", [])
        rst = next((e for e in reversed(evs)
                    if e.get("type") == "statesync_restart"), None)
        for sn in cluster.live_nodes():
            sn.node.stop()
        if rst is None:
            return None
        return {
            "replayed_blocks": int(rst.get("replayed", 0)),
            "snapshot_blk": int(rst.get("snapshot_blk", 0)),
            "height": int(rst.get("blk", 0)),
            "rejoin_s": round(rejoin_s, 6),
            "elapsed_s": round(time.monotonic() - t0, 2),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _ledger_stage() -> dict | None:
    """Ingress-ledger overhead stage: the verifier scheduler's hot path
    (submit -> coalesce -> recover) timed with and without an ambient
    ledger binding (``eges_tpu/utils/ledger.py``).  The bound pass pays
    the full attribution cost — origin capture per pending row, the
    per-window charge fan-out — so the history series
    ``ledger_overhead_pct`` is gated lower-is-better by
    ``harness/check_regression.py``: provenance must stay effectively
    free on the verify path.

    Runs in the PARENT like ``_coalesced_stage``: the native host
    verifier imports no JAX.  Each timed pass uses a FRESH scheduler so
    the sender-recovery cache cannot serve one mode and not the other;
    differences under the noise floor clamp to 0.0 (same usable-
    baseline convention ``check_regression.py`` applies to tiny
    percentages)."""
    try:
        from eges_tpu.core.types import Transaction
        from eges_tpu.crypto.scheduler import VerifierScheduler
        from eges_tpu.crypto.verify_host import NativeBatchVerifier
        from eges_tpu.utils import ledger as ledger_mod

        rows = 128
        priv = bytes([9]) * 32
        entries = []
        for i in range(rows):
            t = Transaction(nonce=i, gas_price=1, gas_limit=21000,
                            to=bytes(20), value=0).signed(priv)
            parts = t.signature_parts()
            if parts is None:
                return None
            sig, sighash = parts
            entries.append((sighash, sig))
        verifier = NativeBatchVerifier()

        def _pass(bound: bool) -> float:
            best = None
            for _ in range(3):
                sched = VerifierScheduler(verifier)
                try:
                    t0 = time.monotonic()
                    if bound:
                        led = ledger_mod.IngressLedger(
                            clock=time.monotonic)
                        with ledger_mod.bind(led, "bench"):
                            sched.recover_signers(entries)
                    else:
                        sched.recover_signers(entries)
                    dt = time.monotonic() - t0
                finally:
                    sched.close()
                best = dt if best is None else min(best, dt)
            return best

        base_s = _pass(False)
        bound_s = _pass(True)
        if not base_s or base_s <= 0:
            return None
        pct = (bound_s - base_s) / base_s * 100.0
        # sub-noise-floor differences (either sign) are measurement
        # jitter, not ledger cost — clamp so the regression gate sees a
        # stable zero until the overhead is real
        if pct < 1.0:
            pct = 0.0
        return {
            "overhead_pct": round(pct, 3),
            "rows": rows,
            "base_ms": round(base_s * 1e3, 3),
            "bound_ms": round(bound_s * 1e3, 3),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _adaptive_stage() -> dict | None:
    """Adaptive-scheduler stage: the same bursty workload driven twice
    — once under the static 2 ms flush deadline, once with the
    closed-loop controller enabled under a ~2 ms p99 objective — and
    the p99 window latency of each pass compared.  The adaptive pass's
    ``sched_p99_window_ms`` plus the per-class queue waits
    (``sched_queue_wait_p99_ms_consensus`` / ``_bulk``) are gated
    lower-is-better by ``harness/check_regression.py``.

    Runs in the PARENT like ``_coalesced_stage``: the scheduler and
    native host verifier import no JAX.  The adaptive p99 is measured
    AFTER the controller's warm-up windows (its first decisions see
    static-era flights), so the series trends the converged policy, not
    the ramp."""
    try:
        from eges_tpu.crypto import native
        from eges_tpu.crypto import secp256k1 as host
        from eges_tpu.crypto.scheduler import (SchedulerConfig,
                                               VerifierScheduler)
        from eges_tpu.crypto.verify_host import NativeBatchVerifier
        from eges_tpu.utils.metrics import percentile

        # burst size × gap chosen to NOT saturate the host verifier
        # (~0.4 ms/row): each burst forms one window and the flush
        # deadline — the policy under test — dominates its latency,
        # instead of queueing behind the previous window's compute
        n_bursts, rows, gap_s, warmup = 32, 8, 0.012, 8
        entries = []
        for i in range(n_bursts * rows):
            msg = (i + 1).to_bytes(4, "big") * 8
            priv = bytes([(i % 200) + 11]) * 32
            sig = (native.ec_sign(msg, priv) if native.available()
                   else host.ecdsa_sign(msg, priv))
            entries.append((msg, sig))

        def _pass(config: SchedulerConfig) -> dict:
            sched = VerifierScheduler(NativeBatchVerifier(),
                                      config=config)
            futs = []
            try:
                for b in range(n_bursts):
                    part = entries[b * rows:(b + 1) * rows]
                    # every 8th burst is consensus-critical (the vote
                    # quorum shape): it must preempt the bulk windows
                    # at placement and show up in the class split
                    pr = "consensus" if b % 8 == 7 else "bulk"
                    futs.extend(sched.submit(h, s, priority=pr)
                                for h, s in part)
                    time.sleep(gap_s)
                bad = sum(1 for f in futs if f.result(120) is None)
                flights = sched.flights()
                st = sched.stats()
            finally:
                sched.close()
            steady = [f["total_ms"] for f in flights[warmup:]] \
                or [f["total_ms"] for f in flights]
            return {"p99_window_ms":
                        round(percentile(sorted(steady), 99.0), 3),
                    "windows": len(flights), "stats": st,
                    "verify_failures": bad}

        static = _pass(SchedulerConfig(window_ms=2.0, max_batch=256))
        adaptive = _pass(SchedulerConfig(
            window_ms=2.0, max_batch=256, adaptive=True,
            slo_p99_ms=2.0, min_window_ms=0.25, min_target_rows=16,
            adapt_recent=8))
        cw = adaptive["stats"].get("class_wait_ms", {})
        return {
            "bursts": n_bursts, "burst_rows": rows,
            "rows": adaptive["stats"]["rows"],
            "p99_window_ms_static": static["p99_window_ms"],
            "p99_window_ms_adaptive": adaptive["p99_window_ms"],
            "adaptive_beats_static": (adaptive["p99_window_ms"]
                                      < static["p99_window_ms"]),
            "final_window_ms": adaptive["stats"]["window_ms"],
            "final_target_rows": adaptive["stats"]["target_rows"],
            "adapt_decisions":
                adaptive["stats"]["adapt_decisions"],
            "queue_wait_p99_ms_consensus":
                cw.get("consensus", {}).get("p99_ms", 0.0),
            "queue_wait_p99_ms_bulk":
                cw.get("bulk", {}).get("p99_ms", 0.0),
            "verify_failures": (static["verify_failures"]
                                + adaptive["verify_failures"]),
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _profile_stage() -> dict | None:
    """Continuous-profiler stage: the ingest->verify pipeline (TxPool
    window flushes feeding a VerifierScheduler) driven under a private
    high-rate sampler, and the phase-attributed sample split reduced to
    ``host_cpu_share_of_verify_pct`` — the share of pipeline-tagged CPU
    spent in host-side pool phases (``pool_admit``/``pool_queue``)
    rather than the verify window.  Gated lower-is-better by
    ``harness/check_regression.py``: host-side ingest overhead creeping
    up relative to verify compute fails the round even when raw
    verifies/s holds.

    Runs in the PARENT like ``_coalesced_stage``: pool + scheduler +
    native host verifier import no JAX.  The sampler is a dedicated
    instance at 997 Hz (prime, well above the ambient default) so the
    stage neither perturbs nor reads the process-wide DEFAULT profiler.
    Because this is a wall-clock sampler, the pool thread's wait on a
    synchronous window flush is attributed to ``pool_admit`` — that IS
    the host-side cost the series trends."""
    try:
        from eges_tpu.core.txpool import TxPool
        from eges_tpu.core.types import Transaction
        from eges_tpu.crypto.scheduler import (SchedulerConfig,
                                               VerifierScheduler)
        from eges_tpu.crypto.verify_host import NativeBatchVerifier
        from eges_tpu.utils.profiler import SamplingProfiler

        batches, rows, passes = 8, 64, 3
        priv = bytes([9]) * 32
        signed = [Transaction(nonce=i, gas_price=1, gas_limit=21000,
                              to=bytes(20), value=0).signed(priv)
                  for i in range(batches * rows)]

        class _WallClock:
            """Minimal pool clock: every ingest below delivers exactly
            ``max_batch`` rows, so the window flush always fires
            synchronously inside ``add_remotes`` and the fallback
            timer is armed but never load-bearing."""

            @staticmethod
            def now() -> float:
                return time.monotonic()

            @staticmethod
            def call_later(delay, fn):
                class _Never:
                    @staticmethod
                    def cancel() -> None:
                        pass
                return _Never()

        prof = SamplingProfiler(hz=997.0)
        prof.start()
        try:
            # fresh pool + scheduler per pass: a warm dedup set would
            # drop every row (no verify leg) and a warm sender cache
            # would serve recoveries without device work — either one
            # skews the phase split toward the pool side
            for _ in range(passes):
                sched = VerifierScheduler(
                    NativeBatchVerifier(),
                    config=SchedulerConfig(window_ms=2.0, max_batch=256))
                pool = TxPool(_WallClock(), verifier=sched,
                              max_batch=rows)
                try:
                    # the production gossip path: multi-txn windows go
                    # columnar (node.columnarize), so the share this
                    # stage trends is the pipeline users actually run
                    from eges_tpu.ingress import (admit_remotes_window,
                                                  columns_of)
                    for b in range(batches):
                        admit_remotes_window(
                            pool,
                            columns_of(signed[b * rows:(b + 1) * rows]))
                finally:
                    sched.close()
                if pool.stats["admitted"] == 0:
                    return None
        finally:
            prof.stop()

        rep = prof.report()
        share = rep["host_cpu_share_of_verify_pct"]
        if share is None:
            return None  # run too fast to sample; skip the line
        by_phase = rep["by_phase"]
        return {
            "host_cpu_share_of_verify_pct": round(share, 2),
            "samples": rep["samples"],
            "pool_samples": sum(
                by_phase.get(p, 0)
                for p in ("pool_admit", "pool_queue")),
            "verify_samples": sum(
                by_phase.get(p, 0)
                for p in ("verify_stage", "verify_collect")),
            "hz": rep["hz"],
            "overhead_pct": rep["overhead_pct"],
            "rows": batches * rows * passes,
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _ingest_stage() -> dict | None:
    """Wire-speed ingest stage: the columnar datagram->pool pipeline
    (``ingress.columnar.decode_window`` + ``TxPool.add_remotes_window``)
    raced against the legacy per-tx baseline (``Transaction.decode`` +
    singleton ``add_remotes``) over the SAME pre-encoded frame stream.
    Emitted as ``ingest_rows_per_s`` (the columnar figure, with the
    per-tx baseline and speedup as context fields) and gated
    higher-is-better by ``harness/check_regression.py``.

    Runs in the PARENT: decoder, pool and the instant verifier below
    import no JAX.  Signature VALIDITY is irrelevant to ingest cost —
    rows carry structurally-valid synthetic (v, r, s) and the verifier
    "recovers" a deterministic per-row address from the sighash, so
    both paths pay identical (near-zero) verify cost and the measured
    delta is purely the Python-level transition overhead the columnar
    rebuild removes.  Both pools flush at ``window`` rows, so verify
    batching is equal too; the baseline loses on per-frame decode,
    per-row locking and per-row bookkeeping — exactly the claim."""
    try:
        import numpy as np

        from eges_tpu.core.txpool import TxPool
        from eges_tpu.core.types import Transaction
        from eges_tpu.ingress import (admit_remotes, admit_remotes_window,
                                      decode_txn_window)

        window, n_windows, passes = 1024, 4, 3
        frames = [
            Transaction(nonce=i, gas_price=1, gas_limit=21000,
                        to=bytes(20), value=0,
                        v=27, r=i + 1, s=1).encode()
            for i in range(window * n_windows)]
        rows = len(frames)

        class _InstantVerifier:
            """Deterministic O(n) vectorized recover: address = first
            20 bytes of the sighash.  Distinct per row (nonces differ),
            identical for both paths (same sighash math)."""

            @staticmethod
            def recover_addresses(sigs, hashes):
                h = np.asarray(hashes, np.uint8)
                return h[:, :20].copy(), np.ones(len(h), bool)

        class _WallClock:
            """Every delivery below fills exactly ``window`` rows, so
            the flush always fires synchronously inside the admission
            call; the fallback timer is armed but never load-bearing."""

            @staticmethod
            def now() -> float:
                return time.monotonic()

            @staticmethod
            def call_later(delay, fn):
                class _Never:
                    @staticmethod
                    def cancel() -> None:
                        pass
                return _Never()

        def _run_columnar() -> tuple[float, int]:
            pool = TxPool(_WallClock(), verifier=_InstantVerifier(),
                          max_batch=window)
            t0 = time.monotonic()
            for w in range(n_windows):
                cols = decode_txn_window(
                    frames[w * window:(w + 1) * window])
                admit_remotes_window(pool, cols)
            return time.monotonic() - t0, pool.stats["admitted"]

        def _run_per_tx() -> tuple[float, int]:
            # max_batch=1: "per-tx" means the WHOLE pipeline runs per
            # transaction — one decode, one flush, one single-row
            # verify dispatch per frame, no batching at any layer.
            # That is the datagram-at-a-time shape the tentpole
            # replaces; a window-batched flush would smuggle half the
            # columnar win into the baseline.
            pool = TxPool(_WallClock(), verifier=_InstantVerifier(),
                          max_batch=1)
            t0 = time.monotonic()
            for frame in frames:
                admit_remotes(pool, [Transaction.decode(frame)])
            return time.monotonic() - t0, pool.stats["admitted"]

        best_col, best_tx = float("inf"), float("inf")
        admitted_col = admitted_tx = 0
        for _ in range(passes):
            dt, admitted_col = _run_columnar()
            best_col = min(best_col, dt)
            dt, admitted_tx = _run_per_tx()
            best_tx = min(best_tx, dt)
        if admitted_col == 0 or admitted_col != admitted_tx:
            return None  # outcome parity broken — the number is a lie
        col_rps = rows / best_col
        tx_rps = rows / best_tx
        return {
            "rows_per_s_columnar": round(col_rps, 1),
            "rows_per_s_per_tx": round(tx_rps, 1),
            "speedup": round(col_rps / tx_rps, 2),
            "rows": rows,
            "window": window,
            "admitted": admitted_col,
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _devstats_stage() -> dict | None:
    """Device-efficiency stage: a fixed burst schedule driven straight
    through the mesh scheduler, reduced to the goodput ratio (useful
    rows / padded device rows) the devstats ledger accounts.  The
    schedule is chosen so the ratio is EXACT under any legal window
    split: eight bursts of 64 rows (power-of-two, any binary split sums
    to the same padded total) plus one 40-row tail that always rounds
    to a 64-row padded footprint — 552 useful rows on 576 padded rows,
    0.9583.  Gated by ``harness/check_regression.py``: a scheduler
    change that starts over-padding (bucket inflation, premature
    flushes, lost coalescing) moves the ratio and fails the round even
    when raw verifies/s holds.

    Runs in the PARENT like ``_profile_stage``: the native mesh
    verifier imports no JAX.  Hedging is disabled (a hedge loser would
    add wall-clock-dependent waste rows) and the adaptive controller is
    off by default, so the recorded windows are a pure function of the
    submit sizes.  ``device_mem_peak_bytes`` rides along: the HBM peak
    watermark from ``sample_memory()``, 0 on hosts without a device
    backend (lower-is-better gate arms the first time a real chip
    reports)."""
    try:
        from eges_tpu.core.types import Transaction
        from eges_tpu.crypto.scheduler import (SchedulerConfig,
                                               VerifierScheduler)
        from eges_tpu.crypto.verify_host import NativeMeshVerifier
        from eges_tpu.utils import devstats

        bursts, rows, tail = 8, 64, 40
        priv = bytes([11]) * 32
        signed = [Transaction(nonce=i, gas_price=1, gas_limit=21000,
                              to=bytes(20), value=0).signed(priv)
                  for i in range(bursts * rows + tail)]
        parts = [t.signature_parts() for t in signed]
        if any(p is None for p in parts):
            return None
        entries = [(h, sig) for sig, h in parts]

        devstats.DEFAULT.rebase()
        sched = VerifierScheduler(
            NativeMeshVerifier(2),
            config=SchedulerConfig(window_ms=5.0, max_batch=rows,
                                   hedge=False))
        try:
            for b in range(bursts):
                rec = sched.recover_signers(
                    entries[b * rows:(b + 1) * rows])
                if any(r is None for r in rec):
                    return None
            rec = sched.recover_signers(entries[bursts * rows:])
            if any(r is None for r in rec):
                return None
        finally:
            sched.close()

        mem = devstats.sample_memory(devstats.DEFAULT)
        snap = devstats.DEFAULT.snap()
        total_rows = total_bucket = windows = peak = 0
        for d in snap["devices"].values():
            total_rows += d["rows"]
            total_bucket += d["bucket_rows"]
            windows += d["windows"]
            m = d.get("mem")
            if m:
                peak = max(peak, int(m.get("peak_bytes", 0)))
        if not total_bucket:
            return None
        return {
            "goodput_ratio": round(total_rows / total_bucket, 4),
            "rows": total_rows,
            "bucket_rows": total_bucket,
            "pad_rows": total_bucket - total_rows,
            "windows": windows,
            "devices": len(snap["devices"]),
            "device_mem_peak_bytes": peak,
            "mem_devices": len(mem) if isinstance(mem, dict) else 0,
        }
    # analysis: allow-swallow(optional bench stage; a failed leg reports null)
    except Exception:
        return None


def _spawn(deadline: float, max_batch: int) -> subprocess.Popen:
    """The one child that owns the device (ambient JAX platform)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         f"{deadline:.3f}", str(max_batch)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)


def mesh_main() -> None:
    """``bench.py mesh``: regenerate the MESH_SCALING.json artifact
    (psum/ring A/B + recorded collective winner + scheduler saturation
    stage with per-device occupancy, per point) and append one
    ``mesh_sharded_rows_per_s`` history line — the series
    ``harness/check_regression.py`` gates independently of the
    single-chip verifies/s metric."""
    rows, devices = 2048, (1, 2, 4, 8)
    out_path = None
    for a in sys.argv[2:]:
        if a.startswith("--rows="):
            rows = int(a[len("--rows="):])
        elif a.startswith("--devices="):
            devices = tuple(int(x)
                            for x in a[len("--devices="):].split(","))
        elif a.startswith("--out="):
            out_path = a[len("--out="):]

    from harness.mesh_scaling import run

    doc = run(rows, devices, out=out_path)
    # the gated aggregate: the dispatch front's rows/s at the widest
    # device count measured (the scheduler fans one window across every
    # lane, so this IS the mesh-wide number)
    scored = [p for p in doc["points"] if p.get("sched")]
    line = {"metric": "mesh_sharded_rows_per_s", "unit": "rows/s",
            "rows": rows, "device": doc["backend"]}
    if scored:
        top = max(scored, key=lambda p: p["devices"])
        line.update({
            "value": top["sched"]["rows_per_s"],
            "devices": top["devices"],
            "collective": top.get("collective"),
            "window_splits": top["sched"]["window_splits"],
            "per_device_occupancy": [
                d["occupancy"] for d in top["sched"]["per_device"]],
            "points": [{
                "devices": p["devices"],
                "collective": p.get("collective"),
                "sched_rows_per_s": p["sched"]["rows_per_s"],
                "psum_rows_per_s": p["psum"]["rows_per_s"],
                "ring_rows_per_s": p["ring"]["rows_per_s"],
            } for p in scored],
        })
    else:
        line.update({"value": 0.0,
                     "error": "no device count produced a sched stage"})
    line.update(_provenance())
    print(json.dumps(line), flush=True)
    _append_history(line)


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    max_batch = int(args[0]) if args else 16384
    budget = float(os.environ.get("BENCH_BUDGET_S", DEFAULT_BUDGET_S))
    t_start = time.monotonic()
    deadline = t_start + budget

    measured = _cpu_baseline()
    denom = max(measured or 0.0, REF_CLASS_CPU_PER_S)
    # the two sub-second host scheduler stages ride the verifier line
    coalesced = _coalesced_stage()
    pipeline = _pipeline_stage()

    best: dict = {}      # best stage result of the device child
    # {batch(str): {p50_ms, p99_ms}} — every stage's tails, not just
    # the winning batch's
    lat_by_batch: dict = {}
    refused: dict = {}   # the child's NODEVICE report, if it made one

    def compose() -> dict | None:
        if not best:
            return None
        out = {
            "metric": "secp256k1_ecrecover_verifies_per_sec_per_chip",
            "value": round(best["per_sec"], 1),
            "unit": "verifies/s",
            "vs_baseline": round(best["per_sec"] / denom, 3),
            "batch": best["batch"],
            "device": best["device"],
            "compile_s": best.get("compile_s"),
            "cpu_baseline_measured_per_s":
                round(measured, 1) if measured else None,
            "cpu_baseline_ref_class_per_s": REF_CLASS_CPU_PER_S,
            "elapsed_s": round(time.monotonic() - t_start, 1),
        }
        out.update(_provenance())
        if "cold_start_s" in best:
            out["cold_start_seconds"] = best["cold_start_s"]
        if "aot" in best:
            out["aot"] = best["aot"]
        if coalesced:
            out["coalesced"] = dict(coalesced)
        if pipeline:
            out["pipeline"] = dict(pipeline)
        if lat_by_batch:
            out["latency_ms_by_batch"] = dict(sorted(
                lat_by_batch.items(), key=lambda kv: int(kv[0])))
        at_1024 = lat_by_batch.get("1024", {})
        for k, name in (("p50_ms", "p50_latency_ms_at_1024"),
                        ("p99_ms", "p99_latency_ms_at_1024")):
            if k in at_1024:
                out[name] = at_1024[k]
            elif k in best:
                out[name] = best[k]
        return out

    def handle(line: str) -> None:
        if line.startswith("NODEVICE "):
            refused.update(json.loads(line[len("NODEVICE "):]))
            return
        if not line.startswith("RESULT "):
            return
        try:
            res = json.loads(line[len("RESULT "):])
        except ValueError:
            return
        if "p50_ms" in res:
            lat_by_batch[str(res["batch"])] = {
                k: res[k] for k in ("p50_ms", "p99_ms") if k in res}
        if not best or res["per_sec"] >= best["per_sec"]:
            best.update(res)   # earlier p50/p99 carry forward
        else:
            for k in ("p50_ms", "p99_ms"):
                if k in res:
                    best[k] = res[k]
        print(json.dumps(compose()), flush=True)

    # ONE child owns the device for the whole run; it goes first, so a
    # machine without a TPU fails before any host stage spends a second
    proc = _spawn(deadline, max_batch)
    try:
        for raw in iter(proc.stdout.readline, b""):
            handle(raw.decode(errors="replace").rstrip("\n"))
            if time.monotonic() > deadline + 5:
                break
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    if not best:
        # hard failure: nothing is measured on another backend instead
        fail = {
            "metric": "secp256k1_ecrecover_verifies_per_sec_per_chip",
            "error": ("no TPU found: JAX gave platform %r (%s)" % (
                refused.get("platform"), refused.get("device"))
                if refused else
                "the device child produced no result within budget "
                "(rc=%s)" % proc.returncode),
            "device": refused.get("device"),
        }
        fail.update(_provenance())
        print(json.dumps(fail), flush=True)
        sys.exit(2)
    final = compose()
    _append_history(final)
    if "cold_start_seconds" in final:
        # independently gated series (check_regression.py treats
        # cold_start_seconds as lower-is-better): a broken AOT
        # store shows up as a cold-start RISE even when
        # steady-state verifies/s stays healthy
        line = {"metric": "cold_start_seconds",
                "value": final["cold_start_seconds"], "unit": "s",
                "device": final["device"], "aot": final.get("aot")}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)

    # host-only stages: native C++ verifier on the host clock.  Every
    # line says so ("device": "host") — none is a device metric.
    slo = _slo_stage()
    anatomy = _anatomy_stage()
    ledger_bench = _ledger_stage()
    adaptive_bench = _adaptive_stage()
    profile_bench = _profile_stage()
    ingest_bench = _ingest_stage()
    devstats_bench = _devstats_stage()
    rejoin_bench = _rejoin_stage()
    if pipeline and pipeline.get("windows"):
        # parent-side stage: the overlap mechanics, host-measured
        line = {"metric": "pipeline_overlap_ratio",
                "value": pipeline["overlap_ratio"], "unit": "ratio",
                "windows": pipeline["windows"],
                "overlapped": pipeline["overlapped"],
                "rows": pipeline["rows"],
                "device": "host"}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)
    if slo:
        # parent-side stage: a calm sim through the live SLO engine —
        # slo_false_positive_alerts is zero-tolerance-gated, the
        # compliance ratio trends lower-is-worse
        for metric, value, unit in (
                ("slo_compliance_ratio",
                 slo["compliance_ratio"], "ratio"),
                ("slo_false_positive_alerts",
                 slo["false_positive_alerts"], "count")):
            line = {"metric": metric, "value": value, "unit": unit,
                    "eval_ticks": slo["eval_ticks"],
                    "envelopes": slo["envelopes"],
                "device": "host"}
            line.update(_provenance())
            print(json.dumps(line), flush=True)
            _append_history(line)
    if anatomy:
        # parent-side stage: per-block critical-path attribution over a
        # calm sim — gated lower-is-better so a commit-latency
        # regression fails the round even when verifies/s holds
        line = {"metric": "commit_p99_ms",
                "value": anatomy["commit_p99_ms"], "unit": "ms",
                "commit_p50_ms": anatomy["commit_p50_ms"],
                "blocks": anatomy["blocks"],
                "phase_shares": anatomy["phase_shares"],
                "dominant_phase": anatomy["dominant_phase"],
                "device": "host"}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)
    if rejoin_bench:
        # parent-side stage: crash-and-rejoin over the virtual cluster
        # with the checkpoint cadence on — both series lower-is-better,
        # so a restart regressing to O(chain) replay (or a slow
        # snapshot load) fails the round even when verifies/s holds
        for metric, value, unit in (
                ("rejoin_replayed_blocks",
                 rejoin_bench["replayed_blocks"], "blocks"),
                ("rejoin_seconds", rejoin_bench["rejoin_s"], "s")):
            line = {"metric": metric, "value": value, "unit": unit,
                    "snapshot_blk": rejoin_bench["snapshot_blk"],
                    "height": rejoin_bench["height"],
                "device": "host"}
            line.update(_provenance())
            print(json.dumps(line), flush=True)
            _append_history(line)
    if ledger_bench:
        # parent-side stage: scheduler hot path with vs without the
        # ingress provenance binding — gated lower-is-better so
        # attribution cost creeping onto the verify path fails the round
        line = {"metric": "ledger_overhead_pct",
                "value": ledger_bench["overhead_pct"], "unit": "pct",
                "rows": ledger_bench["rows"],
                "base_ms": ledger_bench["base_ms"],
                "bound_ms": ledger_bench["bound_ms"],
                "device": "host"}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)
    if adaptive_bench:
        # parent-side stage: the closed-loop controller vs the static
        # deadline over one bursty workload — all three series gated
        # lower-is-better so a controller that stops shrinking under
        # burn (or a priority queue that stops preempting) fails the
        # round even when raw verifies/s holds
        for metric, value in (
                ("sched_p99_window_ms",
                 adaptive_bench["p99_window_ms_adaptive"]),
                ("sched_queue_wait_p99_ms_consensus",
                 adaptive_bench["queue_wait_p99_ms_consensus"]),
                ("sched_queue_wait_p99_ms_bulk",
                 adaptive_bench["queue_wait_p99_ms_bulk"])):
            line = {"metric": metric, "value": value, "unit": "ms",
                    "static_p99_window_ms":
                        adaptive_bench["p99_window_ms_static"],
                    "adaptive_beats_static":
                        adaptive_bench["adaptive_beats_static"],
                    "final_window_ms":
                        adaptive_bench["final_window_ms"],
                    "final_target_rows":
                        adaptive_bench["final_target_rows"],
                "device": "host"}
            line.update(_provenance())
            print(json.dumps(line), flush=True)
            _append_history(line)
    if profile_bench:
        # parent-side stage: the ingest->verify pipeline under the
        # continuous sampler — the host-side pool share of
        # pipeline-attributed CPU is gated lower-is-better, so ingest
        # overhead creeping up relative to verify compute fails the
        # round even when raw verifies/s holds
        line = {"metric": "host_cpu_share_of_verify_pct",
                "value": profile_bench["host_cpu_share_of_verify_pct"],
                "unit": "pct",
                "samples": profile_bench["samples"],
                "pool_samples": profile_bench["pool_samples"],
                "verify_samples": profile_bench["verify_samples"],
                "rows": profile_bench["rows"],
                "profile_hz": profile_bench["hz"],
                "sampler_overhead_pct": profile_bench["overhead_pct"],
                "device": "host"}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)
    if ingest_bench:
        # parent-side stage: the columnar datagram->pool pipeline vs
        # the per-tx baseline over the same frame stream — gated
        # higher-is-better, so a change that re-introduces per-row
        # Python transitions into the ingest path fails the round
        line = {"metric": "ingest_rows_per_s",
                "value": ingest_bench["rows_per_s_columnar"],
                "unit": "rows/s",
                "per_tx_rows_per_s": ingest_bench["rows_per_s_per_tx"],
                "speedup_vs_per_tx": ingest_bench["speedup"],
                "rows": ingest_bench["rows"],
                "window": ingest_bench["window"],
                "admitted": ingest_bench["admitted"],
                "device": "host"}
        line.update(_provenance())
        print(json.dumps(line), flush=True)
        _append_history(line)
    if devstats_bench:
        # parent-side stage: the fixed burst schedule through the mesh
        # scheduler — goodput_ratio gated on any drop (over-padding
        # regression) and device_mem_peak_bytes gated lower-is-better
        # (HBM watermark creep on real backends; 0 on host-only runs)
        for metric, unit in (("goodput_ratio", "ratio"),
                             ("device_mem_peak_bytes", "bytes")):
            line = {"metric": metric, "value": devstats_bench[metric],
                    "unit": unit,
                    "rows": devstats_bench["rows"],
                    "bucket_rows": devstats_bench["bucket_rows"],
                    "pad_rows": devstats_bench["pad_rows"],
                    "windows": devstats_bench["windows"],
                    "devices": devstats_bench["devices"],
                    "mem_devices": devstats_bench["mem_devices"],
                "device": "host"}
            line.update(_provenance())
            print(json.dumps(line), flush=True)
            _append_history(line)

    # trend the static-analysis counts alongside the perf series: one
    # findings_by_rule/unsuppressed_by_rule line per bench round, the
    # history harness/check_regression.py --analysis gates on — any
    # rise in a rule fails, and rules absent from the previous line
    # count as zero, so newly added rules — the device-hygiene pass,
    # then the architecture pass (layer-violation, import-cycle,
    # private-reach, perimeter-breach) — gate from their first
    # recorded line onward
    analysis_history = os.environ.get(
        "ANALYSIS_HISTORY", os.path.join(_REPO, "harness",
                                         "analysis_history.jsonl"))
    try:
        subprocess.run(
            [sys.executable, "-m", "harness.analysis",
             "--summary", analysis_history],
            cwd=_REPO, capture_output=True, timeout=120)
    # analysis: allow-swallow(trend bookkeeping must not fail the bench)
    except Exception:
        pass


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(float(sys.argv[2]), int(sys.argv[3]))
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "mesh":
        mesh_main()
        sys.exit(0)
    main()
