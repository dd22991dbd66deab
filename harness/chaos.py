"""Chaos scenario runner: scripted fault storms with safety/liveness checks.

The executable form of the reference's manual robustness drill —
``start.py`` a cluster, ``kill.py`` a node mid-run, ``re-start.py`` it,
then grep the logs to see whether consensus survived — rebuilt on the
deterministic simulator: every scenario is a :class:`FaultPlan`
(``eges_tpu/sim/faults.py``) armed against a virtual-time
:class:`SimCluster`, and every run checks the two properties that
matter:

* **safety** — no two live nodes ever commit conflicting blocks: for
  every height up to the shortest live chain, all live nodes hold the
  SAME block hash (and after heal the heights themselves converge);
* **liveness** — commit lag recovers: within a bounded number of
  *virtual* seconds after the last fault heals, every live node commits
  a fixed number of NEW blocks.

Runs are bit-deterministic: same scenario + same seed dumps a
byte-identical merged journal (``--check-determinism`` runs twice and
compares).  The only real-time field a journal row carries
(``waited_ms`` on ``verifier_flush``) is stripped from the canonical
dump.

Usage::

    python harness/chaos.py --list
    python harness/chaos.py --scenario combo --seed 0
    python harness/chaos.py --all --fast
    python harness/chaos.py --scenario combo --check-determinism
    python harness/chaos.py --scenario leader_kill_storm --dump /tmp/chaos
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from eges_tpu.sim.cluster import SimCluster
from eges_tpu.sim.faults import FaultInjector, FaultPlan
from harness import observatory

# journal attrs measured in real (wall-clock) time, per event type —
# stripped from the canonical dump so determinism is judged on protocol
# content only (everything else is virtual-time stamped)
VOLATILE_KEYS = {
    "verifier_flush": ("waited_ms",),      # real queue wait
    "block_committed": ("dt",),            # real insert duration
    # real queue wait + thread-race-dependent lane choice: which device
    # serves a window depends on real dispatch timing, so the whole
    # event is scheduling metadata, not protocol content ("bit-identical
    # modulo device index")
    "verifier_mesh_dispatch": ("queue_wait_ms", "device", "occupancy",
                               "rows", "diverted"),
    # real load/compile durations of the AOT artifact prewarm — how
    # long the warm took is wall-clock, WHAT was warmed is protocol
    "verifier_aot_load": ("load_s", "compile_s", "cold_start_s"),
    # the sampled registry payload mixes virtual-time counters with
    # wall-clock histograms (timer means, percentile points) — the
    # sample's EXISTENCE and step number are protocol, its values are
    # measurements
    "telemetry_sample": ("metrics",),
    # the verify_window stage mirrors verifier_flush plus wall-clock
    # interiors and a thread-race-dependent lane pick; the pool/seal
    # stages are fully virtual-time and keep every attribute (they never
    # carry these keys).  "trace"/"traces" are os.urandom-derived span
    # linkage — observability-only, never protocol.
    "commit_anatomy": ("wait_ms", "stage_ms", "compute_ms", "lane",
                       "trace", "traces"),
    # the dominant-phase hint on a firing alert can name a lane (racy
    # under mesh dispatch) and a share derived from wall-clock-adjacent
    # aggregates — the FIRING itself is the protocol content
    "slo_firing": ("phase", "phase_share", "lane"),
    # the ingress ledger keeps every wall-clock account (per-origin
    # device/host ms) under this ONE top-level key by design; the
    # decayed counts and deltas are virtual-time deterministic
    "ingress_ledger": ("costs",),
}


# -- checks ---------------------------------------------------------------

def check_safety(cluster) -> tuple[bool, int]:
    """No two live nodes hold conflicting blocks: every height up to the
    shortest live chain maps to ONE hash across all live nodes.
    Returns (ok, heights_checked)."""
    live = cluster.live_nodes()
    if not live:
        return True, 0
    hmin = min(sn.chain.height() for sn in live)
    for h in range(1, hmin + 1):
        hashes = {sn.chain.store.get_hash_by_number(h) for sn in live}
        # fast-synced nodes legitimately lack pre-pivot ancestors: a
        # missing block is not a conflict, only two DIFFERENT hashes are
        hashes.discard(None)
        if len(hashes) > 1:
            return False, h
    return True, hmin


def canonical_dump(by_node: dict[str, list[dict]]) -> bytes:
    """Deterministic byte serialization of a merged journal collection:
    sorted node order, sorted JSON keys, volatile (wall-clock) fields
    stripped.  Two same-seed runs of one scenario must produce identical
    bytes — the acceptance criterion for the whole fault layer."""
    lines = []
    for name in sorted(by_node):
        for ev in by_node[name]:
            drop = VOLATILE_KEYS.get(ev.get("type"), ())
            ev = {k: v for k, v in ev.items() if k not in drop}
            lines.append(json.dumps(ev, sort_keys=True))
    return ("\n".join(lines) + "\n").encode()


# -- scenario skeleton ----------------------------------------------------

def _finish(name: str, seed: int, cluster, extra_blocks: int,
            bound_s: float, grace_s: float = 120.0,
            checks: dict | None = None) -> dict:
    """Shared recovery phase: called once the last fault has healed.
    Measures liveness (``extra_blocks`` new commits on every live node
    within ``bound_s`` virtual seconds), then convergence (equal live
    heights), then safety over the common prefix."""
    live = cluster.live_nodes()
    base = min(sn.chain.height() for sn in live)
    target = base + extra_blocks
    t0 = cluster.clock.now()

    def _reached() -> bool:
        return min(sn.chain.height()
                   for sn in cluster.live_nodes()) >= target

    cluster.run(bound_s, stop_condition=_reached)
    liveness = _reached()
    recovered_in = round(cluster.clock.now() - t0, 6)

    def _equal() -> bool:
        return len({sn.chain.height()
                    for sn in cluster.live_nodes()}) == 1

    cluster.run(grace_s, stop_condition=_equal)
    converged = _equal()
    safety, checked = check_safety(cluster)

    checks = dict(checks or {})
    ok = bool(safety and liveness and converged
              and all(checks.values()))
    for sn in cluster.live_nodes():
        sn.node.stop()
    return {
        "scenario": name, "seed": seed, "ok": ok,
        "safety": safety, "liveness": liveness, "converged": converged,
        "heights": cluster.heights(), "heights_checked": checked,
        "recovered_in_s": recovered_in, "bound_s": bound_s,
        "extra_blocks": extra_blocks, "net": cluster.net_stats(),
        "checks": checks,
        "journals": cluster.journals(),
    }


def _names(cluster) -> list[str]:
    return [sn.name for sn in cluster.nodes]


def _enable_slo(cluster, interval_s: float = 5.0):
    """Wire the live telemetry plane into a scenario: the cluster pushes
    journal-tail envelopes on the virtual clock into a
    :class:`~harness.collector.ClusterCollector`, whose burn-rate SLO
    engine journals alert transitions.  The engine's journal is attached
    as the cluster's ``slo`` stream so alerts land in the merged dump
    (and therefore in the ``--check-determinism`` byte comparison)."""
    from harness.collector import ClusterCollector
    col = ClusterCollector()
    cluster.enable_telemetry(sink=col.ingest, interval_s=interval_s)
    cluster.slo_journal = col.slo.journal
    return col


def _slo_checks(res: dict, cluster, col, checks_fn) -> dict:
    """Shared tail for SLO-enabled scenarios: flush the last telemetry
    tick, finalize the collector, re-collect journals (so the flush's
    sample + any final transitions are in the dump), and merge the
    scenario's alert checks.  ``checks_fn`` is a thunk so the checks
    read collector state AFTER the flush."""
    cluster.flush_telemetry()
    col.finalize()
    checks = checks_fn()
    res["journals"] = cluster.journals()
    res["slo"] = {"alert_states": col.slo.alert_states(),
                  "alerts_fired": col.slo.fired_total,
                  "compliance_ratio": round(col.slo.compliance_ratio, 6)}
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


# -- scenarios ------------------------------------------------------------

def _scn_leader_kill_storm(seed: int, fast: bool) -> dict:
    """Kill the elected leader the moment it wins, repeatedly; each
    victim restarts from its surviving chain (the kill.py/re-start.py
    drill aimed at the worst possible instant)."""
    kills = 1 if fast else 3
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)
    inj.apply(FaultPlan().kill_leader(1.0, times=kills,
                                      restart_after=15.0))
    cluster.start()

    def _crashes() -> int:
        return sum(1 for f in inj.fired if f["kind"] == "crash")

    cluster.run(600.0, stop_condition=lambda: (
        _crashes() >= kills
        and not any(sn.crashed for sn in cluster.nodes)))
    healed = (_crashes() >= kills
              and not any(sn.crashed for sn in cluster.nodes))
    return _finish("leader_kill_storm", seed, cluster,
                   extra_blocks=3 if fast else 4, bound_s=300.0,
                   checks={"all_kills_fired_and_recovered": healed,
                           "leader_kills": _crashes() == kills})


def _scn_rolling_restarts(seed: int, fast: bool) -> dict:
    """Crash and restart every node in turn — each restart replays the
    surviving chain through the GeecNode constructor and must catch up
    on blocks it missed while down."""
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)
    plan = FaultPlan()
    idxs = range(1, 3) if fast else range(4)
    step = 20.0 if fast else 30.0
    last = 0.0
    for j, i in enumerate(idxs):
        plan.crash(5.0 + step * j, f"node{i}")
        plan.restart(12.0 + step * j, f"node{i}")
        last = 12.0 + step * j
    inj.apply(plan)
    cluster.start()
    cluster.run(last + 2.0 - cluster.clock.now())
    cluster.run(60.0, stop_condition=lambda: not any(
        sn.crashed for sn in cluster.nodes))
    res = _finish("rolling_restarts", seed, cluster,
                  extra_blocks=3 if fast else 4, bound_s=240.0,
                  checks={"all_restarted": not any(
                      sn.crashed for sn in cluster.nodes)})
    # rejoin-to-first-verified-window per restarted node: virtual time
    # from the fault_restart to that node's next committed block, which
    # must be bounded by the AOT artifact load (the cold_start_s its
    # rebuilt verifier journaled), not by a recompile stall.  The 120 s
    # slack is the consensus catch-up allowance (block cadence +
    # elections), identical with or without an artifact store.
    journals = res["journals"]
    restarts = [(ev.get("target"), ev["ts"])
                for ev in journals.get("faults", [])
                if ev.get("type") == "fault_restart"]
    rejoin = {}
    bounded = True
    for target, t_restart in restarts:
        evs = journals.get(target, [])
        commit = next((ev["ts"] for ev in evs
                       if ev.get("type") == "block_committed"
                       and ev["ts"] >= t_restart), None)
        load_s = sum(ev.get("cold_start_s", 0.0) for ev in evs
                     if ev.get("type") == "verifier_aot_load"
                     and ev["ts"] >= t_restart)
        dt = None if commit is None else round(commit - t_restart, 6)
        rejoin[target] = {"rejoin_s": dt,
                          "aot_load_s": round(load_s, 3)}
        if dt is None or dt > 120.0 + load_s:
            bounded = False
    res["rejoin"] = rejoin
    res["checks"]["rejoin_bounded_by_artifact_load"] = bounded
    res["ok"] = bool(res["ok"] and bounded)
    return res


def _scn_loss_jitter(seed: int, fast: bool) -> dict:
    """20% message loss plus latency jitter on both planes — the retry
    ladders and version-bump recovery must keep the chain advancing,
    and fully recover once the link cleans up."""
    heal_t = 30.0 if fast else 60.0
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)
    inj.apply(FaultPlan()
              .set_net(2.0, drop_rate=0.2, jitter_s=0.05)
              .set_net(heal_t, drop_rate=0.0, jitter_s=0.002))
    cluster.start()
    cluster.run(heal_t + 1.0)
    return _finish("loss_jitter", seed, cluster,
                   extra_blocks=3 if fast else 4, bound_s=240.0,
                   checks={"saw_drops": cluster.net.stats["dropped"] > 0})


def _scn_asym_partition_ttl(seed: int, fast: bool) -> dict:
    """Asymmetric partition: node3's OUTBOUND links are cut while
    inbound still flows, so it keeps ingesting blocks but its votes and
    TTL renewals never land.  The membership economy must expire it on
    the live side (~5 decay intervals), and after the heal it must
    detect its own expiry and re-register cleanly."""
    cluster = SimCluster(4, seed=seed, failure_test=True)
    inj = FaultInjector(cluster)
    plan = FaultPlan()
    for dst in ("node0", "node1", "node2"):
        plan.block_link(2.0, "node3", dst)
    inj.apply(plan)
    cluster.start()
    victim = cluster.nodes[3]
    others = [sn for sn in cluster.nodes[:3]]
    # run until every live peer has expired node3 from its membership
    # (TTL floor: initial_ttl=50 decaying by 10 every 10 blocks)
    cluster.run(4000.0, stop_condition=lambda: all(
        victim.addr not in sn.node.membership for sn in others))
    expired = all(victim.addr not in sn.node.membership for sn in others)
    # heal: clear every link rule (journaled like any scripted action)
    inj.fire_now("heal_link", src=None, dst=None)
    # rejoin: node3 catches up, notices its own expiry, re-registers
    cluster.run(600.0, stop_condition=lambda: (
        victim.node.registered
        and all(victim.addr in sn.node.membership
                for sn in cluster.nodes)))
    rejoined = (victim.node.registered
                and all(victim.addr in sn.node.membership
                        for sn in cluster.nodes))
    return _finish("asym_partition_ttl", seed, cluster,
                   extra_blocks=4, bound_s=300.0,
                   checks={"ttl_expired_under_partition": expired,
                           "clean_reregistration": rejoined})


def _scn_corruption_flood(seed: int, fast: bool) -> dict:
    """25% of datagrams truncated or bit-flipped: every mangled message
    must be rejected by decode/auth — a node crash surfaces as an
    exception out of the event loop and fails the run."""
    heal_t = 30.0 if fast else 60.0
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)
    inj.apply(FaultPlan()
              .set_net(2.0, corrupt_rate=0.25)
              .set_net(heal_t, corrupt_rate=0.0))
    cluster.start()
    cluster.run(heal_t + 1.0)
    return _finish("corruption_flood", seed, cluster,
                   extra_blocks=3 if fast else 4, bound_s=240.0,
                   checks={"saw_corruption":
                           cluster.net.stats["corrupted"] > 0})


def _scn_verifier_blackout(seed: int, fast: bool) -> dict:
    """The accelerator dies permanently: every device dispatch raises.
    The scheduler must fail over each window to the host recover path,
    trip the circuit breaker (with half-open re-probes that keep
    failing), and consensus must keep committing signed blocks."""
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    # long window => flushes are kick-driven only (deterministic rows);
    # the breaker cooldown runs on the VIRTUAL clock
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0,
                              breaker_cooldown_s=30.0)
    cluster = SimCluster(4, seed=seed, verifier=sched, signed=True)
    sched.breaker_clock = cluster.clock.now

    def _dead_device(rows: int) -> None:
        raise RuntimeError("device lost (injected blackout)")

    sched.failure_hook = _dead_device
    inj = FaultInjector(cluster)     # journals the (empty) fault plan
    col = _enable_slo(cluster)
    cluster.start()
    blocks = 4 if fast else 6
    cluster.run(600.0,
                stop_condition=lambda: cluster.min_height() >= blocks)
    # snapshot BEFORE the heal: the blackout-phase invariants
    # (breaker open throughout, every window diverted) are judged here
    stats = sched.stats()
    # heal the device: the next half-open probe succeeds, closes the
    # breaker, and the breaker_open SLO must burn down and resolve
    sched.failure_hook = None

    def _slo_cycled() -> bool:
        evs = col.slo.journal.events()
        return (any(e["type"] == "slo_firing"
                    and e["objective"] == "breaker_open" for e in evs)
                and any(e["type"] == "slo_resolved"
                        and e["objective"] == "breaker_open"
                        for e in evs))

    cluster.run(600.0, stop_condition=_slo_cycled)
    res = _finish("verifier_blackout", seed, cluster,
                  extra_blocks=2, bound_s=240.0,
                  checks={"breaker_tripped": stats["breaker_trips"] >= 1,
                          "device_never_recovered":
                              stats["breaker"] == "open",
                          "windows_host_diverted":
                              stats["breaker_diverted"] > 0
                              or stats["host_diverted"] > 0})
    res = _slo_checks(res, cluster, col, lambda: {
        "slo_breaker_fired": any(
            e["type"] == "slo_firing" and e["objective"] == "breaker_open"
            for e in col.slo.alerts()),
        "slo_breaker_resolved": any(
            e["type"] == "slo_resolved"
            and e["objective"] == "breaker_open"
            for e in col.slo.alerts())})
    sched.close()
    res["verifier"] = sched.stats()
    return res


def _scn_mesh_device_blackout(seed: int, fast: bool) -> dict:
    """One device of a 4-lane verifier mesh dies: every dispatch on that
    lane raises.  Only THAT lane's windows may divert — its per-lane
    breaker trips and stays open (cooldown beyond the run) — while every
    other lane keeps the device path, and consensus keeps committing
    signed blocks throughout."""
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeMeshVerifier

    mesh = NativeMeshVerifier(4)
    # long window => flushes are kick-driven only (deterministic rows);
    # a huge cooldown pins the dead lane's breaker open for the run
    sched = VerifierScheduler(mesh, window_ms=10_000.0,
                              breaker_cooldown_s=1e9)
    cluster = SimCluster(4, seed=seed, verifier=sched, signed=True)
    sched.breaker_clock = cluster.clock.now
    victim = 2

    def _dead_lane(rows: int) -> None:
        raise RuntimeError("device 2 lost (injected mesh blackout)")

    mesh.device_targets()[victim].failure_hook = _dead_lane
    inj = FaultInjector(cluster)     # journals the (empty) fault plan
    cluster.start()
    blocks = 4 if fast else 6
    cluster.run(600.0,
                stop_condition=lambda: cluster.min_height() >= blocks)
    stats = sched.stats()
    devs = stats["devices"]
    dead = devs[victim]
    healthy = [d for d in devs if d["device"] != victim]
    # the window flight recorder must attribute the straggling to the
    # victim lane: its breaker-diverted windows mark it (the thw_flight
    # waterfall renders the same attribution)
    flights = sched.flights()
    stragglers = observatory.flight_straggler_lanes(flights)
    res = _finish("mesh_device_blackout", seed, cluster,
                  extra_blocks=2, bound_s=240.0,
                  checks={
                      "dead_lane_breaker_open":
                          dead["breaker"] == "open",
                      "dead_lane_diverted":
                          dead["straggler_diverts"] > 0
                          or dead["breaker_diverted"] > 0,
                      "healthy_lanes_untouched": all(
                          d["device_errors"] == 0
                          and d["breaker"] == "closed" for d in healthy),
                      "healthy_lanes_served": any(
                          d["rows"] > 0 for d in healthy),
                      "flight_straggler_attributed":
                          victim in stragglers,
                  })
    sched.close()
    res["verifier"] = sched.stats()
    res["flight_stragglers"] = stragglers
    return res


def _scn_straggler_hedge(seed: int, fast: bool) -> dict:
    """One lane of a 2-lane mesh pinned slow (its device dispatch
    blocks until healed): the hedge monitor must re-place the stuck
    window on the healthy sibling, p99 window latency must recover to
    within 2x the healthy baseline (floored at the hedge detection
    allowance), the ledger must never double-bill a hedged window, and
    both phases must stay byte-deterministic."""
    import threading

    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeMeshVerifier
    from eges_tpu.utils.metrics import percentile

    blocks = 3 if fast else 5

    def _phase(pin: bool):
        mesh = NativeMeshVerifier(2)
        # kick-driven flushes (deterministic rows); a huge cooldown
        # keeps both breakers closed so hedging (not the breaker) is
        # the rescue
        sched = VerifierScheduler(
            mesh, window_ms=10_000.0, breaker_cooldown_s=1e9,
            hedge=True, hedge_min_windows=4, hedge_floor_ms=25.0,
            hedge_poll_ms=2.0)
        cluster = SimCluster(4, seed=seed, verifier=sched, signed=True)
        sched.breaker_clock = cluster.clock.now
        col = _enable_slo(cluster)
        release = threading.Event()
        if pin:
            victim = mesh.device_targets()[0]
            orig = victim.recover_addresses

            def _stuck(sigs, hashes):
                release.wait()
                return orig(sigs, hashes)

            victim.recover_addresses = _stuck
        FaultInjector(cluster)       # journals the (empty) fault plan
        cluster.start()
        cluster.run(600.0,
                    stop_condition=lambda: cluster.min_height() >= blocks)
        # heal BEFORE the recovery phase: the pinned lane wakes up, the
        # losing (wasted) duplicate completes, and close() can join the
        # lane thread instead of deadlocking on the stuck dispatch
        release.set()
        return cluster, col, sched

    # phase A — healthy baseline
    cluster_a, col_a, sched_a = _phase(pin=False)
    for sn in cluster_a.live_nodes():
        sn.node.stop()
    cluster_a.flush_telemetry()
    col_a.finalize()
    sched_a.close()
    journals_a = cluster_a.journals()
    totals_a = sorted(f["total_ms"] for f in sched_a.flights())
    p99_a = percentile(totals_a, 99.0)

    # phase B — lane 0 pinned slow; hedging is the only way out
    cluster_b, col_b, sched_b = _phase(pin=True)
    res = _finish("straggler_hedge", seed, cluster_b,
                  extra_blocks=2, bound_s=240.0, checks={})
    sched_b.close()
    stats = sched_b.stats()
    totals_b = sorted(f["total_ms"] for f in sched_b.flights())
    p99_b = percentile(totals_b, 99.0)
    # the p99 bound carries a hedge-detection allowance: the monitor
    # cannot act before the straggler threshold (hedge_floor_ms) plus a
    # poll tick, so a sub-millisecond healthy baseline does not demand
    # a sub-millisecond rescue
    bound_ms = 2.0 * max(p99_a, sched_b.hedge_floor_ms)
    # exactly-once billing: only the winning dispatch runs the window's
    # bookkeeping (the loser never touches the pending-origin map), so
    # rows billed across every node ledger can never exceed the rows
    # the scheduler recorded
    billed = sum(
        o.get("rows", 0.0)
        for sn in cluster_b.nodes
        for o in sn.node.ledger.snapshot().get("origins", []))
    res = _slo_checks(res, cluster_b, col_b, lambda: {
        "hedge_fired": stats["hedges"] >= 1,
        "hedge_won": stats["hedge_wins"] >= 1,
        "hedges_accounted": stats["hedges"] == (
            stats["hedge_cancelled"] + stats["hedge_wasted"]),
        "p99_recovered": p99_b <= bound_ms,
        "no_double_billing": billed <= stats["rows"],
    })
    # fold the healthy phase's streams into the dump under a distinct
    # prefix so --check-determinism byte-compares BOTH phases
    res["journals"].update(
        {"healthy.%s" % name: evs for name, evs in journals_a.items()})
    res["verifier"] = stats
    res["hedge"] = {
        "p99_healthy_ms": round(p99_a, 3),
        "p99_hedged_ms": round(p99_b, 3),
        "bound_ms": round(bound_ms, 3),
        "hedges": stats["hedges"],
        "hedge_wins": stats["hedge_wins"],
        "hedge_cancelled": stats["hedge_cancelled"],
        "hedge_wasted": stats["hedge_wasted"],
    }
    return res


def _scn_calm_baseline(seed: int, fast: bool) -> dict:
    """No faults at all: a healthy cluster with the live telemetry plane
    enabled must fire ZERO SLO alerts — the false-positive guard for the
    burn-rate thresholds (and the ``slo_false_positive_alerts`` bench
    metric's scenario twin)."""
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)     # journals the (empty) fault plan
    # sub-second cadence: healthy sims commit fast in virtual time, and
    # the false-positive guard needs many evaluation ticks, not one
    col = _enable_slo(cluster, interval_s=0.5)
    cluster.start()
    blocks = 4 if fast else 8
    cluster.run(600.0,
                stop_condition=lambda: cluster.min_height() >= blocks)
    res = _finish("calm_baseline", seed, cluster,
                  extra_blocks=2, bound_s=240.0, checks={})
    res = _slo_checks(res, cluster, col, lambda: {
        "zero_alerts_fired": col.slo.fired_total == 0,
        "no_transitions_journaled": not col.slo.alerts(),
        "fully_compliant": col.slo.compliance_ratio == 1.0,
        "samples_flowed": col.envelopes > 0})
    return res


def _scn_commit_attribution(seed: int, fast: bool) -> dict:
    """The commit-anatomy profiler must blame the fault we injected:
    a partition hold-back makes cross-node propagation the dominant
    phase, a verifier blackout makes the divert path dominant — both
    verdicts byte-deterministic across same-seed runs."""
    from harness import anatomy as anatomy_mod

    # part A: isolate node3, then heal — its catch-up commits stretch
    # cross-node propagation (t_last_commit - t_first_commit) far past
    # every other phase of the partition-era blocks
    heal_t = 30.0 if fast else 60.0
    cluster = SimCluster(4, seed=seed, txn_per_block=5, txpool=True)
    inj = FaultInjector(cluster)
    inj.apply(FaultPlan()
              .partition(2.0, "node3")
              .heal(heal_t, "node3"))
    cluster.start()
    cluster.run(heal_t + 1.0)
    res = _finish("commit_attribution", seed, cluster,
                  extra_blocks=3, bound_s=240.0, checks={})
    part = anatomy_mod.assemble(res["journals"])
    dom_part = part.get("dominant") or {}

    # part B: same blackout shape as verifier_blackout, never healed —
    # every window fails over host-side, so the assembler's divert-share
    # test must name the verify path (with its lane), not a macro phase
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    # long window => flushes are kick-driven only (deterministic rows);
    # a huge cooldown pins the breaker open for the whole run
    sched = VerifierScheduler(NativeBatchVerifier(), window_ms=10_000.0,
                              breaker_cooldown_s=1e9)
    cluster_b = SimCluster(4, seed=seed, verifier=sched, signed=True)
    sched.breaker_clock = cluster_b.clock.now

    def _dead_device(rows: int) -> None:
        raise RuntimeError("device lost (injected blackout)")

    sched.failure_hook = _dead_device
    FaultInjector(cluster_b)         # journals the (empty) fault plan
    cluster_b.start()
    blocks = 3 if fast else 5
    cluster_b.run(600.0,
                  stop_condition=lambda: cluster_b.min_height() >= blocks)
    for sn in cluster_b.live_nodes():
        sn.node.stop()
    sched.close()
    journals_b = cluster_b.journals()
    blackout = anatomy_mod.assemble(journals_b)
    dom_black = blackout.get("dominant") or {}

    # fold part B's streams into the dump under a distinct prefix so
    # --check-determinism byte-compares BOTH attributions
    res["journals"].update(
        {"blackout.%s" % name: evs for name, evs in journals_b.items()})
    res["anatomy"] = {
        "partition_dominant": dom_part,
        "blackout_dominant": dom_black,
        "blackout_divert_share": blackout["verify"]["divert_share"],
    }
    checks = {
        "propagation_blamed": dom_part.get("phase") == "propagation",
        "blackout_diverted":
            blackout["verify"]["divert_share"] >= 0.5,
        "verify_divert_blamed":
            dom_black.get("phase") == "verify_divert",
    }
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


def _scn_ingress_flood_attribution(seed: int, fast: bool) -> dict:
    """An injected peer floods the cluster with invalid-signature
    transactions: the ingress ledger must name it the dominant offender
    (honest origins keep zero rejects), the invalid_sig_reject_ratio
    SLO must fire while the flood runs and resolve after it stops —
    all byte-deterministic across same-seed runs."""
    from eges_tpu.core.types import Transaction
    from eges_tpu.utils import ledger as ledger_mod
    import eges_tpu.consensus.messages as M

    cluster = SimCluster(4, seed=seed, txn_per_block=4, txpool=True)
    inj = FaultInjector(cluster)     # journals the (empty) fault plan
    col = _enable_slo(cluster)
    cluster.net.join("flooder", "10.0.0.99", 9999,
                     lambda d: None, lambda d: None)
    cluster.net.join("client", "10.0.0.98", 9998,
                     lambda d: None, lambda d: None)

    # a little honest traffic so attribution has someone NOT to blame:
    # a well-behaved client gossips a few valid-signed transactions
    priv = bytes([7]) * 32
    good = tuple(Transaction(nonce=i, gas_price=1, gas_limit=21000,
                             to=bytes(20), value=0).signed(priv)
                 for i in range(4))

    def honest():
        cluster.net.deliver_gossip("client", M.pack_gossip(
            M.GOSSIP_TXNS, M.TxnsMsg(txns=good)))

    # the flood: waves of unique-nonce junk whose r=0 signature fails
    # the pool's range check — cheap rejects, never device rows.
    # Unique nonces per wave keep every row a REJECT (fresh hash), not
    # a duplicate drop, so the abuse signal is unambiguous.
    flooding = [True]
    wave = [0]

    def flood():
        if not flooding[0]:
            return
        base = 1000 + wave[0] * 100
        wave[0] += 1
        bad = tuple(Transaction(nonce=base + i, gas_price=1,
                                gas_limit=21000, to=bytes(20), value=0,
                                v=27, r=0, s=1) for i in range(8))
        cluster.net.deliver_gossip("flooder", M.pack_gossip(
            M.GOSSIP_TXNS, M.TxnsMsg(txns=bad)))
        cluster.clock.call_later(2.0, flood)

    cluster.clock.call_later(0.5, honest)
    cluster.clock.call_later(1.0, flood)
    cluster.start()

    def _fired() -> bool:
        return any(e["type"] == "slo_firing"
                   and e["objective"] == "invalid_sig_reject_ratio"
                   for e in col.slo.journal.events())

    cluster.run(600.0, stop_condition=_fired)
    fired = _fired()
    # heal: the flood stops; with no further high-reject snapshots the
    # bad observations age out of the burn windows and the alert must
    # resolve on its own
    flooding[0] = False

    def _cycled() -> bool:
        return fired and any(
            e["type"] == "slo_resolved"
            and e["objective"] == "invalid_sig_reject_ratio"
            for e in col.slo.journal.events())

    cluster.run(600.0, stop_condition=_cycled)
    res = _finish("ingress_flood_attribution", seed, cluster,
                  extra_blocks=2, bound_s=240.0,
                  checks={"flood_waves_sent": wave[0] > 0})
    res = _slo_checks(res, cluster, col, lambda: {
        "slo_invalid_sig_fired": any(
            e["type"] == "slo_firing"
            and e["objective"] == "invalid_sig_reject_ratio"
            for e in col.slo.alerts()),
        "slo_invalid_sig_resolved": any(
            e["type"] == "slo_resolved"
            and e["objective"] == "invalid_sig_reject_ratio"
            for e in col.slo.alerts())})
    # forensics over the FINAL journals (_slo_checks re-collected them):
    # the assembler must name the flooder, and no honest origin may
    # carry a single reject
    rep = ledger_mod.assemble(res["journals"])
    dom = rep.get("dominant") or {}
    honest_rows = [o for o in rep.get("origins", [])
                   if o["origin"] != "peer:flooder"]
    checks = {
        "flooder_named_dominant": dom.get("origin") == "peer:flooder",
        "flooder_abuse_majority": dom.get("share", 0.0) >= 0.5,
        "honest_origins_unblamed": all(
            o.get("rejects", 0.0) <= 0.0 for o in honest_rows),
        "honest_client_admitted": any(
            o["origin"] == "peer:client" and o.get("admits", 0.0) > 0
            for o in rep.get("origins", [])),
    }
    res["ledger"] = {"dominant": dom,
                     "origins": len(rep.get("origins", [])),
                     "snapshots": rep.get("snapshots", 0)}
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


def _scn_oversized_payload_flood(seed: int, fast: bool) -> dict:
    """Live proof of the static taint bounds: an injected peer floods
    the cluster with (a) datagrams past INGRESS_MAX_BYTES — dropped for
    the price of a length check, before RLP ever runs — and (b)
    far-future GOSSIP_QUERY messages that stuff the defer queue until
    the DEFER_MAX eviction path sheds oldest-first — plus (c) multi-txn
    invalid-signature gossip windows that ride the COLUMNAR ingest path
    (decode -> window dedup -> batched verify reject), so the cheap
    whole-window reject is exercised under the same storm.  Consensus
    must keep committing, every node's defer AND pool ingest queues
    must end at or under their caps, and the ingress ledger must bill
    every abuse family (drops, deferrals, rejects) to the flooder —
    byte-deterministic across same-seed runs."""
    from eges_tpu.core.types import QueryBlockMsg, Transaction
    from eges_tpu.utils import ledger as ledger_mod
    from eges_tpu.utils.metrics import DEFAULT as metrics
    import eges_tpu.consensus.messages as M

    cluster = SimCluster(4, seed=seed, txn_per_block=4, txpool=True)
    inj = FaultInjector(cluster)     # journals the (empty) fault plan
    cluster.net.join("flooder", "10.0.0.99", 9999,
                     lambda d: None, lambda d: None)
    cluster.net.join("client", "10.0.0.98", 9998,
                     lambda d: None, lambda d: None)
    # shrink the defer cap so the eviction path is exercised in a few
    # virtual seconds (same override both runs -> still deterministic)
    for sn in cluster.nodes:
        sn.node.DEFER_MAX = 64

    # metric counters are process-global: gate the checks on deltas so
    # back-to-back runs (the determinism harness) stay independent
    oversized0 = metrics.counter("consensus.ingress_oversized").value
    evicted0 = metrics.counter("consensus.deferred_dropped").value

    # honest contrast traffic: a well-behaved client's signed txns
    priv = bytes([7]) * 32
    good = tuple(Transaction(nonce=i, gas_price=1, gas_limit=21000,
                             to=bytes(20), value=0).signed(priv)
                 for i in range(4))

    def honest():
        cluster.net.deliver_gossip("client", M.pack_gossip(
            M.GOSSIP_TXNS, M.TxnsMsg(txns=good)))

    from eges_tpu.consensus.node import GeecNode as _Node
    junk = b"\x00" * (_Node.INGRESS_MAX_BYTES + 1)
    flooding = [True]
    wave = [0]

    def flood():
        if not flooding[0]:
            return
        # one oversized datagram per wave: must die at the byte gate
        cluster.net.deliver_gossip("flooder", junk)
        # a burst of unique far-future queries: each one is a deferral
        base = 100_000 + wave[0] * 16
        # a 16-row invalid-signature txn window: rides the pool's ingest
        # (window dedup + batched verify) straight into the
        # whole-window reject, billed per row to this flooder
        bad = tuple(Transaction(nonce=base + i, gas_price=1,
                                gas_limit=21000, to=bytes(20), value=0,
                                v=27, r=0, s=1)
                    for i in range(16))
        cluster.net.deliver_gossip("flooder", M.pack_gossip(
            M.GOSSIP_TXNS, M.TxnsMsg(txns=bad)))
        wave[0] += 1
        for i in range(16):
            cluster.net.deliver_gossip("flooder", M.pack_gossip(
                M.GOSSIP_QUERY,
                QueryBlockMsg(block_number=base + i, version=1,
                              ip="10.0.0.99", retry=0, port=9999)))
        cluster.clock.call_later(2.0, flood)

    cluster.clock.call_later(0.5, honest)
    cluster.clock.call_later(1.0, flood)
    cluster.start()

    def _tripped() -> bool:
        return (metrics.counter("consensus.ingress_oversized").value
                > oversized0
                and metrics.counter("consensus.deferred_dropped").value
                > evicted0)

    cluster.run(600.0, stop_condition=_tripped)
    flooding[0] = False
    res = _finish("oversized_payload_flood", seed, cluster,
                  extra_blocks=2, bound_s=240.0,
                  checks={
                      "flood_waves_sent": wave[0] > 0,
                      "oversized_dropped_pre_decode": (
                          metrics.counter(
                              "consensus.ingress_oversized").value
                          > oversized0),
                      "defer_evictions_counted": (
                          metrics.counter(
                              "consensus.deferred_dropped").value
                          > evicted0),
                      "defer_queues_capped": all(
                          len(sn.node._deferred) <= sn.node.DEFER_MAX
                          for sn in cluster.nodes),
                      # the pool's ingest queue never holds more than
                      # one un-flushed window's worth of rows: the
                      # max_batch threshold flushes anything beyond it
                      "pool_ingest_queues_bounded": all(
                          sn.node.txpool._queue_rows
                          <= sn.node.txpool.max_batch
                          for sn in cluster.nodes
                          if sn.node.txpool is not None),
                  })
    # forensics: both drop families must bill to the flooder, who must
    # out-rank every honest origin on both (honest peers DO carry some
    # drops — duplicate re-gossip — and protocol deferrals; the signal
    # is the flooder sitting on top of both columns).  The well-behaved
    # client must stay entirely unblamed.
    rep = ledger_mod.assemble(res["journals"])
    rows = {o["origin"]: o for o in rep.get("origins", [])}
    flooder = rows.get("peer:flooder", {})
    honest = [o for name, o in rows.items() if name != "peer:flooder"]
    client = rows.get("peer:client", {})
    checks = {
        "flooder_billed_drops": flooder.get("drops", 0.0) > 0,
        "flooder_billed_deferred": flooder.get("deferred", 0.0) > 0,
        # the invalid-signature windows reject in the pool's flush and
        # bill back to their deliverer
        "flooder_billed_rejects": flooder.get("rejects", 0.0) > 0,
        "flooder_top_offender": all(
            flooder.get("drops", 0.0) > o.get("drops", 0.0)
            and flooder.get("deferred", 0.0) > o.get("deferred", 0.0)
            and flooder.get("rejects", 0.0) > o.get("rejects", 0.0)
            for o in honest),
        "honest_client_unblamed": (client.get("drops", 0.0) <= 0.0
                                   and client.get("deferred", 0.0) <= 0.0
                                   and client.get("rejects", 0.0) <= 0.0
                                   and client.get("admits", 0.0) > 0),
    }
    res["ledger"] = {"origins": len(rows),
                     "flooder_drops": flooder.get("drops", 0.0)}
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


def _scn_rejoin_tail_bound(seed: int, fast: bool) -> dict:
    """O(tail) rejoin proof: with a durable checkpoint cadence on, a
    crashed-and-restarted node must anchor its boot replay on the
    newest root-verified checkpoint and replay only the tail past it —
    never the whole chain.  The restarted node's statesync_restart
    event carries the anchor height and the replayed count, so the
    bound is asserted from the journal, byte-deterministically."""
    cluster = SimCluster(4, seed=seed, txn_per_block=2,
                         checkpoint_every=4)
    inj = FaultInjector(cluster)
    cluster.start()
    pre = 10 if fast else 14
    cluster.run(900.0, stop_condition=lambda: cluster.min_height() >= pre)
    inj.fire_now("crash", node="node1")
    # survivors extend the chain: THIS tail is what the restart replays
    tail_target = pre + 4
    cluster.run(240.0, stop_condition=lambda: min(
        sn.chain.height() for sn in cluster.live_nodes()) >= tail_target)
    inj.fire_now("restart", node="node1")
    res = _finish("rejoin_tail_bound", seed, cluster, extra_blocks=2,
                  bound_s=240.0)
    evs = res["journals"].get("node1", [])
    rst = next((e for e in evs if e.get("type") == "statesync_restart"
                and e.get("snapshot_blk", 0) > 0), None)
    ckpts = [e for e in res["journals"].get("node0", [])
             if e.get("type") == "statesync_checkpoint"]
    checks = {
        "checkpoints_written": len(ckpts) > 0,
        "restart_anchored_on_checkpoint": rst is not None,
        # the O(tail) contract: replayed <= height - snapshot height,
        # and strictly less than the whole chain
        "replay_tail_bounded": (
            rst is not None
            and rst["replayed"] <= rst["blk"] - rst["snapshot_blk"]
            and rst["replayed"] < rst["blk"]),
    }
    res["rejoin"] = rst
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


# a dozen funded genesis accounts so fast-sync downloads span several
# pages (servers page 2 accounts at a time in the statesync scenarios)
_STATESYNC_ALLOC = {bytes([i + 1]) * 20: 10 ** 6 for i in range(12)}


def _scn_byzantine_snapshot_server(seed: int, fast: bool) -> dict:
    """A byzantine member tampers every state page it serves (one
    balance inflated per page).  The fast-syncing late joiner must
    detect the poison at the certified-root check, never adopt it,
    blacklist the serving peer, re-anchor the download on an honest
    server, and finish the sync — with the poisoner billed in the
    ingress ledger as the dominant offender."""
    from eges_tpu.utils import ledger as ledger_mod
    import eges_tpu.consensus.messages as M

    cluster = SimCluster(4, n_bootstrap=3, txn_per_block=2, seed=seed,
                         reg_timeout_s=5.0, defer={3}, fast_sync={3},
                         alloc=_STATESYNC_ALLOC)
    joiner = cluster.nodes[3]
    joiner.node.FASTSYNC_MIN_GAP = 16
    for sn in cluster.nodes[:3]:
        sn.node.STATE_PAGE_MAX = 2  # force multi-page downloads
    # the joiner pins its first serving peer deterministically: the
    # member rotation picks sorted_others[1] on the first tick (rr=1,
    # retry=0, 3 bootstrap peers) — make THAT node the poisoner, so the
    # first download is guaranteed to run against it
    order = sorted(sn.node.coinbase for sn in cluster.nodes[:3])
    evil_addr = order[1]
    evil = next(sn for sn in cluster.nodes[:3]
                if sn.node.coinbase == evil_addr)
    cluster.start()

    def _tamper_reply(reply):
        acc = list(reply.accounts)
        if not acc:
            return None
        a0 = list(acc[0])
        a0[2] = int(a0[2]) + 1_000_000  # inflate one balance
        acc[0] = tuple(a0)
        return M.StateChunkReply(
            block_num=reply.block_num, root=reply.root,
            cursor=reply.cursor, total=reply.total,
            accounts=tuple(acc), codes=reply.codes)

    t = evil.node.transport
    orig_direct, orig_gossip = t.send_direct, t.gossip

    def poisoned_direct(ip, port, data):
        try:
            code, author, msg = M.unpack_direct(data)
        except Exception:
            return orig_direct(ip, port, data)
        if code == M.UDP_STATE:
            bad = _tamper_reply(msg)
            if bad is not None:
                data = M.pack_direct(M.UDP_STATE, author, bad)
        return orig_direct(ip, port, data)

    def poisoned_gossip(data):
        try:
            code, msg = M.unpack_gossip(data)
        except Exception:
            return orig_gossip(data)
        if code == M.GOSSIP_STATE_REPLY:
            bad = _tamper_reply(msg)
            if bad is not None:
                data = M.pack_gossip(M.GOSSIP_STATE_REPLY, bad)
        return orig_gossip(data)

    t.send_direct = poisoned_direct
    t.gossip = poisoned_gossip

    # deep warmup: the serving pivot is head-PIVOT_LAG, so the chain
    # must be well past the lag for a real mid-chain pivot to exist
    cluster.run(900.0, stop_condition=lambda: min(
        sn.chain.height() for sn in cluster.nodes[:3]) >= 60)
    cluster.start_deferred(3)
    cluster.run(600.0, stop_condition=lambda: joiner.node._fs_done)
    res = _finish("byzantine_snapshot_server", seed, cluster,
                  extra_blocks=2, bound_s=240.0)
    evs = res["journals"].get("node3", [])
    evil_hex = evil_addr.hex()[:8]
    poisoned = [e for e in evs if e.get("type") == "statesync_poisoned"]
    adopted = [e for e in evs if e.get("type") == "statesync_adopted"]
    reanchors = [e for e in evs if e.get("type") == "statesync_reanchor"]
    rep = ledger_mod.assemble(res["journals"])
    rows = {o["origin"]: o for o in rep.get("origins", [])}
    offender = rows.get(f"server:{evil_hex}", {})
    dominant = rep.get("dominant") or {}
    checks = {
        # the root check caught the tampered pages and named the server
        "poison_detected": any(e.get("server") == evil_hex
                               for e in poisoned),
        "poisoner_blacklisted": evil_addr in joiner.node._fs_blacklist,
        "download_reanchored": len(reanchors) >= 1,
        # the sync still completed — via an honest server, not replay:
        # the joiner never fetched the pre-pivot ancestors
        "sync_completed": bool(adopted) and joiner.node._fs_done,
        "ancestors_skipped": joiner.chain.get_block_by_number(1) is None,
        # forensics: the wasted staged rows billed to the poisoning
        # server, ranking it the dominant abuse origin
        "poisoner_billed": offender.get("rejects", 0.0) > 0,
        "poisoner_dominant": dominant.get("origin") == f"server:{evil_hex}",
    }
    res["statesync"] = {"poisoned": len(poisoned),
                        "reanchors": len(reanchors),
                        "dominant": dominant}
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


def _scn_statesync_crash_resume(seed: int, fast: bool) -> dict:
    """Crash a fast-syncing joiner mid-download: the restarted process
    must find its staged pages in the store, resume the download from
    the staged cursor (statesync_resume), and complete the sync —
    instead of restarting from cursor 0 or falling back to replay."""
    cluster = SimCluster(4, n_bootstrap=3, txn_per_block=2, seed=seed,
                         reg_timeout_s=5.0, defer={3}, fast_sync={3},
                         alloc=_STATESYNC_ALLOC)
    inj = FaultInjector(cluster)
    joiner = cluster.nodes[3]
    joiner.node.FASTSYNC_MIN_GAP = 16
    for sn in cluster.nodes[:3]:
        sn.node.STATE_PAGE_MAX = 2  # force multi-page downloads
    cluster.start()
    # deep warmup: the serving pivot is head-PIVOT_LAG, so the chain
    # must be well past the lag for a real mid-chain pivot to exist
    cluster.run(900.0, stop_condition=lambda: min(
        sn.chain.height() for sn in cluster.nodes[:3]) >= 60)
    cluster.start_deferred(3)

    def _mid_sync() -> bool:
        fs = joiner.node._fs
        return fs is not None and len(fs["accounts"]) >= 2

    cluster.run(600.0, stop_condition=_mid_sync)
    crashed_mid = _mid_sync()
    inj.fire_now("crash", node="node3")
    cluster.run(5.0)
    inj.fire_now("restart", node="node3")
    # the rebuilt node starts with the class-default gap threshold:
    # re-apply the scenario override before the next confirm arrives
    # (fire_now is synchronous; no virtual time has passed)
    cluster.nodes[3].node.FASTSYNC_MIN_GAP = 16
    cluster.run(600.0,
                stop_condition=lambda: cluster.nodes[3].node._fs_done)
    res = _finish("statesync_crash_resume", seed, cluster,
                  extra_blocks=2, bound_s=240.0)
    evs = res["journals"].get("node3", [])
    resume = next((e for e in evs
                   if e.get("type") == "statesync_resume"), None)
    checks = {
        "crashed_mid_sync": crashed_mid,
        "resumed_from_staging": (resume is not None
                                 and resume.get("rows", 0) >= 2),
        "sync_completed": any(e.get("type") == "statesync_adopted"
                              for e in evs),
        "ancestors_skipped": (
            cluster.nodes[3].chain.get_block_by_number(1) is None),
    }
    res["statesync"] = {"resume": resume}
    res["checks"].update(checks)
    res["ok"] = bool(res["ok"] and all(checks.values()))
    return res


def _scn_combo(seed: int, fast: bool) -> dict:
    """The acceptance storm: leader-kill + 20% loss + an asymmetric
    partition, all at once, then heal everything.  Live nodes must
    converge to equal heights with no conflicting commits, within the
    virtual-time bound, bit-identically across same-seed runs."""
    heal_t = 45.0 if fast else 90.0
    cluster = SimCluster(4, seed=seed)
    inj = FaultInjector(cluster)
    inj.apply(FaultPlan()
              .kill_leader(1.0, times=1, restart_after=15.0)
              .set_net(2.0, drop_rate=0.2, jitter_s=0.05)
              .block_link(2.0, "node2", "node1")
              .set_net(heal_t, drop_rate=0.0, jitter_s=0.002)
              .heal_link(heal_t, "node2", "node1"))
    cluster.start()
    cluster.run(heal_t + 1.0)
    cluster.run(120.0, stop_condition=lambda: (
        any(f["kind"] == "crash" for f in inj.fired)
        and not any(sn.crashed for sn in cluster.nodes)))
    return _finish("combo", seed, cluster,
                   extra_blocks=3 if fast else 4, bound_s=300.0,
                   checks={"leader_killed": any(
                       f["kind"] == "crash" for f in inj.fired),
                       "all_recovered": not any(
                           sn.crashed for sn in cluster.nodes)})


SCENARIOS = {
    "leader_kill_storm": _scn_leader_kill_storm,
    "rolling_restarts": _scn_rolling_restarts,
    "loss_jitter": _scn_loss_jitter,
    "asym_partition_ttl": _scn_asym_partition_ttl,
    "corruption_flood": _scn_corruption_flood,
    "verifier_blackout": _scn_verifier_blackout,
    "mesh_device_blackout": _scn_mesh_device_blackout,
    "straggler_hedge": _scn_straggler_hedge,
    "calm_baseline": _scn_calm_baseline,
    "commit_attribution": _scn_commit_attribution,
    "ingress_flood_attribution": _scn_ingress_flood_attribution,
    "oversized_payload_flood": _scn_oversized_payload_flood,
    "rejoin_tail_bound": _scn_rejoin_tail_bound,
    "byzantine_snapshot_server": _scn_byzantine_snapshot_server,
    "statesync_crash_resume": _scn_statesync_crash_resume,
    "combo": _scn_combo,
}


def run_scenario(name: str, seed: int = 0, fast: bool = False) -> dict:
    """Run one named scenario; returns the result dict (``ok`` plus the
    safety/liveness breakdown, net stats, and the merged journals)."""
    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r}; have: "
                       + ", ".join(sorted(SCENARIOS)))
    return SCENARIOS[name](seed, fast)


def check_determinism(name: str, seed: int = 0,
                      fast: bool = False) -> tuple[bool, bytes, bytes]:
    """Run a scenario twice with the same seed and compare the canonical
    journal dumps byte-for-byte."""
    a = canonical_dump(run_scenario(name, seed, fast)["journals"])
    b = canonical_dump(run_scenario(name, seed, fast)["journals"])
    return a == b, a, b


# -- rendering ------------------------------------------------------------

def render_result(res: dict) -> str:
    out = ["chaos %-20s seed=%d  %s" % (
        res["scenario"], res["seed"], "OK" if res["ok"] else "FAILED")]
    out.append("  safety=%s liveness=%s converged=%s  heights=%s "
               "(checked %d)" % (res["safety"], res["liveness"],
                                 res["converged"], res["heights"],
                                 res["heights_checked"]))
    out.append("  recovered %d new block(s) in %.3f virtual s "
               "(bound %.0f s)" % (res["extra_blocks"],
                                   res["recovered_in_s"], res["bound_s"]))
    net = res["net"]
    out.append("  net: " + "  ".join(
        "%s %d" % (k, net[k]) for k in sorted(net)))
    for k, v in sorted(res["checks"].items()):
        out.append("  check %-32s %s" % (k, "ok" if v else "FAILED"))
    if "verifier" in res:
        vs = res["verifier"]
        out.append("  verifier: breaker=%s trips=%d probes=%d "
                   "diverted=%d host=%d batches=%d" % (
                       vs["breaker"], vs["breaker_trips"],
                       vs["breaker_probes"], vs["breaker_diverted"],
                       vs["host_diverted"], vs["batches"]))
    if "slo" in res:
        s = res["slo"]
        out.append("  slo: fired=%d compliance=%.4f  %s" % (
            s["alerts_fired"], s["compliance_ratio"],
            "  ".join("%s=%s" % (k, v)
                      for k, v in sorted(s["alert_states"].items()))))
    if "anatomy" in res:
        a = res["anatomy"]
        out.append("  anatomy: partition blames %s (%.2f%%)  "
                   "blackout blames %s (divert share %.4f)" % (
                       a["partition_dominant"].get("phase", "?"),
                       a["partition_dominant"].get("share", 0.0) * 100.0,
                       a["blackout_dominant"].get("phase", "?"),
                       a["blackout_divert_share"]))
    if "ledger" in res:
        led = res["ledger"]
        dom = led.get("dominant") or {}
        out.append("  ledger: %d snapshot(s), %d origin(s)  "
                   "dominant=%s (%.2f%% of discarded work)" % (
                       led.get("snapshots", 0), led.get("origins", 0),
                       dom.get("origin", "-"),
                       dom.get("share", 0.0) * 100.0))
    if "hedge" in res:
        h = res["hedge"]
        out.append("  hedge: p99 healthy %.3fms -> hedged %.3fms "
                   "(bound %.3fms)  hedges=%d wins=%d cancelled=%d "
                   "wasted=%d" % (
                       h["p99_healthy_ms"], h["p99_hedged_ms"],
                       h["bound_ms"], h["hedges"], h["hedge_wins"],
                       h["hedge_cancelled"], h["hedge_wasted"]))
    if "flight_stragglers" in res:
        out.append("  flight stragglers: %s" % (
            ", ".join(str(d) for d in res["flight_stragglers"])
            or "-"))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default=None,
                    help="run one named scenario")
    ap.add_argument("--all", action="store_true",
                    help="run the full scenario matrix")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fast", action="store_true",
                    help="reduced-scale variants (smoke-test sized)")
    ap.add_argument("--check-determinism", action="store_true",
                    help="run each scenario twice and require "
                         "byte-identical canonical journal dumps")
    ap.add_argument("--dump", metavar="DIR", default=None,
                    help="dump merged journals as JSONL (observatory "
                         "--replay format)")
    ap.add_argument("--observatory", action="store_true",
                    help="render the observatory report (fault timeline "
                         "included) for each run")
    ap.add_argument("--json", action="store_true",
                    help="emit result dicts as JSON lines")
    args = ap.parse_args(argv)

    if args.list:
        for name in sorted(SCENARIOS):
            print("%-20s %s" % (name, (SCENARIOS[name].__doc__ or "")
                                .strip().splitlines()[0]))
        return 0

    names = (sorted(SCENARIOS) if args.all
             else [args.scenario] if args.scenario else ["combo"])
    failed = 0
    for name in names:
        res = run_scenario(name, seed=args.seed, fast=args.fast)
        if args.check_determinism:
            same, _, _ = check_determinism(name, seed=args.seed,
                                           fast=args.fast)
            res["checks"]["deterministic"] = same
            res["ok"] = res["ok"] and same
        journals = res.pop("journals")
        if args.dump:
            outdir = os.path.join(args.dump, name)
            for p in observatory.dump_journals(journals, outdir):
                print("dumped %s" % p, file=sys.stderr)
        if args.json:
            print(json.dumps(res, sort_keys=True))
        else:
            print(render_result(res))
            if args.observatory:
                print(observatory.render(
                    observatory.summarize(journals), net=res["net"]))
        if not res["ok"]:
            failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
