"""Declarative SLO objectives with multi-window burn-rate alerting.

The observatory reconstructs what happened after a run ends; this
module decides — while events stream in — whether the cluster is
burning its error budget.  Each :class:`Objective` names a service-level
condition (commit-latency ceiling, verifier occupancy floor, scheduler
queue-wait bound, dead-letter rate, breaker-open duration, cold-start
ceiling) and the :class:`SLOEngine` reduces every condition to a stream
of (ts, good/bad) observations evaluated with the classic fast/slow
multi-window burn-rate test: an alert needs BOTH a fast window (page on
what is burning now) and a slow window (ignore blips) over their burn
thresholds, where burn = bad_fraction / error_budget.

Alert state follows pending -> firing -> resolved; every transition is
journaled as an ``slo_pending`` / ``slo_firing`` / ``slo_resolved``
event so chaos scenarios assert on alerts deterministically and
``--check-determinism`` byte-compares the alert stream.  The engine is
clock-free: ``evaluate(now)`` takes time from the caller (virtual time
under the simulator), and its journal stamps transitions at that same
instant.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

from eges_tpu.utils.journal import Journal
from eges_tpu.utils.metrics import DEFAULT as metrics

# Per-source badness thresholds (the objective grammar's left-hand
# side).  The wall-clock-derived ones (queue wait, cold start) carry
# generous margins so deterministic sim runs never flap on real-time
# jitter: their alerts exist for real deployments.
COMMIT_GAP_BAD_S = 60.0       # a new height this long after the last
OCCUPANCY_FLOOR = 0.02        # dispatched/padded rows below this
QUEUE_WAIT_BAD_MS = 500.0     # coalescing window wait above this
COLD_START_BAD_S = 30.0       # AOT prewarm slower than this
INVALID_SIG_RATIO_BAD = 0.5   # rejects dominate admits in a snapshot
INGRESS_MIN_ATTEMPTS = 4      # snapshots with fewer attempts abstain
GOODPUT_FLOOR = 0.02          # useful/padded device rows below this
DEVSTATS_MIN_WINDOWS = 2      # ticks with fewer windows abstain


@dataclass(frozen=True)
class Objective:
    """One declarative SLO: breach when the bad fraction of BOTH
    windows exceeds ``burn * budget``."""

    name: str
    description: str
    budget: float              # allowed bad fraction (error budget)
    fast_window_s: float
    slow_window_s: float
    fast_burn: float = 1.0
    slow_burn: float = 1.0
    pending_for_s: float = 10.0   # sustained breach before firing
    resolve_after_s: float = 30.0  # sustained recovery before resolved


DEFAULT_OBJECTIVES = (
    Objective("commit_latency",
              "p99 commit gap stays under the ceiling",
              budget=0.2, fast_window_s=60.0, slow_window_s=240.0,
              fast_burn=2.0, slow_burn=1.0),
    Objective("verifier_occupancy",
              "coalesced windows keep a minimum device occupancy",
              budget=0.5, fast_window_s=60.0, slow_window_s=240.0),
    Objective("sched_queue_wait",
              "submissions clear the coalescing window promptly",
              budget=0.1, fast_window_s=60.0, slow_window_s=240.0),
    Objective("dead_letters",
              "the transport is not dead-lettering messages",
              budget=0.25, fast_window_s=60.0, slow_window_s=240.0),
    Objective("breaker_open",
              "no verifier device breaker stays open",
              budget=0.1, fast_window_s=60.0, slow_window_s=240.0),
    Objective("cold_start",
              "AOT prewarm restores the verifier quickly",
              budget=0.5, fast_window_s=300.0, slow_window_s=600.0,
              pending_for_s=0.0),
    Objective("invalid_sig_reject_ratio",
              "ingest rejects stay a small share of pool admissions",
              budget=0.25, fast_window_s=60.0, slow_window_s=240.0),
    Objective("device_headroom",
              "device lanes keep useful rows above the goodput floor",
              budget=0.5, fast_window_s=60.0, slow_window_s=240.0),
)


class SLOEngine:
    """Event-driven burn-rate evaluator with a journaled alert
    state machine.

    Feed it journal events via :meth:`ingest` (any order within a
    sampling step — the collector sorts) and call :meth:`evaluate`
    once per telemetry step with that step's timestamp.
    """

    def __init__(self, objectives=DEFAULT_OBJECTIVES, *,
                 journal: Journal | None = None, window_points: int = 4096):
        self._objectives = {o.name: o for o in objectives}
        self._obs: dict[str, deque] = {
            o.name: deque(maxlen=window_points) for o in objectives}
        self._state = {o.name: "ok" for o in objectives}
        self._since: dict[str, float | None] = {
            o.name: None for o in objectives}
        self._recover: dict[str, float | None] = {
            o.name: None for o in objectives}
        self._now = 0.0
        self.journal = journal if journal is not None else Journal(
            "slo", clock=lambda: self._now)
        # optional commit-anatomy hook (harness/anatomy.py): a callable
        # returning {"phase", "share"[, "lane"]} or None.  When set (the
        # collector wires its assembler's ``dominant``), every firing
        # transition carries the phase currently dominating commit
        # latency — "commit_latency firing: 61% in verify_divert,
        # lane 0" instead of a bare burn rate.
        self.phase_hint = None
        # routing state
        self._max_blk = -1
        self._last_commit_ts: float | None = None
        self._breaker_open: dict[object, bool] = {}
        # compliance accounting for the bench gate
        self.eval_ticks = 0
        self.firing_ticks = 0
        self.fired_total = 0

    # -- observation plumbing ------------------------------------------
    def observe(self, objective: str, ts: float, bad: bool) -> None:
        obs = self._obs.get(objective)
        if obs is not None:
            obs.append((float(ts), bool(bad)))

    def ingest(self, ev: dict) -> None:
        """Route one journal event to the objectives it informs."""
        etype = ev.get("type")
        ts = float(ev.get("ts", 0.0))
        if etype == "block_committed":
            blk = ev.get("blk")
            if isinstance(blk, int) and blk > self._max_blk:
                if self._last_commit_ts is not None:
                    gap = ts - self._last_commit_ts
                    self.observe("commit_latency", ts,
                                 gap > COMMIT_GAP_BAD_S)
                self._max_blk = blk
                self._last_commit_ts = ts
        elif etype == "verifier_flush":
            occ = ev.get("occupancy")
            if isinstance(occ, (int, float)):
                self.observe("verifier_occupancy", ts,
                             occ < OCCUPANCY_FLOOR)
            waited = ev.get("waited_ms")
            if isinstance(waited, (int, float)):
                self.observe("sched_queue_wait", ts,
                             waited > QUEUE_WAIT_BAD_MS)
        elif etype == "fault_breaker":
            self._breaker_open[ev.get("device", 0)] = (
                ev.get("state") == "open")
        elif etype == "verifier_aot_load":
            cold = ev.get("cold_start_s")
            if isinstance(cold, (int, float)):
                self.observe("cold_start", ts, cold > COLD_START_BAD_S)
        elif etype == "ingress_ledger":
            # per-block ingest snapshot (eges_tpu/utils/ledger.py):
            # bad when signature-invalid rejects dominate the block's
            # admission attempts.  Low-traffic snapshots abstain so a
            # lone stray txn cannot burn the budget.
            rejects = ev.get("rejects_delta")
            admits = ev.get("admits_delta")
            if isinstance(rejects, int) and isinstance(admits, int):
                attempts = rejects + admits
                if attempts >= INGRESS_MIN_ATTEMPTS:
                    self.observe("invalid_sig_reject_ratio", ts,
                                 rejects / attempts
                                 > INVALID_SIG_RATIO_BAD)
        elif etype == "device_efficiency":
            # per-tick device-efficiency delta (utils/devstats.py):
            # bad when this device's tick ran mostly padding — the
            # same floor discipline as verifier_occupancy, over the
            # tick aggregate instead of a single window.  Ticks with
            # few windows (or none that padded a bucket) abstain so a
            # lone probe window cannot burn the budget.
            rows = ev.get("rows")
            bucket_rows = ev.get("bucket_rows")
            windows = ev.get("windows")
            if (isinstance(rows, int) and isinstance(bucket_rows, int)
                    and isinstance(windows, int)
                    and windows >= DEVSTATS_MIN_WINDOWS
                    and bucket_rows > 0):
                self.observe("device_headroom", ts,
                             rows / bucket_rows < GOODPUT_FLOOR)
        elif etype == "telemetry_sample":
            payload = ev.get("metrics")
            if isinstance(payload, dict):
                self.observe("dead_letters", ts,
                             bool(payload.get("net.dead_letters", 0)))

    # -- burn-rate evaluation ------------------------------------------
    def _bad_fraction(self, objective: str, now: float,
                      window_s: float) -> float:
        pts = [bad for ts, bad in self._obs[objective]
               if ts > now - window_s]
        if not pts:
            return 0.0
        return sum(1 for bad in pts if bad) / len(pts)

    def burn_rates(self, objective: str, now: float) -> tuple[float, float]:
        o = self._objectives[objective]
        return (self._bad_fraction(objective, now, o.fast_window_s)
                / o.budget,
                self._bad_fraction(objective, now, o.slow_window_s)
                / o.budget)

    def evaluate(self, now: float) -> list[dict]:
        """Advance every objective's state machine to ``now``; returns
        the transition events recorded this step."""
        self._now = float(now)
        # per-step condition observations that have no event of their
        # own: the breaker objective samples current breaker state
        self.observe("breaker_open", self._now,
                     any(self._breaker_open[k]
                         for k in sorted(self._breaker_open, key=repr)))
        transitions: list[dict] = []
        for name in sorted(self._objectives):
            o = self._objectives[name]
            fast, slow = self.burn_rates(name, self._now)
            breach = fast >= o.fast_burn and slow >= o.slow_burn
            state = self._state[name]
            if state == "ok":
                if breach:
                    self._state[name] = "pending"
                    self._since[name] = self._now
                    transitions.append(self._transition(
                        "slo_pending", name, fast, slow))
                    if self._now - self._since[name] >= o.pending_for_s:
                        # zero-delay objectives fire on first breach
                        self._state[name] = "firing"
                        self._recover[name] = None
                        self.fired_total += 1
                        transitions.append(self._transition(
                            "slo_firing", name, fast, slow))
            elif state == "pending":
                if not breach:
                    self._state[name] = "ok"
                    self._since[name] = None
                elif self._now - self._since[name] >= o.pending_for_s:
                    self._state[name] = "firing"
                    self._recover[name] = None
                    self.fired_total += 1
                    transitions.append(self._transition(
                        "slo_firing", name, fast, slow))
            elif state == "firing":
                if breach:
                    self._recover[name] = None
                elif self._recover[name] is None:
                    self._recover[name] = self._now
                elif self._now - self._recover[name] >= o.resolve_after_s:
                    self._state[name] = "ok"
                    self._since[name] = None
                    self._recover[name] = None
                    transitions.append(self._transition(
                        "slo_resolved", name, fast, slow))
        firing = sum(1 for s in self._state.values() if s == "firing")
        self.eval_ticks += 1
        if firing:
            self.firing_ticks += 1
        metrics.gauge("slo.alerts_firing").set(firing)
        return transitions

    def _transition(self, etype: str, objective: str, fast: float,
                    slow: float) -> dict:
        metrics.counter("slo.transitions").inc()
        extra: dict = {}
        if etype == "slo_firing" and self.phase_hint is not None:
            hint = self.phase_hint()
            if isinstance(hint, dict) and hint.get("phase"):
                extra["phase"] = hint["phase"]
                share = hint.get("share")
                if isinstance(share, (int, float)):
                    extra["phase_share"] = round(float(share), 4)
                if "lane" in hint:
                    extra["lane"] = hint["lane"]
        return self.journal.record(
            etype, objective=objective, burn_fast=round(fast, 4),
            burn_slow=round(slow, 4), **extra)

    # -- export ---------------------------------------------------------
    def alert_states(self) -> dict[str, str]:
        return {name: self._state[name]
                for name in sorted(self._objectives)}

    def alerts(self) -> list[dict]:
        """The journaled transition stream, chronological."""
        return self.journal.events()

    @property
    def compliance_ratio(self) -> float:
        """Fraction of evaluation steps with zero firing objectives."""
        if not self.eval_ticks:
            return 1.0
        return 1.0 - self.firing_ticks / self.eval_ticks
