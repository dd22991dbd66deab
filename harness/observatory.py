"""Consensus observatory: merge per-node event journals into one
cluster report.

The cluster-wide analogue of the reference's ``grep.py`` post-mortem
workflow (scraping "Geec: ..." election log lines out of N geth logs):
every node's consensus event journal (``eges_tpu/utils/journal.py``)
is collected — live from a sim cluster, or offline from the
``journal.jsonl`` dumps a real node writes to its datadir — and merged
into one summary:

- per-block election timeline (started/won/lost/version-bump, in time
  order across all nodes),
- vote-quorum latency percentiles (election p50/p99, ACK-quorum
  p50/p99),
- version-bump (failed-round) rate,
- per-node commit lag behind the cluster-first commit of each block,
- stall detection (gaps between consecutive first-commits).

``summarize`` is pure and deterministic over the event dicts, so the
``--replay`` path (load JSONL dumps) reconstructs the IDENTICAL
summary the live poll produced — the acceptance criterion this module
exists for.

Usage::

    python harness/observatory.py --nodes 4 --blocks 8 --dump /tmp/obs
    python harness/observatory.py --replay /tmp/obs
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from eges_tpu.ingress import admit_remotes
from eges_tpu.utils import devstats as devstats_mod
from eges_tpu.utils import journal as journal_mod
from eges_tpu.utils import ledger as ledger_mod
from eges_tpu.utils import profiler as profiler_mod
from eges_tpu.utils.metrics import percentile
from harness import anatomy as anatomy_mod

# Event types this report consumes; the lint test asserts this is a
# subset of journal.EVENT_TYPES so parser and emit sites cannot drift.
CONSUMED = ("election_started", "election_won", "election_lost",
            "validate_quorum", "version_bump", "block_committed",
            "block_confirmed", "commit_anatomy", "ingress_ledger",
            "fault_crash", "fault_restart", "fault_partition",
            "fault_heal", "fault_link", "fault_net", "fault_skew",
            "fault_trigger", "fault_breaker", "verifier_mesh_dispatch",
            "verifier_aot_load", "telemetry_sample",
            "slo_pending", "slo_firing", "slo_resolved",
            "profiler_report", "device_efficiency",
            "statesync_checkpoint", "statesync_restart",
            "statesync_resume", "statesync_poisoned",
            "statesync_reanchor", "statesync_server_rotate",
            "statesync_abort", "statesync_adopted")

_SLO = ("slo_pending", "slo_firing", "slo_resolved")

_TIMELINE = ("election_started", "election_won", "election_lost",
             "version_bump")

_FAULTS = ("fault_crash", "fault_restart", "fault_partition",
           "fault_heal", "fault_link", "fault_net", "fault_skew",
           "fault_trigger", "fault_breaker")


def _fault_line(name: str, ev: dict) -> str:
    typ = ev["type"]
    if typ == "fault_crash":
        return "crash %s" % ev.get("target", "?")
    if typ == "fault_restart":
        return "restart %s" % ev.get("target", "?")
    if typ == "fault_partition":
        return "partition %s" % ev.get("target", "?")
    if typ == "fault_heal":
        return "heal %s" % ev.get("target", "?")
    if typ == "fault_link":
        return "link %s->%s %s" % (ev.get("src", "?"), ev.get("dst", "?"),
                                   ev.get("change", "?"))
    if typ == "fault_net":
        knobs = ", ".join(
            "%s=%s" % (k, v) for k, v in sorted(ev.items())
            if k not in ("ts", "seq", "node", "type", "trace"))
        return "net-wide: %s" % knobs
    if typ == "fault_skew":
        return "skew %s by %ss" % (ev.get("target", "?"),
                                   ev.get("skew_s", "?"))
    if typ == "fault_trigger":
        if ev.get("event") == "leader_kill":
            return "leader-kill trigger fired on %s" % ev.get("target", "?")
        return "leader-kill armed (kills=%s)" % ev.get("kills", "?")
    # fault_breaker (recorded by the verifier scheduler into the
    # adopting node's journal)
    return "verifier breaker %s on %s" % (ev.get("state", "?"), name)


def summarize(by_node: dict[str, list[dict]],
              stall_gap_s: float = 10.0) -> dict:
    """Merge per-node journals (name -> event list) into the cluster
    summary.  Pure and deterministic: sorted iteration everywhere,
    fixed rounding, no ambient clock — identical input events (live or
    JSON round-tripped) produce an identical dict."""
    election_lat: list[float] = []
    ack_lat: list[float] = []
    version_bumps = 0
    # blk -> node -> earliest commit ts
    commits: dict[int, dict[str, float]] = {}
    # blk -> [(ts, seq, name, line)]
    timeline: dict[int, list[tuple]] = {}
    # flat, time-ordered fault timeline (injector + breaker events)
    faults: list[tuple] = []
    # device index -> aggregated mesh-dispatch stats (the scheduler's
    # per-device window lanes); occupancy is deterministic (rows vs
    # bucket), queue wait is wall-clock and deliberately excluded
    mesh: dict[int, dict] = {}
    # node -> AOT prewarm accounting (service start + sim restarts):
    # how much of each node's cold start was artifact load vs compile
    aot: dict[str, dict] = {}
    # SLO alert transitions (harness/slo.py state machine output) and
    # telemetry sampler heartbeats, merged across streams
    slo_alerts: list[tuple] = []
    telemetry_samples: dict[str, int] = {}
    # continuous-profiler report counts per stream; the attribution
    # itself is folded by profiler.assemble below
    profiler_reports: dict[str, int] = {}
    # device-efficiency report counts per stream; the goodput/roofline
    # fold itself comes from devstats.assemble below
    devstats_reports: dict[str, int] = {}
    # state-sync lifecycle (durable checkpoints, O(tail) restarts,
    # byzantine-tolerant live sync): per-node counters, plus the tail
    # bound of that node's newest restart
    statesync: dict[str, dict] = {}
    # forward compatibility: journals written by a NEWER build may carry
    # event types this parser has never heard of — count and skip them
    # instead of letting a per-type branch trip over missing attrs
    unknown_events: dict[str, int] = {}

    for name in sorted(by_node):
        for ev in by_node[name]:
            typ = ev.get("type")
            if typ not in journal_mod.EVENT_TYPES:
                key = str(typ)
                unknown_events[key] = unknown_events.get(key, 0) + 1
                continue
            blk = ev.get("blk")
            if typ == "telemetry_sample":
                telemetry_samples[name] = telemetry_samples.get(name, 0) + 1
                continue
            if typ == "profiler_report":
                profiler_reports[name] = profiler_reports.get(name, 0) + 1
                continue
            if typ == "device_efficiency":
                devstats_reports[name] = devstats_reports.get(name, 0) + 1
                continue
            if typ in _SLO:
                slo_alerts.append((
                    round(float(ev.get("ts", 0.0)), 6),
                    int(ev.get("seq", 0)), name, typ,
                    str(ev.get("objective", "?")),
                    float(ev.get("burn_fast", 0.0)),
                    float(ev.get("burn_slow", 0.0))))
                continue
            if typ == "verifier_aot_load":
                d = aot.setdefault(name, {
                    "events": 0, "aot_loads": 0, "aot_compiles": 0,
                    "load_s": 0.0, "compile_s": 0.0,
                    "cold_start_s": 0.0})
                d["events"] += 1
                d["aot_loads"] += int(ev.get("aot_loads", 0))
                d["aot_compiles"] += int(ev.get("aot_compiles", 0))
                d["load_s"] += float(ev.get("load_s", 0.0))
                d["compile_s"] += float(ev.get("compile_s", 0.0))
                d["cold_start_s"] += float(ev.get("cold_start_s", 0.0))
                continue
            if typ == "verifier_mesh_dispatch":
                d = mesh.setdefault(int(ev.get("device", -1)), {
                    "windows": 0, "rows": 0, "diverted": 0, "_occ": 0.0})
                d["windows"] += 1
                d["rows"] += int(ev.get("rows", 0))
                d["diverted"] += 1 if ev.get("diverted") else 0
                d["_occ"] += float(ev.get("occupancy", 0.0))
                continue
            if typ.startswith("statesync_"):
                d = statesync.setdefault(name, {
                    "checkpoints": 0, "checkpoint_bytes": 0,
                    "restarts": 0, "replayed": 0, "snapshot_blk": 0,
                    "resumes": 0, "poisoned": 0, "reanchors": 0,
                    "rotates": 0, "aborts": 0, "adopted": 0})
                if typ == "statesync_checkpoint":
                    d["checkpoints"] += 1
                    d["checkpoint_bytes"] = int(ev.get("nbytes", 0))
                elif typ == "statesync_restart":
                    d["restarts"] += 1
                    d["replayed"] = int(ev.get("replayed", 0))
                    d["snapshot_blk"] = int(ev.get("snapshot_blk", 0))
                elif typ == "statesync_resume":
                    d["resumes"] += 1
                elif typ == "statesync_poisoned":
                    d["poisoned"] += 1
                elif typ == "statesync_reanchor":
                    d["reanchors"] += 1
                elif typ == "statesync_server_rotate":
                    d["rotates"] += 1
                elif typ == "statesync_abort":
                    d["aborts"] += 1
                elif typ == "statesync_adopted":
                    d["adopted"] += 1
                continue
            if typ in _FAULTS:
                faults.append((round(float(ev["ts"]), 6),
                               int(ev.get("seq", 0)), name, typ,
                               _fault_line(name, ev)))
                continue
            if typ == "election_won" and "dt" in ev:
                election_lat.append(float(ev["dt"]))
            elif typ == "validate_quorum" and "dt" in ev:
                ack_lat.append(float(ev["dt"]))
            elif typ == "version_bump":
                version_bumps += 1
            elif typ == "block_committed" and blk is not None:
                per = commits.setdefault(int(blk), {})
                ts = float(ev["ts"])
                if name not in per or ts < per[name]:
                    per[name] = ts
            if typ in _TIMELINE and blk is not None:
                if typ == "election_won":
                    line = "%s won v%s (%d votes)" % (
                        name, ev.get("version", 0), ev.get("votes", 0))
                elif typ == "election_lost":
                    line = "%s lost v%s to %s" % (
                        name, ev.get("version", 0), ev.get("winner", "?"))
                elif typ == "version_bump":
                    line = "%s bumped to v%s" % (name, ev.get("version", 0))
                else:
                    line = "%s started v%s (committee %d)" % (
                        name, ev.get("version", 0), ev.get("committee", 0))
                timeline.setdefault(int(blk), []).append(
                    (round(float(ev["ts"]), 6), int(ev.get("seq", 0)),
                     name, typ, line))

    def _pct(vals: list[float]) -> dict:
        if not vals:
            return {"count": 0, "p50_ms": None, "p99_ms": None}
        s = sorted(vals)
        return {"count": len(s),
                "p50_ms": round(percentile(s, 50.0) * 1000.0, 3),
                "p99_ms": round(percentile(s, 99.0) * 1000.0, 3)}

    # per-node lag behind the cluster-first commit of each block
    lags: dict[str, list[float]] = {}
    firsts: list[tuple[int, float]] = []
    for blk in sorted(commits):
        per = commits[blk]
        first = min(per.values())
        firsts.append((blk, first))
        for name in sorted(per):
            lags.setdefault(name, []).append(per[name] - first)
    commit_lag = {
        name: {"mean_s": round(sum(v) / len(v), 6),
               "max_s": round(max(v), 6)}
        for name, v in sorted(lags.items())}

    # stall detection: gaps between consecutive cluster-first commits
    stalls = []
    max_gap = 0.0
    for (b0, t0), (b1, t1) in zip(firsts, firsts[1:]):
        gap = t1 - t0
        max_gap = max(max_gap, gap)
        if gap > stall_gap_s:
            stalls.append({"blk": b1, "gap_s": round(gap, 6)})

    return {
        "nodes": sorted(by_node),
        "blocks": len(commits),
        "election": _pct(election_lat),
        "ack_quorum": _pct(ack_lat),
        "version_bumps": version_bumps,
        "version_bump_rate": round(
            version_bumps / max(1, len(commits)), 4),
        "election_timeline": {
            blk: [{"ts": ts, "node": name, "type": typ, "line": line}
                  for ts, _seq, name, typ, line in sorted(rows)]
            for blk, rows in sorted(timeline.items())},
        "commit_lag": commit_lag,
        "stalls": stalls,
        "max_commit_gap_s": round(max_gap, 6),
        "fault_timeline": [
            {"ts": ts, "node": name, "type": typ, "line": line}
            for ts, _seq, name, typ, line in sorted(faults)],
        "verifier_mesh": {
            dev: {"windows": d["windows"], "rows": d["rows"],
                  "diverted": d["diverted"],
                  "mean_occupancy": round(d["_occ"] / d["windows"], 4)}
            for dev, d in sorted(mesh.items())},
        "verifier_aot": {
            name: {"events": d["events"], "aot_loads": d["aot_loads"],
                   "aot_compiles": d["aot_compiles"],
                   "load_s": round(d["load_s"], 3),
                   "compile_s": round(d["compile_s"], 3),
                   "cold_start_s": round(d["cold_start_s"], 3)}
            for name, d in sorted(aot.items())},
        "slo_alerts": [
            {"ts": ts, "node": name, "type": typ, "objective": obj,
             "burn_fast": fast, "burn_slow": slow}
            for ts, _seq, name, typ, obj, fast, slow
            in sorted(slo_alerts)],
        "telemetry_samples": {
            name: telemetry_samples[name]
            for name in sorted(telemetry_samples)},
        "profiler_reports": {
            name: profiler_reports[name]
            for name in sorted(profiler_reports)},
        "devstats_reports": {
            name: devstats_reports[name]
            for name in sorted(devstats_reports)},
        "statesync": {
            name: dict(statesync[name]) for name in sorted(statesync)},
        "unknown_events": {
            typ: unknown_events[typ] for typ in sorted(unknown_events)},
        "anatomy": anatomy_mod.assemble(by_node),
        "ledger": ledger_mod.assemble(by_node),
        "profile": profiler_mod.assemble(by_node),
        "devstats": devstats_mod.assemble(by_node),
    }


# -- verifier flight recorder ---------------------------------------------

def flight_straggler_lanes(flights: list[dict],
                           outlier_factor: float = 3.0) -> list[int]:
    """Attribute stragglers from flight-recorder entries (the
    ``thw_flight`` RPC payload / ``VerifierScheduler.flights()``).

    A lane is a straggler when the recorder shows breaker-diverted
    windows on it (its device path was down and rows were rescued
    host-side — the blackout victim), or when its median window total
    is an ``outlier_factor`` outlier against the all-lane median (a
    slow-but-alive device)."""
    lanes: set = set()
    totals: dict = {}
    all_totals: list[float] = []
    for f in flights:
        if not isinstance(f, dict):
            continue
        dev = f.get("device")
        total = float(f.get("total_ms", 0.0))
        if f.get("diverted"):
            lanes.add(dev)
        totals.setdefault(dev, []).append(total)
        all_totals.append(total)
    if all_totals:
        med = percentile(sorted(all_totals), 50.0)
        if med > 0.0:
            for dev in totals:
                lane_med = percentile(sorted(totals[dev]), 50.0)
                if lane_med > outlier_factor * med:
                    lanes.add(dev)
    return sorted(lanes, key=repr)


def render_flights(flights: list[dict], width: int = 40,
                   dropped: int = 0) -> str:
    """Text waterfall of verifier window lifecycles: one bar per
    window (``.`` wait, ``=`` stage/dispatch, ``#`` compute/collect)
    scaled against the slowest window, with lane attribution and a
    straggler verdict line.  ``dropped`` is the scheduler's
    ``flight_dropped`` stat (windows the bounded ring evicted unread);
    passing it makes the recorder's silent loss visible in the render
    instead of quietly under-counting windows."""
    rows = [f for f in flights if isinstance(f, dict)]
    head = "verifier flight recorder — %d window(s)" % len(rows)
    if dropped:
        head += " (+%d dropped by ring overflow)" % dropped
    out = [head]
    if not rows:
        out.append("  (no windows recorded)")
        return "\n".join(out)
    rows = sorted(rows, key=lambda f: (int(f.get("window", 0)),
                                       repr(f.get("device"))))
    scale = max(float(f.get("total_ms", 0.0)) for f in rows) or 1.0
    out.append("  %5s %4s %5s %-9s %-*s %9s" % (
        "win", "dev", "rows", "reason", width + 2, "waterfall",
        "total"))
    for f in rows:
        wait = max(0.0, float(f.get("wait_ms", 0.0)))
        stage = max(0.0, float(f.get("stage_ms", 0.0)))
        compute = max(0.0, float(f.get("compute_ms", 0.0)))
        total = float(f.get("total_ms", 0.0))
        n_wait = int(round(wait / scale * width))
        n_stage = int(round(stage / scale * width))
        n_comp = max(1, int(round(compute / scale * width)))
        bar = "." * n_wait + "=" * n_stage + "#" * n_comp
        flags = "*" if f.get("diverted") else \
            ("?" if f.get("probing") else "")
        if f.get("hedged"):
            flags += "H" if f.get("hedge_win") else "h"
        out.append("  %5s %4s %5s %-9s [%-*s] %7.3fms %s" % (
            f.get("window", "?"), f.get("device", "?"),
            f.get("rows", "?"), str(f.get("reason", "?"))[:9],
            width, bar[:width], total, flags))
    stragglers = flight_straggler_lanes(rows)
    out.append("  stragglers: %s   (* diverted, ? breaker probe,"
               " H hedge won, h hedged)" % (
                   ", ".join(str(d) for d in stragglers)
                   if stragglers else "-"))
    return "\n".join(out)


# -- commit anatomy -------------------------------------------------------

# one glyph per macro phase in the per-block waterfall bars
_PHASE_GLYPH = {"pool_admit": "a", "pool_queue": "q", "election": "e",
                "ack_quorum": "k", "seal_other": "s", "publish": "p",
                "propagation": "~"}


def render_anatomy(rep: dict, width: int = 40,
                   max_blocks: int = 8) -> str:
    """Text view of an anatomy report (``AnatomyAssembler.report`` /
    ``anatomy.assemble``): phase-attribution table, per-block waterfall
    of the newest blocks, verify-lane sub-account, and the dominant
    verdict line."""
    out = ["commit anatomy — %d block(s)" % rep.get("blocks", 0)]
    if not rep.get("blocks"):
        out.append("  (no committed blocks assembled)")
        return "\n".join(out)

    def _ms(v) -> str:
        return "-" if v is None else "%.3f ms" % v

    out.append("  commit e2e: p50 %s  p99 %s" % (
        _ms(rep.get("commit_p50_ms")), _ms(rep.get("commit_p99_ms"))))
    phases = rep.get("phases", {})
    if phases:
        out.append("  phase attribution (share of total e2e):")
        for name in anatomy_mod.PHASE_ORDER:
            d = phases.get(name)
            if d is None:
                continue
            bar = "#" * int(round(d["share"] * width))
            out.append("    %-12s %8.3f s  %6.2f%%  %s" % (
                name, d["total_s"], d["share"] * 100.0, bar))
    blocks = rep.get("per_block", [])[-max_blocks:]
    if blocks:
        out.append("  per-block waterfall (newest %d; %s):" % (
            len(blocks), " ".join(
                "%s=%s" % (_PHASE_GLYPH[p], p)
                for p in anatomy_mod.PHASE_ORDER)))
        for r in blocks:
            e2e = r.get("e2e_s", 0.0) or 0.0
            bar = ""
            if e2e > 0:
                for p in anatomy_mod.PHASE_ORDER:
                    v = r.get("phases", {}).get(p, 0.0)
                    bar += _PHASE_GLYPH[p] * int(round(v / e2e * width))
            crit = r.get("critical_path", [])
            out.append("    blk %-4s [%-*s] %9.6f s  crit: %s" % (
                r.get("blk", "?"), width, bar[:width], e2e,
                " > ".join(crit[:3]) if crit else "-"))
    verify = rep.get("verify", {})
    if verify.get("windows"):
        out.append(
            "  verify windows (wall-clock sub-account): %d window(s)  "
            "%d rows  divert share %.4f" % (
                verify["windows"], verify["rows"],
                verify["divert_share"]))
        for lane, d in sorted(verify.get("lanes", {}).items()):
            out.append(
                "    lane %-3s %4d window(s)  %6d rows  "
                "wait %8.3f ms  stage %8.3f ms  compute %8.3f ms%s" % (
                    lane, d["windows"], d["rows"], d["wait_ms"],
                    d["stage_ms"], d["compute_ms"],
                    "  [diverted %d]" % d["diverted_rows"]
                    if d["diverted_rows"] else ""))
    dom = rep.get("dominant")
    if dom:
        lane = ("  (lane %s)" % dom["lane"]) if "lane" in dom else ""
        out.append("  dominant: %s at %.2f%% of commit latency%s" % (
            dom["phase"], dom["share"] * 100.0, lane))
    return "\n".join(out)


# -- ingress provenance ledger --------------------------------------------

def render_ledger(rep: dict) -> str:
    """Text view of a ledger report (``LedgerAssembler.report`` /
    ``ledger.assemble``): per-origin cost table, reject-ratio ranking,
    and the dominant-offender verdict line."""
    out = ["ingress provenance ledger — %d snapshot(s), %d node(s)" % (
        rep.get("snapshots", 0), rep.get("nodes", 0))]
    origins = rep.get("origins") or []
    if not origins:
        out.append("  (no ingress activity recorded)")
        return "\n".join(out)
    out.append("  cumulative deltas: rows %d  admits %d  rejects %d  "
               "drops %d" % (
                   rep.get("rows_delta_total", 0),
                   rep.get("admits_total", 0),
                   rep.get("rejects_total", 0),
                   rep.get("drops_total", 0)))
    out.append("  per-origin decayed cost (cluster-merged, heaviest "
               "first):")
    out.append("    %-14s %8s %8s %8s %7s %6s %6s %9s %9s %5s" % (
        "origin", "rows", "admits", "rejects", "drops", "defer",
        "hit%", "device", "host", "snd"))
    for row in origins:
        hits = float(row.get("cache_hits", 0.0))
        misses = float(row.get("cache_misses", 0.0))
        hit_pct = (100.0 * hits / (hits + misses)
                   if hits + misses > 0 else 0.0)
        out.append(
            "    %-14s %8.1f %8.1f %8.1f %7.1f %6.1f %5.1f%% "
            "%7.2fms %7.2fms %5d" % (
                str(row.get("origin", "?"))[:14], row.get("rows", 0.0),
                row.get("admits", 0.0), row.get("rejects", 0.0),
                row.get("drops", 0.0), row.get("deferred", 0.0),
                hit_pct, row.get("device_ms", 0.0),
                row.get("host_ms", 0.0), row.get("senders", 0)))
    ranked = sorted(
        (r for r in origins if r.get("reject_ratio", 0.0) > 0.0),
        key=lambda r: (-float(r.get("reject_ratio", 0.0)),
                       str(r.get("origin", ""))))
    if ranked:
        out.append("  reject-ratio ranking: " + "  ".join(
            "%s %.2f" % (r["origin"], r["reject_ratio"])
            for r in ranked[:5]))
    dom = rep.get("dominant")
    if dom:
        out.append(
            "  dominant offender: %s at %.2f%% of discarded work "
            "(rejects %.1f, drops %.1f)" % (
                dom["origin"], dom["share"] * 100.0, dom["rejects"],
                dom["drops"]))
    else:
        out.append("  dominant offender: - (abuse below floor)")
    return "\n".join(out)


# -- continuous CPU profile -----------------------------------------------

def render_profile(rep: dict) -> str:
    """Text view of a profile report (``ProfileAssembler.report`` /
    ``profiler.assemble``): per-phase CPU attribution with shares, the
    per-role split, and the top self-time functions — the table that
    answers "what fraction of pool_admit CPU is decode vs LRU probe vs
    lock wait" down to named functions."""
    out = ["continuous profiler — %d sample(s), %d report(s), "
           "%d node(s)" % (rep.get("samples", 0), rep.get("reports", 0),
                           len(rep.get("nodes") or {}))]
    samples = int(rep.get("samples", 0))
    if samples <= 0:
        out.append("  (no profile samples recorded — plane disabled or "
                   "run too short)")
        return "\n".join(out)
    out.append("  sampling: %.0f Hz  dropped %d" % (
        float(rep.get("hz", 0.0)), rep.get("dropped", 0)))
    out.append("  per-phase CPU attribution (share of sampled wall "
               "time):")
    by_phase = rep.get("by_phase") or {}
    for ph, n in sorted(by_phase.items(), key=lambda kv: (-kv[1], kv[0])):
        share = 100.0 * n / samples
        out.append("    %-16s %8d  %5.1f%%  %s" % (
            ph, n, share, "#" * int(share / 2.0)))
    host_share = rep.get("host_cpu_share_of_verify_pct")
    if host_share is not None:
        out.append("  host CPU share of verify pipeline: %.2f%%  "
                   "(pool_* / (pool_* + verify_*))" % host_share)
    by_role = rep.get("by_role") or {}
    if by_role:
        out.append("  per-role: " + "  ".join(
            "%s %.1f%%" % (role, 100.0 * n / samples)
            for role, n in sorted(by_role.items(),
                                  key=lambda kv: (-kv[1], kv[0]))))
    top = rep.get("top_self") or []
    if top:
        out.append("  top self-time functions:")
        out.append("    %-52s %-14s %7s %7s" % (
            "function", "phase", "samples", "share"))
        for row in top:
            out.append("    %-52s %-14s %7d %6.2f%%" % (
                str(row.get("func", "?"))[:52],
                str(row.get("phase", "?"))[:14],
                int(row.get("samples", 0)),
                float(row.get("pct", 0.0))))
    return "\n".join(out)


def render_devices(rep: dict, width: int = 30) -> str:
    """Text view of a device-efficiency report
    (``DevstatsAssembler.report`` / ``devstats.assemble``): per-lane
    goodput bars, the waste decomposition (pad/cache/dedup/hedge plus
    host rescues), HBM watermarks when the backend reports them, and
    the fraction-of-roofline anchored to the captured TPU bench."""
    tot = rep.get("totals") or {}
    out = ["device efficiency — %d window(s), %d report(s), "
           "%d device(s)" % (tot.get("windows", 0),
                             rep.get("reports", 0),
                             len(rep.get("devices") or {}))]
    if not tot.get("windows"):
        out.append("  (no device windows recorded — scheduler idle or "
                   "plane disabled)")
        return "\n".join(out)
    gp = tot.get("goodput_ratio")
    if gp is not None:
        bar = "#" * int(round(gp * width))
        out.append("  cluster goodput: %6.2f%%  |%-*s|  "
                   "(%d useful rows / %d padded device rows)" % (
                       100.0 * gp, width, bar,
                       tot.get("rows", 0), tot.get("bucket_rows", 0)))
    waste = rep.get("waste") or {}
    out.append("  waste decomposition (rows):")
    for key, label in (("pad_rows", "padding burned"),
                       ("cache_rows", "cache served (free)"),
                       ("dedup_rows", "in-flight deduped (free)"),
                       ("hedge_wasted_rows", "hedge losers burned"),
                       ("diverted_rows", "host rescued")):
        out.append("    %-26s %8d" % (label, int(waste.get(key, 0))))
    out.append("  per-lane goodput:")
    for dev, d in sorted((rep.get("devices") or {}).items(),
                         key=lambda kv: int(kv[0])):
        gp = d.get("goodput_ratio")
        bar = "#" * int(round((gp or 0.0) * width))
        frac = d.get("fraction_of_roofline")
        rate = d.get("rows_per_s")
        out.append(
            "    lane %-3s %4d window(s)  %6d rows  "
            "goodput %s  |%-*s|%s%s" % (
                dev, d.get("windows", 0), d.get("rows", 0),
                ("%6.2f%%" % (100.0 * gp)) if gp is not None else "     -",
                width, bar,
                ("  %s rows/s" % rate) if rate is not None else "",
                ("  %5.2f%% of roofline" % (100.0 * frac))
                if frac is not None else ""))
        mem = d.get("mem")
        if mem:
            out.append(
                "             HBM: in use %s B  peak %s B  limit %s B"
                % (mem.get("bytes_in_use", "-"),
                   mem.get("peak_bytes", "-"),
                   mem.get("limit_bytes", "-")))
        for bucket, b in sorted((d.get("buckets") or {}).items(),
                                key=lambda kv: int(kv[0])):
            ceil = b.get("ceiling_rows_per_s")
            bgp = b.get("goodput_ratio")
            out.append(
                "             bucket %-6s %4d window(s)  %6d rows  "
                "goodput %s%s" % (
                    bucket, b.get("windows", 0), b.get("rows", 0),
                    ("%6.2f%%" % (100.0 * bgp))
                    if bgp is not None else "     -",
                    ("  ceiling %.1f rows/s" % ceil)
                    if ceil is not None else ""))
    src = rep.get("roofline_source")
    if src:
        out.append("  roofline ceilings from %s" % src)
    return "\n".join(out)


# -- collection -----------------------------------------------------------

def collect_live(cluster) -> dict[str, list[dict]]:
    """Poll every node of a (sim) cluster for its journal."""
    return cluster.journals()


def dump_journals(by_node: dict[str, list[dict]], outdir: str) -> list[str]:
    """Write each node's collected events as ``<name>.journal.jsonl``
    (same row format as a real node's datadir ``journal.jsonl``)."""
    os.makedirs(outdir, exist_ok=True)
    paths = []
    for name in sorted(by_node):
        path = os.path.join(outdir, f"{name}.journal.jsonl")
        with open(path, "w", encoding="utf-8") as fh:
            for ev in by_node[name]:
                fh.write(json.dumps(ev, sort_keys=True) + "\n")
        paths.append(path)
    return paths


def load_journals(indir: str) -> dict[str, list[dict]]:
    """Load dumped journals back: ``<name>.journal.jsonl`` files (our
    own dumps) and ``<nodedir>/journal.jsonl`` (real-cluster datadirs,
    node name = directory name)."""
    by_node: dict[str, list[dict]] = {}
    for path in sorted(glob.glob(os.path.join(indir, "*.journal.jsonl"))):
        name = os.path.basename(path)[: -len(".journal.jsonl")]
        by_node[name] = journal_mod.load(path)
    for path in sorted(glob.glob(os.path.join(indir, "*", "journal.jsonl"))):
        name = os.path.basename(os.path.dirname(path))
        by_node.setdefault(name, []).extend(journal_mod.load(path))
    return by_node


def run_sim(nodes: int = 4, blocks: int = 6, seconds: float = 600.0,
            seed: int = 0, profile_hz: float | None = None):
    """Run a virtual-time sim cluster until every node holds ``blocks``
    blocks; returns the cluster (stopped virtual clock, journals full).
    The continuous profiling plane rides along by default
    (``profile_hz=None`` resolves EGES_PROFILE_HZ, default ~97; pass
    ``0`` to disable) so a bare ``python -m harness.observatory``
    renders the per-phase CPU attribution table; the sampler is joined
    before journals are collected, so the summary stays a pure
    function of the returned events.  The device-efficiency plane
    rides along too: a 2-lane JAX-free host mesh gives the shared
    scheduler real per-device window lanes to account, so the device
    section renders goodput/waste/roofline on a bare run."""
    from eges_tpu.sim.cluster import SimCluster

    cluster = SimCluster(nodes, seed=seed, txn_per_block=5, txpool=True,
                         mesh_devices=2)
    cluster.enable_profiling(hz=profile_hz)
    cluster.enable_devstats(interval_s=30.0)
    cluster.start()
    _inject_pool_load(cluster)
    cluster.run(seconds, stop_condition=lambda: cluster.min_height() >= blocks)
    cluster.stop_profiling()
    cluster.stop_devstats()
    return cluster


def _inject_pool_load(cluster, rows: int = 96) -> None:
    """Feed signed transactions through node0's txpool so the profiler
    has live pool_admit extents to sample: a bare consensus sim never
    calls ``add_remotes``, and the consensus phases are record_span()'d
    after the fact from virtual-clock durations (no live extent), so
    without real ingest the per-phase table renders 100% untagged.  The
    batch is sized exactly to ``max_batch`` so the flush — per-entry
    sender recovery included — runs synchronously inside the
    ``txpool.ingest`` span on this thread, where the sampler can
    attribute it."""
    from eges_tpu.core.types import Transaction

    pool = cluster.nodes[0].node.txpool
    if pool is None:
        return
    pool.max_batch = rows
    priv = bytes([11]) * 32
    txns = [Transaction(nonce=i, gas_limit=21_000, to=bytes(20),
                        value=0).signed(priv, chain_id=1)
            for i in range(rows)]
    admit_remotes(pool, txns)


# -- rendering ------------------------------------------------------------

def render(summary: dict, net: dict | None = None) -> str:
    def _ms(v) -> str:
        # empty event series produce None percentiles; render a dash
        # instead of "None ms"
        return "-" if v is None else str(v)

    out = []
    out.append("consensus observatory — %d node(s), %d block(s)" % (
        len(summary["nodes"]), summary["blocks"]))
    if net:
        out.append("  net: " + "  ".join(
            "%s %d" % (k, net[k]) for k in sorted(net)))
    e, a = summary["election"], summary["ack_quorum"]
    out.append("  elections   : %4d  p50 %s ms  p99 %s ms" % (
        e["count"], _ms(e["p50_ms"]), _ms(e["p99_ms"])))
    out.append("  ack quorums : %4d  p50 %s ms  p99 %s ms" % (
        a["count"], _ms(a["p50_ms"]), _ms(a["p99_ms"])))
    out.append("  version bumps: %d (%.4f per block)" % (
        summary["version_bumps"], summary["version_bump_rate"]))
    out.append("  max commit gap: %.3f s; stalls(> threshold): %d" % (
        summary["max_commit_gap_s"], len(summary["stalls"])))
    for s in summary["stalls"]:
        out.append("    STALL before blk %d: %.3f s" % (s["blk"], s["gap_s"]))
    if summary["commit_lag"]:
        out.append("  commit lag behind cluster-first:")
        for name, lag in summary["commit_lag"].items():
            out.append("    %-8s mean %8.6f s  max %8.6f s" % (
                name, lag["mean_s"], lag["max_s"]))
    else:
        out.append("  commit lag behind cluster-first: - (no commits)")
    out.append("  election timeline:")
    for blk, rows in summary["election_timeline"].items():
        out.append("    blk %s:" % blk)
        for r in rows:
            out.append("      %12.6f  %s" % (r["ts"], r["line"]))
    if summary.get("fault_timeline"):
        out.append("  fault timeline:")
        for r in summary["fault_timeline"]:
            out.append("      %12.6f  %s" % (r["ts"], r["line"]))
    if summary.get("verifier_mesh"):
        out.append("  verifier mesh dispatch (per device):")
        for dev, d in summary["verifier_mesh"].items():
            out.append(
                "    device %-3s %4d window(s)  %6d rows  "
                "occupancy %.4f  diverted %d" % (
                    dev, d["windows"], d["rows"],
                    d["mean_occupancy"], d["diverted"]))
    if summary.get("verifier_aot"):
        out.append("  verifier AOT prewarm (per node):")
        for name, d in summary["verifier_aot"].items():
            out.append(
                "    %-8s %d prewarm(s)  loads %d (%.3f s)  "
                "compiles %d (%.3f s)  cold start %.3f s" % (
                    name, d["events"], d["aot_loads"], d["load_s"],
                    d["aot_compiles"], d["compile_s"],
                    d["cold_start_s"]))
    if summary.get("telemetry_samples"):
        out.append("  telemetry samples: " + "  ".join(
            "%s %d" % (name, n)
            for name, n in summary["telemetry_samples"].items()))
    if summary.get("slo_alerts"):
        out.append("  SLO alert timeline:")
        for r in summary["slo_alerts"]:
            out.append(
                "      %12.6f  %s %s  burn fast %.2f / slow %.2f" % (
                    r["ts"], r["type"].removeprefix("slo_"),
                    r["objective"], r["burn_fast"], r["burn_slow"]))
    if summary.get("statesync"):
        out.append("  state sync (per node):")
        for name, d in summary["statesync"].items():
            out.append(
                "    %-8s checkpoints %d (last %d B)  restarts %d "
                "(anchor blk %d, replayed %d)" % (
                    name, d["checkpoints"], d["checkpoint_bytes"],
                    d["restarts"], d["snapshot_blk"], d["replayed"]))
            if (d["adopted"] or d["resumes"] or d["poisoned"]
                    or d["reanchors"] or d["rotates"] or d["aborts"]):
                out.append(
                    "    %-8s live sync: adopted %d  resumes %d  "
                    "poisoned %d  reanchors %d  rotates %d  aborts %d"
                    % ("", d["adopted"], d["resumes"], d["poisoned"],
                       d["reanchors"], d["rotates"], d["aborts"]))
    if summary.get("unknown_events"):
        out.append("  unknown event types (skipped): " + "  ".join(
            "%s %d" % (typ, n)
            for typ, n in summary["unknown_events"].items()))
    if summary.get("anatomy") is not None:
        out.append(render_anatomy(summary["anatomy"]))
    if summary.get("ledger") is not None:
        out.append(render_ledger(summary["ledger"]))
    if summary.get("profile") is not None:
        out.append(render_profile(summary["profile"]))
    if summary.get("devstats") is not None:
        out.append(render_devices(summary["devstats"]))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replay", metavar="DIR", default=None,
                    help="rebuild the summary offline from dumped "
                         "journal JSONL instead of running a sim")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=600.0,
                    help="virtual-time budget for the sim run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dump", metavar="DIR", default=None,
                    help="dump collected journals as JSONL for --replay")
    ap.add_argument("--stall-gap", type=float, default=10.0,
                    help="first-commit gap (s) that counts as a stall")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    args = ap.parse_args(argv)

    net = None
    if args.replay:
        by_node = load_journals(args.replay)
        if not by_node:
            print("no *.journal.jsonl under %s" % args.replay,
                  file=sys.stderr)
            return 2
    else:
        cluster = run_sim(args.nodes, args.blocks, args.seconds, args.seed)
        by_node = collect_live(cluster)
        net = cluster.net_stats()
        if args.dump:
            for p in dump_journals(by_node, args.dump):
                print("dumped %s" % p, file=sys.stderr)

    summary = summarize(by_node, stall_gap_s=args.stall_gap)
    if args.json and net is not None:
        summary = dict(summary, net=net)
    try:
        print(json.dumps(summary, sort_keys=True) if args.json
              else render(summary, net=net))
    except BrokenPipeError:
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())
