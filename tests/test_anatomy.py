"""Commit-anatomy profiler tests.

Covers: the critical-path assembler's per-block phase math and
critical-path ordering (``harness/anatomy.py``), the verify-divert
dominance verdict (singleton host-recoveries excluded from the divert
share, lane attribution deterministic), report determinism across a
JSON round-trip, the SLO engine's dominant-phase attachment on firing
alerts, the shared RPC limit clamp pinned across all three bounded
RPCs (``thw_traces`` / ``thw_journal`` / ``thw_flight``), the bench's
``platform_detail`` stamp, the anatomy waterfall rendering, and (slow)
the chaos attribution scenario blaming the injected fault.
"""

import json

import pytest

from harness.anatomy import (PHASE_ORDER, AnatomyAssembler, assemble)


def _synthetic_block(blk: int = 5, base: float = 10.0):
    """One fully-instrumented committed block across three nodes."""
    return {
        "n0": [
            {"type": "commit_anatomy", "stage": "pool", "blk": blk,
             "ts": base + 1.6, "node": "n0", "seq": 0, "count": 3,
             "t_first_ingest": base, "t_last_admit": base + 0.4,
             "ingest_to_admit_s": 0.4},
            {"type": "commit_anatomy", "stage": "seal", "blk": blk,
             "ts": base + 1.55, "node": "n0", "seq": 1,
             "t_seal_start": base + 1.0, "seal_s": 0.55,
             "election_s": 0.25, "ack_s": 0.2},
            {"type": "block_committed", "blk": blk, "ts": base + 1.6,
             "node": "n0", "seq": 2},
        ],
        "n1": [{"type": "block_committed", "blk": blk, "ts": base + 1.8,
                "node": "n1", "seq": 0}],
        "n2": [{"type": "block_committed", "blk": blk, "ts": base + 1.95,
                "node": "n2", "seq": 0}],
    }


def test_assembler_per_block_phase_math_and_critical_path():
    rep = assemble(_synthetic_block())
    assert rep["blocks"] == 1
    rec = rep["per_block"][0]
    assert rec["blk"] == 5 and rec["proposer"] == "n0"
    assert rec["commits"] == 3
    # the causal chain telescopes: ingest 10.0 -> admit 10.4 -> seal
    # start 11.0 (election .25 + ack .2 + other .1) -> seal done 11.55
    # -> first commit 11.6 -> last commit 11.95
    assert rec["phases"] == {
        "pool_admit": 0.4, "pool_queue": 0.6, "election": 0.25,
        "ack_quorum": 0.2, "seal_other": 0.1, "publish": 0.05,
        "propagation": 0.35}
    assert rec["e2e_s"] == 1.95
    assert abs(sum(rec["phases"].values()) - rec["e2e_s"]) < 1e-6
    # durations all distinct: the critical path is strictly descending
    assert rec["critical_path"] == [
        "pool_queue", "pool_admit", "propagation", "election",
        "ack_quorum", "seal_other", "publish"]
    assert rep["commit_p50_ms"] == rep["commit_p99_ms"] == 1950.0
    assert set(rep["phases"]) <= set(PHASE_ORDER)
    assert rep["phases"]["pool_queue"]["share"] == 0.3077
    assert rep["dominant"] == {"phase": "pool_queue", "share": 0.3077}


def test_assembler_verify_divert_dominance_excludes_singletons():
    asm = AnatomyAssembler()
    # lane 0: three multi-row windows, all breaker-diverted
    for i in range(3):
        asm.ingest({"type": "commit_anatomy", "stage": "verify_window",
                    "ts": float(i), "node": "n0", "seq": i, "lane": 0,
                    "rows": 4, "reason": "kick", "diverted": True,
                    "wait_ms": 1.0, "stage_ms": 1.0, "compute_ms": 1.0})
    # singleton windows host-recover BY DESIGN (healthy device or not):
    # they must not dilute the divert share
    for i in range(5):
        asm.ingest({"type": "commit_anatomy", "stage": "verify_window",
                    "ts": 10.0 + i, "node": "n0", "seq": 10 + i,
                    "lane": 0, "rows": 1, "reason": "kick",
                    "diverted": False, "wait_ms": 0.5, "stage_ms": 0.1,
                    "compute_ms": 0.1})
    # lane 1: one healthy multi-row window
    asm.ingest({"type": "commit_anatomy", "stage": "verify_window",
                "ts": 20.0, "node": "n0", "seq": 20, "lane": 1,
                "rows": 2, "reason": "full", "diverted": False,
                "wait_ms": 1.0, "stage_ms": 1.0, "compute_ms": 1.0})
    v = asm.verify_summary()
    assert v["windows"] == 9 and v["rows"] == 19
    assert v["eligible_rows"] == 14 and v["diverted_rows"] == 12
    assert v["divert_share"] == round(12 / 14, 4)
    assert v["lanes"]["0"]["diverted_rows"] == 12
    # 12/14 >= 0.5: the verify path is named, with the guilty lane
    dom = asm.dominant()
    assert dom["phase"] == "verify_divert" and dom["lane"] == "0"
    assert dom["share"] == round(12 / 14, 4)


def test_assembler_report_survives_json_round_trip():
    by_node = _synthetic_block()
    a = json.dumps(assemble(by_node), sort_keys=True)
    b = json.dumps(assemble(json.loads(json.dumps(by_node))),
                   sort_keys=True)
    assert a == b


def test_render_anatomy_waterfall_and_attribution_table():
    from harness import observatory

    text = observatory.render_anatomy(assemble(_synthetic_block()))
    assert "commit anatomy — 1 block(s)" in text
    assert "phase attribution" in text
    assert "pool_queue" in text and "propagation" in text
    assert "blk 5" in text
    assert "dominant: pool_queue at 30.77%" in text


def test_slo_firing_alert_carries_dominant_phase():
    from harness.slo import SLOEngine

    hint = {"phase": "verify_divert", "share": 0.61, "lane": "3"}
    eng = SLOEngine()
    eng.phase_hint = lambda: dict(hint)
    eng.ingest({"type": "fault_breaker", "ts": 0.0, "state": "open",
                "device": 0})
    for k in range(1, 8):
        eng.evaluate(5.0 * k)
    firing = [e for e in eng.alerts() if e["type"] == "slo_firing"]
    assert firing, eng.alerts()
    assert firing[0]["phase"] == "verify_divert"
    assert firing[0]["phase_share"] == 0.61
    assert firing[0]["lane"] == "3"
    # pending/resolved transitions stay hint-free
    assert all("phase" not in e for e in eng.alerts()
               if e["type"] != "slo_firing")

    # a hint that has no data yet must not decorate (or break) firing
    eng2 = SLOEngine()
    eng2.phase_hint = lambda: None
    eng2.ingest({"type": "fault_breaker", "ts": 0.0, "state": "open",
                 "device": 0})
    for k in range(1, 8):
        eng2.evaluate(5.0 * k)
    firing2 = [e for e in eng2.alerts() if e["type"] == "slo_firing"]
    assert firing2 and "phase" not in firing2[0]


def test_rpc_limit_clamp_shared_across_all_three_rpcs():
    from eges_tpu.rpc.server import RpcServer
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils import tracing
    from eges_tpu.utils.limits import (RPC_LIMIT_MAX, RPC_LIMIT_MIN,
                                       clamp_rpc_limit)

    # the shared helper pins the bounds once
    assert (RPC_LIMIT_MIN, RPC_LIMIT_MAX) == (1, 4096)
    assert clamp_rpc_limit(0) == 1
    assert clamp_rpc_limit(-5) == 1
    assert clamp_rpc_limit(10**9) == 4096
    assert clamp_rpc_limit(17) == 17
    assert clamp_rpc_limit("12") == 12
    assert clamp_rpc_limit(None) == 1
    assert clamp_rpc_limit("junk") == 1

    c = SimCluster(3, seed=1)
    c.start()
    c.run(120, stop_condition=lambda: c.min_height() >= 2)
    for sn in c.nodes:
        sn.node.stop()
    rpc = RpcServer(c.nodes[0].chain, node=c.nodes[0].node)
    # seed the span ring so thw_traces has more than one row to clamp
    for i in range(3):
        tracing.DEFAULT.record_span("clamp-test", 0.001, idx=i)

    # limit=0 clamps up to exactly one row on every bounded RPC
    assert len(rpc.dispatch("thw_journal", [0])) == 1
    assert len(rpc.dispatch("thw_traces", [0])) == 1
    # the flight recorder may legitimately be empty (no scheduler) but
    # must never exceed the clamped limit
    assert len(rpc.dispatch("thw_flight", [0])) <= 1
    # an absurd limit clamps down: no RPC ships more than 4096 rows
    for method in ("thw_journal", "thw_traces", "thw_flight"):
        assert len(rpc.dispatch(method, [10**9])) <= 4096


@pytest.mark.slow
def test_chaos_commit_attribution_blames_the_injected_fault():
    from harness import chaos

    res = chaos.run_scenario("commit_attribution", seed=0, fast=True)
    assert res["ok"], {k: v for k, v in res.items() if k != "journals"}
    assert res["checks"]["propagation_blamed"]
    assert res["checks"]["verify_divert_blamed"]
    assert res["anatomy"]["blackout_divert_share"] >= 0.5
    same, _, _ = chaos.check_determinism("commit_attribution", seed=0,
                                         fast=True)
    assert same
