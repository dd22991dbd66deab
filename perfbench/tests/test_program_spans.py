"""The program's spans as the benchmark reads them: in the trace, where they
name an idle gap of the chip, and in the registry, where their self time is
a share of the window.  ``data/spans_gap.json`` is a quarter of a second of a
traced ``c1024.mixed-backlog`` run on a TPU v5e (PR 25): the device's
operations on both sides of one idle gap (the eight nearest on each side,
and the modules' line) and every host event of 1 ms and more around it,
names cut to 120 characters, times from the cut's start."""

import json
import os

import pytest

from perfbench import harness, trace
from perfbench.readers import (flight_field_opt, histogram_share,
                               journal_count, journal_mean)

ROWS = json.load(open(os.path.join(harness.HERE, "tests", "data",
                                   "spans_gap.json")))


def test_a_program_span_inside_the_drivers_annotation_names_the_gap():
    red = trace.reduce(ROWS, None, program="jit_call|ecrecover")
    label, seconds = red["idle_gaps"][0]
    assert seconds == pytest.approx(0.121369, rel=1e-4)
    # the driver's own ``pool_admit`` covers the gap too (109 ms), and so
    # does the pool's flush (114 ms); the shortest span over half of it is
    # the admission of the flushed slice (75.5 ms)
    ops = sorted((r for r in ROWS if r[1] == trace.OPS_LINE),
                 key=lambda r: r[3])
    gap = max((b[3] - (a[3] + a[4]), (a[3] + a[4], b[3]))
              for a, b in zip(ops, ops[1:]))[1]
    over = {r[2]: r[4] for r in ROWS if not trace.DEVICE_PLANE.match(r[0])
            and (min(gap[1], r[3] + r[4]) - max(gap[0], r[3])) * 2
            >= gap[1] - gap[0]}
    assert {"pool_admit", "txpool.flush", "txpool.admit_window"} <= set(over)
    assert over["txpool.admit_window"] < over["pool_admit"] \
        < over["txpool.flush"]
    assert label == "txpool.admit_window"
    # every host event of the cut is a program span, a driver's annotation
    # or jax's own: no Python frame
    assert not any(".py:" in r[2] for r in ROWS)


def test_a_share_is_the_histograms_time_in_the_window_over_its_length():
    a, b = "span.self_seconds;name=a", "span.self_seconds;name=b"
    obs = {"window_s": 40.0,
           "before": {a: {"count": 10, "mean": 0.5}},
           "after": {a: {"count": 30, "mean": 0.3},
                     b: {"count": 4, "mean": 1.0}}}
    # a: 30 * 0.3 - 10 * 0.5 = 4 s; b: 4 s; of 40 s
    assert histogram_share.read(obs, names=[a]) == pytest.approx(10.0)
    assert histogram_share.read(obs, names=[a, b]) == pytest.approx(20.0)
    # a program without the span has nothing to read: None, never 0
    assert histogram_share.read(obs, names=["span.self_seconds;name=c"]) \
        is None
    assert histogram_share.read({**obs, "window_s": 0}, names=[a]) is None


def test_a_flight_field_the_program_does_not_write_reads_as_nothing():
    old = [{"t_done": 1.0, "klass": "consensus", "stage_ms": 2.0},
           {"t_done": 2.0, "klass": "consensus", "stage_ms": 4.0}]
    obs = {"flights": old, "t_begin": 0.0, "t_end": 3.0}
    assert flight_field_opt.read(obs, field="resolve_ms", q=0.5,
                                 klass="consensus") is None
    assert flight_field_opt.read(obs, field="stage_ms", q=0.5,
                                 klass="consensus") == pytest.approx(3.0)
    new = [dict(f, resolve_ms=ms) for f, ms in zip(old, (1.0, 5.0))]
    assert flight_field_opt.read({**obs, "flights": new},
                                 field="resolve_ms", q=0.5) == \
        pytest.approx(3.0)
    assert flight_field_opt.read({**obs, "flights": []},
                                 field="resolve_ms", q=0.5) is None


def test_a_phase_is_the_mean_over_every_proposers_events_in_the_window():
    won = [{"type": "election_won", "node": n, "ts": ts, "dt": dt}
           for n, ts, dt in (("aa", 0.5, 9.0),     # before the window
                             ("aa", 1.5, 0.010), ("bb", 2.0, 0.030),
                             ("cc", 2.5, 0.080),
                             ("bb", 9.5, 9.0))]    # after it
    other = [{"type": "validate_quorum", "node": "bb", "ts": 2.0,
              "dt": 0.2},
             {"type": "election_resend", "node": "bb", "ts": 2.0}]
    obs = {"journal": won + other, "t_begin": 1.0, "t_end": 3.0}
    assert journal_mean.read(obs, types=["election_won"], field="dt",
                             scale=1e3) == pytest.approx(40.0)
    assert journal_mean.read(obs, types=["validate_quorum"], field="dt",
                             scale=1e3) == pytest.approx(200.0)
    # whichever node proposed: a window in which one node (the chip node,
    # say) won nothing still reads the others'
    assert journal_mean.read(
        {**obs, "journal": [e for e in won if e["node"] != "aa"]},
        types=["election_won"], field="dt") == pytest.approx(0.055)
    # nothing to read is None, never 0: no journal, no such event in the
    # window, an event without the field
    assert journal_mean.read({**obs, "journal": None},
                             types=["election_won"], field="dt") is None
    assert journal_mean.read({**obs, "t_begin": 3.5, "t_end": 9.0},
                             types=["election_won"], field="dt") is None
    assert journal_mean.read(obs, types=["election_resend"],
                             field="dt") is None
    # a count, beside it, is 0 where nothing happened
    assert journal_count.read(obs, types=["election_resend"]) == 1
    assert journal_count.read(obs, types=["validate_retry"]) == 0


def test_the_span_metrics_sit_in_their_cells():
    bench = harness.load_json("BENCHMARK.json")
    per = {m["name"]: m for m in bench["per_layer"]}
    reports = {w["name"]: {e["name"] for e in harness.Cell(
        w["name"], rehearse=False).end_to_end()} for w in bench["workloads"]}

    def sits_in(metric: str, cell: str) -> bool:
        """The metric lists ``cell``, and every cell it lists reports the
        end-to-end metric it moves (a later PR may list more)."""
        m = per[metric]
        return cell in m["workloads"] and all(
            m["moves"] in reports[w] for w in m["workloads"])

    shares = ["decode_share", "pool_ingest_share", "pool_flush_share",
              "pool_admit_share", "pool_evict_share", "sched_submit_share",
              "sched_stage_share", "sched_collect_share",
              "sched_resolve_share"]
    for name in shares:
        assert sits_in(name + ".rows", "c1024.mixed-backlog")
        spec = harness.metric_file(name + ".rows")
        assert spec["reader"] == "histogram_share"
        assert all(n.startswith("span.self_seconds;name=")
                   for n in spec["args"]["names"])
    for name in ("vote_submit_ms", "vote_await_ms", "sched_stage_ms",
                 "sched_resolve_ms"):
        assert sits_in(name + ".vote", "c1024.mixed-steady")
    for name in ("election_ms", "ack_ms", "chain_insert_ms",
                 "confirm_handle_ms", "rpc_handle_ms", "loop_lag_ms",
                 "election_resends"):
        assert sits_in(name + ".lat", "ref3.signed-steady")
    # the proposer's two phases are read where every proposer writes them,
    # the journal, and not in the chip node's registry, which is empty in
    # a run where node 0 wins no election (3 of 95 blocks are its own)
    for name, event in (("election_ms", "election_won"),
                        ("ack_ms", "validate_quorum")):
        spec = harness.metric_file(name + ".lat")
        assert spec["reader"] == "journal_mean"
        assert spec["args"] == {"types": [event], "field": "dt",
                                "scale": 1000.0}
    # the two burst metrics read the burst's own histograms, not the
    # mean over the 32-row election call as well
    for name, span in (("vote_submit_ms", "sched.submit"),
                       ("vote_await_ms", "sched.await")):
        assert harness.metric_file(name + ".vote")["args"]["name"] == \
            f"span.seconds;name={span},class=consensus,size=burst"
    assert harness.metric_file("sched_resolve_ms.vote")["args"] == {
        "field": "resolve_ms", "q": 0.5, "klass": "consensus"}
