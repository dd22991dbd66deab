"""PR 38's per-layer metrics: a span's CPU time beside its wall time, the
process's CPU by thread role, a window's queue wait split at the
dispatcher's pop.  Each sits in the cells it was listed for, finds its
file and its reader, and reads nothing (None, never 0, never an error)
on a program that lacks the histogram family, the gauges or the flight
fields, as the parent commit does.  ISSUE 38 named three of them
``.zipf`` / ``.quorum``; ``test_validator_cell.py`` and
``test_votes_cell.py`` pin those two sets with ``==``, and this PR may
edit no file the benchmark has, so they carry the suffix of what they
move instead (``.rows``, ``.vote``) and list their one cell."""

import pytest

from perfbench import harness
from perfbench.readers import counter_share

BACKLOG = ["c1024.mixed-backlog", "c1024x4.mixed-backlog", "c64.zipf-backlog"]
STEADY = ["c1024.mixed-steady", "c1024x4.mixed-steady", "c256.votes-steady"]
REF3 = ["ref3.signed-steady"]

# metric -> (cells, layer, reader, its wall-time twin or None)
SHARES = {
    "decode_cpu_share": ("rpc and ingress", "decode_share"),
    "pool_admit_cpu_share": ("pool", "pool_admit_share"),
    "pool_evict_cpu_share": ("pool", "pool_evict_share"),
    "sched_submit_cpu_share": ("scheduler", "sched_submit_share"),
    "sched_stage_cpu_share": ("scheduler", "sched_stage_share"),
    "sched_collect_cpu_share": ("scheduler", "sched_collect_share"),
    "sched_resolve_cpu_share": ("scheduler", "sched_resolve_share"),
}
TABLE = {
    **{f"{name}.rows": (BACKLOG, layer, "histogram_share", twin + ".rows")
       for name, (layer, twin) in SHARES.items()},
    "block_senders_cpu_share.rows": (["c64.zipf-backlog"], "consensus",
                                     "histogram_share",
                                     "block_senders_share.zipf"),
    "ack_handle_cpu_us.vote": (["c256.votes-steady"], "consensus",
                                 "histogram_mean", "ack_handle_us.quorum"),
    "quorum_verify_cpu_ms.vote": (["c256.votes-steady"], "consensus",
                                    "histogram_mean",
                                    "quorum_verify_ms.quorum"),
    **{f"{name}{suffix}": (cells, "interpreter", "counter_share", None)
       for name in ("process_cpu_share", "threads_cpu_share")
       for suffix, cells in ((".rows", BACKLOG), (".vote", STEADY),
                             (".lat", REF3))},
    "loop_cpu_share.lat": (REF3, "consensus", "counter_share",
                           "loop_lag_ms.lat"),
    **{f"{name}{suffix}": (cells, "scheduler", "flight_field_opt",
                           "sched_wait_ms" + suffix)
       for name in ("sched_flush_ms", "sched_lane_wait_ms")
       for suffix, cells in ((".vote", STEADY), (".rows", BACKLOG))},
}


def _per_layer() -> dict:
    return {m["name"]: m
            for m in harness.load_json("BENCHMARK.json")["per_layer"]}


def test_there_are_twenty_one_and_they_stand_at_the_end():
    assert len(TABLE) == 21
    names = [m["name"] for m in
             harness.load_json("BENCHMARK.json")["per_layer"]]
    assert set(TABLE) <= set(names)
    # appended: nothing that was there stands behind the first of them
    first = min(names.index(n) for n in TABLE)
    assert set(names[first:]) >= set(TABLE)
    assert not set(names[:first]) & set(TABLE)


@pytest.mark.parametrize("name", sorted(TABLE))
def test_the_metric_sits_in_its_cells_with_its_file_and_its_reader(name):
    cells, layer, reader, twin = TABLE[name]
    per = _per_layer()
    m = per[name]
    assert m["workloads"] == cells
    assert (m["layer"], m["better"]) == (layer, "lower")
    spec = harness.metric_file(name)
    assert spec["reader"] == reader
    assert (spec["unit"], spec["layer"], spec["source"]) == (
        m["unit"], m["layer"], m["source"])
    assert m["source"] == ("program_counter" if reader == "counter_share"
                           else "program_span")
    # every cell that lists it reports the end-to-end metric it moves
    for cell in cells:
        assert m["moves"] in {e["name"] for e in harness.Cell(
            cell, rehearse=False).end_to_end()}, cell
    if twin is None:
        assert "no wall-time twin" in spec["what"]
        return
    # the file's ``what`` names the wall-time twin (a file of several
    # suffixes without one), which is still there, in the same layer
    # and, for a span's metric, over the same spans
    assert twin.rpartition(".")[0] in spec["what"] and twin in per
    twin_spec = harness.metric_file(twin)
    if reader == "histogram_share":
        assert per[twin]["layer"] == layer
        assert spec["args"]["names"] == [
            n.replace("span.self_seconds;", "span.self_cpu_seconds;")
            for n in twin_spec["args"]["names"]]
    elif reader == "histogram_mean":
        assert per[twin]["layer"] == layer
        assert spec["args"]["scale"] == twin_spec["args"]["scale"]
        assert spec["args"]["name"].startswith("span.self_cpu_seconds;name="
                                               + twin_spec["args"]["name"]
                                               .partition("name=")[2])
    elif reader == "flight_field_opt":
        assert per[twin]["layer"] == layer
        assert spec["args"] == {**twin_spec["args"],
                                "field": spec["args"]["field"]}


def test_the_roles_are_the_programs_vocabulary():
    from eges_tpu.utils import profiler

    roles = getattr(profiler, "ROLES", None)
    if roles is None:  # the parent's program: the closed list of its table
        roles = tuple(dict.fromkeys(
            r for _p, r in profiler._ROLE_PREFIXES)) + ("other",)
    assert harness.metric_file("threads_cpu_share.rows")["args"]["names"] \
        == [f"threads.cpu_seconds;role={r}" for r in roles]
    assert harness.metric_file("loop_cpu_share.lat")["args"]["names"] == [
        "threads.cpu_seconds;role=main"]
    assert profiler.role_of("MainThread") == "main"


# two snapshots of one process, 40 s apart (a recorded pair, cut to the
# numbers the readers want)
BEFORE = {"process.cpu_seconds": 61.5,
          "threads.cpu_seconds;role=main": 20.25,
          "threads.cpu_seconds;role=lane": 4.0,
          "threads.cpu_seconds;role=dispatch": 1.5,
          "threads.cpu_seconds;role=other": 30.0,
          "verifier.rows": {"count": 10}}
AFTER = {"process.cpu_seconds": 125.5,
         "threads.cpu_seconds;role=main": 22.25,
         "threads.cpu_seconds;role=lane": 12.0,
         "threads.cpu_seconds;role=dispatch": 3.5,
         "threads.cpu_seconds;role=other": 58.0,
         "verifier.rows": {"count": 90}}


def test_a_counter_share_is_the_growth_over_the_window():
    obs = {"window_s": 40.0, "before": BEFORE, "after": AFTER}
    assert counter_share.read(obs, names=["process.cpu_seconds"]) == \
        pytest.approx(160.0)
    threads = harness.metric_file("threads_cpu_share.rows")["args"]["names"]
    # 2 + 8 + 2 + 28 of 40 s; the roles the process has no thread of
    # are absent and add nothing
    assert counter_share.read(obs, names=threads) == pytest.approx(100.0)
    assert counter_share.read(
        obs, names=["threads.cpu_seconds;role=main"]) == pytest.approx(5.0)
    # a program without the gauges: None, never 0
    old = {"window_s": 40.0, "before": {"verifier.rows": {"count": 1}},
           "after": {"verifier.rows": {"count": 9}}}
    assert counter_share.read(old, names=threads) is None
    assert counter_share.read(old, names=["process.cpu_seconds"]) is None
    assert counter_share.read({**obs, "window_s": 0},
                              names=["process.cpu_seconds"]) is None
    # a gauge that first appears inside the window grew from 0
    assert counter_share.read(
        {**obs, "before": {}}, names=["threads.cpu_seconds;role=lane"]) == \
        pytest.approx(30.0)


def test_every_one_reads_nothing_on_the_parents_program():
    """An ``obs`` of the parent's shape: the two wall-time families, no
    CPU family, no gauges, flights without the two halves."""
    hist = {"count": 50, "mean": 0.01, "min": 0.0, "max": 0.1,
            "p50": 0.01, "p95": 0.02, "p99": 0.03}
    after = {}
    for name in TABLE:
        args = harness.metric_file(name).get("args", {})
        for n in args.get("names", []) + [args.get("name", "")]:
            if n.startswith("span.self_cpu_seconds;"):
                tail = n.partition(";")[2]
                after["span.seconds;" + tail] = dict(hist)
                after["span.self_seconds;" + tail] = dict(hist)
    assert len(after) >= 2 * 12
    flights = [{"t_done": 1.0 + i, "klass": k, "wait_ms": 2.0,
                "stage_ms": 1.0, "resolve_ms": 0.5, "rows": 256}
               for i, k in enumerate(("consensus", "bulk") * 4)]
    obs = {"window_s": 40.0, "before": {}, "after": after,
           "flights": flights, "t_begin": 0.0, "t_end": 40.0,
           "samples": {}, "trace": None, "journal": None}
    for cell in BACKLOG + STEADY + REF3:
        got = harness.read_per_layer(harness.Cell(cell, rehearse=False), obs)
        assert not set(got) & set(TABLE), (cell, set(got) & set(TABLE))
    # and with the family, the gauges and the fields there, each reads
    new = dict(after)
    for n in list(after):
        if n.startswith("span.self_seconds;"):
            new[n.replace("span.self_seconds;", "span.self_cpu_seconds;")] \
                = dict(hist, mean=0.004)
    new.update(AFTER)
    obs_new = {**obs, "before": dict(BEFORE), "after": new,
               "flights": [dict(f, flush_ms=0.5, lane_wait_ms=1.5)
                           for f in flights]}
    seen = set()
    for cell in BACKLOG + STEADY + REF3:
        got = harness.read_per_layer(harness.Cell(cell, rehearse=False),
                                     obs_new)
        mine = {n for n in TABLE if cell in TABLE[n][0]}
        assert mine <= set(got), (cell, mine - set(got))
        seen |= mine
        if cell in BACKLOG:
            # 50 observations of 4 ms of CPU against 10 ms of wall
            assert got["sched_collect_cpu_share.rows"]["value"] == \
                pytest.approx(0.5)
            assert got["sched_collect_share.rows"]["value"] == \
                pytest.approx(1.25)
            assert got["sched_flush_ms.rows"]["value"] + got[
                "sched_lane_wait_ms.rows"]["value"] == pytest.approx(
                    got["sched_wait_ms.rows"]["value"])
    assert seen == set(TABLE)
