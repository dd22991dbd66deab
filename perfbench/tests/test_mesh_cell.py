"""The four-chip cell ``c1024x4.mixed-backlog``: it finds its files, it is
the one-chip backlog cell's deployment and traffic number for number, and
each reader it brings returns what a hand-made ``obs`` says.  What holds for
EVERY cell on several chips is an invariant of its own, so that a later PR
adds one without an edit here."""

import pytest

from perfbench import harness
from perfbench.readers import (counter_ratio, lane_rows,
                               program_us_per_row, program_us_per_row_lanes)

CELL, ONE_CHIP = "c1024x4.mixed-backlog", "c1024.mixed-backlog"
MESH = ["sched_place_share.mesh", "lane_rows_min_share.mesh",
        "lane_window_rows.mesh", "hedge_wasted_share.mesh",
        "recover_us_per_row.mesh"]


def _sched(lanes: list, **flat) -> dict:
    """A snapshot as the node driver takes it, ``lanes`` as ``(rows,
    batches)`` a device."""
    return {"scheduler": {
        "lanes": len(lanes), **flat,
        "devices": [{"device": i, "rows": r, "batches": b}
                    for i, (r, b) in enumerate(lanes)]}}


def test_the_cell_finds_its_files_and_differs_from_one_chip_in_layout_alone():
    cell = harness.Cell(CELL, rehearse=False)
    one = harness.Cell(ONE_CHIP, rehearse=False)
    assert cell.chips == 4 and one.chips == 1
    assert cell.config["driver"] == "node"
    # the two cells read as a ratio: the same blocks on one chip and four
    assert cell.traffic == one.traffic
    assert cell.config["deployment"] == one.config["deployment"]
    assert cell.config["rehearse"] == one.config["rehearse"]
    assert cell.config["reduced"] == ["cluster"]
    assert cell.config["guarantees"][:5] == one.config["guarantees"]
    assert len(cell.config["guarantees"]) == 6
    lay = cell.config["layout"]
    assert (lay["devices"], lay["processes"], lay["lanes"]) == (4, 1, 4)
    # the chunk cap is the program's own rule, not a number of the file's
    from eges_tpu.crypto.bucketing import lane_chunk_cap
    assert lay["chunk_cap_rows"] == lane_chunk_cap(
        cell.config["deployment"]["max_batch"], lay["lanes"])
    assert {m["name"] for m in cell.end_to_end()} == {"verify_rows_per_s",
                                                      "setup_s"}
    mine = {m["name"] for m in cell.per_layer()}
    assert set(MESH) <= mine and "device_idle.rows" in mine
    # the plain reader would show a quarter of a row's cost on four planes
    assert "recover_us_per_row.rows" not in mine
    assert {m["name"] for m in one.per_layer()}.isdisjoint(MESH)
    for m in cell.per_layer():
        if m["name"] in MESH:
            assert m["moves"] == "verify_rows_per_s"


def test_every_cell_on_several_chips_keeps_its_layout_and_the_lanes_metrics():
    """Invariants for any number of cells: a cell that asks for several
    chips runs a configuration laid out over exactly that many, and a
    metric of the lanes (``.mesh*``) is listed in such cells alone, in at
    least one, and moves an end-to-end metric each of them reports."""
    bench = harness.load_json("BENCHMARK.json")
    wide = [w["name"] for w in bench["workloads"] if w["chips"] > 1]
    assert CELL in wide
    for name in wide:
        cell = harness.Cell(name, rehearse=False)
        lay = cell.config.get("layout", {})
        assert lay.get("devices") == lay.get("lanes") == cell.chips, name
    lanes_metrics = [m for m in bench["per_layer"]
                     if m["name"].rpartition(".")[2].startswith("mesh")]
    assert {m["name"] for m in lanes_metrics} >= set(MESH)
    for m in lanes_metrics:
        assert m["workloads"] and set(m["workloads"]) <= set(wide), m["name"]
        for name in m["workloads"]:
            reports = {e["name"] for e in harness.Cell(
                name, rehearse=False).end_to_end()}
            assert m["moves"] in reports, (m["name"], name)


def test_the_least_lanes_share_and_the_rows_a_device_window():
    obs = {"before": _sched([(100, 1), (100, 1), (100, 1), (100, 1)]),
           "after": _sched([(500, 3), (300, 2), (200, 2), (1100, 5)])}
    # in the window: 400, 200, 100, 1000 rows in 2 + 1 + 1 + 4 windows
    assert lane_rows.read(obs, stat="min_share") == pytest.approx(
        100.0 * 100 / 1700)
    assert lane_rows.read(obs, stat="rows_per_window") == pytest.approx(
        1700 / 8)
    even = {"before": _sched([(0, 0)] * 4), "after": _sched([(220, 1)] * 4)}
    assert lane_rows.read(even, stat="min_share") == pytest.approx(25.0)
    # a lane that served nothing in the window reads 0, not None
    idle = {"before": obs["before"],
            "after": _sched([(500, 3), (100, 1), (200, 2), (1100, 5)])}
    assert lane_rows.read(idle, stat="min_share") == 0.0
    # one lane (a one-chip node, the native rehearsal): all of it
    assert lane_rows.read({"before": _sched([(10, 1)]),
                           "after": _sched([(450, 3)])},
                          stat="min_share") == pytest.approx(100.0)
    # nothing to read: a program without the breakdown, a window in which
    # no lane served a row
    assert lane_rows.read({"before": {}, "after": {"scheduler": {}}},
                          stat="min_share") is None
    assert lane_rows.read({"before": obs["before"],
                           "after": obs["before"]},
                          stat="rows_per_window") is None
    with pytest.raises(ValueError):
        lane_rows.read(obs, stat="median")


def test_a_rows_device_cost_counts_every_lanes_chip():
    obs = {"trace": {"program_s": 0.55}, "trace_rows": 200_000,
           "before": {}, "after": _sched([(1, 1)] * 4)}
    # program_s is the mean over four planes: 2.2 s of device time in all
    assert program_us_per_row.read(obs) == pytest.approx(2.75)
    assert program_us_per_row_lanes.read(obs) == pytest.approx(11.0)
    assert program_us_per_row_lanes.read({**obs, "trace": None}) is None
    assert program_us_per_row_lanes.read({**obs, "after": {}}) is None


def test_the_hedges_waste_is_over_the_windows_recorded():
    spec = harness.metric_file("hedge_wasted_share.mesh")
    assert spec["reader"] == "counter_ratio"
    obs = {"before": _sched([], hedge_wasted=1, batches=100),
           "after": _sched([], hedge_wasted=4, batches=700)}
    assert counter_ratio.read(obs, **spec["args"]) == pytest.approx(0.5)
    # a program that never hedged reads 0, one that recorded no window None
    assert counter_ratio.read({"before": _sched([], batches=1),
                               "after": _sched([], batches=9)},
                              **spec["args"]) == 0.0
    assert counter_ratio.read({"before": {}, "after": {}},
                              **spec["args"]) is None


def test_a_program_without_the_place_span_reports_no_share_of_it():
    spec = harness.metric_file("sched_place_share.mesh")
    assert spec["args"]["names"] == ["span.self_seconds;name=sched.place"]
    from perfbench.readers import histogram_share
    name = spec["args"]["names"][0]
    obs = {"window_s": 40.0, "before": {name: {"count": 10, "mean": 1e-4}},
           "after": {name: {"count": 30010, "mean": 2e-5}}}
    # 30010 * 2e-5 - 10 * 1e-4 = 0.5992 s of 40
    assert histogram_share.read(obs, **spec["args"]) == pytest.approx(1.498)
    assert histogram_share.read({"window_s": 40.0, "before": {},
                                 "after": {}}, **spec["args"]) is None
