"""PR 39's one per-layer metric: ``block_native_share.rows``, the signed
rows of block bodies that ``recover_senders``' one native pass filled in
over the signed rows it handed to the verifier.  It sits in
``c64.zipf-backlog`` alone (no other cell decodes a body), at the end of
the list, and reads nothing (None, never 0, never an error) on a program
without the counter, as the parent commit is (reader ``counter_ratio_opt``,
new: ``counter_ratio`` reads an absent numerator as 0).  ``.rows``, not ``.zipf``:
``test_validator_cell.py`` pins the ``.zipf`` set with ``==``."""

import pytest

from perfbench import harness

NAME = "block_native_share.rows"
CELL = "c64.zipf-backlog"


def _obs(before: dict, after: dict) -> dict:
    return {"before": before, "after": after, "window_s": 40.0,
            "samples": {}, "flights": [], "trace": None, "t_begin": 0.0,
            "t_end": 40.0}


def test_it_is_the_last_entry_and_sits_in_the_validator_cell_alone():
    per = harness.load_json("BENCHMARK.json")["per_layer"]
    assert per[-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "consensus",
        "moves": "verify_rows_per_s", "workloads": [CELL]}
    spec = harness.metric_file(NAME)
    assert spec["reader"] == "counter_ratio_opt"
    assert (spec["unit"], spec["layer"], spec["source"]) == (
        "%", "consensus", "program_counter")
    assert spec["args"] == {"num": ["chain.sender_native_rows"],
                            "den": ["chain.sender_rows"], "scale": 100.0}
    for w in harness.load_json("BENCHMARK.json")["workloads"]:
        cell = harness.Cell(w["name"], rehearse=False)
        assert (NAME in {m["name"] for m in cell.per_layer()}) == (
            w["name"] == CELL)


@pytest.mark.parametrize("native, rows, want", [
    (18000, 18000, 100.0),   # both counters agree: every row native
    (17982, 18000, 99.9),    # a refused body's bad row fell back
    (0, 18000, 0.0),         # a library without the decoder
])
def test_it_reads_the_share_of_the_rows_the_native_pass_filled(native, rows,
                                                               want):
    cell = harness.Cell(CELL, rehearse=False)
    got = harness.read_per_layer(cell, _obs(
        {"chain.sender_rows": 2000, "chain.sender_native_rows":
         2000 * native // rows},
        {"chain.sender_rows": 2000 + rows, "chain.sender_native_rows":
         2000 * native // rows + native}))
    assert got[NAME] == {"value": pytest.approx(want), "unit": "%"}


def test_a_program_without_the_counter_reads_nothing():
    cell = harness.Cell(CELL, rehearse=False)
    # the parent: the rows are counted, the native rows are not; a 0
    # there would read as "counted, and none"
    assert NAME not in harness.read_per_layer(cell, _obs(
        {"chain.sender_rows": 2000}, {"chain.sender_rows": 20000}))
    assert NAME not in harness.read_per_layer(cell, _obs({}, {}))
    # no body in the window: nothing to divide by
    assert NAME not in harness.read_per_layer(cell, _obs(
        {"chain.sender_rows": 5, "chain.sender_native_rows": 5},
        {"chain.sender_rows": 5, "chain.sender_native_rows": 5}))
