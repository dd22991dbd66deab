"""The promise of ``perfbench/README.md``: a later PR adds a configuration
with a driver of its own, a traffic mix, cells on one chip and on four and a
per-layer metric with a suffix of its own by ADDING files and entries, and
the benchmark's own tests still pass with no file that was there touched.

The additions are made in a temporary copy of the tree's benchmark files;
``harness.ROOT`` is the one thing pointed at the copy."""

import hashlib
import json
import os
import shutil

import pytest
import test_benchmark_json
import test_mesh_cell
import test_program_spans

from perfbench import harness

DRIVER = '''"""A stub: the driver of a deployment that is not the node's."""


def run(cell, args, t0) -> int:
    raise SystemExit("a stub runs nothing")
'''


def _digests(root) -> dict:
    out = {}
    for base, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _write(root, rel: str, obj) -> None:
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel  # an addition, never an edit
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj, indent=1))


@pytest.fixture
def grown(tmp_path, monkeypatch):
    """A copy of the benchmark's files with a later PR's additions."""
    root = str(tmp_path)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(harness.ROOT, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digests(os.path.join(root, "perfbench"))
    mesh4 = harness.load_json("perfbench", "configs",
                              "committee-1024-mesh4.json")
    _write(root, "perfbench/drivers/senders.py", DRIVER)
    _write(root, "perfbench/configs/committee-64-senders.json", {
        **mesh4, "name": "committee-64-senders", "driver": "senders",
        "source": "BASELINE.json configs[1]: batched tx-sender recovery"})
    _write(root, "perfbench/traffic/senders-zipf.json",
           {"what": "skewed senders", "arrival": "schedule"})
    _write(root, "perfbench/metrics/sched_wait_ms.senders.json", {
        **harness.metric_file("sched_wait_ms.vote"),
        "suffixes": {".senders": {"moves": "vote_p50_ms"}}})
    _write(root, "perfbench/metrics/lane_window_rows.meshsenders.json", {
        **harness.metric_file("lane_window_rows.mesh"),
        "suffixes": {".meshsenders": {"moves": "vote_p50_ms"}}})
    bench = harness.load_json("BENCHMARK.json")
    bench["configs"].append({
        "name": "committee-64-senders", "source": "BASELINE.json configs[1]",
        "file": "perfbench/configs/committee-64-senders.json",
        "reduced": ["cluster"], "why": "a driver of its own"})
    cells = [("c64.senders-zipf", 1), ("c64x4.senders-zipf", 4)]
    for name, chips in cells:
        bench["workloads"].append({
            "name": name, "config": "committee-64-senders",
            "traffic": "senders-zipf", "chips": chips, "why": "added"})
    # a pair of configuration and traffic appears once: the four-chip
    # cell takes the steady traffic that is there
    bench["workloads"][-1]["traffic"] = "mixed-steady"
    names = [name for name, _chips in cells]
    for m in bench["end_to_end"]:
        if m["name"] == "vote_p50_ms":
            m["workloads"] = m["workloads"] + names
    # a metric that is there is read in the new cells too
    next(m for m in bench["per_layer"]
         if m["name"] == "vote_await_ms.vote")["workloads"] += names
    entry = {"unit": "ms", "better": "lower", "source": "program_span",
             "layer": "scheduler", "moves": "vote_p50_ms"}
    bench["per_layer"].append({**entry, "name": "sched_wait_ms.senders",
                               "workloads": names})
    bench["per_layer"].append({
        **entry, "name": "lane_window_rows.meshsenders", "unit": "rows",
        "better": "higher", "source": "program_counter",
        "workloads": names[1:]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    after = _digests(os.path.join(root, "perfbench"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 5
    monkeypatch.setattr(harness, "ROOT", root)
    return names


def test_the_benchmarks_own_tests_pass_on_a_tree_that_only_added(grown):
    one, four = grown
    cell = harness.Cell(four, rehearse=False)
    assert (cell.chips, cell.config["driver"]) == (4, "senders")
    assert {m["name"] for m in cell.per_layer()} == {
        "sched_wait_ms.senders", "lane_window_rows.meshsenders",
        "vote_await_ms.vote"}
    assert harness.Cell(one, rehearse=False).traffic["what"] == \
        "skewed senders"
    # the three tests that pinned the cells, the drivers and the lists
    test_benchmark_json.\
        test_every_cell_finds_its_files_and_every_metric_its_reader()
    test_benchmark_json.test_no_tail_is_judged()
    test_mesh_cell.\
        test_the_cell_finds_its_files_and_differs_from_one_chip_in_layout_alone()
    test_mesh_cell.\
        test_every_cell_on_several_chips_keeps_its_layout_and_the_lanes_metrics()
    test_program_spans.test_the_span_metrics_sit_in_their_cells()


def test_an_addition_that_breaks_an_invariant_is_still_caught(grown):
    """The invariants bite: a lanes metric listed in a one-chip cell, and
    a four-chip cell on a configuration laid out over another number."""
    one, four = grown
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    bench = harness.load_json("BENCHMARK.json")
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "lane_window_rows.meshsenders")
    entry["workloads"] = [one, four]
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(AssertionError):
        test_mesh_cell.\
            test_every_cell_on_several_chips_keeps_its_layout_and_the_lanes_metrics()
    entry["workloads"] = [four]
    next(w for w in bench["workloads"]
         if w["name"] == "c1024.mixed-steady")["chips"] = 4
    with open(path, "w") as f:
        json.dump(bench, f)
    with pytest.raises(AssertionError):
        test_mesh_cell.\
            test_every_cell_on_several_chips_keeps_its_layout_and_the_lanes_metrics()
