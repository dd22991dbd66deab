"""``BENCHMARK.json`` against the files it names."""

import importlib.util
import os

from perfbench import harness


def _driver(name: str):
    """``perfbench/drivers/<name>.py`` under the benchmark's root, loaded
    by its path: a configuration may bring a driver of its own, and the
    file is all it has to add."""
    path = os.path.join(harness.ROOT, "perfbench", "drivers", name + ".py")
    assert os.path.exists(path), path
    spec = importlib.util.spec_from_file_location(
        "_perfbench_driver_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_cell_finds_its_files_and_every_metric_its_reader():
    bench = harness.load_json("BENCHMARK.json")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"], rehearse=False)
        assert callable(_driver(cell.config["driver"]).run), w["name"]
        for key in ("source", "reduced", "assumed", "guarantees",
                    "message_delay"):
            assert key in cell.config, (w["name"], key)
        names = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in names and len(names) == 2
        assert cell.per_layer()
        for m in cell.per_layer():
            spec = harness.metric_file(m["name"])
            assert (spec["unit"], spec["layer"], spec["source"]) == (
                m["unit"], m["layer"], m["source"])
            suffix = "." + m["name"].rpartition(".")[2]
            assert spec["suffixes"][suffix]["moves"] == m["moves"]
            assert m["moves"] in names and m["moves"] in e2e
            importlib.import_module("perfbench.readers." + spec["reader"])
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        assert len(c["source"]) <= 200


def test_no_tail_is_judged():
    bench = harness.load_json("BENCHMARK.json")
    assert {m["name"] for m in bench["end_to_end"]} == {
        "commit_p50_ms", "verify_rows_per_s", "vote_p50_ms", "setup_s"}
    per = {m["name"] for m in bench["per_layer"]}
    assert {"commit_p95_ms.lat", "vote_p95_ms.vote"} <= per
