"""What decides ``correct``, at a size a test run can hold (the host C++
verifier behind the same scheduler and pool, no chip): a sound run passes
every comparison, the control fails, and a run whose timed path is broken
underneath comes out not correct."""

import io
import json
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

from perfbench import run as runmod


def drive(*extra, workload="c1024.mixed-steady", seed=2**31 + 17):
    """One rehearsal run in this process; its result line, parsed."""
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = runmod.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", "3", "--trace", "0",
                          "--rehearse", "native", *extra])
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    return rc, line, err.getvalue()


def failed(line) -> list:
    ok = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim,
          "==": lambda v, lim: v == lim}
    return [n for n, (v, rule, lim) in line["checks"].items()
            if not ok[rule](v, lim)]


@pytest.mark.parametrize("workload",
                         ["c1024.mixed-steady", "c1024.mixed-backlog"])
def test_a_sound_run_passes_every_comparison_and_is_still_no_pass(workload):
    rc, line, err = drive(workload=workload)
    assert failed(line) == []
    # a rehearsal can never print a passing device line
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert line["device"]["platform"] != "tpu"
    assert list(line)[-1] == "checks"
    assert "check wrong_answers: 0 <= 0 ok" in err


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_the_control_fails(seed):
    """``accept_all`` answers rows that are no signatures."""
    _, line, _ = drive("--control", "accept_all", seed=seed)
    assert line["correct"] is False
    assert "wrong_answers" in failed(line)
    assert "invalid_frames_not_refused" in failed(line)


def test_a_cycle_the_caches_remember_is_caught():
    """``short_cycle``: every block is the same block, so the recovery
    cache answers the votes and the work shrinks."""
    _, line, _ = drive("--control", "short_cycle",
                       workload="c1024.mixed-backlog")
    assert line["correct"] is False
    assert "cache_hit_share_pct" in failed(line)


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    """The verifier underneath returns one row's address with a bit
    flipped, once a window: ``correct`` comes out false."""
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    real = NativeBatchVerifier.recover_addresses

    def altered(self, sigs, hashes):
        addrs, ok = real(self, sigs, hashes)
        addrs = addrs.copy()
        addrs[0, 3] ^= 1
        return addrs, ok

    monkeypatch.setattr(NativeBatchVerifier, "recover_addresses", altered)
    _, line, _ = drive()
    assert line["correct"] is False
    assert {"wrong_answers", "reference_mismatches"} & set(failed(line))


def test_a_part_of_the_batch_left_out_is_caught(monkeypatch):
    """The scheduler drops the answers of a window's second half."""
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    real = NativeBatchVerifier.recover_addresses

    def halved(self, sigs, hashes):
        addrs, ok = real(self, sigs, hashes)
        ok = ok.copy()
        ok[len(ok) // 2:] = False
        return addrs, ok

    monkeypatch.setattr(NativeBatchVerifier, "recover_addresses", halved)
    _, line, _ = drive()
    assert line["correct"] is False and failed(line)


def _lanes_obs(rows: list, lanes=None) -> dict:
    """A window's snapshots as the node driver takes them: ``rows`` a lane
    served in the window, on top of 1000 each before it."""
    def snap(extra):
        return {"scheduler": {
            "lanes": len(rows) if lanes is None else lanes,
            "devices": [{"device": i, "rows": 1000 + r, "batches": 4 + r}
                        for i, r in enumerate(extra)]}}
    return {"before": snap([0] * len(rows)), "after": snap(rows)}


@pytest.mark.parametrize("chips, obs, fails", [
    # four lanes share the rows as the ledger reads them: a sound run
    (4, _lanes_obs([24600, 25100, 25400, 24900]), []),
    # one lane idle through the window (the breaker open, a placement
    # that forgets it): its share reads 0
    (4, _lanes_obs([33000, 33500, 0, 33500]), ["lane_rows_min_share_pct"]),
    # a process that holds four chips and drives one lane
    (4, _lanes_obs([100000], lanes=1), ["lanes"]),
    # a program without the per-lane breakdown has nothing to be held to
    (4, {"before": {}, "after": {}}, ["lanes", "lane_rows_min_share_pct"]),
    # a one-chip cell gets neither comparison
    (1, _lanes_obs([100000]), None),
])
def test_a_cell_on_several_chips_is_held_to_its_layout(chips, obs, fails):
    from perfbench import harness
    from perfbench.drivers.node import layout_checks

    checks = harness.Checks()
    layout_checks(checks, chips, obs)
    if fails is None:
        assert checks.rows == []
        return
    assert [r["name"] for r in checks.rows] == ["lanes",
                                                "lane_rows_min_share_pct"]
    assert [r["name"] for r in checks.rows if not r["ok"]] == fails
    assert checks.correct is (not fails)
    floor = next(r for r in checks.rows
                 if r["name"] == "lane_rows_min_share_pct")
    assert floor["limit"] == pytest.approx(6.25)


def test_no_program_no_result(tmp_path):
    """In a directory with only the benchmark's files the command prints
    no result and exits non-zero."""
    import shutil
    import subprocess

    from perfbench import harness

    shutil.copy(harness.ROOT + "/BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "c1024.mixed-backlog", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""
