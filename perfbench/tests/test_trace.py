"""The reduction from trace to busy time, gaps and program time, on a small
trace recorded on a TPU v5e (PR 24, ``scratch`` probe: one 512-row and one
256-row recover window under the profiler, names cut to 160 characters)."""

import json
import os

import pytest

from perfbench import harness, peaks, trace
from perfbench.readers import device_idle, program_us_per_row

ROWS = json.load(open(os.path.join(harness.HERE, "tests", "data",
                                   "trace_small.json")))
WINDOW_S = 0.0655  # first to last event of the recording


def test_the_recorded_trace_reduces_to_what_its_rows_say():
    red = trace.reduce(ROWS, WINDOW_S, program="jit_call|ecrecover")
    # two programs ran: 5.908251 ms (512 rows) and 2.959132 ms (256 rows)
    assert red["program_s"] == pytest.approx(8.867383e-3, rel=1e-6)
    # their operations run back to back, so busy time is a hair under it
    assert 0.99 * red["program_s"] < red["busy_s"] <= red["program_s"]
    assert red["window_s"] == WINDOW_S
    # a trace whose start and stop nobody timed is as long as its events
    assert trace.reduce(ROWS, None)["window_s"] == pytest.approx(
        0.06536, rel=1e-3)
    top = red["device_ops"][0]
    assert top[0] == "ecrecover_batch.25 u32[16,8192]"
    assert top[1] == pytest.approx(2.837041e-3, rel=1e-6)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10
    # the one long gap lies between the two windows; most of it is the
    # probe's own sleep, which no host span covers
    label, seconds = red["idle_gaps"][0]
    assert seconds == pytest.approx(26.875e-3, rel=1e-3)
    assert label == trace.NO_HOST_SPAN


def test_readers_and_the_work_function_on_the_reduction():
    red = trace.reduce(ROWS, WINDOW_S, program="jit_call|ecrecover")
    obs = {"trace": red, "trace_rows": 768}
    assert program_us_per_row.read(obs) == pytest.approx(11.546, rel=1e-3)
    idle = device_idle.read(obs)
    assert 86.0 < idle < 87.0
    rate = peaks.achieved(red, 768, "TPU v5 lite")
    assert rate == pytest.approx(
        768 * peaks.FIELD_MULS_PER_ROW * peaks.LIMB_MACS_PER_FIELD_MUL
        / red["program_s"])
    with pytest.raises(KeyError):
        peaks.achieved(red, 768, "some other chip")


def test_nothing_to_read_reads_as_nothing_never_as_zero():
    host_only = [r for r in ROWS if not trace.DEVICE_PLANE.match(r[0])]
    assert trace.reduce(host_only, 1.0) is None
    assert device_idle.read({"trace": None}) is None
    assert program_us_per_row.read({"trace": None, "trace_rows": 5}) is None


def test_intervals_that_overlap_count_once():
    rows = [["/device:TPU:0", "XLA Ops", "%a = u32[8] x", 0.0, 10e6],
            ["/device:TPU:0", "XLA Ops", "%b = u32[8] x", 5e6, 10e6],
            ["/device:TPU:0", "XLA Ops", "%c = u32[8] x", 40e6, 10e6]]
    red = trace.reduce(rows, 0.05)
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["idle_gaps"] == [[trace.NO_HOST_SPAN, pytest.approx(0.025)]]
    # a gap takes the name of the shortest host span over half of it
    rows += [["/host:CPU", "python3", "whole_run", 0.0, 50e6],
             ["/host:CPU", "python3", "decode_window", 16e6, 20e6],
             ["/host:CPU", "python3", "too_short", 15e6, 5e6]]
    assert trace.reduce(rows, 0.05)["idle_gaps"][0][0] == "decode_window"


def test_a_share_of_the_window_is_the_samples_sum_over_its_length():
    from perfbench.readers import sample_share

    obs = {"samples": {"gc_ms": [10.0, 30.0], "none": []}, "window_s": 2.0}
    assert sample_share.read(obs, sample="gc_ms") == pytest.approx(2.0)
    assert sample_share.read(obs, sample="none") is None
    assert sample_share.read(obs, sample="absent") is None
