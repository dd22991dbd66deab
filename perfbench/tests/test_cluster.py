"""The cluster driver at a size a test run can hold: three real node
processes on localhost, all on the host C++ verifier (no chip, no jax).
Slow (each run starts a cluster): ``-m slow`` runs them."""

import io
import json
from contextlib import redirect_stderr, redirect_stdout

import pytest

from perfbench import run as runmod
from perfbench.drivers import cluster

pytestmark = pytest.mark.slow


def drive(*extra, workload="ref3.signed-steady", seed=2**31 + 41):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = runmod.main(["--workload", workload, "--seed", str(seed),
                          "--seconds", "6", "--rehearse", "native", *extra])
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])


def failed(line) -> list:
    ok = {"<=": lambda v, lim: v <= lim, ">=": lambda v, lim: v >= lim}
    return [n for n, (v, rule, lim) in line["checks"].items()
            if not ok[rule](v, lim)]


@pytest.mark.parametrize("trace", ["1", "0"])
def test_every_guarantee_holds_and_a_rehearsal_is_no_pass(trace):
    rc, line = drive("--trace", trace)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert line["device"]["platform"] != "tpu"
    if trace == "1":  # no device: device metrics are left out, not zero
        assert "device_idle.lat" not in line["metrics"]
        assert "commit_p95_ms.lat" in line["metrics"]
        assert "block_interval_ms.lat" in line["metrics"]


def test_the_control_fails():
    """``host_verifier``: no sender is recovered on the device."""
    _, line = drive("--trace", "0", "--control", "host_verifier")
    assert line["correct"] is False
    assert "device_rows_per_txn" in failed(line)


def test_an_acknowledgement_for_what_was_never_sent_is_caught(monkeypatch):
    """The client's submit path loses the last transaction of every
    eighth batch and still reports it acknowledged: it never commits, and
    its sender's count falls short."""
    from perfbench.ref.keccak import keccak256

    real, sends = cluster.rpc, [0]

    def lossy(port, calls, timeout=60.0):
        if calls and calls[0][0] == "eth_sendRawTransaction":
            sends[0] += 1
            if sends[0] % 8 == 0:
                lost = bytes.fromhex(calls[-1][1][0][2:])
                kept = real(port, calls[:-1], timeout) if calls[:-1] else []
                return kept + ["0x" + keccak256(lost).hex()]
        return real(port, calls, timeout)

    monkeypatch.setattr(cluster, "rpc", lossy)
    _, line = drive("--trace", "0")
    assert line["correct"] is False
    assert "acked_not_committed" in failed(line)
