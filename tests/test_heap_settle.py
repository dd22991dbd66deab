"""A serving process settles its heap ONCE (``utils/heap.py``): the
scheduler's dispatcher as it starts, a sidecar client once it is built.
Every case runs in a process of its own (``python tests/test_heap_settle.py
<case>``), because a test worker that already settled proves nothing; the
child asserts, the parent reads its exit code.
"""

import gc
import os
import subprocess
import sys
import threading
import weakref

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAIT_S = 120.0


def _scheduler():
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    return VerifierScheduler(NativeBatchVerifier(), max_batch=16)


def _rows(sched, n: int, salt: int) -> None:
    """``n`` fresh rows through ``sched``, each answered as the host
    model answers it."""
    from tests.test_scheduler import _host_model, _sign_entries

    entries = _sign_entries(n, salt=salt)
    assert sched.recover_signers(entries) == _host_model(entries)


def _gauges() -> dict:
    from eges_tpu.utils.metrics import DEFAULT as metrics

    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("process.gc_")}


def _took(before: int, tracked: int, frozen: int) -> bool:
    """Whether the freeze took about what the collector tracked a moment
    before it: less what the collection freed (import-time cycles, a
    fifteenth here), plus what the rows' own threads made."""
    return 0.8 * tracked < frozen - before < tracked + 1_000


def _frozen_gauge_reads(frozen: int) -> bool:
    """The gauge is the count AT the freeze; a frozen object whose last
    reference goes is freed as ever, so the live count sinks a little."""
    got = _gauges()["process.gc_frozen_objects"]
    return 0 <= got - frozen < 0.01 * got


def _settled_by_first_row():
    """A scheduler, its first computed rows, and what the collector held
    around them: ``(scheduler, frozen before, tracked before)``."""
    sched = _scheduler()
    # building a scheduler serves nothing: nothing is frozen by it
    before = gc.get_freeze_count()
    assert "process.gc_frozen_objects" not in _gauges()
    tracked = len(gc.get_objects())
    _rows(sched, 5, salt=1)
    return sched, before, tracked


def case_first_row():
    sched, before, tracked = _settled_by_first_row()
    frozen = gc.get_freeze_count()
    assert tracked > 5_000 and _took(before, tracked, frozen), \
        (before, tracked, frozen)
    assert _frozen_gauge_reads(frozen)
    assert 0.0 < _gauges()["process.gc_settle_seconds"] < 10.0
    sched.close()


def case_second_is_none():
    from eges_tpu.utils import heap

    sched, _before, _tracked = _settled_by_first_row()
    frozen, got = gc.get_freeze_count(), _gauges()
    again = _scheduler()
    _rows(again, 5, salt=2)
    _rows(sched, 5, salt=3)
    assert heap.settle() is None
    assert gc.get_freeze_count() <= frozen and _gauges() == got
    again.close()
    sched.close()


def case_collector_left_on():
    sched, _before, _tracked = _settled_by_first_row()
    assert gc.isenabled()
    assert gc.get_threshold() == (700, 10, 10)
    sched.close()


def case_cycle_after_freeze():
    sched, _before, _tracked = _settled_by_first_row()

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    ref = weakref.ref(a)
    del a, b
    assert ref() is not None
    gc.collect()
    assert ref() is None
    sched.close()


def case_sidecar_client():
    """``verify_path.build("sidecar", ...)`` against a served socket: a
    process that never has a scheduler settles as the client is built."""
    import tempfile
    import time

    from eges_tpu.crypto import verify_path
    from tests.test_scheduler import _host_model, _sign_entries

    with tempfile.TemporaryDirectory() as tmp:
        sock = os.path.join(tmp, "v.sock")
        server = subprocess.Popen(
            [sys.executable, "-m", "eges_tpu.crypto.sidecar", "--socket",
             sock, "--verifier", "native"], cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + WAIT_S
            while not os.path.exists(sock):
                assert server.poll() is None and time.monotonic() < deadline
                time.sleep(0.01)
            before, tracked = gc.get_freeze_count(), len(gc.get_objects())
            path = verify_path.build("sidecar", sidecar_path=sock)
            frozen = gc.get_freeze_count()
            assert path.verifier.stats()["connected"]
            assert _took(before, tracked, frozen), (before, tracked, frozen)
            assert _frozen_gauge_reads(frozen)
            entries = _sign_entries(5)
            assert path.verifier.recover_signers(entries) == \
                _host_model(entries)
            assert path.verifier.stats()["fallback_rows"] == 0
            # no dispatcher ever ran here: the client alone settled it
            assert not any(t.name == "verifier-scheduler"
                           for t in threading.enumerate())
            assert gc.get_freeze_count() <= frozen
            path.verifier.close()
        finally:
            server.terminate()
            server.wait(WAIT_S)


def case_sim_journal():
    """A collection is not an event: one short cluster run's journal is
    byte for byte the same with ``heap.settle`` patched to a no-op."""
    from eges_tpu.crypto.verify_host import NativeBatchVerifier
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils import heap
    from harness.chaos import canonical_dump

    def run() -> bytes:
        cluster = SimCluster(3, txn_per_block=4, seed=43,
                             verifier=NativeBatchVerifier())
        cluster.start()
        cluster.run(600.0, stop_condition=lambda: cluster.min_height() >= 5)
        for sn in cluster.nodes:
            sn.node.stop()
        assert cluster.min_height() >= 5
        cluster.verifier.close()
        return canonical_dump(cluster.journals())

    real, heap.settle = heap.settle, lambda: None
    before = gc.get_freeze_count()
    unsettled = run()
    assert gc.get_freeze_count() == before
    heap.settle = real
    settled = run()
    assert gc.get_freeze_count() > before + 5_000
    assert settled == unsettled


CASES = {name[len("case_"):]: fn for name, fn in list(globals().items())
         if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_serving_process_settles_its_heap_once(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), case], cwd=REPO,
        env=env, capture_output=True, text=True, timeout=WAIT_S * 2)
    assert done.returncode == 0 and done.stdout.endswith(f"ok {case}\n"), \
        done.stdout + done.stderr


if __name__ == "__main__":
    CASES[sys.argv[1]]()
    print(f"ok {sys.argv[1]}")
