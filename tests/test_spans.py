"""Program spans: the one timing primitive of the served path.

A live ``Tracer.span`` observes ``span.seconds`` / ``span.self_seconds``
and, from its thread's CPU clock, ``span.self_cpu_seconds`` (PR 38),
lands in the profiler's trace beside the device operations, and roots no
trace of its own unless it is a transaction's ingest; the registry's
snapshot reads the process's CPU and its threads' by role; the
scheduler's flight recorder carries ``resolve_ms``, splits ``wait_ms``
at the dispatcher's pop and keeps a whole run; the 1 s
election re-send and the validate retry say whose message was missing;
the trace armer leaves the profiler's Python tracer off.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from eges_tpu.utils import metrics as metrics_mod
from eges_tpu.utils import profiler, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    """A clock the test moves by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _tracer():
    clock, reg = _Clock(), metrics_mod.Registry()
    return tracing.Tracer(clock=clock, metrics=reg), clock, reg


# -- the primitive --------------------------------------------------------

def test_span_observes_duration_and_self_time_without_its_child():
    t, clock, reg = _tracer()
    with t.span("txpool.flush"):
        clock.t += 0.010
        with t.span("sched.await", **{"class": "bulk", "size": "call"}):
            clock.t += 0.030
        clock.t += 0.005
    snap = reg.snapshot()
    outer = snap["span.seconds;name=txpool.flush"]
    assert outer["count"] == 1 and outer["mean"] == pytest.approx(0.045)
    assert snap["span.self_seconds;name=txpool.flush"]["mean"] == \
        pytest.approx(0.015)
    # the table's label attributes become the histogram's labels
    inner = snap["span.seconds;name=sched.await,class=bulk,size=call"]
    assert inner["mean"] == pytest.approx(0.030)
    assert snap["span.self_seconds;name=sched.await,class=bulk,size=call"][
        "mean"] == pytest.approx(0.030)
    # and the ring still has both, innermost first
    assert [s["name"] for s in t.finished()] == ["sched.await",
                                                 "txpool.flush"]


def test_self_time_is_kept_per_thread():
    import threading

    t, clock, reg = _tracer()

    def other():
        with t.span("sched.stage"):
            pass

    with t.span("txpool.flush"):
        clock.t += 0.020
        th = threading.Thread(target=other)
        th.start()
        th.join(10)
        assert not th.is_alive()
    # a span on another thread is nobody's child here
    assert reg.snapshot()["span.self_seconds;name=txpool.flush"][
        "mean"] == pytest.approx(0.020)


# -- the thread's CPU clock beside the wall clock (PR 38) -------------------

def _spin(seconds: float) -> None:
    """Keep this thread on a core for ``seconds`` of ITS CPU time."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        sum(range(200))


@pytest.fixture
def every_span_reads_the_clock(monkeypatch):
    monkeypatch.setattr(tracing, "CPU_EVERY", 1)


def test_a_busy_span_ran_and_a_sleeping_span_waited(
        every_span_reads_the_clock):
    t = tracing.Tracer(metrics=metrics_mod.Registry())
    with t.span("sched.stage"):
        _spin(0.05)
    with t.span("sched.collect"):
        time.sleep(0.05)
    busy, asleep = t.finished()
    assert busy["name"] == "sched.stage"
    # it ran nearly all of its wall time (another process may take the
    # core for a moment: the wall time is then the longer)
    assert busy["cpu_s"] >= 0.05 - 1e-4
    assert busy["cpu_s"] <= busy["duration_s"] + 1e-4
    if busy["duration_s"] < 0.06:  # nobody took the core
        assert busy["cpu_s"] == pytest.approx(busy["duration_s"], rel=0.2)
    assert asleep["duration_s"] >= 0.05 and asleep["cpu_s"] < 0.005
    snap = t.metrics.snapshot()
    assert snap["span.self_cpu_seconds;name=sched.stage"]["mean"] == \
        pytest.approx(busy["cpu_s"], abs=2e-6)
    assert snap["span.self_cpu_seconds;name=sched.collect"]["mean"] < 0.005
    # wall less CPU is the wait
    assert snap["span.self_seconds;name=sched.collect"]["mean"] - snap[
        "span.self_cpu_seconds;name=sched.collect"]["mean"] > 0.045


def test_a_childs_cpu_leaves_its_parents_self_cpu_per_thread(
        every_span_reads_the_clock):
    import threading

    t = tracing.Tracer(metrics=metrics_mod.Registry())

    def other():
        with t.span("sched.stage"):
            _spin(0.03)

    with t.span("txpool.flush"):
        _spin(0.02)
        with t.span("sched.await", **{"class": "bulk", "size": "call"}):
            _spin(0.04)
        # a span on another thread is nobody's child here, and its CPU
        # is on its own thread's clock
        th = threading.Thread(target=other)
        th.start()
        th.join(10)
        assert not th.is_alive()
    snap = t.metrics.snapshot()

    def cpu(name):
        return snap[f"span.self_cpu_seconds;name={name}"]["mean"]

    assert 0.02 <= cpu("txpool.flush") < 0.03
    # labelled spans get labelled histograms, as the wall-time ones do
    assert 0.04 <= cpu("sched.await,class=bulk,size=call") < 0.05
    assert 0.03 <= cpu("sched.stage") < 0.04
    # the ring entry keeps the WHOLE CPU time: the child's inside it
    whole = {s["name"]: s["cpu_s"] for s in t.finished()}
    assert 0.06 <= whole["txpool.flush"] < 0.08
    assert set(snap) == {
        f"span.{fam};name={n}"
        for fam in ("seconds", "self_seconds", "self_cpu_seconds")
        for n in ("txpool.flush", "sched.stage",
                  "sched.await,class=bulk,size=call")}
    # the three histograms of a span that read the clock move in step
    assert {snap[n]["count"] for n in snap} == {1}


def test_one_span_in_cpu_every_reads_the_clock_and_counts_for_all():
    """The thread's CPU clock is a system call (5.4-6.1 us on the chip's
    host, where it moves in steps of 10 ms): a span that is outermost
    on its thread reads it by the toss of a coin, one in CPU_EVERY, and
    every span inside it does; the histogram takes each reading
    CPU_EVERY times, so its count times its mean estimates them all."""
    every = tracing.CPU_EVERY
    assert every == 8
    n = 4000
    clock, reg = _Clock(), metrics_mod.Registry()
    t = tracing.Tracer(clock=clock, capacity=2 * n, metrics=reg)
    for _ in range(n):
        with t.span("txpool.flush"):
            clock.t += 0.25
            with t.span("sched.await", **{"class": "bulk", "size": "call"}):
                clock.t += 0.25
    done = t.finished()
    inner, outer = done[0::2], done[1::2]
    assert {s["name"] for s in outer} == {"txpool.flush"}
    read = ["cpu_s" in s for s in outer]
    # one in eight, give or take five standard deviations
    assert n / every - 105 <= sum(read) <= n / every + 105
    # a span inside one that reads the clock reads it, and no other
    assert ["cpu_s" in s for s in inner] == read
    # not by count: the gaps between readings are not all alike
    at = [i for i, r in enumerate(read) if r]
    assert len({b - a for a, b in zip(at, at[1:])}) > 8
    snap = reg.snapshot()
    for tail in ("txpool.flush", "sched.await,class=bulk,size=call"):
        cpu = snap[f"span.self_cpu_seconds;name={tail}"]
        assert snap[f"span.seconds;name={tail}"]["count"] == n
        assert abs(cpu["count"] - n) <= 105 * every
        assert cpu["count"] % every == 0
    # the reading leaves the wall time alone: on a clock moved by hand
    # a span that read the CPU clock is as long as one that did not
    assert {s["duration_s"] for s in outer} == {0.5}
    assert {s["duration_s"] for s in inner} == {0.25}
    flush = snap["span.self_seconds;name=txpool.flush"]
    assert (flush["min"], flush["max"]) == (0.25, 0.25)


def test_a_recorded_span_has_no_cpu_and_observes_nothing():
    t, clock, reg = _tracer()
    clock.t = 5.0
    sp = t.record_span("consensus.election", 0.25)
    assert sp.cpu_s is None and sp.duration_s == pytest.approx(0.25)
    (entry,) = t.finished()
    assert "cpu_s" not in entry and entry["duration_s"] == 0.25
    assert reg.snapshot() == {}
    assert t.stats()["started"] == 1


def test_the_ring_hands_out_dicts_made_when_asked(
        tmp_path, every_span_reads_the_clock):
    import json

    t, clock, _reg = _tracer()
    with t.span("sched.stage", rows=3) as sp:
        clock.t += 0.5
    sp.set_attr("late", 1)  # the ring keeps the span, not a copy
    (entry,) = t.finished()
    assert entry == {"name": "sched.stage", "trace": sp.trace_id,
                     "span": sp.span_id, "parent": None, "start_s": 0.0,
                     "duration_s": 0.5, "cpu_s": entry["cpu_s"],
                     "attrs": {"rows": 3, "late": 1}}
    assert t.finished(trace=sp.trace_id) == [entry]
    assert t.finished(trace="0" * 32) == []
    path = str(tmp_path / "spans.jsonl")
    assert t.dump(path) == 1 and t.finished() == []
    assert json.loads(open(path).read()) == entry
    assert t.stats() == {"started": 1, "buffered": 0, "dropped": 0,
                         "capacity": 4096}


def test_the_snapshot_reads_the_process_cpu_and_the_roles():
    """``process.cpu_seconds`` and ``threads.cpu_seconds;role=*`` are set
    when the DEFAULT registry is read, by no thread of their own: a lane
    worker that computes makes its role's gauge grow."""
    import threading

    from eges_tpu.utils.metrics import DEFAULT as metrics
    from eges_tpu.utils.metrics import prometheus_text

    if not hasattr(time, "pthread_getcpuclockid"):
        pytest.skip("no per-thread CPU clock on this platform")
    go, done = threading.Event(), threading.Event()

    def lane():
        while go.wait(10) and not done.is_set():
            _spin(0.05)
            go.clear()

    th = threading.Thread(target=lane, name="verifier-lane-7", daemon=True)
    th.start()
    try:
        threads_before = threading.active_count()
        before = metrics.snapshot()
        go.set()
        while go.is_set():
            time.sleep(0.005)
        after = metrics.snapshot()
        assert threading.active_count() <= threads_before  # none started
    finally:
        done.set()
        go.set()
        th.join(10)
    grew = (after["threads.cpu_seconds;role=lane"]
            - before["threads.cpu_seconds;role=lane"])
    assert 0.05 <= grew < 0.2
    roles = {n.rpartition("=")[2]: v for n, v in after.items()
             if n.startswith("threads.cpu_seconds;role=")}
    assert set(roles) == set(profiler.ROLES) and "other" in roles
    assert roles["main"] > 0.0 and roles["hedge"] >= 0.0
    # every thread of the process, those Python does not know included
    assert after["process.cpu_seconds"] >= sum(roles.values())
    assert after["process.cpu_seconds"] - before["process.cpu_seconds"] \
        >= grew
    text = prometheus_text()
    assert 'threads_cpu_seconds{role="lane"}' in text
    assert "\nprocess_cpu_seconds " in text
    # a registry of one's own reads nothing off the process
    assert metrics_mod.Registry().snapshot() == {}


def test_a_span_joins_a_trace_and_roots_one_only_when_told():
    t, _clock, _reg = _tracer()
    with t.span("sched.stage") as lone:
        # timed and recorded, but not the current context: nothing it
        # sends carries a header, no journal event gets its id
        assert t.current_context() is None
        assert tracing.inject_current(b"x", t) == b"x"
    with t.span("txpool.ingest", root=True) as root:  # a txn's trace
        assert t.current_context() == root.context()
        with t.span("txpool.flush") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert t.current_context() == child.context()
        assert t.current_context() == root.context()
    assert t.current_context() is None
    assert lone.trace_id != root.trace_id
    # every name of the table is <layer>.<verb>
    assert all(n.count(".") == 1 for n in tracing.SPANS)


def test_span_ids_cost_no_entropy_per_span(monkeypatch):
    def no_entropy(n):
        raise AssertionError("a span drew entropy")

    monkeypatch.setattr(os, "urandom", no_entropy)
    t, _clock, _reg = _tracer()
    seen = set()
    for _ in range(50):
        with t.span("sched.stage") as sp:
            seen.add((sp.trace_id, sp.span_id))
            assert len(sp.trace_id) == 32 and len(sp.span_id) == 16
    assert len(seen) == 50
    ctx, rest = tracing.extract(tracing.inject(sp.context(), b"payload"))
    assert ctx == sp.context() and rest == b"payload"


def test_scheduler_spans_tag_the_profiler_phases():
    def phase_now():
        import threading
        return profiler._PHASES.get(threading.get_ident())

    t, _clock, _reg = _tracer()
    with t.span("sched.stage"):
        assert phase_now() == "verify_stage"
        with t.span("sched.collect"):
            assert phase_now() == "verify_collect"
        assert phase_now() == "verify_stage"
    assert phase_now() is None


def test_no_jax_import_for_a_span():
    """A node on the native verifier has no jax in its process; a span
    must not be what brings it in."""
    code = (
        "import sys\n"
        "from eges_tpu.utils import tracing\n"
        "from eges_tpu.core.txpool import TxPool\n"
        "from eges_tpu.sim.simnet import SimClock\n"
        "pool = TxPool(SimClock(), verifier=None)\n"
        "with tracing.DEFAULT.span('txpool.flush'):\n"
        "    with tracing.DEFAULT.span('sched.await'):\n"
        "        pass\n"
        "pool.remove_included([])\n"
        "assert tracing.DEFAULT.stats()['started'] >= 3\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=dict(os.environ, PYTHONPATH=ROOT),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "clean"


def test_a_pool_flush_is_found_in_the_profilers_host_plane(tmp_path):
    """Under a real profiler session (CPU backend) the pool's flush span
    is an event of the host plane, as ``perfbench.trace.load`` reads it:
    that is what names an idle gap of the chip."""
    import jax

    from eges_tpu.core.txpool import TxPool
    from eges_tpu.core.types import Transaction
    from eges_tpu.sim.simnet import SimClock
    from perfbench import trace as tracemod

    class SlowVerifier:
        """Every sender recovered, after the while a device takes."""

        def recover_addresses(self, sigs, hashes):
            time.sleep(0.004)
            n = len(sigs)
            return np.ones((n, 20), np.uint8), np.ones((n,), bool)

    jax.devices()  # the backend is up before the session starts
    pool = TxPool(SimClock(), verifier=SlowVerifier(), max_batch=4)
    txns = [Transaction(nonce=i, gas_limit=21000, to=bytes(20),
                        value=1).signed(bytes([7]) * 32, chain_id=1)
            for i in range(4)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        pool.add_remotes(txns)  # a full batch flushes inline
    finally:
        jax.profiler.stop_trace()
    assert pool.stats["batches"] == 1
    rows = tracemod.load(str(tmp_path))
    flush = [r for r in rows if r[2] == "txpool.flush"]
    assert flush and flush[0][0].startswith("/host:CPU")
    assert flush[0][4] >= 4e6  # nanoseconds: it waited for the verifier
    # no Python frame is in the trace: the armer's option, see below
    assert not any(".py:" in r[2] for r in rows)


# -- the scheduler's flight recorder ---------------------------------------

def test_flights_carry_resolve_ms_and_a_run_of_300_windows_drops_none():
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    sig = host.ecdsa_sign(b"\x11" * 32, b"\x07" * 32)
    sched = VerifierScheduler(NativeBatchVerifier())
    assert sched.stats()["flight_capacity"] == 4096
    try:
        for k in range(300):
            # fresh rows every time (the cache answers a repeated one),
            # two of them (a single row is recovered on the host)
            rows = [(k.to_bytes(4, "big") * 8, sig),
                    ((k + 1000).to_bytes(4, "big") * 8, sig)]
            assert len(sched.recover_signers(rows)) == 2
        flights = sched.flights()
        st = sched.stats()
    finally:
        sched.close()
    assert st["flight_dropped"] == 0 and len(flights) >= 300
    for f in flights:
        assert f["resolve_ms"] >= 0.0
        # from the device's answer to the last future: never shorter
        # than the part of it that lies before ``t_done``
        assert f["resolve_ms"] >= round(
            (f["t_done"] - f["t_collect"]) * 1e3, 3) - 1e-6
    assert [f["window"] for f in sched.flights(limit=5)] == \
        [f["window"] for f in flights[-5:]]
    names = {s["name"] for s in tracing.DEFAULT.finished()}
    assert {"sched.submit", "sched.await", "sched.stage",
            "sched.resolve"} <= names
    assert "verifier.sched_dispatch" not in names


@pytest.mark.parametrize("target", ["inline", "pipelined", "four_lanes"])
def test_a_windows_wait_is_split_where_the_dispatcher_takes_it(target):
    """``flush_ms`` (the oldest row's entry to the dispatcher's pop) and
    ``lane_wait_ms`` (the pop to the stage's begin) add up to
    ``wait_ms`` in every flight of a run of 300 windows, host-served
    ones included; the chunks of one window share its ``t_flush``."""
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import (
        NativeBatchVerifier, NativeMeshVerifier, PipelinedNativeVerifier,
    )

    verifier = {"inline": NativeBatchVerifier,
                "pipelined": PipelinedNativeVerifier,
                "four_lanes": lambda: NativeMeshVerifier(4)}[target]()
    sig = host.ecdsa_sign(b"\x13" * 32, b"\x07" * 32)
    sched = VerifierScheduler(verifier, max_batch=32, min_split=4,
                              hedge=False)
    try:
        for k in range(100):
            # fresh rows every time; one row (the host's by rule), two,
            # then a full window, which four lanes split into 4 x 8
            for n, base in ((1, 20000), (2, 30000), (32, 40000)):
                rows = [((base + 64 * k + i).to_bytes(4, "big") * 8, sig)
                        for i in range(n)]
                assert len(sched.recover_signers(rows)) == n
        flights = sched.flights()
        st = sched.stats()
    finally:
        sched.close()
        close = getattr(verifier, "close", None)
        if close:
            close()
    assert st["host_diverted"] == 100 and st["flight_dropped"] == 0
    assert len(flights) == (600 if target == "four_lanes" else 300)
    for f in flights:
        assert f["flush_ms"] >= 0.0 and f["lane_wait_ms"] >= 0.0
        assert f["flush_ms"] + f["lane_wait_ms"] == pytest.approx(
            f["wait_ms"], abs=0.002)
        assert f["t_submit"] <= f["t_flush"] <= f["t_begin"]
    by_flush: dict = {}
    for f in flights:
        by_flush.setdefault(f["t_flush"], []).append(f["rows"])
    want = [[1], [2], [8, 8, 8, 8] if target == "four_lanes" else [32]]
    assert sorted(by_flush.values()) == sorted(want * 100)


def test_a_mesh_window_is_placed_under_a_span_and_its_lane_is_no_label(
        tmp_path):
    """``sched.place`` goes around the split and the lane choice; the
    lane's three spans name their lane in the attribute ``device`` of the
    ring entry and of the profiler's event, never in a histogram's name
    (the metric files read ``span.self_seconds;name=sched.stage``)."""
    import jax

    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeMeshVerifier
    from eges_tpu.utils.metrics import DEFAULT as metrics
    from jax.profiler import ProfileData

    assert tracing.SPANS["sched.place"][0] == ()
    assert tracing.SPANS["sched.stage"][0] == ()
    sig = host.ecdsa_sign(b"\x12" * 32, b"\x07" * 32)
    rows = [(k.to_bytes(4, "big") * 8, sig) for k in range(5000, 5032)]
    tracing.DEFAULT.clear()
    places = metrics.histogram("span.seconds;name=sched.place").count
    jax.devices()  # the backend is up before the session starts
    sched = VerifierScheduler(NativeMeshVerifier(4), max_batch=32,
                              min_split=4, hedge=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert len(sched.recover_signers(rows)) == 32
    finally:
        jax.profiler.stop_trace()
        sched.close()
    assert sched.stats()["window_splits"] == 1
    done = tracing.DEFAULT.finished()
    place = [s for s in done if s["name"] == "sched.place"]
    assert len(place) == 1 and place[0]["attrs"] == {"rows": 32}
    assert metrics.histogram(
        "span.seconds;name=sched.place").count == places + 1
    for name in ("sched.stage", "sched.resolve"):
        lanes = sorted(s["attrs"]["device"] for s in done
                       if s["name"] == name)
        assert lanes == [0, 1, 2, 3], (name, lanes)
    assert not [n for n in metrics.snapshot()
                if n.startswith("span.") and "device" in n]
    # in the trace the event keeps the span's name; the lane is a stat
    import glob
    pb = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True))[-1]
    staged = [ev for pl in ProfileData.from_file(pb).planes
              if pl.name.startswith("/host:CPU")
              for ln in pl.lines for ev in ln.events
              if ev.name == "sched.stage"]
    assert sorted(dict(ev.stats)["device"] for ev in staged) == [0, 1, 2, 3]
    assert all(dict(ev.stats)["rows"] == 8 for ev in staged)


def test_a_burst_and_a_small_call_keep_histograms_of_their_own():
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.scheduler import BURST_ROWS, VerifierScheduler
    from eges_tpu.crypto.verify_host import NativeBatchVerifier
    from eges_tpu.utils.metrics import DEFAULT as metrics

    def count(span, size):
        return metrics.histogram(f"span.seconds;name={span},"
                                 f"class=consensus,size={size}").count

    before = {(sp, z): count(sp, z) for sp in ("sched.submit", "sched.await")
              for z in ("call", "burst")}
    sig = host.ecdsa_sign(b"\x11" * 32, b"\x07" * 32)
    rows = [((k + 5000).to_bytes(4, "big") * 8, sig)
            for k in range(BURST_ROWS)]
    sched = VerifierScheduler(NativeBatchVerifier())
    try:
        assert len(sched.recover_signers(rows[:32],
                                         priority="consensus")) == 32
        assert len(sched.recover_signers(rows, priority="consensus")) == \
            BURST_ROWS
    finally:
        sched.close()
    for key, n in before.items():
        assert count(*key) == n + 1, key


def test_an_ack_burst_is_one_window_under_the_burst_labels():
    """The 1025-row ACK burst as the benchmark's driver hands it over
    (``verify_host.recover_signers`` on a scheduler): its two spans
    keep the names and labels that ``vote_submit_ms.vote`` and
    ``vote_await_ms.vote`` read, and it flies as one full window and a
    one-row tail."""
    from eges_tpu.crypto import secp256k1 as host
    from eges_tpu.crypto.scheduler import VerifierScheduler
    from eges_tpu.crypto.verify_host import (
        NativeBatchVerifier, recover_signers,
    )
    from eges_tpu.utils.metrics import DEFAULT as metrics

    def hist(span):
        return metrics.histogram(f"span.seconds;name={span},"
                                 "class=consensus,size=burst").count

    before = {sp: hist(sp) for sp in ("sched.submit", "sched.await")}
    sig = host.ecdsa_sign(b"\x11" * 32, b"\x07" * 32)
    rows = [((k + 9000).to_bytes(4, "big") * 8, sig) for k in range(1025)]
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=1024)
    try:
        assert len(recover_signers(rows, sched, priority="consensus")) == 1025
        flights = sched.flights()
        st = sched.stats()
    finally:
        sched.close()
    for sp, n in before.items():
        assert hist(sp) == n + 1, sp
    mine = [s for s in tracing.DEFAULT.finished()
            if s["name"] in ("sched.submit", "sched.await")
            and s["attrs"].get("rows") == 1025]
    assert [s["name"] for s in mine[-2:]] == ["sched.submit", "sched.await"]
    for s in mine[-2:]:
        assert (s["attrs"]["class"], s["attrs"]["size"]) == \
            ("consensus", "burst")
    assert [(f["rows"], f["reason"], f["klass"]) for f in flights] == \
        [(1024, "full", "consensus"), (1, "kick", "consensus")]
    assert (st["window_submits_consensus"], st["window_rows_consensus"]) \
        == (1, 1025)


# -- the journal ------------------------------------------------------------

def test_resend_and_retry_say_whose_message_was_missing():
    from eges_tpu.sim.cluster import SimCluster

    c = SimCluster(3, seed=5)
    silenced = c.nodes[1].addr.hex()[:8]
    c.net.partition("node1")
    # the two live members hear each other 1.2 s late: a vote misses
    # the 1 s re-send, an ACK the 500 ms validate retry
    c.net.set_link("node2", "node0", latency_s=1.2, jitter_s=0.0)
    c.net.set_link("node0", "node2", latency_s=1.2, jitter_s=0.0)
    c.start()
    c.run(8.0)
    live = [e for name, evs in c.journals().items() if name != "node1"
            for e in evs]
    resends = [e for e in live if e["type"] == "election_resend"]
    retries = [e for e in live if e["type"] == "validate_retry"]
    assert resends and retries
    for e in resends + retries:
        assert e["retry"] >= 1 and e["have"] < e["need"]
        assert silenced in e["missing"]
        assert len(e["missing"]) <= 3 and all(
            len(m) == 8 for m in e["missing"])
    # a first send is no re-send
    started = [e for e in live if e["type"] == "election_started"]
    assert len(started) >= 1 and all("missing" not in e for e in started)


def test_a_message_handled_is_a_span_by_its_kind():
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils.metrics import DEFAULT as metrics

    def count(kind):
        return metrics.histogram(
            f"span.seconds;name=consensus.handle,kind={kind}").count

    before = {k: count(k) for k in ("elect", "vote", "validate_req",
                                    "validate_reply", "confirm")}
    c = SimCluster(3, seed=2)
    c.start()
    c.run(120, stop_condition=lambda: c.min_height() >= 2)
    assert c.min_height() >= 2
    for kind, n in before.items():
        assert count(kind) > n, kind
    assert metrics.histogram(
        "span.seconds;name=consensus.verify_quorum").count > 0


def test_rpc_handle_labels_by_the_first_method_of_a_closed_vocabulary():
    import json

    from eges_tpu.rpc.server import RpcServer
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils.metrics import DEFAULT as metrics

    def count(method):
        return metrics.histogram(
            f"span.seconds;name=rpc.handle,method={method}").count

    c = SimCluster(3, seed=3)
    rpc = RpcServer(c.nodes[0].chain, node=c.nodes[0].node)
    n_known, n_other = count("eth_blockNumber"), count("other")
    batch = [{"jsonrpc": "2.0", "id": i, "method": "eth_blockNumber",
              "params": []} for i in range(3)]
    out = json.loads(rpc._handle_body(json.dumps(batch).encode()))
    assert [r["result"] for r in out] == ["0x0"] * 3
    # a batch counts once; a name from outside never makes a series
    assert count("eth_blockNumber") == n_known + 1
    rpc._handle_body(json.dumps({"jsonrpc": "2.0", "id": 1, "method":
                                 "x;name=evil,k=v", "params": []}).encode())
    rpc._handle_body(b'{"id": 2, "method": 5}')
    assert count("other") == n_other + 2
    span = tracing.DEFAULT.finished(limit=3)[0]
    assert span["name"] == "rpc.handle" and span["attrs"]["calls"] == 3


# -- the trace armer ---------------------------------------------------------

def test_armer_starts_the_profiler_with_its_python_tracer_off(monkeypatch,
                                                              tmp_path):
    from eges_tpu.utils.devstats import DeviceTraceArmer

    calls = []

    class _Options:
        python_tracer_level = 1

    class _Profiler:
        ProfileOptions = _Options

        @staticmethod
        def start_trace(path, profiler_options=None):
            calls.append((path, profiler_options))

        @staticmethod
        def stop_trace():
            calls.append("stop")

    class _Jax:
        profiler = _Profiler()

    monkeypatch.setitem(sys.modules, "jax", _Jax())
    armer = DeviceTraceArmer()
    armer.arm(2, outdir=str(tmp_path))
    armer.step()
    assert armer.status()["state"] == "tracing"
    (path, opts), = calls
    assert path.startswith(str(tmp_path))
    assert opts is not None and opts.python_tracer_level == 0
    assert armer.disarm()["captures"] == 1 and calls[-1] == "stop"


# -- the event loop's lag ------------------------------------------------------

def test_loop_lag_tick_observes_how_late_it_fired():
    from eges_tpu.node.service import NodeService
    from eges_tpu.utils.metrics import DEFAULT as metrics

    class _Timers:
        def __init__(self):
            self.t, self.calls = 10.0, []

        def now(self):
            return self.t

        def call_later(self, delay, fn):
            self.calls.append((delay, fn))
            return self

        def cancel(self):
            pass

    svc = NodeService.__new__(NodeService)  # the tick needs only a clock
    svc.clock = _Timers()
    hist = metrics.histogram("service.loop_lag_seconds")
    n, total = hist.count, hist.total
    svc._lag_tick(svc.clock.now())            # on time
    (delay, again), = svc.clock.calls
    assert delay == NodeService.LAG_TICK_S == 0.02
    svc.clock.t += 0.02 + 0.035               # the loop was busy 35 ms
    again()
    assert hist.count == n + 2
    assert hist.total - total == pytest.approx(0.035)
    assert len(svc.clock.calls) == 2
