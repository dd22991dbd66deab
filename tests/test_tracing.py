"""Tracing + percentile-metrics subsystem tests.

Covers the observability tentpole: span nesting/parent links, wire
context propagation across simnet hops, the reservoir histogram against
a numpy reference, Prometheus text-format shape, the idempotent
``get_logger``, registry thread-safety, the end-to-end one-trace-per-txn
guarantee across a multi-node sim cluster, and the breakdown_report
merge tool.
"""

import logging
import threading

import numpy as np
import pytest

from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import (
    Histogram, Registry, percentile, prometheus_text,
)


# -- spans ---------------------------------------------------------------

def test_span_nesting_and_parent_ids():
    t = tracing.Tracer()
    with t.span("outer", parent=None) as outer:
        assert outer.parent_id is None
        with t.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            with t.span("leaf") as leaf:
                assert leaf.trace_id == outer.trace_id
                assert leaf.parent_id == inner.span_id
    # finished in end order: leaf, inner, outer
    names = [s["name"] for s in t.finished()]
    assert names == ["leaf", "inner", "outer"]
    by_name = {s["name"]: s for s in t.finished()}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["leaf"]["parent"] == by_name["inner"]["span"]
    assert t.current_context() is None  # fully unwound


def test_span_attrs_and_record_span():
    t = tracing.Tracer()
    with t.span("op", rows=7) as sp:
        sp.set_attr("bucket", 16)
    rec = t.record_span("virtual", 1.5, parent=None, phase="election")
    assert rec.duration_s == pytest.approx(1.5)
    fin = t.finished()
    assert fin[0]["attrs"] == {"rows": 7, "bucket": 16}
    assert fin[1]["attrs"] == {"phase": "election"}
    assert fin[1]["duration_s"] == pytest.approx(1.5)


def test_ring_buffer_drops_oldest():
    t = tracing.Tracer(capacity=4)
    for i in range(7):
        t.record_span(f"s{i}", 0.0, parent=None)
    fin = t.finished()
    assert len(fin) == 4
    assert [s["name"] for s in fin] == ["s3", "s4", "s5", "s6"]
    assert t.stats()["dropped"] == 3
    assert t.finished(limit=2)[-1]["name"] == "s6"


def test_wire_inject_extract_roundtrip():
    t = tracing.Tracer()
    assert tracing.extract(b"no header here") == (None, b"no header here")
    with t.span("send", root=True) as sp:
        data = tracing.inject_current(b"\x01payload", t)
    ctx, payload = tracing.extract(data)
    assert payload == b"\x01payload"
    assert ctx == sp.context()
    assert tracing.payload_of(data) == b"\x01payload"
    assert tracing.payload_of(b"plain") == b"plain"
    # no active context -> no header
    assert tracing.inject_current(b"x", t) == b"x"


def test_context_propagates_across_simnet_hop():
    from eges_tpu.sim.simnet import SimClock, SimNet

    clock = SimClock()
    net = SimNet(clock)
    got = {}
    net.join("a", "10.0.0.1", 1, lambda d: None, lambda d: None)
    net.join("b", "10.0.0.2", 2,
             lambda d: got.setdefault("gossip", d),
             lambda d: got.setdefault("direct", d))
    ta = net._gossip_sinks  # sanity: two members joined
    assert len(ta) == 2
    transport = tracing.DEFAULT  # use the process tracer like prod code
    sender = net.join("c", "10.0.0.3", 3, lambda d: None, lambda d: None)
    with transport.span("cross-hop", root=True) as sp:
        sender.gossip(b"\x05hello")
        sender.send_direct("10.0.0.2", 2, b"\x06direct")
    clock.run_until(1.0)
    ctx, payload = tracing.extract(got["gossip"])
    assert payload == b"\x05hello"
    assert ctx.trace_id == sp.trace_id and ctx.span_id == sp.span_id
    ctx2, payload2 = tracing.extract(got["direct"])
    assert payload2 == b"\x06direct"
    assert ctx2.trace_id == sp.trace_id


# -- histogram / percentile math ----------------------------------------

def test_histogram_percentiles_match_numpy():
    h = Histogram()
    vals = np.random.RandomState(7).rand(500) * 3.0
    for v in vals:
        h.observe(float(v))
    # under the reservoir size the sample is exact
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    assert h.mean == pytest.approx(float(vals.mean()))
    assert h.count == 500
    assert h.max == pytest.approx(float(vals.max()))
    assert h.min == pytest.approx(float(vals.min()))


@pytest.mark.parametrize("n", [1, 7, 300, 5000])
def test_histogram_weighted_observation_equals_n_observations(n):
    """``observe(v, n)`` is ``n`` observations of ``v``: count, total,
    extremes and mean are theirs, and below the reservoir's size so is
    every percentile; beyond it the reservoir keeps about the share of
    them that one draw an observation keeps."""
    one, many = Histogram(), Histogram()
    for v in (0.25, 2.0, 0.5):
        one.observe(v, n)
        for _ in range(n):
            many.observe(v)
    assert (one.count, one.min, one.max) == (many.count, many.min,
                                             many.max) == (3 * n, 0.25, 2.0)
    assert one.total == pytest.approx(many.total, rel=1e-12)
    assert one.mean == pytest.approx(many.mean, rel=1e-12)
    if 3 * n <= Histogram.RESERVOIR:
        assert sorted(one._sample) == sorted(many._sample)
        assert one.percentiles((1, 50, 95, 99)) == \
            many.percentiles((1, 50, 95, 99))
    else:
        assert len(one._sample) == Histogram.RESERVOIR
        for v in (0.25, 2.0, 0.5):
            assert one._sample.count(v) == pytest.approx(
                Histogram.RESERVOIR / 3, rel=0.2)
    one.observe(9.0, 0)
    assert one.count == 3 * n and one.max == 2.0


def test_histogram_reservoir_is_bounded():
    h = Histogram()
    for i in range(5 * Histogram.RESERVOIR):
        h.observe(float(i))
    assert h.count == 5 * Histogram.RESERVOIR
    assert len(h._sample) == Histogram.RESERVOIR
    # exact extremes survive sampling; p50 is near the true median
    assert h.max == 5 * Histogram.RESERVOIR - 1
    assert h.percentile(50) == pytest.approx(
        5 * Histogram.RESERVOIR / 2, rel=0.15)


def test_percentile_helper_matches_numpy_interpolation():
    vals = sorted([0.1, 4.0, 2.5, 9.9, 7.3])
    for q in (0, 10, 50, 90, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert percentile([], 50) == 0.0
    assert percentile([3.3], 99) == 3.3


# -- prometheus exposition ----------------------------------------------

def test_prometheus_text_shape():
    reg = Registry()
    reg.counter("chain.blocks").inc(5)
    reg.gauge("chain.height").set(7)
    reg.gauge("verifier.device_name").set("TpuDevice(id=0)")
    reg.timer("verifier.device").update(0.25)
    reg.timer("verifier.device").update(0.75)
    reg.meter("verifier.rows").mark(100)
    for name in ("verifier.device_seconds",
                 "verifier.device_seconds;bucket=128"):
        h = reg.histogram(name)
        for v in range(1, 101):
            h.observe(v / 100.0)
    txt = prometheus_text(reg)
    lines = txt.splitlines()
    assert "# TYPE chain_blocks counter" in lines
    assert "chain_blocks 5" in lines
    assert "# TYPE chain_height gauge" in lines
    assert "chain_height 7" in lines
    # non-numeric gauge becomes an _info series, not a crash
    assert ('verifier_device_name_info{value="TpuDevice(id=0)"} 1'
            in lines)
    assert "# TYPE verifier_device summary" in lines
    assert "verifier_device_count 2" in lines
    assert "verifier_device_sum 1" in lines
    assert "verifier_rows_total 100" in lines
    # one TYPE line per family even with labeled members
    assert txt.count("# TYPE verifier_device_seconds summary") == 1
    assert 'verifier_device_seconds{quantile="0.5"} 0.505' in txt
    assert ('verifier_device_seconds{bucket="128",quantile="0.99"}'
            in txt)
    assert 'verifier_device_seconds_count{bucket="128"} 100' in lines
    # every sample line is "name{labels} value" shaped
    for ln in lines:
        if not ln.startswith("#"):
            assert len(ln.rsplit(" ", 1)) == 2


def test_registry_snapshot_has_timer_min_and_histogram_percentiles():
    reg = Registry()
    reg.timer("t").update(0.1)
    reg.timer("t").update(0.3)
    for v in range(1, 101):
        reg.histogram("h").observe(float(v))
    snap = reg.snapshot()
    assert snap["t"]["min_s"] == pytest.approx(0.1)
    assert snap["t"]["max_s"] == pytest.approx(0.3)
    assert snap["h"]["count"] == 100
    assert snap["h"]["p50"] == pytest.approx(50.5)
    assert snap["h"]["p99"] == pytest.approx(
        float(np.percentile(range(1, 101), 99)))


# -- registry thread-safety ---------------------------------------------

def test_registry_thread_safety():
    reg = Registry()
    errs = []

    def hammer():
        try:
            for _ in range(2000):
                reg.counter("c").inc()
                reg.timer("t").update(0.001)
                reg.histogram("h").observe(1.0)
                reg.meter("m").mark()
        except Exception as e:  # registry races raise here
            errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert reg.counter("c").value == 16000
    assert reg.timer("t").count == 16000
    assert reg.histogram("h").count == 16000
    assert reg.meter("m").count == 16000


# -- get_logger idempotency (satellite) ---------------------------------

def test_get_logger_relevel_and_single_handler(tmp_path):
    import io

    from eges_tpu.utils.log import get_logger

    name = "geec.test-relevel"
    get_logger(name, verbosity=3)
    logger = logging.getLogger(name)
    n_handlers = len(logger.handlers)
    # second call with different verbosity must re-level, not no-op
    get_logger(name, verbosity=1)
    assert logger.level == logging.ERROR
    assert len(logger.handlers) == n_handlers
    get_logger(name, verbosity=5)
    assert logger.level == 1
    assert len(logger.handlers) == n_handlers
    # switching stream retargets the SAME handler instead of stacking
    buf = io.StringIO()
    log = get_logger(name, verbosity=3, stream=buf)
    assert len(logger.handlers) == n_handlers
    log.geec("hello", blk=1)
    assert "hello blk=1" in buf.getvalue()
    buf2 = io.StringIO()
    get_logger(name, verbosity=3, stream=buf2)
    log.geec("again", blk=2)
    assert "again blk=2" in buf2.getvalue()
    assert "again" not in buf.getvalue()


# -- end-to-end: one trace from ingest to commit across nodes -----------

def test_one_trace_links_txn_across_cluster():
    """A txn submitted at node0 must produce txpool.ingest ->
    txpool.admit_window -> tx.commit spans sharing ONE trace id (it is
    its slice's first transaction, so the slice's one admit span is in
    its trace), with commit
    spans from at least two distinct nodes (the wire header carried the
    context across the simnet hop)."""
    from eges_tpu.core.state import INTRINSIC_GAS
    from eges_tpu.core.types import Transaction
    from eges_tpu.crypto import secp256k1 as secp
    from eges_tpu.crypto.keys import deterministic_node_key
    from eges_tpu.sim.cluster import SimCluster

    priv = deterministic_node_key(0)
    sender = secp.pubkey_to_address(secp.privkey_to_pubkey(priv))
    dest = bytes([0x42]) * 20
    eth = 10 ** 18

    tracing.DEFAULT.clear()
    c = SimCluster(3, txn_per_block=2, seed=4, alloc={sender: eth},
                   txpool=True)
    for sn in c.nodes:
        sn.node.txpool.owner = sn.name
    c.start()
    t = Transaction(nonce=0, gas_price=0, gas_limit=INTRINSIC_GAS,
                    to=dest, value=3).signed(priv, chain_id=1)
    c.nodes[0].node.submit_txns([t])
    c.run(60, stop_condition=lambda: all(
        sn.chain.head_state().balance(dest) == 3 for sn in c.nodes))
    assert all(sn.chain.head_state().balance(dest) == 3 for sn in c.nodes)

    spans = tracing.DEFAULT.finished()
    tx_prefix = t.hash.hex()[:16]
    commits = [s for s in spans if s["name"] == "tx.commit"
               and s["attrs"].get("tx") == tx_prefix]
    assert commits, "no tx.commit spans recorded"
    traces = {s["trace"] for s in commits}
    assert len(traces) == 1, f"commit spans split across traces: {traces}"
    trace_id = traces.pop()
    owners = {s["attrs"]["owner"] for s in commits}
    assert len(owners) >= 2, f"trace only covered {owners}"
    # same trace covers the whole lifecycle on-node too
    linked = [s for s in spans if s["trace"] == trace_id]
    names = {s["name"] for s in linked}
    assert "txpool.ingest" in names
    assert "txpool.admit_window" in names
    # commit spans carry the including block number
    assert all(isinstance(s["attrs"].get("block"), int) for s in commits)


def test_breakdown_spans_and_histograms_from_consensus():
    """Consensus phase timings land in BOTH the phase histograms and the
    span buffer (the [Breakdown] call sites now emit all sinks)."""
    from eges_tpu.sim.cluster import SimCluster
    from eges_tpu.utils.metrics import DEFAULT as metrics

    tracing.DEFAULT.clear()
    c = SimCluster(3, seed=2)
    c.start()
    c.run(120, stop_condition=lambda: c.min_height() >= 2)
    assert c.min_height() >= 2
    spans = tracing.DEFAULT.finished()
    names = {s["name"] for s in spans}
    assert "consensus.election" in names
    assert "consensus.seal_total" in names
    assert "chain.insert" in names
    assert metrics.histogram(
        "consensus.phase_seconds;phase=election").count > 0
    assert metrics.histogram("chain.insert_seconds").count > 0


# -- breakdown_report (grep.py analog) ----------------------------------

def test_breakdown_report_merges_logs_and_spans(tmp_path, capsys):
    import json as _json

    from harness import breakdown_report

    log = tmp_path / "node0.log"
    log.write_text(
        "12:00:00 GEEC geec.aabb head height=1\n"
        "12:00:01 GEEC geec.aabb [Breakdown] election time=0.125000s blk=1\n"
        "12:00:02 GEEC geec.aabb [Breakdown] election time=0.375000s blk=2\n"
        "12:00:03 GEEC geec.aabb [Breakdown] seal_total time=1.000000s blk=2\n")
    spandir = tmp_path / "node0"
    spandir.mkdir()
    rows = [{"name": "verifier.batch", "trace": "00" * 16, "span": "11" * 8,
             "parent": None, "start_s": 1.0, "duration_s": d,
             "attrs": {"rows": 8}} for d in (0.010, 0.030)]
    (spandir / "spans.jsonl").write_text(
        "\n".join(_json.dumps(r) for r in rows) + "\n{torn")

    phases = breakdown_report.collect([str(tmp_path)])
    assert phases["election"] == [0.125, 0.375]
    assert phases["seal_total"] == [1.0]
    assert phases["verifier.batch"] == [0.010, 0.030]

    assert breakdown_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "phase" in out and "p99_ms" in out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("election"))
    cols = line.split()
    assert cols[1] == "2"                      # count
    assert float(cols[2]) == pytest.approx(250.0)   # mean_ms
    assert float(cols[4]) == pytest.approx(372.5)   # p99_ms
    # empty input is a reported error, not a crash
    assert breakdown_report.main([str(tmp_path / "missing-dir")]) == 1
