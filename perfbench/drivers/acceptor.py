"""One acceptor of a large committee that EXECUTES the blocks it votes
on, in one process that holds the chip.

Builds what ``eges_tpu/node/service.py`` builds: the verify path of
``crypto/verify_path.py`` (the default verifier behind the coalescing
scheduler, every bucket warmed), a ``BlockChain`` over the genesis
allocation, a ``GeecNode`` on the wall clock with the chain's 1024
bootstrap members and the node's own signing key, and a ``TxPool`` on the
node's lock.  In the transport's place stands an object that keeps what
the node sends (its relays, its ``ValidateReply``).  Height after height it
is handed what such a node receives (``perfbench/gen_chain.py``,
everything from ``--seed``):

* the block's gossip windows of raw transaction frames through
  ``decode_txn_window`` and ``admit_remotes_window``, on a feeder thread
  that runs one block ahead of the block path;
* once the block's gossip is in and the block before is inserted, the
  proposer's validate request as BYTES through the node's own gossip
  entry point: ``_handle_validate_request`` checks the author and the
  signature, ``chain.validate_candidate`` executes the block against the
  parent state, and the node's signed ACK goes out through the transport;
* then, at once, the confirm with its certificate, as bytes through the
  same entry point: ``_confirm_ok`` (513 supporters' signatures as one
  consensus-class call), ``chain.offer``, the insert, the node's listener
  (``remove_included``, the working block moved on).

One height in ``bad_block_every`` first brings a bad block (four kinds in
turn) and then the sound block of that height as the next proposer sends
it.  The other 1023 validators and the clients are the generator; nothing
stands in for them inside the program.  ``correct`` is decided against
``perfbench/ref/`` alone.
"""

from __future__ import annotations

import importlib
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time

from perfbench import control_accept, gen_chain, harness, peaks
from perfbench.clock import ThreadClock
from perfbench.drivers import validator
from perfbench.drivers.node import Compiles, GcPauses, _no_span, _snapshot
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import secp
from perfbench.ref import senders as ref_senders

# readings of older metrics that this cell cannot list
# (``tests/test_cpu_metrics.py`` and ``test_block_native_share.py`` pin
# their ``workloads`` with ``==``; a traced run may meet no full
# collection): read through their own metric files, for the ``info`` line
UNLISTED = ("decode_cpu_share.rows", "pool_admit_cpu_share.rows",
            "pool_evict_cpu_share.rows", "sched_submit_cpu_share.rows",
            "sched_stage_cpu_share.rows", "sched_collect_cpu_share.rows",
            "sched_resolve_cpu_share.rows", "block_senders_cpu_share.rows",
            "process_cpu_share.rows", "threads_cpu_share.rows",
            "sched_flush_ms.rows", "sched_lane_wait_ms.rows",
            "gc_full_pause_ms.rows", "block_native_share.rows")


class Transport:
    """In the transport's place: keeps what the node sends."""

    def __init__(self):
        self.relayed: list = []  # (when, bytes) of each gossip relay
        self.direct: list = []   # (ip, port, datagram), oldest first

    def gossip(self, data: bytes, **_kw) -> None:
        self.relayed.append((time.monotonic(), len(data)))

    def send_direct(self, ip: str, port: int, data: bytes, **_kw) -> None:
        self.direct.append((ip, port, data))

    def take_direct(self) -> list:
        out, self.direct = self.direct, []
        return out


class Tally(validator.Tally):
    """The validator's tally of the gossip path (every admission, every
    window handed over, judged after the window), and beside it every
    answer of the block path."""

    def __init__(self, feed: gen_chain.ChainFeed):
        super().__init__(feed)
        self.steps: list = []     # (block, step, ACKs, height after, when)
        self.inserted: list = []  # (number, hash) as the chain inserts
        self.step_rows = 0        # rows of handled requests and confirms

    def on_block(self, blk) -> None:
        self.inserted.append((blk.number, blk.hash))

    def judge_blocks(self, node_addr: bytes) -> dict:
        """The block path, step by step: ACKs that must and must not be,
        each ACK through the reference's own recovery, what the chain
        inserted and in which order."""
        feed = self.feed
        out = {"sound_blocks_refused": 0, "bad_blocks_acked": 0,
               "acks_wrong": 0, "acks": 0}
        for p, step, acks, height, _t in self.steps:
            if step.what == "request":
                mine = [a for a in acks if a is not None]
                out["acks"] += len(mine)
                if step.sound and not mine:
                    out["sound_blocks_refused"] += 1
                if not step.sound and mine:
                    out["bad_blocks_acked"] += 1
                for author, num, accepted, bhash, sig in mine:
                    ok = (author == node_addr and num == p + 1
                          and accepted == 1 and bhash == step.block_hash
                          and secp.recover(ref_quorum.ack_sighash(
                              num, author, accepted, bhash), sig)
                          == node_addr)
                    out["acks_wrong"] += not ok
            elif step.sound and height < p + 1:
                out["sound_blocks_refused"] += 1  # certified, not inserted
        numbers = [n for n, _h in self.inserted]
        out["blocks_out_of_order"] = sum(
            1 for i, n in enumerate(numbers) if n != i + 1)
        out["bad_blocks_inserted"] = sum(
            1 for _n, h in self.inserted if h in feed.never_insert)
        out["off_chain_blocks"] = sum(
            1 for n, h in self.inserted
            if n > len(feed.block_hashes) or h != feed.block_hashes[n - 1])
        return out


class Acceptor:
    """The node, and the two threads that feed it."""

    def __init__(self, feed, node, pool, chain, transport, tally, annotate):
        self.feed, self.node, self.pool = feed, node, pool
        self.chain, self.transport = chain, transport
        self.tally, self.annotate = tally, annotate
        self.cv = threading.Condition()
        self.gossip_in = 0     # blocks whose gossip is handed over
        self.started = 0       # blocks whose block path has begun
        self.done = 0          # blocks whose steps are all handed over
        self.stop = threading.Event()
        self.exhausted = threading.Event()
        self.go = threading.Event()  # the window is open
        self.closing = threading.Event()  # it closes with the height in hand
        self.closed = threading.Event()
        self.warm = 0                # the block it opens with
        from eges_tpu.ingress import gossip_sink

        self.sink = gossip_sink(node)

    def feeder(self) -> None:
        """Gossip, one block ahead of the block path."""
        from eges_tpu.ingress import admit_remotes_window, decode_txn_window

        feed = self.feed
        for p in range(len(feed.blocks)):
            with self.cv:
                self.cv.wait_for(lambda: self.started >= p
                                 or self.stop.is_set())
            for idx in feed.windows(p):
                if self.stop.is_set():
                    return
                with self.annotate("decode_window"):
                    cols = decode_txn_window([feed.frames[k] for k in idx])
                with self.annotate("pool_admit"):
                    admit_remotes_window(self.pool, cols)
                self.tally.handed.append((p, idx))
            with self.cv:
                self.gossip_in = p + 1
                self.cv.notify_all()

    def block_path(self) -> None:
        """Requests and confirms, a height at a time."""
        feed = self.feed
        for p in range(len(feed.steps)):
            with self.cv:
                self.cv.wait_for(lambda: self.gossip_in > p
                                 or self.stop.is_set())
                if self.stop.is_set():
                    return
                self.started = p + 1
                self.cv.notify_all()
            if p == self.warm:
                self.go.wait()
            for step in feed.steps[p]:
                with self.annotate("block_" + step.what):
                    self.sink(step.data)
                acks = [ref_quorum.read_ack(dg)
                        for _ip, _port, dg in self.transport.take_direct()]
                self.tally.steps.append((p, step, acks, self.chain.height(),
                                         time.monotonic()))
                self.tally.step_rows += step.rows
            with self.cv:
                self.done = p + 1
                self.cv.notify_all()
            if self.closing.is_set():
                self.closed.set()
                return
        self.exhausted.set()
        self.closed.set()

    def outcomes(self) -> int:
        """Rows whose results have come back so far."""
        s = self.pool.stats
        return (s["admitted"] + s["rejected"] + s["duplicate"]
                + self.tally.step_rows)


def _unlisted(obs: dict) -> dict:
    out = {}
    for name in UNLISTED:
        spec = harness.metric_file(name)
        value = importlib.import_module(
            "perfbench.readers." + spec["reader"]).read(obs, **spec["args"])
        if value is not None:
            out[name] = round(value, 2)
    return out


def build_node(feed, d: dict, chain, sched, transport):
    """The node as ``node/service.py`` wires one: the chain's bootstrap
    members, the node's own key, the wall clock."""
    from eges_tpu.consensus.config import (BootstrapNode, ChainGeecConfig,
                                           NodeConfig)
    from eges_tpu.consensus.node import GeecNode

    me = {a: (ip, port) for a, ip, port in feed.validators}[feed.node_addr]
    ncfg = NodeConfig(
        coinbase=feed.node_addr, consensus_ip=me[0], consensus_port=me[1],
        n_candidates=d["committee"], n_acceptors=d["acceptors"],
        txn_per_block=d["txn_per_block"], txn_size=d["payload_bytes"],
        total_nodes=d["validators"],
        privkey=feed.node_priv.to_bytes(32, "big"))
    ccfg = ChainGeecConfig(bootstrap=tuple(
        BootstrapNode(account=a, ip=ip, port=port)
        for a, ip, port in feed.validators))
    return GeecNode(chain, ThreadClock(), transport, ncfg, ccfg,
                    verifier=sched)


def run(cell: harness.Cell, args, t0: float) -> int:
    try:
        from eges_tpu.crypto import verify_path
        from eges_tpu.utils import tracing
        if "chain.validate_candidate" not in tracing.SPANS:
            raise ImportError("no span chain.validate_candidate")
    except ImportError as e:
        print(f"this program has no measured block path ({e}): the cell "
              f"{cell.name} cannot run on it", file=sys.stderr)
        return 2
    d = cell.config["deployment"]
    tr = cell.traffic
    rehearse = args.rehearse
    if args.control not in (None,) + control_accept.NAMES:
        raise SystemExit(f"no control {args.control!r} for this driver")

    # -- the chip, or no run ------------------------------------------------
    device = {"platform": "none", "kind": "host C++ verifier", "count": 0,
              "memory_peak_bytes": 0}
    devs, compiles, annotate = [], None, _no_span
    if rehearse != "native":
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        if not rehearse and (device["platform"] != "tpu"
                             or len(devs) < cell.chips):
            print(f"this cell needs {cell.chips} TPU chip(s); jax found "
                  f"{device}", file=sys.stderr)
            return 3
        compiles = Compiles()
        annotate = jax.profiler.TraceAnnotation

    # -- the verify path warms while the chain is made from the seed -------
    meant: dict = {}
    path = control_accept.verify_path_of(
        args.control, "native" if rehearse == "native" else "jax", meant,
        max_batch=d["max_batch"])
    sched = path.verifier
    warmer = threading.Thread(target=verify_path.warm, args=(path,))
    warmer.start()
    # the reference's tries of a full-size chain are built in processes of
    # their own, beside this one's cores for the warm-up
    workers = 0 if rehearse else max(1, min(8, (os.cpu_count() or 2) - 2))
    feed = gen_chain.ChainFeed(args.seed, d, workers=workers,
                               first_bad=control_accept.FIRST_BAD[
                                   args.control])
    meant.update(feed.meant)
    warmer.join()

    # -- the node -----------------------------------------------------------
    from eges_tpu.core.txpool import TxPool

    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts when block ``warm`` does
    tally = Tally(feed)
    transport = Transport()
    chain = control_accept.chain_class(args.control)(
        verifier=sched, alloc={a: feed.balance for a in feed.addrs})
    chain.add_listener(tally.on_block)
    node = build_node(feed, d, chain, sched, transport)
    node.quorum = control_accept.quorum_of(args.control, node)
    pool = TxPool(node.clock, verifier=sched, on_admitted=tally.on_admitted)
    node.txpool = pool
    node.start()
    acc = Acceptor(feed, node, pool, chain, transport, tally, annotate)
    acc.warm = warm
    first_blocks = [k for p in (warm, warm + 1) if p < len(feed.blocks)
                    for w in feed.windows(p) for k in w]
    odd = [k for k in first_blocks if feed.kind[k] is not None]
    sample = set(odd[:n_ref // 4]) | set(rng.sample(
        first_blocks, min(len(first_blocks), n_ref - n_ref // 4)))

    threads = [threading.Thread(target=acc.feeder),
               threading.Thread(target=acc.block_path)]
    for t in threads:
        t.start()
    with acc.cv:  # warm every path the window drives
        acc.cv.wait_for(lambda: acc.done >= warm)

    # -- the measured window ---------------------------------------------------
    seconds = args.seconds
    before = _snapshot(sched, pool)
    compiles_before = compiles.count if compiles else 0
    out_before = acc.outcomes()
    pauses = GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds
    acc.go.set()

    # the traced part of the window: its last seconds
    trace_dir, trace_from, trace_rows0 = None, None, None
    if args.trace and devs:
        import jax

        trace_s = min(tr["trace_seconds"], seconds)
        acc.exhausted.wait(max(0.0, t_end - trace_s - time.monotonic()))
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_rows0 = _snapshot(sched, pool)
        trace_from = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # the window closes with the height that is in hand when its seconds
    # are up (whole heights over the time they took: at two seconds a
    # height, a window cut at the second would count a height more or
    # less, 4% of a run, by where the cut fell); a run that reaches the
    # end of its chain closes there
    acc.exhausted.wait(max(0.0, t_end - time.monotonic()))
    acc.closing.set()
    acc.closed.wait(30.0)
    t_close = time.monotonic()
    after = _snapshot(sched, pool)
    out_after = acc.outcomes()
    compiles_in = (compiles.count if compiles else 0) - compiles_before
    flights = sched.flights()
    lat = pauses.close(t_begin, t_close)
    exhausted = acc.exhausted.is_set()
    with acc.cv:
        acc.stop.set()
        acc.cv.notify_all()
    if trace_dir:
        traced_s = time.monotonic() - trace_from
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    node.stop()
    # the pool's window timer (5 ms) flushes what the last window left
    handed = sum(len(idx) for _b, idx in tally.handed) + tally.step_rows
    deadline = time.monotonic() + 5.0
    while acc.outcomes() < handed and time.monotonic() < deadline:
        time.sleep(0.01)
    window_s = t_close - t_begin

    # -- what the window measured --------------------------------------------
    rows_back = out_after - out_before
    end_to_end = {"verify_rows_per_s": rows_back / window_s,
                  "setup_s": setup_s}
    if devs:
        peak = max(((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for dv in devs), default=0)
        device["memory_peak_bytes"] = int(peak)
    final = _snapshot(sched, pool)
    sched.close()

    obs = {"before": before, "after": after, "window_s": window_s,
           "samples": lat, "flights": flights,
           "t_begin": t_begin, "t_end": t_close, "trace": None}
    breakdown = None
    if trace_dir:
        from perfbench import trace as tracemod

        red = tracemod.reduce(tracemod.load(trace_dir), traced_s,
                              program=tr["recover_program"])
        if red:
            obs["trace"] = red
            obs["trace_rows"] = (harness.pick(after, "verifier.rows") or 0) \
                - (harness.pick(trace_rows0, "verifier.rows") or 0)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            rate = peaks.achieved(red, obs["trace_rows"], device["kind"])
            if rate:
                print(json.dumps({"recover_program_u32_mac_per_s": rate,
                                  "note": "nominal textbook work over "
                                  "traced device time; no ceiling yet"}))
        shutil.rmtree(trace_dir, ignore_errors=True)

    in_window = [s for s in tally.steps if t_begin <= s[4] <= t_close]
    inserted_in = sum(1 for p, step, _a, height, _t in in_window
                      if step.what == "confirm" and step.sound
                      and height >= p + 1)
    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    per_5s = [0] * (int(window_s / 5.0) + 1)
    for _p, step, _a, _h, t in in_window:
        if step.what == "confirm" and step.sound:
            per_5s[min(int((t - t_begin) / 5.0), len(per_5s) - 1)] += 1
    print("info " + json.dumps({
        "blocks_per_5s": per_5s,  # how steady the window was inside
        "blocks_inserted": inserted_in,
        "blocks_per_s": inserted_in / window_s,
        "bad_heights_in_window": sorted(
            {p + 1 for p, step, _a, _h, _t in in_window if step.bad}),
        "relays": len(transport.relayed),
        "pool_pending": sum(len(v) for v in pool.pending.values()),
        "scheduler_rows": harness.delta(obs, "scheduler.cache_hits")
        + harness.delta(obs, "scheduler.cache_misses"),
        "cache_hits": harness.delta(obs, "scheduler.cache_hits"),
        "device_rows": dev_rows, "host_rows": host_rows,
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "gc_full_max_ms": max(lat["gc_full_ms"], default=None),
        "unlisted": _unlisted(obs),
        "verify_rows_per_s": end_to_end["verify_rows_per_s"]}),
        file=sys.stderr)

    # -- correct: the block path, the state, then the gossip path ----------
    checks = harness.Checks()
    blocks = tally.judge_blocks(feed.node_addr)
    for name in ("sound_blocks_refused", "bad_blocks_acked",
                 "bad_blocks_inserted", "blocks_out_of_order",
                 "off_chain_blocks", "acks_wrong"):
        checks.at_most(name, blocks[name], 0)
    # every account's nonce and balance at the last inserted height
    height = chain.height()
    want, got = feed.state_at(height), chain.head_state()
    checks.at_most("accounts_wrong", sum(
        1 for a, (n, b) in want.items()
        if (got.nonce(a), got.balance(a)) != (n, b)), 0)
    checks.at_least("accounts_compared", len(want), d["accounts"])
    checks.at_least("blocks_inserted", inserted_in, d["blocks_inserted_min"])
    if not rehearse:  # a rehearsal's chain is a dozen blocks long
        checks.equals("chain_exhausted", exhausted, False)
    verdict = tally.judge(sample)
    sent, st = verdict["sent"], final["txpool"]
    # every frame and every row of a request or confirm handed over since
    # the start has an outcome
    checks.at_most("unanswered_rows", handed - acc.outcomes(), 0)
    checks.at_most("wrong_answers", verdict["wrong"], 0)
    checks.at_most("valid_frames_refused", max(0, sent["admit"]
                   + sent["admit_other"] - verdict["admitted"]["admit"]
                   - verdict["admitted"]["admit_other"]), 0)
    checks.at_most("invalid_frames_not_refused",
                   max(0, sent["reject"] - st["rejected"]), 0)
    ref_bad = sum(1 for k in sorted(sample)
                  if verdict["frames"].get(k)
                  != ref_senders.frame_sender(feed.frames[k]))
    checks.at_most("reference_mismatches", ref_bad, 0)
    checks.at_least("reference_rows", len(sample), n_ref // 2)
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
        # what the recovery cache and the windows in flight are for: of
        # the rows ASKED a block, far fewer are computed
        checks.at_most("device_rows_per_block",
                       (dev_rows + host_rows) / max(inserted_in, 1),
                       d["device_rows_per_block_limit"])
    checks.at_most("compiles_in_window", compiles_in, 0)

    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=rows_back,
                          failed=verdict["wrong"] + blocks["acks_wrong"],
                          breakdown=breakdown, rehearse=bool(rehearse))
