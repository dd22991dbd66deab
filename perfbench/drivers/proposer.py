"""The proposer of a 256-validator committee, in one process that holds
the chip.

Builds what ``eges_tpu/node/service.py`` builds for its verify path, as
``drivers/node.py`` does (the default verifier behind the coalescing
scheduler, every bucket warmed from the artifact store, a ``TxPool`` on
that scheduler), and beside it what ``consensus/node.py`` builds for its
quorums: a ``Membership`` of the 256 validators under the chain's
``validate_threshold`` and the ``QuorumTally`` on the same scheduler.
Block after block it plays the validator whose turn it is to propose and
is handed what such a node receives (``perfbench/gen_votes.py``,
everything from ``--seed``):

* the block's gossip windows of raw transaction frames through
  ``decode_txn_window`` and ``admit_remotes_window``, and the election's
  vote rows and the header's row through ``recover_signers(...,
  priority="consensus")``: ``drivers/node.py``'s calls, imported;
* the block's 255 ACK datagrams, as bytes, through the program's own
  path: ``consensus.quorum.handle_direct`` (the node's ``on_direct``: the
  lock, the ``consensus.handle`` span, ``M.unpack_direct``) into
  ``QuorumTally.ack`` (the body of the node's ``_handle_validate_reply``)
  on a ``WorkingBlock``, by ONE thread in arrival order and none before
  it is due.  Nothing of the tally lives here: this file opens a block,
  hands the bytes over and notes when the program says its quorum stands.

The other 255 validators are the generator.  ``correct`` holds what the
timed path itself certified, block for block, against
``perfbench/ref/quorum.py`` (who may be counted, when a quorum may and
must stand; a sample of blocks from the datagrams' bytes, their
certificates signature by signature), and the bulk rows as
``drivers/node.py`` holds them.
"""

from __future__ import annotations

import json
import random
import shutil
import sys
import tempfile
import threading
import time

from perfbench import control_votes, gen_votes, harness, peaks
from perfbench.clock import ThreadClock
from perfbench.drivers.node import (Compiles, GcPauses, Node, Tally,
                                    _no_span, _reference, _snapshot)
from perfbench.ref import quorum as ref_quorum

INGRESS_MAX_BYTES = 1 << 20  # GeecNode.INGRESS_MAX_BYTES


class Proposer:
    """The ACK path of one proposer: the program's membership, tally and
    per-height scratchpad, and the calls that feed them."""

    def __init__(self, feed, sched, annotate, fraction, signing: bool):
        from eges_tpu.consensus import messages as M
        from eges_tpu.consensus.config import ChainGeecConfig, ttl_params
        from eges_tpu.consensus.membership import Member, Membership
        from eges_tpu.consensus.quorum import QuorumTally, handle_direct
        from eges_tpu.consensus.working_block import WorkingBlock
        from eges_tpu.utils import ledger

        d = feed.d
        # the chain's configuration, through the program's own reading
        # of its genesis section
        ccfg = ChainGeecConfig.from_json(
            {"signed_votes": signing, **({} if fraction is None else
                                         {"validate_threshold": fraction})})
        tp = ttl_params(d["validators"])
        self.membership = Membership(
            d["committee"], d["acceptors"],
            validate_fraction=ccfg.validate_threshold, **tp)
        for a in feed.members:
            self.membership.add(Member(addr=a, ip="", port=0, referee=a,
                                       ttl=tp["initial_ttl"]))
        self.tally = QuorumTally(self.membership, sched,
                                 signing=ccfg.signed_votes)
        self.feed, self.annotate = feed, annotate
        self._handle_direct = handle_direct
        self._reply_code = M.UDP_EXAMINE_REPLY
        self.lock = threading.RLock()
        self.book = ledger.IngressLedger(clock=time.monotonic)
        self.wb = WorkingBlock(feed.members[0])
        self.index, self.block, self.handed, self.stands = 0, None, 0, None
        self.log: list = []  # a record a block handed over, as it closes
        self.dropped: list = []  # what the ingress said of a datagram

    def open(self, b: int) -> None:
        """Block ``b``'s proposal is out: the scratchpad moves to its
        height and waits for its quorum."""
        self.index, self.block = b, self.feed.block(b)
        self.wb.advance(self.block.number)
        self.wb.validate_threshold = self.membership.validate_threshold()
        self.handed, self.stands = 0, None

    def on_direct(self, data: bytes) -> None:
        """One datagram, as the node's ``on_direct`` takes it."""
        self.handed += 1
        self._handle_direct(data, self._dispatch, lock=self.lock,
                            book=self.book, max_bytes=INGRESS_MAX_BYTES,
                            log=self._dropped)

    def _dropped(self, what: str, **kw) -> None:
        self.dropped.append((what, kw))

    def _dispatch(self, code: int, msg, author: bytes) -> None:
        if code == self._reply_code and self.tally.ack(
                self.wb, msg, seed=self.block.seed,
                block_hash=self.block.hash,
                collecting=self.stands is None):
            self.stands = {"t": time.monotonic(), "at": self.handed,
                           "supporters": tuple(self.wb.validate_replies),
                           "cert": dict(self.wb.validate_cert)}

    def close(self) -> dict | None:
        """The block's stream has been handed over; what the program
        certified (None: no quorum)."""
        self.log.append((self.index, self.handed, self.stands))
        return self.stands

    def burst(self, b: int, due: float | None, gap_s: float) -> dict | None:
        """Block ``b``'s ACK stream, a datagram at a time and none
        before it is due (``due`` None: back to back)."""
        self.open(b)
        with self.annotate("ack_burst"):
            for k, data in enumerate(self.block.datagrams):
                if due is not None:
                    harness.sleep_until(due + k * gap_s)
                self.on_direct(data)
        return self.close()

    def confirm(self, stands: dict, block, short: bool = False):
        """The confirm a proposer would build on its quorum; ``short``
        leaves it one supporter under the program's threshold."""
        from eges_tpu.core.types import ConfirmBlockMsg

        sups = stands["supporters"]
        if short:
            sups = sups[:self.membership.validate_threshold() - 1]
        return ConfirmBlockMsg(
            block_number=block.number, hash=block.hash, confidence=1000,
            supporters=sups,
            supporter_sigs=tuple(stands["cert"].get(a, b"") for a in sups))


def judge_quorums(feed, proposer: Proposer, fraction, warm: int,
                  n_ref: int) -> dict:
    """After the window: every block's quorum against who may be
    counted and when a quorum may and must stand (the generator's
    construction, in the reference's own shape), then ``n_ref`` blocks of
    the window once more from their datagrams' bytes through the plain
    reference, and their certificates signature by signature and through
    the program's own check."""
    out = {"forged": 0, "under": 0, "pruned": 0, "missed": 0, "blocks": 0,
           "ref_blocks": 0, "ref_mismatches": 0, "certs_refused_by_ref": 0,
           "certs_refused": 0, "short_certs_accepted": 0}
    picked = []
    for b, handed, stands in proposer.log:
        got = ref_quorum.judge_quorum(
            feed.judged(b, handed), stands and stands["at"],
            stands["supporters"] if stands else ())
        for k, v in got.items():
            out[k] += v
        out["blocks"] += 1
        if b >= warm and stands and len(picked) < n_ref:
            picked.append((b, handed, stands))
    for b, handed, stands in picked:
        blk = feed.block(b)
        ref = ref_quorum.tally(blk.datagrams[:handed], feed.members,
                               fraction, blk.number, blk.hash)
        out["ref_blocks"] += 1
        # the reference's reading of the bytes is the construction's,
        # and the program's quorum is sound by it
        out["ref_mismatches"] += ref != feed.judged(b, handed)
        out["ref_mismatches"] += sum(ref_quorum.judge_quorum(
            ref, stands["at"], stands["supporters"]).values())
        out["certs_refused_by_ref"] += ref_quorum.check_certificate(
            stands["supporters"],
            [stands["cert"].get(a, b"") for a in stands["supporters"]],
            feed.members, fraction, blk.number, blk.hash) is not None
        out["certs_refused"] += not proposer.tally.cert_ok(
            proposer.confirm(stands, blk), blk.seed)
        out["short_certs_accepted"] += proposer.tally.cert_ok(
            proposer.confirm(stands, blk, short=True), blk.seed)
    return out


def run(cell: harness.Cell, args, t0: float) -> int:
    try:
        import eges_tpu.consensus.quorum  # noqa: F401
    except ImportError as exc:
        print("this program cannot run the deployment (no ACK tally to "
              f"drive, no validate_threshold): {exc}", file=sys.stderr)
        return 4
    tr = cell.traffic
    rehearse = args.rehearse
    d, fraction, signing = control_votes.apply(
        args.control, cell.config["deployment"])

    # -- the chip, or no run ------------------------------------------------
    device = {"platform": "none", "kind": "host C++ verifier", "count": 0,
              "memory_peak_bytes": 0}
    devs, compiles, annotate = [], None, _no_span
    if rehearse == "native":
        from eges_tpu.crypto.verify_host import NativeBatchVerifier
        raw = NativeBatchVerifier()
    else:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        if not rehearse and (device["platform"] != "tpu"
                             or len(devs) < cell.chips):
            print(f"this cell needs {cell.chips} TPU chip(s); jax found "
                  f"{device}", file=sys.stderr)
            return 3
        from eges_tpu.crypto import aotstore
        from eges_tpu.crypto.verifier import default_verifier

        aotstore.enable_persistent_cache()
        compiles = Compiles()
        raw = default_verifier()
        annotate = jax.profiler.TraceAnnotation

    from eges_tpu.core.txpool import TxPool
    from eges_tpu.crypto.scheduler import scheduler_for

    sched = scheduler_for(raw, max_batch=d["max_batch"])
    if hasattr(raw, "aot_prewarm"):
        # every bucket a window can be padded to, as node/service.py
        # warms them (the facade rounds these up to its own ladder)
        raw.aot_prewarm(buckets=tuple(
            16 << i for i in range(16) if 16 << i <= sched.max_batch))

    # -- traffic from the seed; the pool and the tally on the scheduler ----
    feed = gen_votes.VotesFeed(args.seed, d)
    bulk = feed.bulk
    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts at block ``warm``
    first_blocks = [k for w in bulk.windows(warm) + bulk.windows(warm + 1)
                    for k in w]
    odd = [k for k in first_blocks if bulk.frame_kind[k] is not None]
    sample = set(odd[:n_ref // 4]) | set(rng.sample(first_blocks,
                                                    n_ref // 2))
    el0, hd0, _ack = bulk.votes(warm)
    vote_rows0 = list(el0) + list(hd0)
    vodd = [i for i in vote_rows0 if bulk.vote_kind[i] is not None]
    vote_sample = set(vodd[:n_ref // 8]) | set(rng.sample(
        vote_rows0, min(len(vote_rows0), n_ref // 8)))
    tally = Tally(bulk)
    pool = TxPool(ThreadClock(), verifier=sched,
                  on_admitted=tally.on_admitted)
    node = Node(bulk, sched, pool, tally, annotate)
    proposer = Proposer(feed, sched, annotate, fraction, signing)
    gap_s = tr["ack_spread_ms"] / 1e3 / len(feed.block(0).datagrams)

    def votes(rows) -> None:
        tally.votes(rows, node.vote_batch(rows))

    # warm every path the window drives: whole blocks, back to back
    for b in range(warm):
        for idx in bulk.windows(b):
            node.window(b, idx)
        el, hd, _ack = bulk.votes(b)
        votes(el)
        votes(hd)
        proposer.burst(b, None, gap_s)
        node.commit()

    # -- the measured window ---------------------------------------------------
    stop = threading.Event()
    lat = {"vote_ms": [], "gen_late_ms": []}
    seconds = args.seconds
    before = _snapshot(sched, pool)
    compiles_before = compiles.count if compiles else 0
    out_before = node.outcomes()
    pauses = GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds
    period = 1.0 / tr["blocks_per_s"]
    n_blocks = int(seconds * tr["blocks_per_s"])

    def feeder():
        for j in range(n_blocks):
            b = warm + j
            wins = bulk.windows(b)
            for i, idx in enumerate(wins):
                due = (t_begin + j * period
                       + i * period * tr["txn_spread"] / len(wins))
                lat["gen_late_ms"].append(harness.sleep_until(due) * 1e3)
                if stop.is_set():
                    return
                node.window(b, idx)
            node.commit()

    def loop():
        """The node's one loop: the election's rows at the block's
        start, then at the ACK point the header's row and the
        datagrams."""
        for j in range(n_blocks):
            b = warm + j
            el, hd, _ack = bulk.votes(b)
            t_blk = t_begin + j * period
            harness.sleep_until(t_blk)
            if stop.is_set():
                return
            votes(el)
            due = t_blk + period * tr["ack_point"]
            lat["gen_late_ms"].append(harness.sleep_until(due) * 1e3)
            if stop.is_set():
                return
            votes(hd)
            stands = proposer.burst(b, due, gap_s)
            if stands:
                lat["vote_ms"].append((stands["t"] - due) * 1e3)
    threads = [threading.Thread(target=feeder),
               threading.Thread(target=loop)]
    for t in threads:
        t.start()

    # the traced part of the window: its last seconds
    trace_dir, trace_from, trace_rows0 = None, None, None
    if args.trace and devs:
        import jax

        trace_s = min(tr["trace_seconds"], seconds)
        harness.sleep_until(t_end - trace_s)
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_rows0 = _snapshot(sched, pool)
        trace_from = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    harness.sleep_until(t_end)
    t_close = time.monotonic()
    after = _snapshot(sched, pool)
    out_after = node.outcomes()
    compiles_in = (compiles.count if compiles else 0) - compiles_before
    flights = sched.flights()
    lat.update(pauses.close(t_begin, t_close))
    stop.set()
    if trace_dir:
        traced_s = time.monotonic() - trace_from
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    # the pool's window timer (5 ms) flushes what the last block left
    handed = sum(len(idx) for _b, idx in tally.handed) + sum(
        len(a) for _r, a in tally.vote_log)
    deadline = time.monotonic() + 5.0
    while node.outcomes() < handed and time.monotonic() < deadline:
        time.sleep(0.01)
    window_s = t_close - t_begin

    # -- what the window measured --------------------------------------------
    rows_back = out_after - out_before
    end_to_end = {"vote_p50_ms": harness.quantile(lat["vote_ms"], 0.5),
                  "setup_s": setup_s}
    if devs:
        peak = max(((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for dv in devs), default=0)
        device["memory_peak_bytes"] = int(peak)
    final = _snapshot(sched, pool)

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

    # -- correct: every quorum, every bulk answer, then the references ------
    checks = harness.Checks()
    quorums = judge_quorums(feed, proposer, cell.config["deployment"][
        "validate_threshold"], warm, d["reference_blocks"])
    sched.close()
    in_window = [s for b, _h, s in proposer.log if b >= warm]
    from eges_tpu.utils import tracing

    by_attempt: dict = {}  # the ring's newest verify_quorum spans
    for sp in tracing.DEFAULT.finished():
        if sp["name"] == "consensus.verify_quorum":
            by_attempt.setdefault(sp["attrs"].get("attempt"), []).append(
                sp["duration_s"] * 1e3)
    acks = sum(h for b, h, _s in proposer.log if b >= warm)
    mine = [f for f in flights if t_begin <= f.get("t_done", 0) <= t_close
            and f.get("klass") == "consensus"]
    print("info " + json.dumps({
        "gen_late_p95_ms": harness.quantile(lat["gen_late_ms"], 0.95),
        "gen_late_max_ms": max(lat["gen_late_ms"], default=None),
        "vote_p50_ms": end_to_end["vote_p50_ms"],
        "vote_p95_ms": harness.quantile(lat["vote_ms"], 0.95),
        "vote_max_ms": max(lat["vote_ms"], default=None),
        "blocks": len(in_window),
        "certified_at": sorted({s["at"] for s in in_window if s}),
        "supporters": sorted({len(s["supporters"]) for s in in_window if s}),
        "quorum_attempts": harness.delta(obs, "consensus.quorum_attempts"),
        "quorum_rows": harness.delta(obs, "consensus.quorum_rows"),
        "quorum_pruned": harness.delta(obs, "consensus.quorum_pruned"),
        "quorums": harness.delta(obs, "consensus.quorums"),
        # attempt -> [spans in the ring, their median ms]
        "verify_quorum_ms": {str(k): [len(v), harness.quantile(v, 0.5)]
                             for k, v in by_attempt.items()},
        # consensus-class device windows: how many, and the medians of
        # their rows and of a window's four phases
        "consensus_windows": [len(mine)] + [
            harness.quantile([f[k] for f in mine if k in f], 0.5)
            for k in ("rows", "wait_ms", "stage_ms", "compute_ms",
                      "resolve_ms")],
        "cache_hits": harness.delta(obs, "scheduler.cache_hits"),
        "cache_misses": harness.delta(obs, "scheduler.cache_misses"),
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "dropped": proposer.dropped[:3],
        "judged": quorums}), file=sys.stderr)

    # every block whose stream was handed over certified its quorum, on
    # sound supporters alone, on the deployment's threshold at the
    # least, and dropped no sound ACK it held
    checks.at_most("quorums_missed", quorums["missed"], 0)
    checks.at_most("forged_supporters", quorums["forged"], 0)
    checks.at_most("supporters_under_threshold", quorums["under"], 0)
    checks.at_most("sound_acks_pruned", quorums["pruned"], 0)
    checks.at_least("quorums_judged", quorums["blocks"],
                    warm + int(0.9 * n_blocks))
    # no datagram failed to decode, no handler raised
    checks.at_most("datagrams_dropped", len(proposer.dropped), 0)
    # the sampled blocks from their bytes, through the plain reference
    checks.at_most("reference_quorum_mismatches",
                   quorums["ref_mismatches"], 0)
    checks.at_least("reference_blocks", quorums["ref_blocks"],
                    d["reference_blocks"])
    checks.at_most("certificates_refused_by_reference",
                   quorums["certs_refused_by_ref"], 0)
    checks.at_most("certificates_refused", quorums["certs_refused"], 0)
    checks.at_most("short_certificates_accepted",
                   quorums["short_certs_accepted"], 0)
    # the bulk rows, as drivers/node.py holds them
    verdict = tally.judge(sample, vote_sample)
    sent, st = verdict["sent"], final["txpool"]
    checks.at_most("unanswered_rows", handed - node.outcomes(), 0)
    checks.at_most("wrong_answers", verdict["wrong"], 0)
    checks.at_most("valid_frames_refused", max(0, sent["admit"]
                   + sent["admit_other"] - verdict["admitted"]["admit"]
                   - verdict["admitted"]["admit_other"]), 0)
    checks.at_most("invalid_frames_not_refused",
                   max(0, sent["reject"] - st["rejected"]), 0)
    ref_rows, ref_bad = _reference(bulk, verdict, sample)
    checks.at_most("reference_mismatches", ref_bad, 0)
    checks.at_least("reference_rows", ref_rows, d["reference_rows"] // 2)
    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window", compiles_in, 0)
    # the cycle of vote rows is longer than the recovery cache remembers:
    # the hits are a second attempt's (166 of a block's 1467 scheduler
    # rows by construction); more would mean a pass costs less than
    # fresh rows would
    hits = harness.delta(obs, "scheduler.cache_hits")
    checks.at_most("cache_hit_share_pct", 100.0 * hits / max(
        hits + harness.delta(obs, "scheduler.cache_misses"), 1),
        d["cache_hit_share_limit_pct"])

    failed = verdict["wrong"] + sum(
        quorums[k] for k in ("forged", "under", "pruned", "missed"))
    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=rows_back + acks, failed=failed,
                          breakdown=breakdown, rehearse=bool(rehearse))
