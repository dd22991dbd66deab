"""One node of a large committee, in one process that holds the chip.

Builds what ``eges_tpu/node/service.py`` builds for its verify path (the
default verifier behind the coalescing scheduler, every bucket warmed from
the artifact store, a ``TxPool`` on that scheduler) and hands it, block
after block, what such a node receives: gossip windows of raw transaction
frames through ``decode_txn_window`` and ``add_remotes_window``, election
votes, a header signature and the ACK replies through
``recover_signers(..., priority="consensus")``.  The rest of the
committee is the generator (``perfbench/gen.py``).
"""

from __future__ import annotations

import contextlib
import gc
import json
import random
import shutil
import sys
import tempfile
import threading
import time

from perfbench import gen, harness, peaks
from perfbench.clock import ThreadClock
from perfbench.readers import lane_rows
from perfbench.ref import secp


class Tally:
    """Every answer the program gave, logged as it comes (an append, so
    that the harness takes next to nothing of the window's CPU) and held
    against what the generator knows by construction once the window has
    closed."""

    def __init__(self, feed: gen.NodeFeed):
        self.feed = feed
        self.lock = threading.Lock()
        self.admits: list = []    # (nonce, to, sender) as the pool admits
        self.handed: list = []    # (block, frame indices) as handed over
        self.vote_log: list = []  # (rows, answers)
        self.vote_rows = 0

    def on_admitted(self, txn, sender) -> None:
        self.admits.append((txn.nonce, txn.to, sender))

    def votes(self, rows, answers) -> None:
        self.vote_log.append((rows, answers))
        with self.lock:
            self.vote_rows += len(answers)

    def judge(self, sample: set, vote_sample: set) -> dict:
        """After the window: what was handed over by kind, what came
        back, how many answers are wrong, and the sampled rows' answers."""
        feed, n_acc = self.feed, self.feed.d["accounts"]
        sent = {"admit": 0, "admit_other": 0, "reject": 0, "duplicate": 0,
                "frames": 0, "votes": 0}
        seen_in: dict = {}
        for b, idx in self.handed:
            seen = seen_in.setdefault(b, set())
            for k in idx:
                if k in seen:
                    sent["duplicate"] += 1
                else:
                    seen.add(k)
                    sent[feed.frame_expect(k)[0]] += 1
            sent["frames"] += len(idx)
        wrong = 0
        admitted = {"admit": 0, "admit_other": 0}
        seen_frames: dict = {}
        for nonce, to, sender in self.admits:
            k = nonce * n_acc + (feed.to_index.get(to, 0) - 1) % n_acc
            what, addr = feed.frame_expect(k)
            # a frame the pool must refuse, or a sender that is not the
            # signer (or is, where the message was altered), is wrong
            if (what == "admit" and sender == addr) or \
                    (what == "admit_other" and sender != addr):
                admitted[what] += 1
            else:
                wrong += 1
            if k in sample:
                seen_frames[k] = sender
        seen_votes: dict = {}
        for rows, answers in self.vote_log:
            sent["votes"] += len(answers)
            for i, got in zip(rows, answers):
                kind, want = feed.vote_kind[i], feed.vote_expect[i]
                if kind is None:
                    wrong += got != want
                elif kind == "flipped_message":
                    wrong += got is None or got == want
                else:
                    wrong += got is not None
                if i in vote_sample:
                    seen_votes[i] = got
        return {"sent": sent, "wrong": wrong, "admitted": admitted,
                "frames": seen_frames, "votes": seen_votes}


class Node:
    """The verify path of one node, and the calls that feed it."""

    def __init__(self, feed, sched, pool, tally, annotate):
        self.feed, self.sched, self.pool = feed, sched, pool
        self.tally, self.annotate = tally, annotate
        self.lock = threading.Lock()

    def window(self, block: int, idx: list) -> None:
        """One gossip window: decode, admit."""
        from eges_tpu.ingress import admit_remotes_window, decode_txn_window

        feed = self.feed
        with self.annotate("decode_window"):
            cols = decode_txn_window([feed.frames[k] for k in idx])
        with self.annotate("pool_admit"):
            admit_remotes_window(self.pool, cols)
        self.tally.handed.append((block, idx))

    def vote_batch(self, rows) -> list:
        from eges_tpu.crypto.verify_host import recover_signers

        entries = [self.feed.vote_entries[i] for i in rows]
        with self.annotate("vote_batch"):
            return recover_signers(entries, self.sched,
                                   priority="consensus")

    def commit(self) -> None:
        """The block is final: its transactions leave the pool."""
        self.pool.remove_included(self.pool.pending_txns())

    def outcomes(self) -> int:
        """Rows whose results have come back so far."""
        s = self.pool.stats
        return (s["admitted"] + s["rejected"] + s["duplicate"]
                + self.tally.vote_rows)


def _snapshot(sched, pool) -> dict:
    from eges_tpu.utils.metrics import DEFAULT as metrics

    out = metrics.snapshot()
    out["txpool"] = dict(pool.stats)
    out["scheduler"] = sched.stats()
    return out


class Compiles:
    """jax's backend compiles, counted on every thread."""

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class GcPauses:
    """The interpreter's garbage collections, timed by ``gc.callbacks``:
    each stops every thread of the process for as long as it runs.  The
    collector is left as the node has it (thresholds unchanged, nothing
    frozen); this only reads the clock around it."""

    def __init__(self):
        self.pauses: list = []  # (start, generation, ms)
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.pauses.append((self._t, info["generation"],
                                (time.monotonic() - self._t) * 1e3))

    def close(self, t_from: float, t_to: float) -> dict:
        """Stop listening; the pauses that began in the window, all of
        them and the full (oldest generation) ones apart."""
        gc.callbacks.remove(self._on)
        mine = [(g, ms) for t, g, ms in self.pauses if t_from <= t <= t_to]
        return {"gc_ms": [ms for _g, ms in mine],
                "gc_full_ms": [ms for g, ms in mine if g == 2]}


def _reference(feed, verdict: dict, sample: set) -> tuple:
    """The sampled rows once more, through the plain reference's own
    recovery: ``(rows compared, rows on which it disagrees)``."""
    rows = bad = 0
    for k in sorted(sample):
        h, sig = feed.frame_parts(k)
        want = secp.recover(h, sig) if sig else None
        rows += 1
        # a frame the pool never admitted must be one the reference refuses
        bad += verdict["frames"].get(k) != want
    for i, got in sorted(verdict["votes"].items()):
        rows += 1
        bad += secp.recover(*feed.vote_entries[i]) != got
    return rows, bad


def layout_checks(checks: harness.Checks, chips: int, obs: dict) -> None:
    """A cell on several chips is held to its layout: the scheduler drives
    one lane a chip, and in the window every lane served its part of the
    rows, a quarter of an even share at the least (an even share is 25% on
    four chips and reads 24.5-24.9; a lane that served nothing reads 0).
    A one-chip cell gets neither comparison."""
    if chips <= 1:
        return
    checks.equals("lanes", (obs["after"].get("scheduler") or {}).get(
        "lanes"), chips)
    checks.at_least("lane_rows_min_share_pct",
                    lane_rows.read(obs, stat="min_share"),
                    100.0 / (4 * chips))


def _no_span(name: str):
    """In place of ``jax.profiler.TraceAnnotation`` where no jax is."""
    return contextlib.nullcontext()


def run(cell: harness.Cell, args, t0: float) -> int:
    d = cell.config["deployment"]
    tr = cell.traffic
    rehearse = args.rehearse

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
    if args.control == "accept_all":
        from perfbench.control import AcceptAll
        raw = AcceptAll(raw)
    elif args.control == "short_cycle":
        d = {**d, "pool_blocks": 1, "vote_pool_blocks": 1}
    elif args.control == "one_lane":
        from perfbench.control import OneLane
        raw = OneLane(raw)
    elif args.control:
        raise SystemExit(f"no control {args.control!r} for this driver")

    from eges_tpu.core.txpool import TxPool
    from eges_tpu.crypto.scheduler import scheduler_for

    sched = scheduler_for(raw, max_batch=d["max_batch"])
    if hasattr(raw, "aot_prewarm"):
        # every bucket a window can be padded to, as node/service.py
        # warms them (the facade rounds these up to its own ladder)
        raw.aot_prewarm(buckets=tuple(
            16 << i for i in range(16) if 16 << i <= sched.max_batch))

    # -- traffic from the seed, the pool on the scheduler --------------------
    feed = gen.NodeFeed(args.seed, d)
    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts at block ``warm``
    first_blocks = [k for w in feed.windows(warm) + feed.windows(warm + 1)
                    for k in w]
    odd = [k for k in first_blocks if feed.frame_kind[k] is not None]
    sample = set(odd[:n_ref // 4]) | set(rng.sample(first_blocks,
                                                    n_ref // 2))
    vote_rows0 = [i for part in feed.votes(warm) for i in part]
    vodd = [i for i in vote_rows0 if feed.vote_kind[i] is not None]
    vote_sample = set(vodd[:n_ref // 8]) | set(rng.sample(vote_rows0,
                                                          n_ref // 8))
    tally = Tally(feed)
    pool = TxPool(ThreadClock(), verifier=sched,
                  on_admitted=tally.on_admitted)
    node = Node(feed, sched, pool, tally, annotate)

    def whole_block(b: int) -> None:
        for idx in feed.windows(b):
            node.window(b, idx)
        el, hd, ack = feed.votes(b)
        for rows in (el, hd, ack):
            tally.votes(rows, node.vote_batch(rows))
        node.commit()

    # warm every path the window drives: one block by each worker
    next_block = [warm]
    if tr["arrival"] == "backlog":
        ws = [threading.Thread(target=whole_block, args=(b,))
              for b in range(warm)]
        for w in ws:
            w.start()
        for w in ws:
            w.join()
    else:
        for b in range(warm):
            whole_block(b)

    # -- the measured window ---------------------------------------------------
    stop = threading.Event()
    lat = {"vote_ms": [], "gen_late_ms": []}
    threads: list = []
    seconds = args.seconds
    before = _snapshot(sched, pool)
    compiles_before = compiles.count if compiles else 0
    out_before = node.outcomes()
    pauses = GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds

    if tr["arrival"] == "backlog":
        def worker():
            while not stop.is_set():
                with node.lock:
                    b = next_block[0]
                    next_block[0] += 1
                if b >= tr.get("max_blocks", b + 1):
                    return  # a rehearsal stops before its small pool ends
                whole_block(b)
        threads = [threading.Thread(target=worker)
                   for _ in range(tr["blocks_in_flight"])]
    else:
        period = 1.0 / tr["blocks_per_s"]
        n_blocks = int(seconds * tr["blocks_per_s"])

        def feeder():
            for j in range(n_blocks):
                b = warm + j
                wins = feed.windows(b)
                for i, idx in enumerate(wins):
                    due = (t_begin + j * period
                           + i * period * tr["txn_spread"] / len(wins))
                    lat["gen_late_ms"].append(
                        harness.sleep_until(due) * 1e3)
                    if stop.is_set():
                        return
                    node.window(b, idx)
                node.commit()

        def voter():
            for j in range(n_blocks):
                el, hd, ack = feed.votes(warm + j)
                t_blk = t_begin + j * period
                harness.sleep_until(t_blk)
                if stop.is_set():
                    return
                tally.votes(el, node.vote_batch(el))
                due = t_blk + period * tr["ack_point"]
                lat["gen_late_ms"].append(harness.sleep_until(due) * 1e3)
                if stop.is_set():
                    return
                rows = list(hd) + list(ack)
                got = node.vote_batch(rows)
                lat["vote_ms"].append((time.monotonic() - due) * 1e3)
                tally.votes(rows, got)
        threads = [threading.Thread(target=feeder),
                   threading.Thread(target=voter)]
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
    end_to_end = {"verify_rows_per_s": rows_back / window_s,
                  "vote_p50_ms": harness.quantile(lat["vote_ms"], 0.5),
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

    print("info " + json.dumps({
        "gen_late_p95_ms": harness.quantile(lat["gen_late_ms"], 0.95),
        "gen_late_max_ms": max(lat["gen_late_ms"], default=None),
        "vote_max_ms": max(lat["vote_ms"], default=None),
        "vote_p50_ms": end_to_end["vote_p50_ms"],
        "vote_p95_ms": harness.quantile(lat["vote_ms"], 0.95),
        "votes": len(lat["vote_ms"]),
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "gc_full_max_ms": max(lat["gc_full_ms"], default=None),
        "verify_rows_per_s": end_to_end["verify_rows_per_s"]}),
        file=sys.stderr)

    # -- correct: every answer, then a sample through the plain reference --
    checks = harness.Checks()
    verdict = tally.judge(sample, vote_sample)
    sent, st = verdict["sent"], final["txpool"]
    # every frame and vote row handed over since the start has an outcome
    checks.at_most("unanswered_rows", handed - node.outcomes(), 0)
    checks.at_most("wrong_answers", verdict["wrong"], 0)
    # each fresh valid frame was admitted and each fresh invalid one
    # refused, at least once (a copy that comes after the pool's dedup
    # history was cleared is, rightly, judged again)
    checks.at_most("valid_frames_refused", max(0, sent["admit"]
                   + sent["admit_other"] - verdict["admitted"]["admit"]
                   - verdict["admitted"]["admit_other"]), 0)
    checks.at_most("invalid_frames_not_refused",
                   max(0, sent["reject"] - st["rejected"]), 0)
    ref_rows, ref_bad = _reference(feed, verdict, sample)
    checks.at_most("reference_mismatches", ref_bad, 0)
    checks.at_least("reference_rows", ref_rows, d["reference_rows"] // 2)
    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window", compiles_in, 0)
    if rehearse != "native":  # the host C++ verifier is one lane by nature
        layout_checks(checks, cell.chips, obs)
    # the cycle of rows is longer than the program's caches remember;
    # hits beyond the few that a cleared dedup history lets through
    # would mean a pass costs less than fresh rows would
    checks.at_most("cache_hit_share_pct", 100.0 * harness.delta(
        obs, "scheduler.cache_hits") / max(rows_back, 1),
        d["cache_hit_share_limit_pct"])

    attempted = int(harness.delta(obs, "txpool.admitted")
                    + harness.delta(obs, "txpool.rejected")
                    + harness.delta(obs, "txpool.duplicate")) \
        + len(lat["vote_ms"])
    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=max(attempted, rows_back),
                          failed=verdict["wrong"], breakdown=breakdown,
                          rehearse=bool(rehearse))
