"""One validator of a small committee whose work is transaction-sender
recovery, in one process that holds the chip.

Builds what ``eges_tpu/node/service.py`` builds for its verify path, as
``drivers/node.py`` does (the default verifier behind the coalescing
scheduler, every bucket warmed from the artifact store, a ``TxPool`` on
that scheduler; no cache size, priority or window policy of its own), and
hands it, block after block, what such a validator receives
(``perfbench/gen_zipf.py``, everything from ``--seed``):

* the block's gossip windows of raw transaction frames through
  ``decode_txn_window`` and ``admit_remotes_window``;
* then the proposer's block body, decoded by the program's own RLP
  decoder into ``Transaction``s as ``Block.from_rlp`` decodes them, through
  ``eges_tpu.core.state.recover_senders(txns, scheduler)``: the call
  ``chain._process`` makes first with a proposed block.  A block that is
  refused (``StateError``) is followed by the same block without its bad
  transaction, as the next proposer would send it;
* then the block is final: ``pool.remove_included(block's transactions)``,
  the call ``consensus/node.py`` makes, and with it the strays a node with
  state would drop as stale (the configuration's ``assumed.strays``).

The other 63 validators are the generator.  ``correct`` holds every
answer of the timed path against what the generator knows by construction,
and a sample of rows of both paths against the plain reference
``perfbench/ref/senders.py``.
"""

from __future__ import annotations

import itertools
import json
import random
import shutil
import sys
import tempfile
import threading
import time

from perfbench import control_zipf, gen_zipf, harness, peaks
from perfbench.clock import ThreadClock
from perfbench.drivers.node import (Compiles, GcPauses, _no_span, _snapshot,
                                    layout_checks)
from perfbench.ref import senders as ref_senders


class Tally:
    """Every answer the program gave, logged as it comes (an append, so
    that the harness takes next to nothing of the window's CPU) and held
    against what the generator knows by construction once the window has
    closed."""

    def __init__(self, feed: gen_zipf.ZipfFeed):
        self.feed = feed
        self.lock = threading.Lock()
        self.admits: list = []   # (transaction hash, sender) as admitted
        self.handed: list = []   # (block, frame indices) as handed over
        self.bodies: list = []   # (block, repaired, senders or None)
        self.body_at: list = []  # when each body was answered
        self.body_rows = 0       # rows of block bodies answered

    def on_admitted(self, txn, sender) -> None:
        self.admits.append((txn.hash, sender))

    def body(self, block: int, repaired: bool, rows: int, senders) -> None:
        self.bodies.append((block, repaired, senders))
        self.body_at.append(time.monotonic())
        with self.lock:
            self.body_rows += rows

    def judge(self, sample: set) -> dict:
        """After the window: what was handed over by kind, what came
        back, how many answers are wrong, and the sampled frames'
        answers."""
        feed = self.feed
        sent = {"admit": 0, "admit_other": 0, "reject": 0, "duplicate": 0,
                "frames": 0}
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
        for h, sender in self.admits:
            k = feed.index_of.get(h)
            what, addr = feed.frame_expect(k) if k is not None \
                else ("reject", None)
            # a frame the pool must refuse, or a sender that is not the
            # signer (or is, where the message was altered), is wrong
            if (what == "admit" and sender == addr) or \
                    (what == "admit_other" and sender != addr):
                admitted[what] += 1
            else:
                wrong += 1
            if k in sample:
                seen_frames[k] = sender
        passed_bad = refused_good = 0
        for b, repaired, got in self.bodies:
            rows = feed.rows_of(b, repaired)
            if feed.is_bad(b) and not repaired:
                passed_bad += got is not None
            elif got is None:
                refused_good += 1
            else:
                wrong += sum(1 for k, s in zip(rows, got)
                             if s != feed.signer(k))
        return {"sent": sent, "wrong": wrong, "admitted": admitted,
                "frames": seen_frames, "bad_blocks_not_refused": passed_bad,
                "blocks_wrongly_refused": refused_good}


class Validator:
    """The verify path of one validator, and the calls that feed it."""

    def __init__(self, feed, sched, pool, tally, annotate):
        self.feed, self.sched, self.pool = feed, sched, pool
        self.tally, self.annotate = tally, annotate
        self.decode_ms: list = []  # a body's decode, which no span bounds
        from eges_tpu.core.types import Transaction

        # what leaves the pool with a block besides its transactions, as
        # the program's own objects (decoded once, at set-up)
        self.strays = []
        for p, wins in enumerate(feed.blocks):
            own = range(p * feed.d["txn_per_block"],
                        (p + 1) * feed.d["txn_per_block"])
            ks = {k for w in wins for k in w
                  if (feed.kind[k] is None and k not in own)
                  or feed.kind[k] == "flipped_message"}
            if p in feed.bad:
                ks.add(feed.origin[feed.bad[p][0]])
            txns = [Transaction.decode(feed.frames[k]) for k in sorted(ks)]
            for t in txns:
                t.hash  # memoised: no digest is taken inside the window
            self.strays.append(txns)

    def window(self, block: int, idx: list) -> None:
        """One gossip window: decode, admit."""
        from eges_tpu.ingress import admit_remotes_window, decode_txn_window

        feed = self.feed
        with self.annotate("decode_window"):
            cols = decode_txn_window([feed.frames[k] for k in idx])
        with self.annotate("pool_admit"):
            admit_remotes_window(self.pool, cols)
        self.tally.handed.append((block, idx))

    def body(self, block: int, repaired: bool = False):
        """The proposer's block body: decoded as ``Block.from_rlp``
        decodes a block's transactions, then the call ``chain._process``
        makes first.  The transactions, or None for a refused block."""
        from eges_tpu.core import rlp
        from eges_tpu.core.state import StateError, recover_senders
        from eges_tpu.core.types import Transaction

        data = self.feed.body(block, repaired)
        with self.annotate("block_validate"):
            t_in = time.monotonic()
            txns = [Transaction.from_rlp(t) for t in rlp.decode(data)]
            self.decode_ms.append((time.monotonic() - t_in) * 1e3)
            try:
                senders = recover_senders(txns, self.sched)
            except StateError:
                senders = None
        self.tally.body(block, repaired, len(txns), senders)
        return txns if senders is not None else None

    def commit(self, block: int, txns: list) -> None:
        """The block is final: its transactions leave the pool, and the
        strays that came with its windows."""
        with self.annotate("block_commit"):
            self.pool.remove_included(txns, block=block)
            self.pool.remove_included(
                self.strays[block % len(self.strays)])

    def whole_block(self, block: int) -> None:
        for idx in self.feed.windows(block):
            self.window(block, idx)
        txns = self.body(block)
        if txns is None:  # refused: the next proposer's block, without it
            txns = self.body(block, repaired=True)
        if txns is not None:
            self.commit(block, txns)

    def outcomes(self) -> int:
        """Rows whose results have come back so far."""
        s = self.pool.stats
        return (s["admitted"] + s["rejected"] + s["duplicate"]
                + self.tally.body_rows)


def _reference(feed, tally: Tally, verdict: dict, sample: set, warm: int,
               n_rows: int, rng) -> tuple:
    """The sampled rows once more, through the plain reference: gossip
    frames by their bytes, and rows of the first refused and the first
    accepted block bodies of the window (the bad row among them).
    ``(rows compared, rows on which it disagrees)``."""
    rows = bad = 0
    for k in sorted(sample):
        rows += 1
        # a frame the pool never admitted must be one the reference refuses
        bad += verdict["frames"].get(k) != ref_senders.frame_sender(
            feed.frames[k])
    picked: dict = {}
    for b, repaired, got in tally.bodies:
        if b >= warm:
            picked.setdefault(got is None, (b, repaired, got))
    for b, repaired, got in picked.values():
        ks = feed.rows_of(b, repaired)
        at = set(rng.sample(range(len(ks)), min(len(ks), n_rows)))
        at |= {i for i, k in enumerate(ks) if feed.kind[k] is not None}
        at = sorted(at)
        want = ref_senders.block_senders(feed.body(b, repaired), at)
        rows += len(at)
        if got is None or want == ref_senders.REFUSE:
            bad += len(at) * ((got is None) != (want == ref_senders.REFUSE))
        else:
            bad += sum(1 for i, s in zip(at, want) if got[i] != s)
    return rows, bad


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
    raw, d, sched_kw = control_zipf.apply(args.control, raw, d)

    from eges_tpu.core.txpool import TxPool
    from eges_tpu.crypto.scheduler import scheduler_for

    sched = scheduler_for(raw, max_batch=d["max_batch"], **sched_kw)
    if hasattr(raw, "aot_prewarm"):
        # every bucket a window can be padded to, as node/service.py
        # warms them (the facade rounds these up to its own ladder)
        raw.aot_prewarm(buckets=tuple(
            16 << i for i in range(16) if 16 << i <= sched.max_batch))

    # -- traffic from the seed, the pool on the scheduler --------------------
    feed = gen_zipf.ZipfFeed(args.seed, d)
    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts at block ``warm``
    first_blocks = [k for w in feed.windows(warm) + feed.windows(warm + 1)
                    for k in w]
    odd = [k for k in first_blocks if feed.kind[k] is not None]
    sample = set(odd[:n_ref // 4]) | set(rng.sample(first_blocks,
                                                    n_ref // 4))
    tally = Tally(feed)
    pool = TxPool(ThreadClock(), verifier=sched,
                  on_admitted=tally.on_admitted)
    node = Validator(feed, sched, pool, tally, annotate)

    # warm every path the window drives: one block by each worker
    ws = [threading.Thread(target=node.whole_block, args=(b,))
          for b in range(warm)]
    for w in ws:
        w.start()
    for w in ws:
        w.join()

    # -- the measured window ---------------------------------------------------
    stop = threading.Event()
    blocks = itertools.count(warm)  # next() is one step under the GIL
    seconds = args.seconds
    before = _snapshot(sched, pool)
    compiles_before = compiles.count if compiles else 0
    out_before = node.outcomes()
    pauses = GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds

    def worker():
        while not stop.is_set():
            b = next(blocks)
            if b >= tr.get("max_blocks", b + 1):
                return  # a rehearsal stops before its small pool ends
            node.whole_block(b)
    threads = [threading.Thread(target=worker)
               for _ in range(tr["blocks_in_flight"])]
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
    lat = pauses.close(t_begin, t_close)
    stop.set()
    if trace_dir:
        traced_s = time.monotonic() - trace_from
        jax.profiler.stop_trace()
    for t in threads:
        t.join()
    # the pool's window timer (5 ms) flushes what the last block left
    handed = sum(len(idx) for _b, idx in tally.handed) + sum(
        len(feed.rows_of(b, rep)) for b, rep, _s in tally.bodies)
    deadline = time.monotonic() + 5.0
    while node.outcomes() < handed and time.monotonic() < deadline:
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

    hits = harness.delta(obs, "scheduler.cache_hits")
    misses = harness.delta(obs, "scheduler.cache_misses")
    mine = [f for f in flights if t_begin <= f.get("t_done", 0) <= t_close]
    small = [f for f in mine if f["rows"] <= 256]  # a body's misses
    large = [f for f in mine if f["rows"] > 256]   # the pool's batches
    per_5s = [0] * (int(window_s / 5.0) + 1)
    for t in tally.body_at:
        if t_begin <= t < t_close:
            per_5s[int((t - t_begin) / 5.0)] += 1
    print("info " + json.dumps({
        "bodies_per_5s": per_5s,  # how steady the window was inside
        "body_decode_ms": harness.quantile(node.decode_ms, 0.5),
        # device windows by size: how many, median rows, median queue wait
        **{name: [len(fs)] + [harness.quantile([f[k] for f in fs], 0.5)
                  for k in ("rows", "wait_ms")]
           for name, fs in (("windows_to_256", small),
                            ("windows_over_256", large))},
        "blocks": sum(1 for b, rep, _s in tally.bodies
                      if b >= warm and not rep),
        "scheduler_rows": hits + misses,
        "coalesced_rows": harness.delta(obs, "scheduler.coalesced_rows"),
        "device_windows": harness.delta(obs, "scheduler.batches"),
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "verify_rows_per_s": end_to_end["verify_rows_per_s"]}),
        file=sys.stderr)

    # -- correct: every answer, then a sample through the plain reference --
    checks = harness.Checks()
    verdict = tally.judge(sample)
    sent, st = verdict["sent"], final["txpool"]
    # every frame and block row handed over since the start has an outcome
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
    checks.at_most("blocks_wrongly_refused",
                   verdict["blocks_wrongly_refused"], 0)
    checks.at_most("bad_blocks_not_refused",
                   verdict["bad_blocks_not_refused"], 0)
    ref_rows, ref_bad = _reference(feed, tally, verdict, sample, warm,
                                   n_ref // 4, rng)
    checks.at_most("reference_mismatches", ref_bad, 0)
    checks.at_least("reference_rows", ref_rows, n_ref // 2)
    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window", compiles_in, 0)
    if rehearse != "native":  # the host C++ verifier is one lane by nature
        layout_checks(checks, cell.chips, obs)
    # half of what enters the scheduler is answered by the recovery cache:
    # below the band the cache answers too little (a validator recovers
    # every sender twice), above it the cycle of rows is one the caches
    # remember and a pass costs less than fresh rows would
    share = 100.0 * hits / max(hits + misses, 1)
    checks.at_least("cache_hit_share_pct", share,
                    d["cache_hit_share_min_pct"])
    checks.at_most("cache_hit_share_pct.max", share,
                   d["cache_hit_share_max_pct"])

    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=rows_back,
                          failed=verdict["wrong"], breakdown=breakdown,
                          rehearse=bool(rehearse))
