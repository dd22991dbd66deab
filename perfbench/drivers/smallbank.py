"""One validator of a large committee whose blocks are CONTRACT CALLS:
``drivers/acceptor.py``'s node, fed BLOCKBENCH's Smallbank
(``perfbench/gen_contracts.py``) where that is fed transfers.

Taken from ``drivers/acceptor.py`` as they are: ``Acceptor`` (the node and
the two threads that feed it: gossip windows through ``decode_txn_window``
and ``admit_remotes_window``, requests and confirms as bytes through the
node's gossip entry point), ``Transport``, ``Tally`` (``judge_blocks``
and, from ``drivers/validator.py``, the gossip path's ``judge``),
``build_node`` and ``_unlisted``.  Written here: :func:`run`, which is
``acceptor.run`` with the genesis (the contract's code and 200,000 slots
under upstream's ``genesis.json`` keys, the block gas limit in the genesis
header), the controls of ``control_contracts``, the checks of the
contract's state and of the ``evm.*`` counters, and the ``.evm`` readings
in the ``info`` line; :func:`_evm_readings` and :func:`_contract_state`.
A fold of the two drivers (ROADMAP D-B z) has ``run`` to fold and nothing
else.

The block path is the node's own: ``GeecNode`` -> ``validate_candidate``
-> ``process_block`` -> ``apply_txn`` -> ``EVM.call``, 4000 times a
height on one account with code.  ``correct`` is decided against
``perfbench/ref/`` alone: every header's state root, receipts root and
gas used are the reference's (``ref/evm.py``, ``ref/trie.py``), so an
insert compares them at the published size.
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

from perfbench import control_contracts, gen_contracts, harness, peaks
from perfbench.drivers.acceptor import (Acceptor, Tally, Transport,
                                        _unlisted, build_node)
from perfbench.drivers.node import Compiles, GcPauses, _no_span, _snapshot
from perfbench.ref import contracts
from perfbench.ref import senders as ref_senders

# the metric files this cell brings; BENCHMARK.json's 128 per-layer
# entries are spent, so they are read for the ``info`` line until a
# benchmark PR lists them
EVM_FILES = ("evm_us_per_call.evm", "evm_ops_per_call.evm",
             "evm_revert_share.evm", "storage_writes_per_block.evm",
             "storage_root_share.evm", "store_nodes_per_height.evm")


def _evm_readings(obs: dict) -> dict:
    out = {}
    for name in EVM_FILES:
        spec = harness.metric_file(name)
        value = importlib.import_module(
            "perfbench.readers." + spec["reader"]).read(obs, **spec["args"])
        if value is not None:
            out[name] = round(value, 2)
    return out


def _contract_state(feed, state, height: int, sample) -> dict:
    """The head's state against the reference's at ``height``: the state
    root, the contract's storage root and code hash, and the two balances
    of every customer of ``sample``; the number of each that differ."""
    acct = state.account(feed.contract)
    want = feed.balances_at(height, sample)
    got = {c: tuple(state.storage_at(feed.contract, feed.slot[m, c])
                    for m in (contracts.SAVING, contracts.CHECKING))
           for c in sample}
    return {"state_root": int(state.root() != feed.state_roots[height]),
            "storage_root": int(acct.storage_root()
                                != feed.storage_roots[height]),
            "code_hash": int(acct.code_hash != gen_contracts.CODE_HASH),
            "customers": sum(1 for c in sample if got[c] != want[c])}


def run(cell: harness.Cell, args, t0: float) -> int:
    try:
        from eges_tpu.crypto import verify_path
        from eges_tpu.utils import tracing
        for span in ("chain.validate_candidate", "state.storage_root"):
            if span not in tracing.SPANS:
                raise ImportError("no span " + span)
    except ImportError as e:
        print(f"this program has no measured block path over a genesis "
              f"with code and storage ({e}): the cell {cell.name} cannot "
              f"run on it", file=sys.stderr)
        return 2
    d = cell.config["deployment"]
    tr = cell.traffic
    rehearse = args.rehearse
    if args.control not in (None,) + control_contracts.NAMES:
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
    path = control_contracts.verify_path_of(
        args.control, "native" if rehearse == "native" else "jax", meant,
        max_batch=d["max_batch"])
    sched = path.verifier
    warmer = threading.Thread(target=verify_path.warm, args=(path,))
    warmer.start()
    # the reference's tries of a full-size chain are built in processes of
    # their own, beside this one's cores for the warm-up
    workers = 0 if rehearse else max(1, min(8, (os.cpu_count() or 2) - 2))
    feed = gen_contracts.ContractFeed(
        args.seed, d, workers=workers,
        first_bad=control_contracts.FIRST_BAD[args.control])
    meant.update(feed.meant)
    warmer.join()

    # -- the node -----------------------------------------------------------
    from eges_tpu.core.txpool import TxPool

    rng = random.Random(args.seed ^ 0x5A17)
    n_ref = d["reference_rows"]
    warm = tr["warm_blocks"]  # the window starts when block ``warm`` does
    tally = Tally(feed)
    transport = Transport()
    # the genesis as a permissioned chain ships its contract: code and
    # storage in the allocation, the block gas limit in the header; all
    # of it (and the generator's objects) before the first row is
    # computed, which is where the process settles its heap
    chain = control_contracts.chain_class(args.control)(
        verifier=sched, alloc=feed.alloc(), gas_limit=d["block_gas_limit"])
    chain.add_listener(tally.on_block)
    node = build_node(feed, d, chain, sched, transport)
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

    started = _snapshot(sched, pool)  # the counters as this run found them
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
        "unlisted": {**_unlisted(obs), **_evm_readings(obs)},
        "gc_frozen_objects": harness.pick(after,
                                          "process.gc_frozen_objects"),
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
    # the contract: the head's state root, its storage root and code
    # hash, and the balances of sampled customers (the hot ones, those
    # whom the aborted payments of inserted blocks named, and others by
    # the seed) against the reference's at that height
    checks.equals("genesis_hash", chain.genesis.hash == feed.genesis_hash,
                  True)
    named = [c for k in range(height * d["txn_per_block"])
             if feed.aborted[k] for c in feed.calls[k][1][:2]]
    some = set(range(1, d["hot_customers"] + 1)) | set(named[-n_ref:])
    some |= set(rng.sample(range(1, d["customers"] + 1),
                           min(d["customers"], n_ref)))
    wrong = _contract_state(feed, got, height, sorted(some))
    checks.at_most("contract_state_wrong", sum(wrong.values()), 0)
    checks.at_least("customers_compared", len(some), n_ref)
    # every call of every block that was executed went through the
    # interpreter, and the aborted ones ended in REVERT: an execution is
    # a request's (in full; up to the transaction that cannot apply; none
    # where the signatures gave no sender, unless the block got an ACK)
    # or, where chain.executions counts more, an insert's of a sound
    # block whose validation was not kept
    ran = [(step, acks) for _p, step, acks, _h, _t in tally.steps
           if step.what == "request"]
    full = d["txn_per_block"]
    calls = sum(full if any(a is not None for a in acks) else step.calls
                for step, acks in ran)
    reverts = sum(step.reverts for step, _acks in ran)
    since = {"before": started, "after": final}
    again = harness.delta(since, "chain.executions") - len(ran)
    checks.equals("evm_calls_unexplained", harness.delta(since, "evm.calls")
                  - calls - full * max(again, 0), 0)
    checks.at_least("evm_reverts", harness.delta(since, "evm.reverts"),
                    reverts)
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
