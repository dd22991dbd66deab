"""Several validators of one chain on one host, ONE chip: the verify
sidecar's deployment.

This driver's process IS the sidecar's: it builds the verify path as the
sidecar's entry point does (``crypto/verify_path.build`` and ``warm``:
``default_verifier()`` behind ``scheduler_for()``, every bucket warmed)
and serves it (``crypto/sidecar.serve``), so the device trace, the
scheduler's ``stats()``, its flight ring and the registry are read where
``drivers/node.py`` reads them.  It starts the configuration's ``nodes``
node processes (``drivers/sidecar_node.py``: a ``SidecarClient``, a
``TxPool``, no jax) and hands them the one chain they all follow
(``perfbench/gen_shared.py``): a block goes to every node at the same
instant, and the next-but-one when ALL have answered it, so at most
``blocks_in_flight`` blocks are open across the host and a straggler
holds the block, as validators of one chain keep in step.

**What a driver of several processes owes** (for the next one):

* ONE ``obs``, laid together from several registries.  Its top level
  (``before`` / ``after`` / ``samples`` / ``flights`` / ``trace``) is the
  process that holds the chip, so every metric the one-process cells
  report of scheduler, verifier, kernels, device and interpreter reads
  the same thing here; each other process's snapshots stand under
  ``obs["nodes"][i]`` (``before`` / ``after`` / ``window_s``, the
  registry's flat names with ``txpool`` and ``client`` beside them), read
  by the readers that know of them (``readers/sidecar_call_extra.py``).
  A share "of the window" that each of three processes spends would sum
  past what one process can: it is listed only where it is read a node
  and averaged, else it goes to the ``info`` line (``node_shares``).
* the trace in the process that holds the chip: only that process can
  trace the device, and a child that imports jax would take the chip.
  The nodes report ``jax_imported`` and ``correct`` asks it false.
* clocks: ``time.monotonic()`` is one clock for every process of a host;
  a node stamps its own snapshots, the window is the driver's.
* ``verify_rows_per_s`` is rows answered to ALL nodes over the window
  (frames given an outcome by a node's pool plus vote rows answered, as
  ``drivers/node.py`` counts them, summed).
* nothing a row crosses the control pipes: a line a block.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from perfbench import control_sidecar, harness, peaks
from perfbench.drivers import node as one
from perfbench.drivers import sidecar_node
from perfbench.readers import (client_rows, histogram_share,
                               sidecar_call_extra)

# a node that says nothing for this long has died or hangs: the run ends
# with an error, never with a wait that has no limit
QUIET_S = 180.0


class ProcNode:
    """A node process and the two pipes it is driven by."""

    def __init__(self, index: int, cell, args, socket_path: str, on_done):
        self.index, self.on_done = index, on_done
        self._events: dict = {}
        self._cond = threading.Condition()
        self._wlock = threading.Lock()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "perfbench.drivers.sidecar_node",
             "--workload", cell.name, "--seed", str(args.seed),
             "--node", str(index), "--socket", socket_path,
             "--rehearse", str(int(bool(args.rehearse)))],
            cwd=harness.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if not line.startswith(sidecar_node.MARK):
                sys.stderr.write(f"node {self.index}: {line}")
                continue
            msg = json.loads(line[len(sidecar_node.MARK):])
            if msg["ev"] == "done":
                self.on_done(self.index, msg["b"])
                continue
            with self._cond:
                self._events[msg.get("tag") or msg["ev"]] = msg.get(
                    "data", True)
                self._cond.notify_all()
        with self._cond:
            self._events["eof"] = True
            self._cond.notify_all()

    def _say(self, **msg) -> None:
        with self._wlock:
            self.proc.stdin.write(json.dumps(msg) + "\n")
            self.proc.stdin.flush()

    def wait(self, key: str, timeout: float = QUIET_S):
        with self._cond:
            ok = self._cond.wait_for(
                lambda: key in self._events or "eof" in self._events,
                timeout)
            if not ok or key not in self._events:
                raise SystemExit(f"node {self.index} gave no {key!r}")
            return self._events.pop(key)

    def block(self, b: int) -> None:
        self._say(op="block", b=b)

    def ask_snap(self, tag: str) -> None:
        self._say(op="snap", tag=tag)

    def ask_finish(self) -> None:
        self._say(op="finish")

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self.proc.stdin.close()
        self.proc.stdout.close()


class LocalNode:
    """``--control in_process``: a node in the driver's own process,
    straight on the scheduler: no client, no socket."""

    def __init__(self, index: int, cell, args, sched, on_done, inner):
        self.index = index
        self.runner = sidecar_node.NodeRunner(cell, args.seed, index, sched,
                                              inner)
        self.loop = sidecar_node.NodeLoop(
            self.runner, cell.traffic["blocks_in_flight"],
            lambda b: on_done(index, b))
        self._snaps: dict = {"ready": True}

    def wait(self, key: str, timeout: float = QUIET_S):
        return self._snaps.pop(key)

    def block(self, b: int) -> None:
        self.loop.block(b)

    def ask_snap(self, tag: str) -> None:
        snap = self.runner.snapshot()
        snap["client"] = {}  # the scheduler's stats are the sidecar's
        self._snaps[tag] = snap

    def ask_finish(self) -> None:
        self.loop.stop()
        self._snaps["result"] = {**self.runner.finish(), "client": {},
                                 "failed": self.loop.failed}

    def close(self) -> None:
        pass


class Chain:
    """The one chain the nodes follow: which block is open, who has
    answered it, when the next is released."""

    def __init__(self, n_nodes: int, max_blocks: int | None):
        self.n, self.max_blocks = n_nodes, max_blocks
        self.nodes: list = []
        self.cond = threading.Condition()
        self.answers: dict = {}
        self.completed: list = []  # (block, when all had answered)
        self.next_block = 0
        self.open = 0
        self.chained = False  # release the next block as one completes
        self.last_event = time.monotonic()

    def release(self, count: int) -> None:
        """Hand the next ``count`` blocks to every node, a block to all
        nodes at the same instant.  Caller holds ``self.cond``."""
        for _ in range(count):
            b = self.next_block
            if self.max_blocks is not None and b >= self.max_blocks:
                return  # a rehearsal stops before its small pool ends
            self.next_block += 1
            self.open += 1
            for node in self.nodes:
                node.block(b)

    def on_done(self, index: int, b: int) -> None:
        with self.cond:
            self.last_event = time.monotonic()
            self.answers[b] = self.answers.get(b, 0) + 1
            if self.answers[b] < self.n:
                return
            del self.answers[b]
            self.completed.append((b, self.last_event))
            self.open -= 1
            if self.chained:
                self.release(1)
            self.cond.notify_all()

    def wait_closed(self) -> None:
        """Until no block is open; a host that answers nothing for
        ``QUIET_S`` ends the run."""
        with self.cond:
            while self.open:
                if not self.cond.wait(5.0) and \
                        time.monotonic() - self.last_event > QUIET_S:
                    raise SystemExit("the nodes stopped answering")


def _snapshot(sched, server) -> dict:
    from eges_tpu.utils.metrics import DEFAULT as metrics

    out = metrics.snapshot()
    out["scheduler"] = sched.stats()
    out["sidecar"] = server.stats() if server is not None else {
        "clients": 0, "served": []}
    return out


def _node_obs(before: dict, after: dict) -> dict:
    """One node's two snapshots as an ``obs`` the readers take."""
    def flat(s):
        return {**s["registry"], "txpool": s["txpool"],
                "client": s["client"]}
    return {"before": flat(before), "after": flat(after),
            "window_s": after["t"] - before["t"],
            "rows": after["outcomes"] - before["outcomes"]}


def _node_shares(nodes_obs: list) -> dict:
    """The nodes' own spans as shares of the window, a node, averaged:
    the one-process cells' metric files name the spans."""
    out = {}
    for name in ("decode_share", "pool_ingest_share", "pool_flush_share",
                 "pool_admit_share", "pool_evict_share"):
        spec = harness.metric_file(name + ".rows")
        vals = [histogram_share.read(o, **spec["args"]) for o in nodes_obs]
        if all(v is not None for v in vals):
            out[name] = round(sum(vals) / len(vals), 2)
    calls = [histogram_share.read(o, names=sidecar_call_extra.CALLS)
             for o in nodes_obs]
    if all(v is not None for v in calls):
        out["sidecar_call_share"] = round(sum(calls) / len(calls), 2)
    for key, fam in (("process_cpu_share", "process.cpu_seconds"),
                     ("other_threads_cpu_share",
                      "threads.cpu_seconds;role=other")):
        vals = [100.0 * harness.delta(o, fam) / o["window_s"]
                for o in nodes_obs]
        out[key] = round(sum(vals) / len(vals), 2)
    return out


# PR 38's readings of the sidecar's own process, which this cell cannot
# list (``tests/test_cpu_metrics.py`` pins their ``workloads`` with ``==``):
# read through their own metric files, for the ``info`` line
UNLISTED = ("sched_submit_cpu_share.rows", "sched_stage_cpu_share.rows",
            "sched_collect_cpu_share.rows", "sched_resolve_cpu_share.rows",
            "process_cpu_share.rows", "threads_cpu_share.rows")


def _unlisted(obs: dict) -> dict:
    out = {}
    for name in UNLISTED:
        spec = harness.metric_file(name)
        value = importlib.import_module(
            "perfbench.readers." + spec["reader"]).read(obs, **spec["args"])
        if value is not None:
            out[name] = round(value, 2)
    return out


def run(cell: harness.Cell, args, t0: float) -> int:
    try:
        from eges_tpu.crypto import sidecar, verify_path  # noqa: F401
    except ImportError as e:
        print(f"this program has no verify sidecar ({e}): the cell "
              f"{cell.name} cannot run on it", file=sys.stderr)
        return 2
    d = cell.config["deployment"]
    tr = cell.traffic
    rehearse = args.rehearse
    n_nodes = d["nodes"]
    if args.control not in (None,) + control_sidecar.NAMES:
        raise SystemExit(f"no control {args.control!r} for this driver")

    # -- the chip, or no run ------------------------------------------------
    device = {"platform": "none", "kind": "host C++ verifier", "count": 0,
              "memory_peak_bytes": 0}
    devs, compiles = [], None
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
        compiles = one.Compiles()

    # -- the sidecar: the verify path a node would build, served ----------
    path = control_sidecar.verify_path_of(
        args.control, "native" if rehearse == "native" else "jax",
        max_batch=d["max_batch"])
    sched = path.verifier
    tmp = tempfile.mkdtemp(prefix="perfbench_sidecar_")
    socket_path = os.path.join(tmp, "verify.sock")
    server = None
    if args.control != "in_process":
        server = control_sidecar.serve_of(args.control, sched, socket_path)
    chain = Chain(n_nodes, tr.get("max_blocks"))
    nodes: list = []
    try:
        # the node processes make their traffic from the seed while the
        # sidecar warms its buckets
        if server is not None:
            nodes = [ProcNode(i, cell, args, socket_path, chain.on_done)
                     for i in range(n_nodes)]
        verify_path.warm(path)
        if server is None:
            from perfbench import gen
            inner = gen.NodeFeed(args.seed, d)
            nodes = [LocalNode(i, cell, args, sched, chain.on_done, inner)
                     for i in range(n_nodes)]
        chain.nodes = nodes
        for node in nodes:
            node.wait("ready", 900.0)
        return _measure(cell, args, t0, path, server, chain, device, devs,
                        compiles)
    finally:
        for node in nodes:
            node.close()
        if server is not None:
            server.close()
        sched.close()
        shutil.rmtree(tmp, ignore_errors=True)


def _measure(cell, args, t0, path, server, chain, device, devs,
             compiles) -> int:
    d, tr, rehearse = cell.config["deployment"], cell.traffic, args.rehearse
    sched, nodes, n_nodes = path.verifier, chain.nodes, d["nodes"]
    seconds = args.seconds

    def snaps(tag: str) -> list:
        for node in nodes:
            node.ask_snap(tag)
        return [node.wait(tag) for node in nodes]

    # warm every path the window drives: the first blocks, all open at once
    with chain.cond:
        chain.release(tr["warm_blocks"])
    chain.wait_closed()

    # -- the measured window ---------------------------------------------------
    before = _snapshot(sched, server)
    nodes_before = snaps("before")
    compiles_before = compiles.count if compiles else 0
    pauses = one.GcPauses()
    t_begin = time.monotonic()
    setup_s = t_begin - t0
    t_end = t_begin + seconds
    with chain.cond:
        chain.chained = True
        chain.release(tr["blocks_in_flight"])

    # the traced part of the window: its last seconds
    trace_dir, trace_from, trace_rows0 = None, None, None
    if args.trace and devs:
        import jax

        trace_s = min(tr["trace_seconds"], seconds)
        harness.sleep_until(t_end - trace_s)
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace_rows0 = _snapshot(sched, server)
        trace_from = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    harness.sleep_until(t_end)
    t_close = time.monotonic()
    after = _snapshot(sched, server)
    nodes_after = snaps("after")
    compiles_in = (compiles.count if compiles else 0) - compiles_before
    flights = sched.flights()
    lat = pauses.close(t_begin, t_close)
    with chain.cond:
        chain.chained = False
    if trace_dir:
        traced_s = time.monotonic() - trace_from
        jax.profiler.stop_trace()
    chain.wait_closed()
    for node in nodes:
        node.ask_finish()
    results = [node.wait("result", 600.0) for node in nodes]
    window_s = t_close - t_begin

    # -- what the window measured --------------------------------------------
    nodes_obs = [_node_obs(b, a) for b, a in zip(nodes_before, nodes_after)]
    rows_back = sum(o["rows"] for o in nodes_obs)
    blocks = sum(1 for _b, t in chain.completed if t_begin <= t <= t_close)
    end_to_end = {"verify_rows_per_s": rows_back / window_s,
                  "setup_s": setup_s}
    if devs:
        peak = max(((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for dv in devs), default=0)
        device["memory_peak_bytes"] = int(peak)
    obs = {"before": before, "after": after, "window_s": window_s,
           "samples": lat, "flights": flights, "nodes": nodes_obs,
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

    dev_rows = harness.delta(obs, "verifier.rows")
    host_rows = harness.delta(obs, "verifier.host_rows")
    asked = harness.delta(obs, "scheduler.window_rows")
    print("info " + json.dumps({
        "verify_rows_per_s": end_to_end["verify_rows_per_s"],
        "node_rows_per_s": [round(o["rows"] / o["window_s"], 1)
                            for o in nodes_obs],
        "blocks": blocks, "blocks_per_s": blocks / window_s,
        "device_rows_per_block": dev_rows / max(blocks, 1),
        "sidecar_rows_per_block": asked / max(blocks, 1),
        "cache_hits_per_block": harness.delta(
            obs, "scheduler.cache_hits") / max(blocks, 1),
        "coalesced_per_block": harness.delta(
            obs, "scheduler.coalesced_rows") / max(blocks, 1),
        "node_shares": _node_shares(nodes_obs),
        "sidecar_cpu": _unlisted(obs),
        "node_gc_ms": [r["gc_ms"] for r in results],
        "gc_pause_ms": sum(lat["gc_ms"]), "gc_full": len(lat["gc_full_ms"]),
        "sidecar": {k: v for k, v in after["sidecar"].items()
                    if k != "served"}}), file=sys.stderr)

    # -- correct: every node's every answer, a sample of each through the
    # plain reference, then what makes this deployment this deployment -----
    checks = harness.Checks()
    failed = [f for r in results for f in r["failed"]]
    for f in failed:
        print(f"a node's block failed: {f}", file=sys.stderr)

    def total(key: str) -> int:
        return sum(r[key] for r in results)

    checks.at_most("unanswered_rows", total("unanswered_rows")
                   + len(failed), 0)
    checks.at_most("wrong_answers", total("wrong_answers"), 0)
    # each fresh valid frame was admitted by EVERY node's pool and each
    # fresh invalid one refused, at least once
    checks.at_most("valid_frames_refused", total("valid_frames_refused"), 0)
    checks.at_most("invalid_frames_not_refused",
                   total("invalid_frames_not_refused"), 0)
    checks.at_most("reference_mismatches", total("reference_mismatches"), 0)
    # at least half of the sample of EACH node went through the reference
    checks.at_least("reference_rows",
                    min(r["reference_rows"] for r in results),
                    d["reference_rows"] // 2)
    if not rehearse:
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window", compiles_in, 0)
    checks.equals("lanes", after["scheduler"].get("lanes"), cell.chips)
    # a lost sidecar is never hidden: no row was answered on a node's host
    checks.at_most("sidecar_fallback_rows", sum(
        (r["client"] or {}).get("fallback_rows", 0) for r in results), 0)
    # every asker is a client of the sidecar, and each was served its part
    # of the rows: a quarter of an even share at the least
    checks.equals("clients", after["sidecar"]["clients"], n_nodes)
    checks.at_least("client_rows_min_share_pct",
                    client_rows.read(obs, stat="min_share"),
                    100.0 / (4 * n_nodes))
    if server is not None:  # the chip is the sidecar's alone
        checks.equals("nodes_with_jax",
                      sum(bool(r["jax_imported"]) for r in results), 0)
    # the askers SHARE: a run in which every node's rows are computed for
    # it alone is a different deployment
    if not rehearse:
        checks.at_most("device_rows_per_block", dev_rows / max(blocks, 1),
                       d["device_rows_per_block_limit"])

    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=max(rows_back, 1),
                          failed=total("wrong_answers"),
                          breakdown=breakdown, rehearse=bool(rehearse))
