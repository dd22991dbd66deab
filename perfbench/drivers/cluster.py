"""A cluster of node processes on real localhost sockets, driven from the
client's side.  This parent never imports jax: node 0 is started first and
alone, holds the chip with the device verifier and warms every bucket
before it serves; the other nodes run the host verifier, pinned off the
device.  Signed transfers enter by ``eth_sendRawTransaction`` on the
ingress nodes on an open-loop schedule; commits are observed on a node
that takes no submissions, by one ``eth_getBlockByNumber(h, false)`` for
each new height.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from perfbench import gen, harness
from perfbench.ref import secp

ROOT = harness.ROOT


# -- the cluster -----------------------------------------------------------

class Cluster:
    """Node processes started by the program's CLI, and their end."""

    def __init__(self, d: dict, rundir: str, chip_verifier: str,
                 rehearse: bool):
        self.d, self.dir, self.n = d, rundir, d["nodes"]
        self.chip_verifier, self.rehearse = chip_verifier, rehearse
        self.procs: dict[int, subprocess.Popen] = {}
        # node keys of the benchmark's own making (any stable keys do)
        self.keys, self.addrs = secp.keys(0xE6E5 << 200, self.n)
        self.genesis = os.path.join(rundir, "genesis.json")
        boot = [{"account": a.hex(), "ip": "127.0.0.1",
                 "port": str(d["ports"]["consensus"] + i)}
                for i, a in enumerate(self.addrs)]
        with open(self.genesis, "w") as f:
            json.dump({"config": {"chainId": 930412, "thw": {
                "bootstrap": boot, "reg_per_blk": 10,
                "registration_timeout": d["registration_timeout_s"],
                "validate_timeout": d["validate_timeout_ms"],
                "election_timeout": d["election_timeout_ms"],
                "backoff_time": 0,
                "signed_votes": d["signed_votes"]}},
                "timestamp": "0x0", "extraData": "geec-perfbench"}, f)

    def port(self, kind: str, i: int) -> int:
        return self.d["ports"][kind] + i

    def start(self, i: int) -> None:
        d, chip = self.d, i == self.d["chip_node"]
        peers = ",".join(f"127.0.0.1:{self.port('gossip', j)}"
                         for j in range(self.n))
        cmd = [sys.executable, "-m", "eges_tpu.node",
               "--datadir", os.path.join(self.dir, f"node{i}"),
               "--genesis", self.genesis,
               "--keyhex", self.keys[i].to_bytes(32, "big").hex(),
               "--consensusIP", "127.0.0.1",
               "--consensusPort", str(self.port("consensus", i)),
               "--gossipIP", "127.0.0.1",
               "--gossipPort", str(self.port("gossip", i)),
               "--geecTxnPort", str(self.port("txn", i)),
               "--rpcPort", str(self.port("rpc", i)),
               "--nCandidates", str(d["n_candidates"]),
               "--nAcceptors", str(d["n_acceptors"]),
               "--txnPerBlock", str(d["txn_per_block"]),
               "--txnSize", str(d["txn_size"]),
               "--blockTimeout", str(d["block_timeout_s"]),
               "--totalNodes", str(self.n), "--breakdown",
               "--verifier", self.chip_verifier if chip else "native",
               "--peers", peers, "--mine"]
        env = dict(os.environ, PYTHONPATH=ROOT)
        if not chip or self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"  # a chip belongs to one process
        with open(os.path.join(self.dir, f"node{i}.log"), "wb") as log:
            self.procs[i] = subprocess.Popen(
                cmd, stdout=log, stderr=subprocess.STDOUT, env=env,
                cwd=ROOT, preexec_fn=_die_with_parent)

    def alive(self, i: int) -> bool:
        return self.procs[i].poll() is None

    def stop(self, grace_s: float = 20.0) -> None:
        """Every node gone, and waited for, before this returns."""
        for p in self.procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + grace_s
        for p in self.procs.values():
            try:
                p.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def log_tails(self, n: int = 2000) -> str:
        out = []
        for i in sorted(self.procs):
            try:
                with open(os.path.join(self.dir, f"node{i}.log"), "rb") as f:
                    out.append(f"--- node{i}.log (tail)\n"
                               + f.read()[-n:].decode(errors="replace"))
            except OSError:
                pass
        return "\n".join(out)


def _die_with_parent() -> None:
    """In the child, before exec: a node never outlives this harness,
    however the harness ends (Linux ``PR_SET_PDEATHSIG``)."""
    import ctypes

    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


def rpc(port: int, calls: list, timeout: float = 60.0) -> list:
    """One JSON-RPC batch; the results in the order of ``calls``.  An
    error entry is returned as ``{"error": ...}`` in its place."""
    body = json.dumps([{"jsonrpc": "2.0", "id": i, "method": m,
                        "params": p}
                       for i, (m, p) in enumerate(calls)]).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}", data=body,
        headers={"Content-Type": "application/json"})
    out = json.loads(urllib.request.urlopen(req, timeout=timeout).read())
    out.sort(key=lambda r: r["id"])
    return [r["result"] if "error" not in r else {"error": r["error"]}
            for r in out]


def rpc1(port: int, method: str, params: list, timeout: float = 60.0,
         tries: int = 3):
    for attempt in range(tries):
        try:
            return rpc(port, [(method, params)], timeout)[0]
        except OSError:
            if attempt == tries - 1:
                raise
            time.sleep(1.0)


def wait_rpc(cluster: Cluster, i: int, deadline_s: float) -> bool:
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        try:
            rpc(cluster.port("rpc", i), [("eth_blockNumber", [])], 5.0)
            return True
        except OSError:
            if not cluster.alive(i):
                return False
            time.sleep(0.5)
    return False


# -- the run ---------------------------------------------------------------

def run(cell: harness.Cell, args, t0: float) -> int:
    d, tr = cell.config["deployment"], cell.traffic
    rehearse = bool(args.rehearse)
    if args.control not in (None, "host_verifier"):
        raise SystemExit(f"no control {args.control!r} for this driver")

    assert "jax" not in sys.modules, "the parent must stay off jax"

    rundir = tempfile.mkdtemp(prefix="perfbench_cluster_")
    host = args.control == "host_verifier" or args.rehearse == "native"
    cluster = Cluster(d, rundir, "native" if host else "jax", rehearse)
    chip, ingress, watch = d["chip_node"], tr["ingress_nodes"], \
        tr["observe_node"]
    port = {i: cluster.port("rpc", i) for i in range(cluster.n)}
    try:
        return _run(cell, args, t0, cluster, chip, ingress, watch, port)
    except BaseException:
        print(cluster.log_tails(), file=sys.stderr)
        raise
    finally:
        cluster.stop()
        shutil.rmtree(rundir, ignore_errors=True)


def _run(cell, args, t0, cluster, chip, ingress, watch, port) -> int:
    d, tr = cell.config["deployment"], cell.traffic
    rehearse = bool(args.rehearse)
    seconds = args.seconds

    # node 0 first and alone: below quorum nothing mines, so the chain
    # starts moving only once the slow starter serves
    cluster.start(chip)
    if not wait_rpc(cluster, chip, d["warm_deadline_s"]):
        print("the chip node never served RPC: no TPU, or its warm-up "
              "failed\n" + cluster.log_tails(), file=sys.stderr)
        return 3
    for i in range(cluster.n):
        if i != chip:
            cluster.start(i)
    for i in range(cluster.n):
        if not wait_rpc(cluster, i, 120.0):
            raise RuntimeError(f"node {i} never served RPC")
    deadline = time.monotonic() + 120.0
    while True:
        heights = [int(rpc1(port[i], "eth_blockNumber", []), 16)
                   for i in range(cluster.n)]
        if min(heights) >= 1:
            break
        if time.monotonic() > deadline:
            raise RuntimeError(f"the chain never went live: {heights}")
        time.sleep(0.25)

    # the device, as the chip node's jax reported it (the node logs
    # ``verifier device device=<platform>:<kind>`` before it warms up)
    met = rpc1(port[chip], "thw_metrics", [])
    device = {"platform": "none", "kind": "host C++ verifier", "count": 0,
              "memory_peak_bytes": 0}
    with open(os.path.join(cluster.dir, f"node{chip}.log"),
              errors="replace") as f:
        for text in f:
            _, found, rest = text.partition("verifier device device=")
            if found:
                plat, _, kind = rest.strip().partition(":")
                device.update(platform=plat, kind=kind,
                              count=int(met["scheduler"]["lanes"]))
                break
    if not rehearse and args.control is None and (
            device["platform"] != "tpu" or device["count"] < cell.chips):
        print(f"this cell needs {cell.chips} TPU chip(s); the chip node "
              f"reports {device}", file=sys.stderr)
        return 3

    # -- traffic from the seed ------------------------------------------------
    tick = tr["tick_ms"] / 1e3
    per_tick = int(round(tr["rate_tx_per_s"] * tick))
    pre_ticks = int(round(tr["pre_traffic_s"] / tick))
    ticks = pre_ticks + int(round(seconds / tick))
    xfers = gen.Transfers(args.seed, accounts=d["accounts"],
                          count=per_tick * ticks,
                          payload_bytes=d["payload_bytes"],
                          gas_limit=d["gas_limit"])
    node_of = {a: ingress[j % len(ingress)]
               for j, a in enumerate(xfers.order)}
    due_of: dict = {}          # txn hash (hex) -> due time
    acked: dict = {}           # txn hash (hex) -> account
    refused = [0]
    samples = {"gen_late_ms": [], "rpc_submit_ms": [], "commit_ms": [],
               "block_interval_ms": []}
    lock = threading.Lock()
    stop = threading.Event()
    blocks: list = []  # (height, t_seen, [txn hashes], hash)
    pending: list = []  # the submit calls' futures

    t_pre = time.monotonic() + 0.2
    t_begin = t_pre + pre_ticks * tick
    t_end = t_begin + seconds
    tracing = bool(args.trace) and cluster.chip_verifier == "jax"
    # the traced part of the window is its last seconds: the program's
    # armer starts the profiler with its Python tracer on, which slows
    # the chip node, and the trace's export, a stall of a second or more
    # on the scheduler's thread, then falls after the window has closed
    t_trace = t_end - min(tr["trace_seconds"], seconds)
    t_cut = t_trace if tracing else t_end

    def submit(node: int, ks: list, due: float, late: float) -> None:
        calls = [("eth_sendRawTransaction",
                  ["0x" + xfers.frames[k].hex()]) for k in ks]
        t_call = time.monotonic()
        try:
            got = rpc(port[node], calls, 30.0)
        except OSError as e:
            got = [{"error": str(e)}] * len(ks)
        t_done = time.monotonic()
        with lock:
            if t_begin <= due < t_cut:
                samples["gen_late_ms"].append(late * 1e3)
                samples["rpc_submit_ms"].append((t_done - t_call) * 1e3)
            for k, res in zip(ks, got):
                want = "0x" + xfers.hashes[k].hex()
                if res == want:
                    acked[want] = xfers.account[k]
                    due_of[want] = due
                else:
                    refused[0] += 1
                    if refused[0] <= 3:
                        print(f"refused by node {node}: {res}",
                              file=sys.stderr)

    def sender(node: int):
        """Open loop: each tick's batch leaves when it is due, on a thread
        of its own, whether or not earlier calls have returned."""
        with ThreadPoolExecutor(tr["max_calls_in_flight"]) as calls:
            for t in range(ticks):
                ks = [k for k in range(t * per_tick, (t + 1) * per_tick)
                      if node_of[xfers.account[k]] == node]
                due = t_pre + t * tick
                late = harness.sleep_until(due)
                if ks:
                    pending.append(calls.submit(submit, node, ks, due,
                                                late))

    def observer():
        nxt = int(rpc1(port[watch], "eth_blockNumber", []), 16) + 1
        while not stop.is_set():
            try:
                blk = rpc(port[watch], [("eth_getBlockByNumber",
                                         [hex(nxt), False])], 10.0)[0]
            except OSError:
                blk = None
            if not blk or "error" in blk:
                time.sleep(tr["observe_poll_ms"] / 1e3)
                continue
            with lock:
                blocks.append((nxt, time.monotonic(),
                               blk["transactions"], blk["hash"]))
            nxt += 1

    threads = [threading.Thread(target=sender, args=(n,)) for n in ingress]
    obs_thread = threading.Thread(target=observer)
    obs_thread.start()
    for t in threads:
        t.start()
    trace_dir = os.path.join(cluster.dir, "trace")

    # -- the measured window ---------------------------------------------------
    harness.sleep_until(t_begin)
    setup_s = time.monotonic() - t0
    journal: list = []
    cursors = {i: 0 for i in range(cluster.n)}

    def poll_journals():
        for i in range(cluster.n):
            evs = rpc1(port[i], "thw_journal",
                       [{"limit": 4096, "since_seq": cursors[i]}])
            if evs:
                cursors[i] = evs[-1]["seq"] + 1
                journal.extend(evs)

    if args.trace:
        for i in range(cluster.n):  # start the cursors at the window
            evs = rpc1(port[i], "thw_journal", [{"limit": 1}])
            cursors[i] = evs[-1]["seq"] + 1 if evs else 0
    before = rpc1(port[chip], "thw_metrics", [])
    if args.trace:
        nxt = t_begin + tr["journal_poll_s"]
        while nxt < t_trace:
            harness.sleep_until(nxt)
            poll_journals()
            nxt += tr["journal_poll_s"]
    if tracing:
        harness.sleep_until(t_trace)
        rpc1(port[chip], "thw_device_trace",
             [{"windows": 4096, "dir": trace_dir}])
        traced_from = rpc1(port[chip], "thw_metrics", [])
    harness.sleep_until(t_end)
    after = rpc1(port[chip], "thw_metrics", [])
    t_close = time.monotonic()
    traced, flights = False, []
    if tracing:
        status = rpc1(port[chip], "thw_device_trace", [{"disarm": True}],
                      timeout=300.0)
        traced = bool(status.get("captures"))
    if args.trace:
        poll_journals()
        flights = rpc1(port[chip], "thw_flight", [{"limit": 4096}])
    for t in threads:
        t.join()
    for f in pending:
        f.result()  # a submit that raised is a fault of the harness

    # -- drain, outside the timing: what was acknowledged must commit -------
    deadline = time.monotonic() + tr["drain_s"]
    while time.monotonic() < deadline:
        with lock:
            mined = {h for b in blocks for h in b[2]}
            pending = len(set(acked) - mined)
        if not pending:
            break
        time.sleep(0.2)
    stop.set()
    obs_thread.join()
    final = rpc1(port[chip], "thw_metrics", [])
    peak = final.get("devstats.mem_peak_bytes;device=0")
    device["memory_peak_bytes"] = int(peak or 0)

    # -- the read-back, from a node that took no submissions ----------------
    tops = [int(rpc1(port[i], "eth_blockNumber", []), 16)
            for i in range(cluster.n)]
    top = min(tops)
    chains = {}
    for i in range(cluster.n):
        hs = [hex(h) for h in range(1, (tops[i] if i == watch else top) + 1)]
        chains[i] = [b for lo in range(0, len(hs), 64) for b in rpc(
            port[i], [("eth_getBlockByNumber", [h, False])
                      for h in hs[lo:lo + 64]], 120.0)]
    mined = {h for b in chains[watch] for h in b["transactions"]}
    sent_by = [0] * d["accounts"]
    for h, a in acked.items():
        sent_by[a] += 1
    nonces = rpc(port[watch], [("eth_getTransactionCount",
                                ["0x" + a.hex(), "latest"])
                               for a in xfers.senders], 120.0)
    hash_gaps = sum(1 for h in range(top) if len(
        {chains[i][h]["hash"] for i in range(cluster.n)}) != 1)

    # -- what the window measured ------------------------------------------
    window_s = t_close - t_begin
    # a traced run's client-side samples leave the traced seconds out:
    # there the armer's Python tracer slows the chip node, and what is
    # recorded of the tail should be the system's, not the profiler's
    seen_at = {h: b[1] for b in blocks for h in b[2]}
    for h, due in due_of.items():
        if t_begin <= due < t_cut and h in seen_at:
            samples["commit_ms"].append((seen_at[h] - due) * 1e3)
    in_win = [b for b in blocks if t_begin <= b[1] <= t_close]
    samples["block_interval_ms"] = [
        (b[1] - a[1]) * 1e3 for a, b in zip(in_win, in_win[1:])
        if b[1] <= t_cut]
    committed = sum(len(b[2]) for b in in_win)
    end_to_end = {"commit_p50_ms": harness.quantile(samples["commit_ms"],
                                                    0.5),
                  "commit_tx_per_s": committed / window_s,
                  "setup_s": setup_s}

    obs = {"before": before, "after": after, "window_s": window_s,
           "samples": samples, "flights": flights, "journal": journal,
           "t_begin": t_begin, "t_end": t_close, "trace": None}
    breakdown = None
    if traced:
        out = os.path.join(cluster.dir, "trace.json")
        subprocess.run([sys.executable, "-m", "perfbench.trace", trace_dir,
                        out, "span", tr["recover_program"]],
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=ROOT), cwd=ROOT, check=True,
                       timeout=300)
        with open(out) as f:
            red = json.load(f)
        if red:
            obs["trace"] = red
            obs["trace_rows"] = (harness.pick(after, "verifier.rows") or 0) \
                - (harness.pick(traced_from, "verifier.rows") or 0)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}

    print("info " + json.dumps({
        "gen_late_p95_ms": harness.quantile(samples["gen_late_ms"], 0.95),
        "rpc_submit_p50_ms": harness.quantile(samples["rpc_submit_ms"], 0.5),
        "commit_p50_ms": end_to_end["commit_p50_ms"],
        "commit_p95_ms": harness.quantile(samples["commit_ms"], 0.95),
        "commit_tx_per_s": end_to_end["commit_tx_per_s"],
        "block_interval_p50_ms": harness.quantile(
            samples["block_interval_ms"], 0.5),
        "longest_commit_gap_ms": max(samples["block_interval_ms"],
                                     default=None),
        "blocks_in_window": len(in_win)}), file=sys.stderr)

    # -- correct: the configuration's guarantees ----------------------------
    checks = harness.Checks()
    n_due = per_tick * (ticks - pre_ticks)
    checks.at_most("refused_or_wrong_ack", refused[0], 0)
    checks.at_most("acked_not_committed", len(set(acked) - mined), 0)
    checks.at_most("sender_nonce_gaps", sum(
        1 for want, got in zip(sent_by, nonces)
        if not isinstance(got, str) or int(got, 16) != want), 0)
    checks.at_most("block_hash_gaps", hash_gaps, 0)
    checks.at_least("common_heights", top, 1)
    dev_rows = (harness.pick(final, "verifier.rows") or 0) \
        - (harness.pick(before, "verifier.rows") or 0)
    host_rows = (harness.pick(final, "verifier.host_rows") or 0) \
        - (harness.pick(before, "verifier.host_rows") or 0)
    in_window = sum(1 for due in due_of.values() if due >= t_begin)
    if not rehearse or args.control:
        checks.at_least("device_rows_per_txn",
                        dev_rows / max(in_window, 1),
                        d["device_rows_per_txn_floor"])
        checks.at_most("host_row_share_pct", 100.0 * host_rows
                       / max(dev_rows + host_rows, 1),
                       d["host_row_share_limit_pct"])
    checks.at_most("compiles_in_window",
                   harness.delta(obs, "verifier.aot_compiles"), 0)
    checks.at_least("committed_in_window", committed, 1)

    return harness.finish(cell, bool(args.trace), end_to_end=end_to_end,
                          obs=obs, device=device, checks=checks,
                          attempted=n_due, failed=refused[0] + len(
                              set(acked) - mined),
                          breakdown=breakdown, rehearse=rehearse)
