"""Local-cluster harness: the reference's ``test.py``/``start.py``/
``kill.py``/``grep.py`` workflow for this build.

Spawns N real node processes on localhost (distinct port triples like
the reference's 619NN/81NN/100NN scheme, ref: test.py), generates keys
and the genesis ``thw`` bootstrap section, tails logs, and asserts chain
liveness the same way the authors did (grep the logs — SURVEY §4 "logs
as the oracle").

Usage:
    python harness/cluster.py start --nodes 3 --dir /tmp/geec-cluster
    python harness/cluster.py status --dir /tmp/geec-cluster
    python harness/cluster.py kill --dir /tmp/geec-cluster
    python harness/cluster.py soak --nodes 3 --dir /tmp/geec-soak --seconds 60
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from eges_tpu.crypto import secp256k1 as secp  # noqa: E402

GOSSIP_BASE = 6190   # ref test.py port scheme
CONSENSUS_BASE = 8100
TXN_BASE = 10000
RPC_BASE = 9100


def node_key(i: int) -> bytes:
    from eges_tpu.crypto.keys import deterministic_node_key
    return deterministic_node_key(i)


class Runner:
    """Process runner abstraction: localhost or ssh fan-out
    (ref: start.py:103-106 — ssh per cluster host)."""

    def __init__(self, host: str | None = None, ssh_opts: tuple = ()):
        self.host = host  # None/"" = local
        self.ssh_opts = tuple(ssh_opts)

    @property
    def remote(self) -> bool:
        return bool(self.host) and self.host not in ("localhost", "local")

    def ip(self, default: str = "127.0.0.1") -> str:
        return self.host if self.remote else default

    def spawn(self, cmd: list[str], log_path: str, env: dict) -> int:
        if not self.remote:
            with open(log_path, "wb") as logf:
                proc = subprocess.Popen(cmd, stdout=logf,
                                        stderr=subprocess.STDOUT,
                                        env=env, cwd=REPO)
            return proc.pid
        # ssh fan-out: run detached on the host, pid echoed back
        envs = " ".join(f"{k}={v}" for k, v in env.items()
                        if k in ("PYTHONPATH", "JAX_PLATFORMS"))
        quoted = " ".join(f"'{c}'" for c in cmd)
        shell = (f"cd {REPO} && nohup env {envs} {quoted} "
                 f"> {log_path} 2>&1 & echo $!")
        out = subprocess.check_output(
            ["ssh", *self.ssh_opts, self.host, shell], text=True)
        return int(out.strip().splitlines()[-1])

    def push(self, path: str) -> None:
        """scp a file to the same path on the host (ref: start.py scp)."""
        if self.remote:
            subprocess.check_call(
                ["ssh", *self.ssh_opts, self.host,
                 f"mkdir -p {os.path.dirname(path)}"])
            subprocess.check_call(
                ["scp", *self.ssh_opts, path, f"{self.host}:{path}"])

    def kill(self, pid: int) -> None:
        if not self.remote:
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        else:
            subprocess.call(["ssh", *self.ssh_opts, self.host,
                             f"kill {pid} 2>/dev/null || true"])

    def read_log(self, path: str) -> bytes:
        if not self.remote:
            try:
                with open(path, "rb") as f:
                    return f.read()
            except FileNotFoundError:
                return b""
        try:
            return subprocess.check_output(
                ["ssh", *self.ssh_opts, self.host, f"cat {path}"],
                stderr=subprocess.DEVNULL)
        except subprocess.CalledProcessError:
            return b""


def parse_hosts(spec: str, n: int) -> list[Runner]:
    """``host1,host2`` round-robined over n nodes; empty = all local."""
    hosts = [h.strip() for h in spec.split(",") if h.strip()] if spec else []
    if not hosts:
        return [Runner() for _ in range(n)]
    return [Runner(hosts[i % len(hosts)]) for i in range(n)]


def write_genesis(path: str, n: int, *, validate_timeout_ms=500,
                  election_timeout_ms=100, backoff_ms=0,
                  reg_timeout_s=10) -> None:
    boot = []
    for i in range(n):
        addr = secp.pubkey_to_address(secp.privkey_to_pubkey(node_key(i)))
        boot.append({"account": addr.hex(), "ip": "127.0.0.1",
                     "port": str(CONSENSUS_BASE + i)})
    doc = {
        "config": {
            "chainId": 930412,
            "thw": {
                "bootstrap": boot,
                "reg_per_blk": 10,
                "registration_timeout": reg_timeout_s,
                "validate_timeout": validate_timeout_ms,
                "election_timeout": election_timeout_ms,
                "backoff_time": backoff_ms,
                # consensus-critical: pinned explicitly so every build
                # generation parses this genesis identically
                "signed_votes": True,
            },
        },
        "timestamp": "0x0",
        "extraData": "geec-tpu-cluster",
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def _node_cmd(i: int, n: int, dirpath: str, genesis: str, runners,
              *, txn_per_block, txn_size, block_timeout, mine,
              bootnodes: str = "", extra_args=()) -> list[str]:
    datadir = os.path.join(dirpath, f"node{i}")
    cmd = [
        sys.executable, "-m", "eges_tpu.node",
        "--datadir", datadir, "--genesis", genesis,
        "--keyhex", node_key(i).hex(),
        "--consensusIP", runners[i].ip(),
        "--consensusPort", str(CONSENSUS_BASE + i),
        "--gossipIP", runners[i].ip() if runners[i].remote else "127.0.0.1",
        "--gossipPort", str(GOSSIP_BASE + i),
        "--geecTxnPort", str(TXN_BASE + i),
        "--rpcPort", str(RPC_BASE + i),
        "--txnPerBlock", str(txn_per_block),
        "--txnSize", str(txn_size),
        "--blockTimeout", str(block_timeout),
        "--totalNodes", str(n),
        "--breakdown",
        # C++ batch verifier by default: a many-node localhost rig gets
        # batched signature verification without N JAX imports + graph
        # compiles serializing on a small host's cores, and a chip
        # belongs to ONE process — jax_nodes names the node that gets
        # "--verifier jax" (the service default) and the device
        "--verifier", "native",
    ]
    if bootnodes:
        cmd += ["--bootnodes", bootnodes]
    else:
        peers = ",".join(f"{runners[j].ip()}:{GOSSIP_BASE + j}"
                         for j in range(n))
        cmd += ["--peers", peers]
    return cmd + (["--mine"] if mine else []) + list(extra_args)


def _node_env(device_node: bool = False) -> dict:
    """A node process's environment.  A chip belongs to one process at
    a time, so only a ``--verifier jax`` node keeps the ambient JAX
    platform (the TPU where there is one; ``JAX_PLATFORMS=cpu`` where
    the caller's environment says so); every other process is pinned
    off the device."""
    env = dict(os.environ, PYTHONPATH=REPO)
    if not device_node:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _save_meta(dirpath: str, meta: dict) -> None:
    with open(os.path.join(dirpath, "cluster.json"), "w") as f:
        json.dump(meta, f, indent=2)


def load_meta(dirpath: str) -> dict | None:
    p = os.path.join(dirpath, "cluster.json")
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def start_cluster(dirpath: str, n: int, *, txn_per_block=100, txn_size=100,
                  block_timeout=20.0, mine=True, extra_args=(),
                  hosts: str = "",
                  use_bootnode: bool = False, skip: set | None = None,
                  jax_nodes: set | None = None,
                  fast_nodes: set | None = None) -> list[int]:
    """Launch an n-node cluster — localhost or ssh fan-out over
    ``hosts`` (ref: start.py; test.py for the localhost triple-port
    scheme).  ``skip`` holds node indices to NOT start (sync tests)."""
    os.makedirs(dirpath, exist_ok=True)
    runners = parse_hosts(hosts, n)
    genesis = os.path.join(dirpath, "genesis.json")
    write_genesis(genesis, n)
    for r in {id(r): r for r in runners}.values():
        r.push(genesis)

    bootnodes = ""
    pids: list[int | None] = []
    boot_pid = None
    if use_bootnode:
        # discovery instead of a static peer list: nodes join knowing
        # only the bootnode (ref: cmd/bootnode + p2p/discover role)
        bootnodes = f"{runners[0].ip()}:30301"
        boot_cmd = [sys.executable, "-m", "eges_tpu.bootnode",
                    "--addr", "0.0.0.0" if runners[0].remote else "127.0.0.1",
                    "--port", "30301"]
        boot_pid = runners[0].spawn(boot_cmd,
                                    os.path.join(dirpath, "bootnode.log"),
                                    _node_env())
        time.sleep(0.5)

    for i in range(n):
        if skip and i in skip:
            pids.append(None)
            continue
        # jax_nodes run the device batch verifier (argparse last-wins
        # overrides the default "--verifier native") on whatever
        # platform the ambient environment gives JAX
        extra = list(extra_args)
        device_node = bool(jax_nodes and i in jax_nodes)
        if device_node:
            extra += ["--verifier", "jax"]
        if fast_nodes and i in fast_nodes:
            extra += ["--syncmode", "fast"]
        cmd = _node_cmd(i, n, dirpath, genesis, runners,
                        txn_per_block=txn_per_block, txn_size=txn_size,
                        block_timeout=block_timeout, mine=mine,
                        bootnodes=bootnodes, extra_args=extra)
        pids.append(runners[i].spawn(
            cmd, os.path.join(dirpath, f"node{i}.log"),
            _node_env(device_node)))
    _save_meta(dirpath, {
        "n": n, "hosts": hosts, "pids": pids, "boot_pid": boot_pid,
        "txn_per_block": txn_per_block, "txn_size": txn_size,
        "block_timeout": block_timeout, "mine": mine,
        "use_bootnode": use_bootnode,
        "jax_nodes": sorted(jax_nodes) if jax_nodes else [],
        "fast_nodes": sorted(fast_nodes) if fast_nodes else [],
    })
    return [p for p in pids if p is not None]


def start_node(dirpath: str, i: int, *, mine=True) -> int:
    """Start one (previously skipped or killed) node of a saved cluster
    — the join leg of the sync scenario (ref: test-sync.py)."""
    meta = load_meta(dirpath)
    assert meta is not None, "no cluster.json; start the cluster first"
    runners = parse_hosts(meta["hosts"], meta["n"])
    genesis = os.path.join(dirpath, "genesis.json")
    device_node = i in meta.get("jax_nodes", [])
    extra = ["--verifier", "jax"] if device_node else []
    if i in meta.get("fast_nodes", []):
        extra += ["--syncmode", "fast"]
    cmd = _node_cmd(i, meta["n"], dirpath, genesis, runners,
                    txn_per_block=meta["txn_per_block"],
                    txn_size=meta["txn_size"],
                    block_timeout=meta["block_timeout"], mine=mine,
                    bootnodes=(f"{runners[0].ip()}:30301"
                               if meta.get("use_bootnode") else ""),
                    extra_args=extra)
    pid = runners[i].spawn(cmd, os.path.join(dirpath, f"node{i}.log"),
                           _node_env(device_node))
    meta["pids"][i] = pid
    _save_meta(dirpath, meta)
    return pid


def kill_cluster(dirpath: str) -> None:
    """(ref: kill.py)"""
    meta = load_meta(dirpath)
    if meta is not None:
        runners = parse_hosts(meta["hosts"], meta["n"])
        for i, pid in enumerate(meta["pids"]):
            if pid is not None:
                runners[i].kill(pid)
        if meta.get("boot_pid"):
            runners[0].kill(meta["boot_pid"])
        meta["pids"] = [None] * meta["n"]
        meta["boot_pid"] = None
        _save_meta(dirpath, meta)
    # legacy pid file support
    pid_file = os.path.join(dirpath, "pids")
    if os.path.exists(pid_file):
        with open(pid_file) as f:
            for line in f:
                try:
                    os.kill(int(line.strip()), signal.SIGTERM)
                except (ProcessLookupError, ValueError):
                    pass
        os.remove(pid_file)


def restart_cluster(dirpath: str) -> list[int]:
    """Relaunch a stopped cluster PRESERVING datadirs and keys — chains
    resume from their FileStores (ref: re-start.py: restart without
    wiping keystores/genesis)."""
    meta = load_meta(dirpath)
    assert meta is not None, "no cluster.json to restart from"
    kill_cluster(dirpath)
    time.sleep(0.5)
    meta = load_meta(dirpath)
    runners = parse_hosts(meta["hosts"], meta["n"])
    genesis = os.path.join(dirpath, "genesis.json")
    if meta.get("use_bootnode"):
        boot_cmd = [sys.executable, "-m", "eges_tpu.bootnode",
                    "--addr", "127.0.0.1", "--port", "30301"]
        meta["boot_pid"] = runners[0].spawn(
            boot_cmd, os.path.join(dirpath, "bootnode.log"),
            _node_env())
    pids = []
    for i in range(meta["n"]):
        cmd = _node_cmd(i, meta["n"], dirpath, genesis, runners,
                        txn_per_block=meta["txn_per_block"],
                        txn_size=meta["txn_size"],
                        block_timeout=meta["block_timeout"],
                        mine=meta["mine"],
                        bootnodes=(f"{runners[0].ip()}:30301"
                                   if meta.get("use_bootnode") else ""))
        pids.append(runners[i].spawn(
            cmd, os.path.join(dirpath, f"node{i}.log"),
            _node_env()))
    meta["pids"] = pids
    _save_meta(dirpath, meta)
    return pids


_HEAD_RE = re.compile(r"head height=(\d+)")


def node_heights(dirpath: str) -> list[int]:
    """Log-grep liveness oracle (ref: grep.py + test-sep-2.sh)."""
    heights = []
    for name in sorted(os.listdir(dirpath)):
        # node logs only — bootnode.log has no head lines and must not
        # drag a -1 into the liveness check
        if not (name.startswith("node") and name.endswith(".log")):
            continue
        h = -1
        with open(os.path.join(dirpath, name), "rb") as f:
            for line in f.read().decode(errors="replace").splitlines():
                m = _HEAD_RE.search(line)
                if m:
                    h = int(m.group(1))
        heights.append(h)
    return heights


def soak(dirpath: str, n: int, seconds: float, **kw) -> bool:
    """Liveness soak (ref: test-sep-2.sh's 5-min loop): chain must keep
    advancing on every node."""
    start_cluster(dirpath, n, **kw)
    try:
        deadline = time.time() + seconds
        last = [-1] * n
        while time.time() < deadline:
            time.sleep(5)
            cur = node_heights(dirpath)
            print(f"[soak] heights={cur}")
            last = cur
        return all(h >= 3 for h in last)
    finally:
        kill_cluster(dirpath)


def synctest(dirpath: str, n: int, seconds: float,
             fast_join: bool = False, **kw) -> bool:
    """Join/sync scenario (ref: test-sync.py): start n-1 nodes, let the
    chain grow, then start the last node and assert it catches up.

    ``fast_join`` runs the joiner with ``--syncmode fast`` (the
    statesync.go role): the chain must first outgrow the fast-sync gap
    threshold, and PASS additionally requires the joiner's log to show
    a pivot state adoption — proof it skipped the early chain."""
    start_cluster(dirpath, n, skip={n - 1},
                  fast_nodes={n - 1} if fast_join else None, **kw)
    # fast sync only engages when the gap clears FASTSYNC_MIN_GAP (128)
    # + PIVOT_LAG headroom; a localhost rig mines ~10+ blocks/s
    pre_join = 220 if fast_join else 3
    try:
        deadline = time.time() + seconds * 0.6
        while time.time() < deadline:
            time.sleep(3)
            hs = node_heights(dirpath)
            print(f"[synctest] pre-join heights={hs}")
            live = [h for h in hs if h >= 0]
            if len(live) >= n - 1 and min(live) >= pre_join:
                break
        start_node(dirpath, n - 1)
        deadline = time.time() + seconds
        while time.time() < deadline:
            time.sleep(3)
            hs = node_heights(dirpath)
            print(f"[synctest] heights={hs}")
            # caught up = within ~one poll interval of the max; the head
            # advances ~10+ blocks/s on a localhost rig, so a small
            # fixed tolerance would fail a node that is tracking head
            if len(hs) == n and hs[-1] >= 3 and hs[-1] >= max(hs) - 15:
                if not fast_join:
                    return True
                log_path = os.path.join(dirpath, f"node{n - 1}.log")
                with open(log_path, errors="replace") as f:
                    adopted = [ln for ln in f if "FASTSYNC adopted" in ln]
                print(f"[synctest] {adopted[-1].strip()}" if adopted
                      else "[synctest] joiner caught up WITHOUT fast "
                           "sync — FAIL for this mode")
                return bool(adopted)
        return False
    finally:
        kill_cluster(dirpath)


def _rpc_once(method, params, port, timeout=10):
    """One JSON-RPC call to a localhost node (module-level probe)."""
    import urllib.request

    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}", data=body,
        headers={"Content-Type": "application/json"})
    return json.loads(
        urllib.request.urlopen(req, timeout=timeout).read())["result"]


def _pid_alive(pid: int) -> bool:
    """Is our own child ``pid`` still running (reaps it if not)."""
    try:
        return os.waitpid(pid, os.WNOHANG)[0] == 0
    except ChildProcessError:
        return False


def _rpc(method, params, port=RPC_BASE, timeout=10, tries=1):
    """:func:`_rpc_once` with retries: a busy node's loop is a slow
    answer, not a failure, until the tries run out."""
    for attempt in range(tries):
        try:
            return _rpc_once(method, params, port, timeout=timeout)
        except Exception:
            if attempt == tries - 1:
                raise
            time.sleep(3)


def _wait_for_rpc(port, deadline_s: float, pid: int | None = None) -> bool:
    """Poll a node's RPC port until it answers: True when it did, False
    when the deadline lapsed or the node process ``pid`` died first."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            _rpc_once("eth_blockNumber", [], port)
            return True
        except Exception:
            if pid is not None and not _pid_alive(pid):
                return False
            time.sleep(3)
    return False


def start_cluster_jax_first(dirpath: str, n: int, jax_node: int,
                            warm_deadline_s: float = 1500.0,
                            **kw) -> None:
    """Start the ``--verifier jax`` node FIRST and alone (below quorum
    nothing mines, so the chain only starts moving once the
    slow-starting node serves), then start the rest — a node that
    finishes its warm-up behind a fast-moving head spends its first
    minutes catching up.  The device node warms itself: on the chip it
    traces and compiles every bucket before it serves RPC (about two
    minutes a cold bucket, seconds from the artifact store), so nothing
    here compiles for it on another backend."""
    assert 0 <= jax_node < n, f"--jaxNode {jax_node} out of range({n})"
    pid, = start_cluster(dirpath, n, jax_nodes={jax_node},
                         skip=set(range(n)) - {jax_node}, **kw)
    if not _wait_for_rpc(RPC_BASE + jax_node, warm_deadline_s, pid=pid):
        raise RuntimeError(
            f"the --verifier jax node never served RPC (see "
            f"{os.path.join(dirpath, f'node{jax_node}.log')})")
    for i in range(n):
        if i != jax_node:
            start_node(dirpath, i)


def loadtest(dirpath: str, n: int, seconds: float, *, n_udp=300,
             jax_node: int = -1, **kw) -> bool:
    """End-to-end load: UDP geec txns (Geec_Client role) + a signed RPC
    txn, asserted on-chain via the RPC surface (the reference drives
    this manually with Geec_Client + log greps; automated here)."""
    import socket

    from eges_tpu.core.types import Transaction

    rpc = _rpc

    if jax_node >= 0:
        start_cluster_jax_first(dirpath, n, jax_node, **kw)
    else:
        start_cluster(dirpath, n, **kw)
    try:
        # wait for chain liveness first (discovery-mode clusters take a
        # few seconds longer to form the mesh than static peer lists)
        deadline = time.time() + max(45.0, seconds)
        while time.time() < deadline:
            time.sleep(3)
            hs = node_heights(dirpath)
            if hs and min(hs) >= 1:
                break
        # the RPC ports this test drives must actually accept — a JAX-
        # verifier node warms its device graph before serving, which on
        # a cold cache outlives the liveness window above.  qport is
        # where chain-state queries go (see below), so it must be
        # covered too when it isn't RPC_BASE.
        qport = RPC_BASE + (1 if 0 == jax_node and n > 1 else 0)
        for port in {RPC_BASE, qport, RPC_BASE + max(jax_node, 0)}:
            _wait_for_rpc(port, 240)
        t = Transaction(nonce=0, gas_price=0, gas_limit=21_000,
                        to=bytes(20), value=0).signed(node_key(0))
        txh = rpc("eth_sendRawTransaction", ["0x" + t.encode().hex()])
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.settimeout(1.0)  # send-only UDP; never blocks, but bound anyway
        for i in range(n_udp):
            s.sendto(b"load payload %d" % i, ("127.0.0.1", TXN_BASE))
            time.sleep(0.005)
        time.sleep(min(8.0, seconds))
        jax_ok = True
        if jax_node >= 0:
            # query the device node's metrics FIRST: its event loop
            # serves RPC between device batches, and on a 1-core rig
            # the sync backlog grows the longer we wait (the CPU-
            # backend XLA verifier is far slower than two native
            # nodes mine; the native default exists precisely for
            # many-node single-host rigs).  The assertion is the
            # HONEST share: device rows only, no C++ batch rows.
            try:
                jmet = rpc("thw_metrics", [], port=RPC_BASE + jax_node,
                           timeout=60, tries=5)
            except Exception as exc:
                # an overloaded 1-core rig can starve the device node's
                # RPC loop for minutes; that's a FAIL verdict for this
                # mode, not a harness crash (4-node rigs hit this)
                print(f"[loadtest] jax node{jax_node}: metrics RPC "
                      f"unreachable ({exc}) — mode FAIL")
                jmet = {}
            jshare = jmet.get("verifier.device_share")
            jrows = jmet.get("verifier.rows", {})
            jrows = jrows.get("count", 0) if isinstance(jrows, dict) else jrows
            jax_ok = bool(jrows) and (jshare or 0) > 0.95
            # "device: ..." names the hardware the node's verifier
            # actually dispatched to, straight from its metrics
            # registry — not an inference from the env
            print(f"device: {jmet.get('verifier.device_name', '?')}")
            print(f"[loadtest] jax node{jax_node}: device_rows={jrows} "
                  f"device_share={jshare}")
        # chain-state queries go to a node AT HEAD (qport): with
        # --jaxNode the ingress node spent its startup compiling the
        # device graph and may still be catching up a fast-moving head
        # — traffic still entered through it, which is what the mode
        # exercises
        # same starvation tolerance for the chain-state node: retried,
        # generous timeouts, and exhaustion is a FAIL verdict — a busy
        # loop is a slow answer, not a harness crash
        try:
            rec = rpc("eth_getTransactionReceipt", [txh], port=qport,
                      timeout=30, tries=4)
            h = int(rpc("eth_blockNumber", [], port=qport,
                        timeout=30, tries=4), 16)
            geec_total = sum(
                rpc("eth_getBlockByNumber", [hex(b), False],
                    port=qport, timeout=30, tries=2)["geecTxnCount"]
                for b in range(1, h + 1))
            met = rpc("thw_metrics", [], port=qport, timeout=30, tries=4)
        except Exception as exc:
            print(f"[loadtest] chain-state RPC on port {qport} "
                  f"unreachable ({exc}) — FAIL")
            return False
        share = met.get("verifier.device_share")
        bshare = met.get("verifier.batched_share")
        print(f"[loadtest] height={h} geec_on_chain={geec_total}/{n_udp} "
              f"signed_mined={(rec or {}).get('status') == '0x1'} "
              f"device_share={share} batched_share={bshare}")
        return (rec is not None and rec.get("status") == "0x1"
                and geec_total >= int(n_udp * 0.8) and jax_ok)
    finally:
        kill_cluster(dirpath)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", choices=["start", "kill", "status", "soak",
                                    "restart", "synctest", "loadtest"])
    ap.add_argument("--dir", required=True)
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=60)
    ap.add_argument("--txnPerBlock", type=int, default=100)
    ap.add_argument("--blockTimeout", type=float, default=20.0)
    ap.add_argument("--hosts", default="",
                    help="comma-separated ssh hosts for fan-out "
                         "(empty = localhost; ref: start.py config.json)")
    ap.add_argument("--bootnode", action="store_true",
                    help="use discovery via a bootnode instead of a "
                         "static peer list")
    ap.add_argument("--fastJoin", action="store_true",
                    help="synctest: the late joiner uses --syncmode "
                         "fast (pivot state download instead of full "
                         "replay); PASS requires the adoption log line")
    ap.add_argument("--jaxNode", type=int, default=-1,
                    help="loadtest: node index to run the JAX device "
                         "batch verifier on the ambient JAX platform "
                         "(the one process that holds the chip; "
                         "others stay on the C++ batch); asserts a "
                         ">95%% on-device share on that node")
    args = ap.parse_args()
    kw = dict(txn_per_block=args.txnPerBlock, block_timeout=args.blockTimeout,
              hosts=args.hosts, use_bootnode=args.bootnode)
    if args.cmd == "start":
        pids = start_cluster(args.dir, args.nodes, **kw)
        print("started pids:", pids)
    elif args.cmd == "kill":
        kill_cluster(args.dir)
        print("killed")
    elif args.cmd == "restart":
        print("restarted pids:", restart_cluster(args.dir))
    elif args.cmd == "status":
        print("heights:", node_heights(args.dir))
    elif args.cmd == "soak":
        ok = soak(args.dir, args.nodes, args.seconds, **kw)
        print("SOAK", "PASS" if ok else "FAIL")
        sys.exit(0 if ok else 1)
    elif args.cmd == "synctest":
        ok = synctest(args.dir, args.nodes, args.seconds,
                      fast_join=args.fastJoin, **kw)
        print("SYNCTEST", "PASS" if ok else "FAIL")
        sys.exit(0 if ok else 1)
    elif args.cmd == "loadtest":
        ok = loadtest(args.dir, args.nodes, args.seconds,
                      jax_node=args.jaxNode, **kw)
        print("LOADTEST", "PASS" if ok else "FAIL")
        sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
