"""Harness unit coverage: host fan-out parsing, genesis pinning,
cluster metadata round-trip (the start.py/config.json machinery that
the end-to-end soaks exercise only implicitly)."""

import json
import sys

sys.path.insert(0, ".")  # harness/ is not a package

from harness.cluster import (  # noqa: E402
    Runner, load_meta, node_key, parse_hosts, write_genesis, _save_meta,
)


def test_parse_hosts_round_robin_and_local():
    rs = parse_hosts("", 3)
    assert len(rs) == 3 and not any(r.remote for r in rs)
    assert all(r.ip() == "127.0.0.1" for r in rs)

    rs = parse_hosts("10.0.0.5,10.0.0.6", 5)
    assert [r.host for r in rs] == ["10.0.0.5", "10.0.0.6", "10.0.0.5",
                                    "10.0.0.6", "10.0.0.5"]
    assert all(r.remote for r in rs)
    assert rs[0].ip() == "10.0.0.5"

    # "localhost" is NOT treated as an ssh target
    rs = parse_hosts("localhost", 2)
    assert not any(r.remote for r in rs)


def test_node_key_matches_sim_scheme():
    from eges_tpu.crypto.keys import deterministic_node_key

    assert node_key(0) == deterministic_node_key(0)
    assert node_key(300) == deterministic_node_key(300)  # >255 works
    assert len({node_key(i) for i in range(64)}) == 64


def test_write_genesis_pins_consensus_critical_flags(tmp_path):
    path = str(tmp_path / "genesis.json")
    write_genesis(path, 4)
    with open(path) as f:
        doc = json.load(f)
    thw = doc["config"]["thw"]
    assert thw["signed_votes"] is True  # pinned explicitly
    assert len(thw["bootstrap"]) == 4
    # bootstrap accounts derive from the shared key scheme
    from eges_tpu.crypto import secp256k1 as secp
    want = secp.pubkey_to_address(secp.privkey_to_pubkey(node_key(2))).hex()
    assert thw["bootstrap"][2]["account"] == want


def test_cluster_meta_round_trip(tmp_path):
    d = str(tmp_path)
    meta = {"n": 3, "hosts": "", "pids": [11, 22, 33], "boot_pid": None,
            "txn_per_block": 5, "txn_size": 100, "block_timeout": 20.0,
            "mine": True, "use_bootnode": False}
    _save_meta(d, meta)
    assert load_meta(d) == meta
    assert load_meta(str(tmp_path / "nope")) is None


def test_runner_local_spawn_and_log(tmp_path):
    r = Runner()
    log = str(tmp_path / "x.log")
    pid = r.spawn([sys.executable, "-c", "print('hello-runner')"], log,
                  {"PATH": "/usr/bin:/bin"})
    import os
    import time
    for _ in range(50):
        time.sleep(0.1)
        if b"hello-runner" in r.read_log(log):
            break
    assert b"hello-runner" in r.read_log(log)
    r.kill(pid)  # no-op if already exited
    assert r.read_log(str(tmp_path / "missing.log")) == b""
