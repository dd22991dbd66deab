"""``c1024a.blocks-backlog``: the issue's numbers are in the files, the
generator's construction is counted at the deployment's size (rows a
height, accounts touched, where the bad blocks stand, every header's
roots the reference's), a rehearsal on the host C++ verifier prints every
check beside its limit, each control comes out not ``correct`` by the
check that is its own, and the new metric files read a hand-made ``obs``
(and nothing on a program without the spans)."""

import json
import os

import pytest
from test_correct import drive, failed

from perfbench import control_accept, gen_chain, harness
from perfbench.ref import membership as ref_members
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import rlp, secp
from perfbench.ref import senders as ref_senders
from perfbench.ref import state as ref_state

CELL, ONE_NODE = "c1024a.blocks-backlog", "c1024.mixed-backlog"
CONFIG = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-1024-acceptor.json")))
FULL = CONFIG["deployment"]
ACCEPT = {"block_validate_ms.accept", "chain_insert_ms.accept",
          "block_execute_share.accept", "state_root_share.accept",
          "block_roots_share.accept", "block_senders_ms.accept",
          "block_cached_share.accept", "cert_verify_ms.accept",
          "executions_per_block.accept"}
CHECKS = {"sound_blocks_refused", "bad_blocks_acked", "bad_blocks_inserted",
          "blocks_out_of_order", "off_chain_blocks", "acks_wrong",
          "accounts_wrong", "accounts_compared", "blocks_inserted",
          "unanswered_rows", "wrong_answers", "valid_frames_refused",
          "invalid_frames_not_refused", "reference_mismatches",
          "reference_rows", "compiles_in_window"}


def test_the_deployments_numbers_are_the_one_node_cells_and_the_issues():
    cell = harness.Cell(CELL, rehearse=False)
    one = harness.Cell(ONE_NODE, rehearse=False)
    mine, theirs = cell.config["deployment"], one.config["deployment"]
    for key in ("validators", "committee", "txn_per_block", "header_sigs",
                "max_batch", "duplicate_share", "gossip_window",
                "invalid_every", "payload_bytes", "gas_limit",
                "reference_rows", "host_row_share_limit_pct"):
        assert mine[key] == theirs[key], key
    # the stream as committee-64-txsenders builds it; what execution needs
    assert (mine["unseen_share"], mine["bad_block_every"]) == (0.10, 16)
    assert (mine["accounts"], mine["senders"], mine["value_wei"]) == (
        16384, theirs["accounts"], 1)
    assert mine["acceptors"] == 1024 and mine["cert_supporters"] == 513 == \
        ref_members.majority(mine["acceptors"], mine["validators"]) == \
        ref_quorum.need(None, 1024)
    assert (cell.chips, cell.config["driver"], cell.config["reduced"]) == (
        1, "acceptor", ["cluster", "accounts"])
    assert all(isinstance(cell.config[k], str) and cell.config[k]
               for k in cell.config["reduced"])
    assert len(cell.config["guarantees"]) == 7
    assert {"accounts", "recipients", "value_and_price", "cert_supporters",
            "bad_block_every", "frame_order", "confirm_follows_ack"} <= \
        set(cell.config["assumed"])
    tiny = cell.config["rehearse"]
    assert (tiny["txn_per_block"], tiny["accounts"], tiny["validators"],
            tiny["chain_blocks"]) == (32, 64, 16, 12)
    tr = cell.traffic
    assert (tr["arrival"], tr["blocks_in_flight"], tr["warm_blocks"],
            tr["trace_seconds"], tr["recover_program"]) == (
        "backlog", 2, 2, 15.0, one.traffic["recover_program"])
    assert {m["name"] for m in cell.end_to_end()} == {"verify_rows_per_s",
                                                      "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {n for n in names if n.endswith(".accept")} == ACCEPT
    assert all(n.endswith((".accept", ".rows")) for n in names)
    assert "gc_full_pause_ms.rows" not in names


@pytest.fixture(scope="module")
def full():
    """Four heights at the deployment's size, the second a bad one."""
    return gen_chain.ChainFeed(2**31 + 5, {**FULL, "chain_blocks": 4,
                                           "bad_block_every": 4})


def test_a_heights_rows_at_the_deployments_size(full):
    c = full.construction(3)  # a sound height with a block before it
    assert c["gossip_frames"] == 5333 == 3600 + 400 + 1333
    assert (c["own_in_time"], c["late_of_previous"],
            c["unseen_at_request"]) == (3600, 400, 400)
    assert c["spoiled"] == 83 and c["copies"] == 1333 - 83
    assert c["steps"] == [("request", 4001, True), ("confirm", 514, True)]
    # ISSUE 44's 9,847 and the confirm's own signature
    assert c["rows_asked"] == 5333 + 4000 + 1 + 513 + 1 == 9848
    assert 4900 <= c["touched_accounts"] <= 5500
    windows = full.windows(3)
    assert len(windows) == 21 and all(len(w) == 256 for w in windows[:-1])
    assert full.construction(0)["gossip_frames"] == 5333 - 400
    # a copy never comes in the window of its original
    for w in windows:
        assert len({full.origin[k] for k in w}) == len(w)
    # the request is the whole block, inside the node's decode budget
    req = full.steps[3][0].data
    assert 780_000 < len(req) < (1 << 20)
    assert len(full.steps[3][1].data) < 60_000


def test_where_the_bad_blocks_stand_and_what_follows_them(full):
    assert full.bad == {1: "state_root"}  # height 2 of every 4
    kinds = [(s.what, s.sound, s.bad) for s in full.steps[1]]
    assert kinds == [("request", False, "state_root"),
                     ("request", True, None), ("confirm", True, None)]
    assert len(full.never_insert) == 1
    assert full.never_insert.isdisjoint(full.block_hashes)
    tiny = {**FULL, **CONFIG["rehearse"], "chain_blocks": 16}
    for control, first in control_accept.FIRST_BAD.items():
        feed = gen_chain.ChainFeed(7, tiny, first_bad=first)
        at = gen_chain.BAD_KINDS.index(first)
        assert [feed.bad[p] for p in sorted(feed.bad)][:4] == [
            gen_chain.BAD_KINDS[(at + i) % 4] for i in range(4)]
        assert sorted(feed.bad) == list(range(0, 16, 2))
        if first == "certificate":
            req, conf, twin, conf2 = feed.steps[sorted(feed.bad)[0]]
            assert (req.sound, conf.sound, twin.sound, conf2.sound) == (
                True, False, True, True)
            assert conf.block_hash == req.block_hash != twin.block_hash


def test_every_headers_roots_are_the_references(full):
    """The chain once more, from the bytes of its requests, by the plain
    reference alone: transfers read off the wire, applied to the state,
    the three roots built whole."""
    keys = dict(zip(full.addrs, ref_state.keccak256_many(full.addrs)))
    state = {a: [0, full.balance] for a in full.addrs}
    parent = full.genesis_hash
    members = sorted(a for a, _ip, _port in full.validators)
    seed = 0
    for p, steps in enumerate(full.steps):
        request, confirm = [s for s in steps if s.sound][-2:]
        code, fields = ref_senders.read(request.data)
        assert code == b"\x11"
        author, version = fields[1], int.from_bytes(fields[6], "big")
        header, _fakes, _geecs, txs, _uncles, _conf = fields[2]
        assert author in ref_members.committee(members, seed, version,
                                               FULL["committee"])
        frames = [rlp.encode(t) for t in txs]
        assert frames == full.frames[p * 4000:(p + 1) * 4000]
        transfers = []
        for i, f in enumerate(txs):
            sender = full.signer(p * 4000 + i)
            if i % 250 == 0:  # sixteen a block through the reference
                assert ref_senders.row_sender(f) == sender
            transfers.append((sender, int.from_bytes(f[0], "big"), f[3],
                              int.from_bytes(f[4], "big"),
                              int.from_bytes(f[2], "big")))
        _touched, gas = ref_state.apply_transfers(state, transfers)
        h = header
        assert h[0] == parent and h[2] == author
        assert h[3] == ref_state.state_root(state, keys)
        assert h[4] == ref_state.derive_sha(frames)
        assert h[5] == ref_state.derive_sha(
            [ref_state.receipt_rlp(1, g) for g in gas])
        assert (h[6], int.from_bytes(h[10], "big")) == (
            ref_state.NO_BLOOM, 4000 * ref_state.TX_GAS)
        parent = ref_state.keccak256(rlp.encode(header))
        assert parent == request.block_hash == full.block_hashes[p]
        # the certificate: the threshold of distinct acceptors, each
        # signature over THIS block's hash
        cfields = ref_senders.read(confirm.data)[1]
        sups, sigs = cfields[3], cfields[7]
        assert len(set(sups)) == len(sups) == 513 and cfields[1] == parent
        assert set(sups) <= set(ref_members.acceptors(
            members, seed, FULL["acceptors"]))
        assert full.node_addr not in sups
        for a, s in list(zip(sups, sigs))[:8]:
            assert secp.recover(ref_quorum.ack_sighash(
                p + 1, a, 1, parent), s) == a
        seed = int.from_bytes(h[16], "big")
        assert {a: tuple(v) for a, v in state.items()} == \
            full.state_at(p + 1)


def test_same_seed_same_chain_and_every_seed_the_same_counts():
    tiny = {**FULL, **CONFIG["rehearse"]}
    a, b = (gen_chain.ChainFeed(2**31 + 9, tiny) for _ in range(2))
    c = gen_chain.ChainFeed(2**31 + 10, tiny)
    assert [[s.data for s in st] for st in a.steps] == \
        [[s.data for s in st] for st in b.steps]
    assert a.frames == b.frames and a.blocks == b.blocks
    assert a.block_hashes != c.block_hashes
    for p in range(12):
        ca, cc = a.construction(p), c.construction(p)
        for key in ("gossip_frames", "own_in_time", "late_of_previous",
                    "copies", "spoiled", "bad", "steps", "rows_asked"):
            assert ca[key] == cc[key], (p, key)


def test_a_rehearsal_prints_every_check_beside_its_limit():
    rc, line, err = drive(workload=CELL)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert set(line["checks"]) == CHECKS
    assert set(line["metrics"]) == {"verify_rows_per_s", "setup_s"}
    for name in line["checks"]:
        assert f"check {name}: " in err
    assert "check bad_blocks_acked: 0 <= 0 ok" in err
    assert line["checks"]["accounts_compared"][0] == 64
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith("info "))[5:])
    assert info["blocks_inserted"] == 10 and info["bad_heights_in_window"]


@pytest.mark.parametrize("control, check", [
    ("accept_all", "bad_blocks_acked"),
    ("trust_roots", "bad_blocks_acked"),
    ("any_cert", "bad_blocks_inserted"),
])
def test_each_control_fails_by_the_check_that_is_its_own(control, check):
    assert control in control_accept.NAMES
    _, line, _ = drive("--control", control, workload=CELL)
    assert line["correct"] is False
    assert check in failed(line)
    if control == "trust_roots":
        assert failed(line) == [check]


def test_the_new_metrics_read_the_spans_and_the_counters():
    cell = harness.Cell(CELL, rehearse=False)
    hist = lambda n, mean: {"count": n, "mean": mean}  # noqa: E731
    span = lambda kind, name: f"span.{kind};name={name}"  # noqa: E731
    snap = lambda n: {  # noqa: E731
        span("seconds", "chain.validate_candidate"): hist(n, 0.600),
        "chain.insert_seconds": hist(n, 0.650),
        span("self_seconds", "chain.execute"): hist(2 * n, 0.040),
        span("self_seconds", "state.root"): hist(2 * n, 0.100),
        span("self_seconds", "chain.verify_body"): hist(2 * n, 0.060),
        span("self_seconds", "chain.receipts_root"): hist(2 * n, 0.040),
        span("seconds", "chain.recover_senders"): hist(2 * n, 0.050),
        span("seconds", "consensus.cert_ok"): hist(n, 0.020),
        "chain.sender_rows": 8000 * n, "chain.sender_cached_rows": 6000 * n,
        "chain.executions": 2 * n + n // 10, "chain.blocks": n}
    obs = {"before": snap(10), "after": snap(30), "window_s": 40.0,
           "samples": {}, "flights": [], "trace": None, "t_begin": 0.0,
           "t_end": 40.0}
    got = harness.read_per_layer(cell, obs)
    assert {n: got[n]["value"] for n in ACCEPT} == {
        "block_validate_ms.accept": pytest.approx(600.0),
        "chain_insert_ms.accept": pytest.approx(650.0),
        "block_execute_share.accept": pytest.approx(4.0),
        "state_root_share.accept": pytest.approx(10.0),
        "block_roots_share.accept": pytest.approx(10.0),
        "block_senders_ms.accept": pytest.approx(50.0),
        "block_cached_share.accept": pytest.approx(75.0),
        "cert_verify_ms.accept": pytest.approx(20.0),
        "executions_per_block.accept": pytest.approx(2.1)}
    # a program without the spans and the counters: nothing, never 0
    old = {"before": {}, "after": {}, "window_s": 40.0, "samples": {},
           "flights": [], "trace": None, "t_begin": 0.0, "t_end": 40.0}
    assert not ACCEPT & set(harness.read_per_layer(cell, old))
    for m in cell.per_layer():
        if m["name"] in ACCEPT:
            spec = harness.metric_file(m["name"])
            assert (spec["unit"], spec["layer"], spec["source"]) == (
                m["unit"], m["layer"], m["source"])
            assert (m["moves"], m["workloads"]) == ("verify_rows_per_s",
                                                    [CELL])
