"""``c1024p.heights-backlog``: the issue's numbers are in the files, the
generator's construction is counted at the deployment's size (5333 frames,
1333 copies, 83 spoiled, 12 + 4 forged ACKs and where they stand, 2
attempts a height, the rows a height), the late signatures are the plain
reference's, a rehearsal on the host C++ verifier prints every check
beside its limit and every ``.propose`` metric, and each control comes out
not ``correct`` by the check that is its own and no other."""

import json
import os

import pytest
from test_correct import drive, failed

from perfbench import control_propose, gen_heights, harness
from perfbench.drivers import block_proposer as bp
from perfbench.ref import membership as ref_members
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import secp
from perfbench.ref.keccak import keccak256_many
from perfbench.ref.late_sign import LateSigner

CELL, ACCEPTOR = "c1024p.heights-backlog", "c1024a.blocks-backlog"
CONFIG = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-1024-proposer.json")))
FULL = CONFIG["deployment"]
PROPOSE = {"proposal_build_ms.propose", "pool_pending_ms.propose",
           "preview_ms.propose", "request_ms.propose", "seal_ms.propose",
           "election_ms.propose", "ack_ms.propose",
           "block_native_share.propose", "chain_insert_ms.propose",
           "executions_per_block.propose", "quorum_attempts.propose",
           "ack_handle_us.propose"}
# files of their own that BENCHMARK.json cannot list (128 per-layer
# metrics is the limit): the driver reads them for its ``info`` line
UNLISTED = {"block_senders_ms.propose", "block_cached_share.propose",
            "block_execute_share.propose", "state_root_share.propose",
            "block_roots_share.propose", "quorum_verify_ms.propose"}
CHECKS = {"blocks_not_executable", "commitments_wrong", "blocks_not_full",
          "unsound_txns_in_blocks", "txns_in_two_blocks",
          "requests_not_the_sealed_block", "forged_supporters",
          "supporters_under_threshold", "certificates_malformed",
          "elections_under_threshold", "reference_signatures_wrong",
          "heights_out_of_order", "sealed_not_the_head",
          "request_bytes_max", "roots_compared", "accounts_wrong",
          "accounts_compared", "sealed_txns_left_in_pool", "heights_sealed",
          "steps_that_never_came", "unanswered_rows", "wrong_answers",
          "valid_frames_refused", "invalid_frames_not_refused",
          "reference_mismatches", "reference_rows", "reference_signatures",
          "compiles_in_window"}


def test_the_deployments_numbers_are_the_acceptors_and_the_issues():
    cell = harness.Cell(CELL, rehearse=False)
    acc = harness.Cell(ACCEPTOR, rehearse=False)
    mine, theirs = cell.config["deployment"], acc.config["deployment"]
    for key in ("validators", "committee", "acceptors", "txn_per_block",
                "max_batch", "duplicate_share", "gossip_window",
                "invalid_every", "accounts", "senders", "payload_bytes",
                "gas_limit", "value_wei", "balance_wei", "cert_supporters",
                "reference_rows", "host_row_share_limit_pct"):
        assert mine[key] == theirs[key], key
    assert (mine["forged_acks"], mine["forged_acks_early"],
            mine["foreign_acks"], mine["forged_votes"]) == (16, 12, 8, 1)
    assert (mine["unexecutable_every"], mine["unexecutable"]) == (16, 8)
    assert mine["request_max_bytes"] == 1 << 20
    assert mine["heights_sealed_min"] == 8
    assert mine["cert_supporters"] == 513 == ref_members.majority(
        mine["acceptors"], mine["validators"]) == ref_quorum.need(None, 1024)
    assert gen_heights.election_threshold(mine["committee"]) == 16
    # 3 heights a second over the window, the warm ones, the feeder's
    assert mine["stream_heights"] >= 3 * 40 + 2 + 1
    assert (cell.chips, cell.config["driver"], cell.config["reduced"]) == (
        1, "block_proposer", ["cluster", "accounts", "proposer_share"])
    assert cell.config["architecture"] is None
    assert all(isinstance(cell.config[k], str) and cell.config[k]
               for k in cell.config["reduced"])
    assert len(cell.config["guarantees"]) == 7
    assert {"frame_order", "unexecutable", "votes", "acks",
            "stream_heights", "thw_seeds"} <= set(cell.config["assumed"])
    conf = next(c for c in cell.bench["configs"]
                if c["name"] == "committee-1024-proposer")
    assert conf["source"] == cell.config["source"]
    assert len(conf["source"]) <= 200 and len(cell.entry["why"]) <= 200
    tr = cell.traffic
    assert (tr["arrival"], tr["warm_blocks"], tr["trace_seconds"],
            tr["recover_program"]) == (
        "backlog", 2, 15.0, acc.traffic["recover_program"])
    assert {m["name"] for m in cell.end_to_end()} == {"verify_rows_per_s",
                                                      "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {n for n in names if n.endswith(".propose")} == PROPOSE
    assert all(n.endswith((".propose", ".rows")) for n in names)
    # the acceptor's unpinned ``.rows`` metrics, all of them
    assert {n for n in names if n.endswith(".rows")} == {
        m["name"] for m in acc.per_layer() if m["name"].endswith(".rows")}
    assert len(cell.bench["per_layer"]) <= 128
    for m in cell.per_layer():
        if m["name"] in PROPOSE:
            spec = harness.metric_file(m["name"])
            assert (spec["unit"], spec["layer"], spec["source"]) == (
                m["unit"], m["layer"], m["source"])
            assert (m["moves"], m["workloads"]) == ("verify_rows_per_s",
                                                    [CELL])
    for name in UNLISTED:
        assert harness.metric_file(name)["suffixes"][".propose"]


@pytest.fixture(scope="module")
def full():
    """Two heights at the deployment's size, the second with transfers
    that cannot execute."""
    return gen_heights.HeightsFeed(2**31 + 5, {
        **FULL, "stream_heights": 2, "unexecutable_every": 2})


def test_a_heights_rows_at_the_deployments_size(full):
    c = full.construction(0)
    assert c["gossip_frames"] == 5333 == 4000 + 1333
    assert (c["fresh"], c["spoiled"], c["copies"], c["windows"]) == (
        4000, 83, 1333 - 83, 21)
    assert c["unexecutable"] == []
    assert (c["votes"], c["forged_votes_among_first_threshold"],
            c["votes_to_elect"]) == (31, 1, 17)
    assert (c["replies"], c["sound"], c["foreign"], len(c["forged"])) == (
        1023, 999, 8, 16)
    assert set(c["forged"]) == set(gen_heights.FORGED)
    assert (c["forged_among_first_need"], c["forged_among_the_next"]) == (
        12, 0)
    # today's tally: 513 rows, 12 pruned; 513 rows again; certified
    assert (c["need"], c["attempts"], c["attempt_rows"],
            c["certified_at_counted"]) == (513, 2, [513, 513], 525)
    assert 525 <= c["certified_at"] <= 525 + 8
    assert c["rows"] == 5333 + 4000 + 17 + 525 == 9875
    one = full.construction(1)
    assert one["unexecutable"] == ["nonce_gap", "over_balance"] * 4
    assert (one["gossip_frames"], one["rows"]) == (5341, 9883)
    windows = full.windows(0)
    assert len(windows) == 21 and all(len(w) == 256 for w in windows[:-1])
    for w in windows:  # a copy never comes in the window of its original
        assert len({full.origin[k] for k in w}) == len(w)
    # each sender's nonces ascend across the whole stream
    nonce: dict = {}
    from perfbench.ref import senders as ref_senders
    for k in range(full.n_valid):
        a = full.account[k]
        assert int.from_bytes(ref_senders.read(full.frames[k])[0],
                              "big") == nonce.get(a, 0)
        nonce[a] = nonce.get(a, 0) + 1
    # those that cannot execute come from accounts that never send
    for k in full.unexecutable[1]:
        assert full._account_of[k] not in set(full.senders)
        assert full.frame_expect(k) == ("admit", full.signer(k))


def test_the_thw_puts_the_node_into_every_committee(full):
    members = full.members
    for h in (1, 2, 3):
        com = ref_members.committee(members, full.seeds[h], 0,
                                    FULL["committee"])
        assert full.node_addr in com and len(com) == 32
        assert full.thw.trust_rand(h - 1) == full.seeds[h]
    assert full.seeds[1] == 0  # the genesis header's
    voters = {a for _dg, _kind, a in full.votes[1]}
    assert voters == set(ref_members.committee(
        members, full.seeds[2], 0, 32)) - {full.node_addr}
    tiny = {**FULL, **CONFIG["rehearse"]}
    a, b = (gen_heights.HeightsFeed(2**31 + 9, tiny) for _ in range(2))
    c = gen_heights.HeightsFeed(2**31 + 10, tiny)
    assert (a.frames, a.streams, a.votes, a.seeds) == (
        b.frames, b.streams, b.votes, b.seeds)
    assert a.acks(3, bytes(32)) == b.acks(3, bytes(32))
    assert a.frames != c.frames and a.seeds != c.seeds
    for p in range(12):
        ca, cc = a.construction(p), c.construction(p)
        for key in ("gossip_frames", "copies", "spoiled", "unexecutable",
                    "votes_to_elect", "certified_at_counted", "rows"):
            assert ca[key] == cc[key], (p, key)


def test_a_late_signature_is_the_references_and_a_reply_reads_back(full):
    privs = [5, 6, 7]
    hashes = keccak256_many([b"a", b"b", b"c"])
    late = LateSigner(privs, 1 << 201)
    assert [late.finish(i, h) for i, h in enumerate(hashes)] == \
        secp.sign_rows(privs, hashes, 1 << 201)
    bhash = keccak256_many([b"a block"])[0]
    replies = full.acks(0, bhash)
    sound = forged = foreign = 0
    for dg, (a, kind, _s) in list(zip(replies, full.ack_plan[0]))[::16]:
        author, num, accepted, h, _sig = ref_quorum.read_ack(dg)
        assert (author, num, accepted, h) == (a, 1, 1, bhash)
        got = ref_quorum.sound_author(dg, full.members, 1, bhash)
        assert (got == a) == (kind is None), kind
        sound += kind is None
        forged += kind in gen_heights.FORGED
        foreign += kind == "non_member"
    assert sound and sound + forged + foreign == 64
    # a request's header is read without its transactions
    tiny = gen_heights.HeightsFeed(3, {**FULL, **CONFIG["rehearse"]})
    from perfbench.ref import rlp
    header = [bytes(32)] * 3 + [b"\x07"]
    req = rlp.encode([0x11, [9, tiny.node_addr, [header, [], [], [[1, 2]]],
                             b"ip", 1]])
    assert gen_heights.request_block(req) == (
        9, keccak256_many([rlp.encode(header)])[0])


def test_a_rehearsal_prints_every_check_and_every_propose_metric():
    rc, line, err = drive("--trace", "1", workload=CELL)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert set(line["checks"]) == CHECKS
    for name in line["checks"]:
        assert f"check {name}: " in err
    assert "check forged_supporters: 0 <= 0 ok" in err
    assert line["checks"]["accounts_compared"][0] == 64
    # every ``.propose`` reader found its span or counter in the run
    assert PROPOSE <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["executions_per_block.propose"] == pytest.approx(2.0)
    assert m["block_native_share.propose"] == 0.0
    assert m["quorum_attempts.propose"] == pytest.approx(2.0)
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith("info "))[5:])
    assert info["heights_sealed"] == 10 and info["failed_step"] is None
    assert info["elected_at"] == [3]
    assert {tuple(c)[1] for c in info["certified_at"]} == {11}
    assert UNLISTED <= set(info["unlisted"])
    # a program without the spans and the counters: nothing, never 0
    old = {"before": {}, "after": {}, "window_s": 40.0, "samples": {},
           "flights": [], "trace": None, "t_begin": 0.0, "t_end": 40.0}
    cell = harness.Cell(CELL, rehearse=False)
    assert not PROPOSE & set(harness.read_per_layer(cell, old))


def test_a_plain_run_prints_the_two_end_to_end_metrics():
    _rc, line, _err = drive(workload=CELL)
    assert set(line["metrics"]) == {"verify_rows_per_s", "setup_s"}


@pytest.mark.parametrize("control, check", [
    ("accept_all", "forged_supporters"),
    ("pad_upstream", "request_bytes_max"),
    ("unfiltered_pool", "blocks_not_full"),
])
def test_each_control_fails_by_the_check_that_is_its_own(control, check):
    assert control in control_propose.NAMES
    _, line, _ = drive("--control", control, workload=CELL)
    assert line["correct"] is False
    assert failed(line) == [check]


def test_the_driver_refuses_a_program_without_the_spans(monkeypatch,
                                                        capsys):
    from eges_tpu.utils import tracing

    spans = dict(tracing.SPANS)
    del spans["consensus.build_proposal"]
    monkeypatch.setattr(tracing, "SPANS", spans)
    cell = harness.Cell(CELL, rehearse=True)
    assert bp.run(cell, None, 0.0) == 2
    assert "cannot run on it" in capsys.readouterr().err
