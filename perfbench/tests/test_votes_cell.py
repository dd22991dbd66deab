"""``c256.votes-steady``: the issue's numbers are in the files, the same
seed gives the same datagrams, a block's bad replies stand where
construction says, the program reads a datagram as the reference wrote
it, the reference's reading of a full-size block is the construction's, a
rehearsal prints every check beside its limit, each control comes out not
``correct`` by the check that is its own, and the new metrics read the
counters and the spans (and nothing on a program without them)."""

import collections
import json
import os

import pytest
from test_correct import drive, failed

from perfbench import control_votes, gen_votes, harness
from perfbench.ref import quorum as ref

CELL = "c256.votes-steady"
CONFIG = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-256-votes.json")))
FULL = CONFIG["deployment"]
TINY = {**FULL, **CONFIG["rehearse"]}
QUORUM = {"quorum_attempts.quorum", "quorum_rows_per_attempt.quorum",
          "quorum_pruned_per_block.quorum", "quorum_ms.quorum",
          "quorum_verify_ms.quorum", "ack_handle_us.quorum",
          "cache_hit_share.quorum"}


@pytest.fixture(scope="module")
def full():
    """The deployment at its real size (16 blocks of 255 datagrams,
    72000 signed transfers)."""
    return gen_votes.VotesFeed(2**31 + 11, FULL)


def test_the_deployments_numbers_are_the_issues():
    assert {k: FULL[k] for k in (
        "validators", "committee", "acceptors", "election_rows",
        "vote_pool_blocks", "validate_threshold", "txn_per_block",
        "payload_bytes", "gossip_window", "duplicate_share",
        "invalid_every", "max_batch", "forged_every", "foreign_every")} == {
        "validators": 256, "committee": 256, "acceptors": 256,
        "election_rows": 128, "vote_pool_blocks": 16,
        "validate_threshold": 0.66, "txn_per_block": 1000,
        "payload_bytes": 100, "gossip_window": 256,
        "duplicate_share": 0.25, "invalid_every": 64, "max_batch": 1024,
        "forged_every": 64, "foreign_every": 128}
    cell = harness.Cell(CELL, rehearse=False)
    assert (cell.chips, cell.config["driver"], cell.config["reduced"],
            cell.config["architecture"]) == (1, "proposer", ["cluster"],
                                             None)
    tr = cell.traffic
    assert (tr["arrival"], tr["blocks_per_s"], tr["ack_point"],
            tr["ack_spread_ms"], tr["warm_blocks"], tr["trace_seconds"]) == (
        "schedule", 2.5, 0.7, 10.0, 2, 15.0)
    assert ref.need(FULL["validate_threshold"], FULL["acceptors"]) == 169
    assert len(CONFIG["guarantees"]) == 9
    assert {m["name"] for m in cell.end_to_end()} == {"vote_p50_ms",
                                                      "setup_s"}
    names = {m["name"] for m in cell.per_layer()}
    assert {n for n in names if n.endswith(".quorum")} == QUORUM
    assert all(n.endswith((".quorum", ".vote")) for n in names)
    # the 1000-row bursts' own metrics find nothing here
    assert not names & {"vote_submit_ms.vote", "vote_await_ms.vote",
                        "burst_stage_ms.vote", "burst_resolve_ms.vote"}


def test_same_seed_same_datagrams_and_every_seed_the_same_counts():
    a, b, c = (gen_votes.VotesFeed(s, TINY) for s in (2**31 + 5, 2**31 + 5,
                                                      9))
    dg = lambda f: [x.datagrams for x in f.blocks]  # noqa: E731
    assert dg(a) == dg(b) and a.bulk.frames == b.bulk.frames
    assert dg(a) != dg(c)
    for f in (a, c):
        assert f.need == 22 and len(f.blocks) == TINY["vote_pool_blocks"]
        for p in range(len(f.blocks)):
            con = f.construction(p)
            assert (con["replies"], con["sound"], len(con["forged"]),
                    con["foreign"]) == (31, 27, 2,
                                        ["foreign_hash", "non_member"])
            assert con["forged_among_first_need"] == 1
            assert con["first_late_arrival"] >= TINY["late_from"]
            assert con["certified_at"] == 23


def test_a_blocks_bad_replies_stand_where_construction_says(full):
    f = full
    assert (f.need, f.n_forged, f.n_foreign) == (169, 4, 2)
    # 16 blocks of 384 vote rows (128 election rows, the header's, 255
    # replies), 1250 frames a block in 5 windows
    assert len(f.blocks) == 16
    assert [len(w) for w in f.bulk.windows(0)] == [256] * 4 + [226]
    el, hd, _unused = f.bulk.votes(0)
    assert (len(el), len(hd)) == (128, 1)
    turn = []
    for p in range(16):
        blk, con = f.block(p), f.construction(p)
        assert con == {**con, "replies": 255, "sound": 249, "need": 169,
                       "foreign": ["foreign_hash", "non_member"],
                       "forged_among_first_need": 3, "attempts": 2,
                       "attempt_rows": [169, 169], "pruned": 3,
                       "certified_at": 172, "cache_hits": 166,
                       "device_rows": [169, 3]}
        assert len(con["forged"]) == 4 and con["first_late_arrival"] >= 200
        assert len(set(blk.authors)) == 255
        assert f.members[blk.proposer] not in blk.authors
        turn += [k for k in blk.kinds if k in gen_votes.FORGED]
        # what the collect, verify, prune, go-on tally makes of it: the
        # 169th arrival starts an attempt over 169 authors of whom 3 are
        # pruned, the 172nd the one that certifies
        judged = f.judged(p)
        assert judged["stands_from"] == 172 and judged["need"] == 169
        assert sum(a is not None for a in judged["sound"][:169]) == 166
        assert all(a is not None for a in judged["sound"][169:200])
    # the three kinds of forgery in turn, over the pool's 64 forged
    assert collections.Counter(turn) == {"other_key": 22,
                                         "s_out_of_range": 21,
                                         "r_off_curve": 21}


def test_the_reference_reads_a_block_as_construction_made_it(full):
    """The whole of ``ref.quorum.tally`` over one full-size block's
    bytes (255 recoveries in plain Python)."""
    blk = full.block(3)
    judged = ref.tally(blk.datagrams, full.members,
                       FULL["validate_threshold"], blk.number, blk.hash)
    assert judged == full.judged(3)
    sups = [a for a in judged["sound"][:172] if a is not None]
    sig_of = dict(zip(blk.authors, blk.sigs))
    assert len(sups) == 169
    assert ref.check_certificate(
        sups, [sig_of[a] for a in sups], full.members,
        FULL["validate_threshold"], blk.number, blk.hash) is None
    assert "fewer" in ref.check_certificate(
        sups[:168], [sig_of[a] for a in sups[:168]], full.members,
        FULL["validate_threshold"], blk.number, blk.hash)


def test_the_program_reads_a_datagram_as_the_reference_wrote_it():
    from eges_tpu.consensus import messages as M

    f = gen_votes.VotesFeed(11, TINY)
    blk = f.block(0)
    for data, author, kind, sig in zip(blk.datagrams, blk.authors,
                                       blk.kinds, blk.sigs):
        code, env_author, msg = M.unpack_direct(data)
        assert code == M.UDP_EXAMINE_REPLY == ref.VALIDATE_REPLY
        a, num, accepted, h, s = ref.read_ack(data)
        assert (msg.author, msg.block_num, int(msg.accepted),
                msg.block_hash, msg.sig) == (a, num, accepted, h, s)
        assert (a, num, s, env_author) == (author, blk.number, sig, author)
        assert msg.signing_hash() == ref.ack_sighash(num, a, accepted, h)
        assert (h == blk.hash) == (kind != "foreign_hash")
        assert (a in f.members) == (kind != "non_member")
        # and back: the program's own encoding of the reply is the bytes
        assert M.pack_direct(code, env_author, msg) == data


def test_a_rehearsal_prints_every_check_beside_its_limit():
    rc, line, err = drive(workload=CELL)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert set(line["checks"]) == {
        "quorums_missed", "forged_supporters", "supporters_under_threshold",
        "sound_acks_pruned", "quorums_judged", "datagrams_dropped",
        "reference_quorum_mismatches", "reference_blocks",
        "certificates_refused_by_reference", "certificates_refused",
        "short_certificates_accepted", "unanswered_rows", "wrong_answers",
        "valid_frames_refused", "invalid_frames_not_refused",
        "reference_mismatches", "reference_rows", "compiles_in_window",
        "cache_hit_share_pct"}
    assert set(line["metrics"]) == {"vote_p50_ms", "setup_s"}
    for name in line["checks"]:
        assert f"check {name}: " in err
    assert "check forged_supporters: 0 <= 0 ok" in err
    info = json.loads(next(x for x in err.splitlines()
                           if x.startswith("info "))[5:])
    # every block the same two attempts, by construction
    assert info["certified_at"] == [23] and info["supporters"] == [22]
    assert info["quorum_attempts"] == 2 * info["quorums"] > 0
    assert info["quorum_rows"] == 22 * info["quorum_attempts"]
    assert info["quorum_pruned"] == info["quorums"]


@pytest.mark.parametrize("control, check", [
    ("accept_all", "forged_supporters"),
    ("majority", "supporters_under_threshold"),
    ("short_cycle", "cache_hit_share_pct"),
])
def test_each_control_fails_by_the_check_that_is_its_own(control, check):
    assert control in control_votes.NAMES
    _, line, _ = drive("--control", control, workload=CELL)
    assert line["correct"] is False
    assert check in failed(line)
    if control == "short_cycle":
        assert failed(line) == [check]
    else:
        # a quorum that is none fails the reference's reading too, and
        # nothing of the bulk rows
        assert set(failed(line)) <= {
            check, "reference_quorum_mismatches",
            "certificates_refused_by_reference"}


def test_the_new_metrics_read_the_counters_and_the_spans():
    cell = harness.Cell(CELL, rehearse=False)
    handle = "name=consensus.handle,kind=validate_reply"
    snap = lambda q: {  # noqa: E731
        "consensus.quorum_attempts": 2 * q, "consensus.quorums": q,
        "consensus.quorum_rows": 338 * q, "consensus.quorum_pruned": 3 * q,
        "consensus.quorum_seconds": {"count": q, "mean": 0.025},
        "span.seconds;name=consensus.verify_quorum": {"count": 2 * q,
                                                      "mean": 0.011},
        "span.self_seconds;" + handle: {"count": 255 * q, "mean": 45e-6},
        "scheduler": {"cache_hits": 166 * q, "cache_misses": 1301 * q}}
    obs = {"before": snap(2), "after": snap(102), "window_s": 40.0,
           "samples": {}, "flights": [], "trace": None, "t_begin": 0.0,
           "t_end": 40.0}
    got = harness.read_per_layer(cell, obs)
    assert {n: got[n]["value"] for n in QUORUM} == {
        "quorum_attempts.quorum": pytest.approx(2.0),
        "quorum_rows_per_attempt.quorum": pytest.approx(169.0),
        "quorum_pruned_per_block.quorum": pytest.approx(3.0),
        "quorum_ms.quorum": pytest.approx(25.0),
        "quorum_verify_ms.quorum": pytest.approx(11.0),
        "ack_handle_us.quorum": pytest.approx(45.0),
        "cache_hit_share.quorum": pytest.approx(100 * 166 / 1467)}
    # a program without the counters and the spans: nothing, never 0
    old = {"before": {}, "after": {}, "window_s": 40.0, "samples": {},
           "flights": [], "trace": None, "t_begin": 0.0, "t_end": 40.0}
    assert not QUORUM & set(harness.read_per_layer(cell, old))
