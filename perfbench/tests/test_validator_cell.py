"""``c64.zipf-backlog``: the generator's counts, sizes and order are every
seed's, its senders follow the Zipfian, a block's hits are what
construction says, the program reads the block bodies as the reference
wrote them, a rehearsal prints every check beside its limit, and each
control comes out not ``correct`` by the check that is its own."""

import collections
import json
import os

import pytest
from test_correct import drive, failed

from perfbench import control_zipf, gen, gen_zipf, harness
from perfbench.ref import senders as ref

CELL = "c64.zipf-backlog"
CONFIG = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-64-txsenders.json")))
FULL = CONFIG["deployment"]
TINY = {**FULL, **CONFIG["rehearse"]}


@pytest.fixture(scope="module")
def full():
    """The deployment at its real size (72000 signed transfers)."""
    return gen_zipf.ZipfFeed(2**31 + 11, FULL)


def test_the_deployments_numbers_are_the_issues():
    assert {k: FULL[k] for k in (
        "validators", "txn_per_block", "payload_bytes", "accounts",
        "zipf_theta", "duplicate_share", "unseen_share", "invalid_every",
        "bad_block_every", "pool_blocks", "gossip_window", "max_batch",
        "gas_limit", "reference_rows", "host_row_share_limit_pct")} == {
        "validators": 64, "txn_per_block": 1000, "payload_bytes": 100,
        "accounts": 4096, "zipf_theta": 0.99, "duplicate_share": 0.25,
        "unseen_share": 0.10, "invalid_every": 64, "bad_block_every": 16,
        "pool_blocks": 72, "gossip_window": 256, "max_batch": 1024,
        "gas_limit": 29000, "reference_rows": 400,
        "host_row_share_limit_pct": 5.0}
    cell = harness.Cell(CELL, rehearse=False)
    assert (cell.chips, cell.config["driver"], cell.config["reduced"]) == (
        1, "validator", ["cluster"])
    assert (cell.traffic["arrival"], cell.traffic["blocks_in_flight"],
            cell.traffic["warm_blocks"], cell.traffic["trace_seconds"]) == (
        "backlog", 2, 2, 15.0)
    assert FULL["cache_hit_share_min_pct"] < 50 < 52 \
        < FULL["cache_hit_share_max_pct"]
    assert len(CONFIG["guarantees"]) == 6


def test_same_seed_same_inputs_and_every_seed_the_same_counts():
    a, b, c = (gen_zipf.ZipfFeed(s, TINY) for s in (2**31 + 5, 2**31 + 5, 9))
    assert a.frames == b.frames and a.blocks == b.blocks
    assert a.body_rows == b.body_rows and a.bad == b.bad
    assert a.frames != c.frames
    for f in (a, c):
        for blk in range(TINY["pool_blocks"]):
            assert [len(w) for w in f.windows(blk)] == \
                [len(w) for w in a.windows(0)]
            con = f.construction(blk)
            assert (con["gossip_frames"], con["fresh"], con["copies"],
                    con["spoiled"]) == (43, 32, 6, 5)
            assert len(f.rows_of(blk)) == 32
            assert len(f.rows_of(blk, True)) == 32 - f.is_bad(blk)
        assert sorted(f.bad) == sorted(a.bad) and len(f.bad) == \
            TINY["pool_blocks"] // TINY["bad_block_every"]
        assert len(f.frames) == len(a.frames)
    kinds = lambda f: collections.Counter(f.kind)  # noqa: E731
    assert kinds(a) == kinds(c)
    assert set(kinds(a)) == {None, *gen.KINDS}


def test_a_copy_never_comes_in_the_window_of_its_original():
    f = gen_zipf.ZipfFeed(3, TINY)
    for b in range(8):
        first: dict = {}
        for w, idx in enumerate(f.windows(b)):
            for k in idx:
                k = f.origin[k]  # a spoiled copy is a copy too
                assert first.get(k, -1) != w
                first.setdefault(k, w)
        assert sum(len(w) for w in f.windows(b)) - len(first) == f.copies


def test_a_blocks_stream_and_body_at_the_deployments_size(full):
    f, per = full, FULL["txn_per_block"]
    assert (f.gossip_frames, f.copies, f.spoiled, f.late) == (
        1333, 333, 21, 100)
    assert sorted(f.bad) == [8, 24, 40, 56]
    assert [f.kind[k] for k, _g in f.bad.values()] == list(
        gen_zipf.BLOCK_KINDS) * 2
    assert [g for _k, g in f.bad.values()] == [True, True, False, False]
    for b in (0, 1, 8, 71):
        wins = f.windows(b)
        assert [len(w) for w in wins] == [256] * 5 + [53]
        seq = [k for w in wins for k in w]
        own = [k for k in seq if f.kind[k] is None and k // per == b]
        late = [k for k in seq if f.kind[k] is None and k // per != b]
        assert len(set(own)) == 900 and len(set(late)) == 100
        assert {k // per for k in late} == {(b - 1) % 72}
        assert set(late) == f.unseen[(b - 1) % 72]
        # a hot sender's nonces come out of order in gossip, and in
        # order in the body
        hot = collections.Counter(f.account[k] for k in f.rows_of(b)
                                  if f.kind[k] is None).most_common(1)[0][0]
        in_body = [k for k in f.rows_of(b, True) if f.account[k] == hot]
        assert in_body == sorted(in_body) and len(in_body) > 60
        in_gossip = [k for k in dict.fromkeys(own) if f.account[k] == hot]
        assert in_gossip != sorted(in_gossip)


def test_the_senders_ranks_follow_the_zipfian(full):
    """Tolerance: the first rank's share within a tenth of 1/H, the first
    sixteen ranks' within a twentieth of theirs, over 72000 draws (three
    standard deviations are 3.3% and 1.4%)."""
    cum = gen_zipf.zipf_cum_weights(FULL["accounts"], FULL["zipf_theta"])
    total = cum[-1]
    assert 1 / 9.5 < cum[0] / total < 1 / 8.5  # about a ninth of a block
    n = len(full.ranks)
    drawn = collections.Counter(full.ranks)
    assert drawn[0] / n == pytest.approx(cum[0] / total, rel=0.10)
    assert sum(drawn[r] for r in range(16)) / n == pytest.approx(
        cum[15] / total, rel=0.05)
    # ranks are bound to accounts by a seeded permutation
    assert sorted(full.by_rank) == list(range(FULL["accounts"]))
    assert full.by_rank[:8] != list(range(8))
    per_block = [len({full.account[k] for k in full.rows_of(b, True)})
                 for b in range(72)]
    assert 300 < min(per_block) and max(per_block) < 500


def test_a_blocks_hits_are_what_construction_says(full):
    """An ordinary block: 1000 of its scheduler rows are hits, and 2000
    enter besides the spoiled gossip frames whose v is well formed; a
    refused block's second pass adds 999 hits.  Checked by playing the
    stream through a cache that forgets nothing."""
    f = full
    cache, pool = set(), set()
    for b in range(72):
        rows = hits = 0
        for k in (k for w in f.windows(b) for k in w):
            if k in pool:
                continue  # the pool's hash history stops a copy
            pool.add(k)
            if f.kind[k] != "bad_recid":
                rows += 1
                hits += k in cache
                cache.add(k)
        passes = [f.rows_of(b)] + ([f.rows_of(b, True)] if f.is_bad(b)
                                   else [])
        for body in passes:
            rows += len(body)
            hits += sum(1 for k in body if k in cache)
            cache.update(body)
        if b in (0, 71):
            # the first block's late rows had no block before; the last
            # block's came with the first, which a cache of 4096 entries
            # has long forgotten and this one has not
            continue
        con = f.construction(b)
        assert (con["scheduler_rows"], con["hits"]) == (rows, hits), b
        spoiled_in = con["gossip_scheduler_rows"] - 1000
        assert spoiled_in in (15, 16)
        if not f.is_bad(b):
            assert con["hits"] == 1000
            assert con["scheduler_rows"] == 2000 + spoiled_in
        else:
            gossiped = f.bad[b][1]
            assert con["hits"] == 1000 + 999 - (not gossiped)
            assert con["scheduler_rows"] == 2999 + spoiled_in


def test_the_program_reads_a_body_as_the_reference_wrote_it():
    from eges_tpu.core import rlp
    from eges_tpu.core.types import Transaction

    f = gen_zipf.ZipfFeed(11, TINY)
    bad = sorted(f.bad)[0]
    for b, repaired in ((0, False), (bad, False), (bad, True)):
        body = f.body(b, repaired)
        txns = [Transaction.from_rlp(t) for t in rlp.decode(body)]
        fields = ref.read(body)
        assert len(txns) == len(fields) == len(f.rows_of(b, repaired))
        for t, item, k in zip(txns, fields, f.rows_of(b, repaired)):
            assert t.hash == f.hashes[k]
            h, sig = ref.row_parts(item)
            assert t.signature_parts() == (sig, h)
            assert (ref.row_sender(item) is None) == (f.kind[k] is not None)
        want = ref.block_senders(body)
        assert (want == ref.REFUSE) == (b == bad and not repaired)
        if want != ref.REFUSE:
            assert want == [f.signer(k) for k in f.rows_of(b, repaired)]
            at = [3, 0, 7]
            assert ref.block_senders(body, at) == [want[i] for i in at]


def test_a_rehearsal_prints_every_check_beside_its_limit():
    rc, line, err = drive(workload=CELL)
    assert failed(line) == []
    assert rc != 0 and line["correct"] is False and line["rehearsal"]
    assert set(line["checks"]) == {
        "unanswered_rows", "wrong_answers", "valid_frames_refused",
        "invalid_frames_not_refused", "blocks_wrongly_refused",
        "bad_blocks_not_refused", "reference_mismatches", "reference_rows",
        "compiles_in_window", "cache_hit_share_pct",
        "cache_hit_share_pct.max"}
    assert set(line["metrics"]) == {"verify_rows_per_s", "setup_s"}
    for name in line["checks"]:
        assert f"check {name}: " in err
    assert "check bad_blocks_not_refused: 0 <= 0 ok" in err


@pytest.mark.parametrize("control, check", [
    ("no_cache", "cache_hit_share_pct"),
    ("short_cycle", "cache_hit_share_pct.max"),
    ("accept_all", "bad_blocks_not_refused"),
])
def test_each_control_fails_by_the_check_that_is_its_own(control, check):
    assert control in control_zipf.NAMES
    _, line, _ = drive("--control", control, workload=CELL)
    assert line["correct"] is False
    assert check in failed(line)
    if control == "no_cache":
        assert failed(line) == [check]


def test_the_new_metrics_read_the_span_and_the_counters():
    cell = harness.Cell(CELL, rehearse=False)
    names = {m["name"] for m in cell.per_layer()}
    zipf = {n for n in names if n.endswith(".zipf")}
    assert zipf == {"cache_hit_share.zipf", "block_cached_share.zipf",
                    "coalesced_share.zipf", "block_senders_ms.zipf",
                    "block_senders_share.zipf"}
    assert all(n.endswith((".zipf", ".rows")) for n in names)
    span = "name=chain.recover_senders"
    snap = lambda hits, rows, n: {  # noqa: E731
        "chain.sender_rows": rows, "chain.sender_cached_rows": hits,
        "scheduler": {"cache_hits": hits, "cache_misses": rows - hits,
                      "coalesced_rows": hits // 100},
        "span.seconds;" + span: {"count": n, "mean": 0.030},
        "span.self_seconds;" + span: {"count": n, "mean": 0.020}}
    obs = {"before": snap(1000, 2000, 10), "after": snap(10000, 20000, 110),
           "window_s": 40.0, "samples": {}, "flights": [], "trace": None,
           "t_begin": 0.0, "t_end": 40.0}
    got = harness.read_per_layer(cell, obs)
    assert {n: got[n]["value"] for n in zipf} == {
        "cache_hit_share.zipf": pytest.approx(50.0),
        "block_cached_share.zipf": pytest.approx(50.0),
        "coalesced_share.zipf": pytest.approx(0.5),
        "block_senders_ms.zipf": pytest.approx(30.0),
        "block_senders_share.zipf": pytest.approx(5.0)}
    # a program without the span and the counters: nothing, never 0
    old = {"before": {}, "after": {}, "window_s": 40.0, "samples": {},
           "flights": [], "trace": None, "t_begin": 0.0, "t_end": 40.0}
    assert not zipf & set(harness.read_per_layer(cell, old))
