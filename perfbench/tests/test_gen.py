"""The generator: the same seed gives the same inputs, every seed the same
counts, and the program reads the frames as the reference wrote them."""

import json
import os

from perfbench import gen, harness

TINY = json.load(open(os.path.join(
    harness.HERE, "configs", "committee-1024-mixed.json")))
DEPLOY = {**TINY["deployment"], **TINY["rehearse"]}


def test_same_seed_same_inputs_and_every_seed_the_same_counts():
    a, b, c = (gen.NodeFeed(s, DEPLOY) for s in (2**31 + 5, 2**31 + 5, 9))
    assert a.frames == b.frames and a.vote_entries == b.vote_entries
    assert a.blocks == b.blocks
    assert a.frames != c.frames
    for f in (a, c):
        assert [len(w) for w in f.windows(3)] == \
            [len(w) for w in a.windows(0)]
        assert sum(len(w) for w in f.windows(0)) == DEPLOY["txn_per_block"]
        assert sum(len(p) for p in f.votes(0)) == f.rows_per_vote_block
    kinds = lambda f: sorted(k for k in f.frame_kind if k)  # noqa: E731
    assert kinds(a) == kinds(c)


def test_a_copy_never_comes_in_the_window_of_its_original():
    f = gen.NodeFeed(3, DEPLOY)
    for b in range(4):
        seen: dict = {}
        for w, idx in enumerate(f.windows(b)):
            for k in idx:
                assert seen.get(k, -1) != w
                seen.setdefault(k, w)
        dup = sum(len(w) for w in f.windows(b)) - len(seen)
        assert dup == f.dups


def test_the_program_reads_the_frames_as_the_reference_wrote_them():
    from eges_tpu.ingress import decode_txn_window

    f = gen.NodeFeed(11, DEPLOY)
    idx = [k for w in f.windows(0) for k in w]
    cols = decode_txn_window([f.frames[k] for k in idx])
    assert cols.decoded.all()
    for j, k in enumerate(idx):
        h, sig = f.frame_parts(k)
        assert bool(cols.valid[j]) == (sig is not None)
        if sig is not None:
            assert bytes(cols.sighash[j]) == h and bytes(cols.sig[j]) == sig


def test_transfers_are_what_the_program_decodes():
    from eges_tpu.core.types import Transaction

    x = gen.Transfers(2**31 + 99, accounts=4, count=12, payload_bytes=100,
                      gas_limit=29000)
    for k, frame in enumerate(x.frames):
        t = Transaction.decode(frame)
        assert t.hash == x.hashes[k] and t.nonce == k // 4
        assert t.sender() == x.senders[k % 4] and len(t.payload) == 100
