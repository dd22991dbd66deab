"""The pool's steps a slice and a block (tier-1).

Two things the pool once did a transaction and now does a flushed
slice or a committed block at a time:

* ``TxColumns.txns(rows)`` builds a slice's ``Transaction`` objects in
  one pass over the columns, without the frozen dataclass's
  ``__init__``: every object must be what ``Transaction.decode(frame)``
  gives, field for field, in ``==``, in ``hash`` and in frozenness;
* ``TxPool.remove_included`` writes ONE ``tx.commit`` record an ingest
  trace (a window's rows share one), not one a transaction, and counts
  both in ``txpool.commit_rows`` / ``txpool.commit_records``.

The admission outcomes themselves (stats, order, billing) are held in
``tests/test_columnar_ingest.py``.
"""

import contextlib
import dataclasses
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from eges_tpu.core.txpool import TxPool
from eges_tpu.core.types import Transaction
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.ingress import columnar
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics
from tests.test_columnar_ingest import _WallClock  # its timer never fires

PRIV = bytes(range(1, 33))
_R = int.from_bytes(bytes(range(101, 133)), "big")
_S = int.from_bytes(bytes(range(7, 39)), "big")
_FIELDS = [f.name for f in dataclasses.fields(Transaction) if f.compare]


def _tx(**kw) -> Transaction:
    base = dict(nonce=3, gas_price=7, gas_limit=21000, to=bytes(range(20)),
                value=5, payload=b"p" * 100, v=27, r=_R, s=_S)
    base.update(kw)
    return Transaction(**base)


def _frames_generator():
    from perfbench import gen

    x = gen.Transfers(2**31 + 11, accounts=5, count=60, payload_bytes=100,
                      gas_limit=29000)
    return list(x.frames)


def _frames_contract_creation():
    return [_tx(to=None).encode(), _tx(to=None, payload=b"").encode(),
            _tx().encode()]


def _frames_zeros():
    return [_tx(nonce=0).encode(), _tx(gas_price=0).encode(),
            _tx(value=0).encode(), _tx(gas_limit=0).encode(),
            _tx(nonce=0, gas_price=0, gas_limit=0, value=0, v=0, r=0,
                s=0).encode(), Transaction().encode()]


def _frames_is_geec():
    return [_tx(is_geec=True).encode(), _tx(is_geec=False).encode(),
            Transaction(is_geec=True, payload=b"udp").encode()]


def _frames_short_r_s():
    # r or s whose 32-byte form begins with zero bytes: the wire holds
    # them shorter, the object the same number
    return [_tx(r=_R >> 8).encode(), _tx(s=_S >> 24).encode(),
            _tx(r=1, s=1).encode(), _tx(r=(1 << 256) - 1).encode(),
            _tx(r=_R >> 136, s=_S >> 200).encode()]


def _frames_payloads():
    return [_tx(payload=b"").encode(), _tx(payload=b"\x00").encode(),
            _tx(payload=b"\x7f").encode(), _tx(payload=b"q" * 55).encode(),
            _tx(payload=b"q" * 56).encode(),
            _tx(payload=bytes(range(100))).encode(),
            _tx(payload=b"z" * 300).encode()]


def _frames_wide_nonce_and_price():
    # past what the uint64 columns hold: they clip, the object must not
    top = (1 << 64) - 1
    return [_tx(nonce=top).encode(), _tx(nonce=top + 1).encode(),
            _tx(gas_price=top).encode(), _tx(gas_price=1 << 70).encode(),
            _tx(nonce=1 << 80, gas_price=1 << 90).encode(),
            _tx(nonce=top - 1, gas_price=top - 1).encode()]


def _frames_eip155_and_unsigned():
    t = _tx(payload=b"signed")
    return [t.signed(PRIV).encode(), t.signed(PRIV, chain_id=77).encode(),
            _tx(v=2**63 + 1).encode(), _tx(v=27, r=0, s=1).encode()]


def _frames_with_dead_rows():
    # rows that never decode stand between the ones that do
    good = _frames_payloads()
    return [b"", good[0], b"\xff\x01\x02", good[1], good[2][:10], good[3]]


FRAME_CASES = {
    "generator": _frames_generator,
    "contract_creation": _frames_contract_creation,
    "zeros": _frames_zeros,
    "is_geec": _frames_is_geec,
    "short_r_s": _frames_short_r_s,
    "payloads": _frames_payloads,
    "wide_nonce_and_price": _frames_wide_nonce_and_price,
    "eip155_and_unsigned": _frames_eip155_and_unsigned,
    "dead_rows_between": _frames_with_dead_rows,
}


def _same_transaction(got: Transaction, ref: Transaction, frame: bytes):
    for name in _FIELDS:
        a, b = getattr(got, name), getattr(ref, name)
        assert a == b and type(a) is type(b), (name, a, b)
    assert got == ref and hash(got) == hash(ref)
    assert got.hash == ref.hash == keccak256(frame)
    assert got.encode() == frame
    assert got.signature_parts() == ref.signature_parts()
    assert repr(got) == repr(ref)
    with pytest.raises(dataclasses.FrozenInstanceError):
        got.nonce = 1
    assert dataclasses.replace(got, nonce=9) == \
        dataclasses.replace(ref, nonce=9)


@pytest.mark.parametrize("decoder", ["dispatched", "python"])
@pytest.mark.parametrize("kind", sorted(FRAME_CASES))
def test_bulk_materialiser_builds_what_decode_builds(kind, decoder):
    frames = FRAME_CASES[kind]()
    cols = columnar.decode_window(frames) if decoder == "dispatched" \
        else columnar._decode_frames(list(frames))
    rows = [i for i in range(cols.n) if cols.decoded[i]]
    assert rows, "the case decodes nothing"
    got = cols.txns(rows)
    assert len(got) == len(rows)
    for i, t in zip(rows, got):
        _same_transaction(t, Transaction.decode(frames[i]), frames[i])
        # the one-row case is the same code and the same object
        assert cols.txn(i) is t
    assert cols.txns(rows) == got and all(
        a is b for a, b in zip(cols.txns(rows), got))


def test_bulk_materialiser_keeps_rows_it_has_and_the_order_asked_for():
    frames = _frames_generator()
    cols = columnar.decode_window(frames)
    first = cols.txn(7)               # a row materialised already
    got = cols.txns([9, 7, 3, 7])
    assert got[1] is first and got[3] is first
    assert [t.hash for t in got] == [cols.hashes[i] for i in (9, 7, 3, 7)]
    assert cols.txns([]) == []
    # only the rows asked for were built
    assert sum(t is not None for t in cols._txns) == 3
    with pytest.raises(IndexError):
        cols.txns([cols.n])


def test_bulk_materialiser_returns_the_objects_of_columns_from_txns():
    txns = [Transaction.decode(f) for f in _frames_generator()[:12]]
    cols = columnar.columns_from_txns(txns)
    assert cols._data is None         # no wire bytes to build from
    got = cols.txns(list(range(12)))
    assert all(a is b for a, b in zip(got, txns))
    assert cols.txn(5) is txns[5]


# -- commit: one tx.commit record an ingest trace ---------------------------

def _signed_frames(n: int, start: int = 0) -> list:
    return [_tx(nonce=start + k, payload=b"w%d" % (start + k))
            .signed(PRIV).encode() for k in range(n)]


@pytest.fixture
def pool():
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    tracing.DEFAULT.clear()
    p = TxPool(_WallClock(), verifier=NativeBatchVerifier(), max_batch=64)
    p.owner = "n0"
    return p


def _commits() -> list:
    return [s for s in tracing.DEFAULT.finished() if s["name"] == "tx.commit"]


def _ingests() -> list:
    return [s for s in tracing.DEFAULT.finished()
            if s["name"] == "txpool.ingest"]


def _counters() -> tuple:
    snap = metrics.snapshot()
    return (snap.get("txpool.commit_rows", 0),
            snap.get("txpool.commit_records", 0))


def test_a_window_committed_leaves_one_record_in_its_ingest_trace(pool):
    frames = _signed_frames(9)
    rows0, recs0 = _counters()
    pool.add_remotes_window(columnar.decode_window(frames))
    pool._on_window()
    assert pool.stats["admitted"] == 9
    pool.remove_included(pool.pending_txns(), block=41)

    (ingest,) = _ingests()
    (rec,) = _commits()
    assert rec["trace"] == ingest["trace"] and rec["parent"] == ingest["span"]
    assert rec["attrs"]["txns"] == 9 and rec["attrs"]["block"] == 41
    assert rec["attrs"]["owner"] == "n0"
    hashes = [keccak256(f) for f in frames]
    assert rec["attrs"]["tx"] == hashes[0].hex()[:16]
    # every transaction of the group is found by its 16-digit prefix
    txs = rec["attrs"]["txs"]
    assert [txs[k:k + 16] for k in range(0, len(txs), 16)] == \
        [h.hex()[:16] for h in hashes]
    assert _counters() == (rows0 + 9, recs0 + 1)
    # and the pool is as empty as a record a transaction left it
    assert len(pool) == 0 and not pool.pending
    assert not pool._ingest_ctx and not pool._ingest_t and not pool._admit_t


def test_two_windows_committed_in_one_block_leave_two_records(pool):
    rows0, recs0 = _counters()
    pool.add_remotes_window(columnar.decode_window(_signed_frames(5)))
    pool.add_remotes_window(columnar.decode_window(_signed_frames(7, 5)))
    pool._on_window()
    pool.remove_included(pool.pending_txns())

    ingests, recs = _ingests(), _commits()
    assert len(ingests) == 2 and len(recs) == 2
    assert [r["trace"] for r in recs] == [s["trace"] for s in ingests]
    assert [r["attrs"]["txns"] for r in recs] == [5, 7]
    assert all("block" not in r["attrs"] for r in recs)
    assert _counters() == (rows0 + 12, recs0 + 2)


def test_rows_of_two_windows_interleaved_in_a_block_still_group(pool):
    pool.add_remotes_window(columnar.decode_window(_signed_frames(4)))
    pool.add_remotes_window(columnar.decode_window(_signed_frames(4, 4)))
    pool._on_window()
    txns = pool.pending_txns()
    mixed = [txns[k] for k in (0, 4, 1, 5, 2, 6, 3, 7)]
    pool.remove_included(mixed, block=2)
    recs = _commits()
    assert [r["attrs"]["txns"] for r in recs] == [4, 4]
    assert recs[0]["attrs"]["tx"] == txns[0].hash.hex()[:16]
    assert recs[1]["attrs"]["tx"] == txns[4].hash.hex()[:16]


def test_single_arrivals_keep_a_record_each_with_its_own_tx(pool):
    txns = [Transaction.decode(f) for f in _signed_frames(6)]
    rows0, recs0 = _counters()
    for t in txns:
        pool.add_remotes([t])
    pool._on_window()
    assert pool.stats["admitted"] == 6
    pool.remove_included(txns, block=3)

    recs = _commits()
    assert [r["attrs"]["tx"] for r in recs] == \
        [t.hash.hex()[:16] for t in txns]
    assert len({r["trace"] for r in recs}) == 6
    assert all(r["attrs"]["txns"] == 1 and r["attrs"]["block"] == 3
               and r["attrs"]["txs"] == r["attrs"]["tx"] for r in recs)
    # each closes the trace its own ingest span began; the slice's ONE
    # admit span is in the trace of its first transaction
    for k, r in enumerate(recs):
        names = {s["name"] for s in tracing.DEFAULT.finished(
            trace=r["trace"])}
        assert {"txpool.ingest", "tx.commit"} <= names
        assert ("txpool.admit_window" in names) == (k == 0)
    assert _counters() == (rows0 + 6, recs0 + 6)


def test_a_transaction_the_pool_never_saw_leaves_no_record(pool):
    seen = _signed_frames(3)
    pool.add_remotes_window(columnar.decode_window(seen))
    pool._on_window()
    rows0, recs0 = _counters()
    strangers = [Transaction.decode(f) for f in _signed_frames(4, 50)]
    pool.remove_included(strangers, block=8)
    assert _commits() == [] and _counters() == (rows0, recs0)
    assert len(pool) == 3

    # strangers among a window's rows are not counted with them
    pool.remove_included(strangers[:2] + pool.pending_txns(), block=9)
    (rec,) = _commits()
    assert rec["attrs"]["txns"] == 3
    assert _counters() == (rows0 + 3, recs0 + 1)
    assert len(pool) == 0


def test_a_window_and_a_single_in_one_slice_admit_in_arrival_order(pool):
    """The flush admits a slice chunk by chunk, a window of frames and
    a one-transaction ``add_remotes`` alike: the hook must see arrival
    order."""
    seen = []
    pool.on_admitted = lambda t, sender: seen.append(t.nonce)
    pool.add_remotes_window(columnar.decode_window(_signed_frames(3)))
    pool.add_remotes([Transaction.decode(_signed_frames(1, 3)[0])])
    bad = _tx(nonce=99, v=27, r=0, s=1)       # no signature: refused
    pool.add_remotes_window(columnar.decode_window(
        _signed_frames(2, 4) + [bad.encode()] + _signed_frames(1, 6)))
    pool._on_window()
    assert seen == [0, 1, 2, 3, 4, 5, 6]
    assert pool.stats["admitted"] == 7 and pool.stats["rejected"] == 1
    pool.remove_included(pool.pending_txns(), block=1)
    assert [r["attrs"]["txns"] for r in _commits()] == [3, 1, 3]


# -- the admission body at its edges, a row and a chunk alike ---------------

def _run_to_capacity(frames: list, *, window: bool, bound: bool):
    from eges_tpu.crypto.verify_host import NativeBatchVerifier
    from eges_tpu.ingress import admit_remotes, admit_remotes_window
    from eges_tpu.utils import ledger as LG

    led = LG.IngressLedger(lambda: 100.0)
    seen = []
    p = TxPool(_WallClock(), verifier=NativeBatchVerifier(), max_batch=8,
               max_pending=5,
               on_admitted=lambda t, sender: seen.append(t.hash))
    with (LG.bind(led, "peer:src") if bound else contextlib.nullcontext()):
        for w in range(0, len(frames), 6):
            part = frames[w:w + 6]
            if window:
                admit_remotes_window(p, columnar.decode_window(part))
            else:
                admit_remotes(p, [Transaction.decode(f) for f in part])
        p._on_window()
    order = [(s, t.hash) for s, t in p._order if t.hash not in p._dead]
    return dict(p.stats), order, seen, led.snapshot(), sorted(p._admit_t)


@pytest.mark.parametrize("bound", [True, False],
                         ids=["billed", "nobody_to_bill"])
def test_a_full_pool_refuses_new_slots_alike_a_row_and_a_chunk(bound):
    """Capacity limits NEW slots only, a price bump must still replace
    in a full pool, and a bid too low is a duplicate: frames by window
    and ``Transaction``s by ``add_remotes`` agree on outcomes, order,
    hook calls and billing, with somebody to bill and with nobody."""
    other = bytes(range(3, 35))
    txs = [_tx(nonce=k, gas_price=10, payload=b"a%d" % k).signed(PRIV)
           for k in range(5)]
    txs += [_tx(nonce=7, payload=b"late").signed(PRIV),       # full
            _tx(nonce=0, payload=b"o").signed(other),         # full, new sender
            _tx(nonce=2, gas_price=12, payload=b"up").signed(PRIV),   # bump
            _tx(nonce=3, gas_price=10, payload=b"low").signed(PRIV),  # too low
            _tx(nonce=8, payload=b"later").signed(PRIV)]      # full still
    frames = [t.encode() for t in txs]
    a = _run_to_capacity(frames, window=True, bound=bound)
    b = _run_to_capacity(frames, window=False, bound=bound)
    assert a == b
    stats = a[0]
    assert stats["admitted"] == 6 and stats["rejected"] == 3
    assert stats["replaced"] == 1 and stats["duplicate"] == 1
    # billed: the one origin was charged every outcome; else nobody was
    billed = a[3]["origins"]
    assert [(o["admits"], o["rejects"], o["drops"]) for o in billed] == \
        ([(6.0, 3.0, 1.0)] if bound else [])
