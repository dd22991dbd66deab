"""``core.state.recover_senders`` in steps a block, held to what it did a
row: a body's signed rows go through ONE native pass over their wire
bytes (``native/ingress.cpp``, the gossip window's decoder) and that pass
must fill, row for row, the two arrays ``signature_parts()`` would have
filled, name the same senders, count the same cache hits, leave behind
the hash ``keccak256(t.encode())`` gives, and refuse a body at the same
point with the same words.

The oracle (:func:`by_the_row`) is yesterday's algorithm written plainly
over copies of the transactions that carry no memo.  Each case runs on
every rung: no verifier (host recovery), a plain batch verifier, the
scheduler over the host C++ verifier and over the jax verifier (the CPU
backend's 16-row bucket, as ``test_senders_reference.py`` builds it), and
the plain verifier again with the library's decoder taken away (the rung
of a checkout without it: the same answers, no native row).
"""

import dataclasses

import numpy as np
import pytest

from eges_tpu.core import rlp
from eges_tpu.core.state import StateError, recover_senders
from eges_tpu.core.types import Transaction, geec_txn
from eges_tpu.crypto import native
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.crypto.keccak import keccak256
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics
from perfbench import gen_zipf
from tests.test_senders_reference import DEPLOY, MAX_BATCH, SEED, _verifier

pytestmark = pytest.mark.skipif(
    not native.has_decode_window(),
    reason="the native library lacks geec_decode_txn_window")

PRIVS = [bytes([k]) * 32 for k in (0x11, 0x22, 0x33)]
ADDRS = [secp.pubkey_to_address(secp.privkey_to_pubkey(p)) for p in PRIVS]
MALFORMED = "malformed transaction signature"
UNRECOVERABLE = "unrecoverable transaction signature"


def tx(i: int, **kw) -> Transaction:
    kw.setdefault("to", bytes([0xA0 + i % 16]) * 20)
    return Transaction(nonce=i, gas_price=10 + i, gas_limit=29000,
                       value=1000 * i, payload=bytes([i % 256]) * 100, **kw)


def signed(i: int, chain_id=None, **kw) -> Transaction:
    return tx(i, **kw).signed(PRIVS[i % len(PRIVS)], chain_id)


def off_wire(txns) -> list:
    """The transactions as a block body brings them: encoded as a list,
    decoded as ``Block.from_rlp`` decodes them."""
    body = rlp.encode([t.to_rlp() for t in txns])
    return [Transaction.from_rlp(item) for item in rlp.decode(body)]


def frames_off_wire(frames) -> list:
    payload = b"".join(frames)
    body = rlp._encode_length(len(payload), 0xC0) + payload
    return [Transaction.from_rlp(item) for item in rlp.decode(body)]


def swap_field(frame: bytes, index: int, enc: bytes) -> bytes:
    """``frame`` with the encoding of its field ``index`` replaced."""
    items = rlp.decode(frame)
    parts = [rlp.encode(x) for x in items]
    parts[index] = enc
    payload = b"".join(parts)
    return rlp._encode_length(len(payload), 0xC0) + payload


def _off_curve() -> Transaction:
    """A signature of sound form that names no sender."""
    for r in range(1, 64):
        t = tx(3, v=27, r=r, s=7)
        sig, h = t.signature_parts()
        try:
            secp.recover_address(h, sig)
        except ValueError:
            return t
    raise AssertionError("no small r off the curve")


# name -> (builder of the body's transactions, rows the native pass
# must have filled or None for "every signed row", words of the refusal)
CASES: dict = {}


def case(name, native_rows=None, refused=None):
    def put(fn):
        CASES[name] = (fn, native_rows, refused)
        return fn
    return put


@case("homestead")
def _():
    return off_wire([signed(i) for i in range(5)])


@case("eip155")
def _():
    return off_wire([signed(i, cid) for i, cid in
                     enumerate((1, 1337, 0, 2**40, (2**63 - 36) // 2))])


@case("creation")
def _():
    return off_wire([signed(0, to=None), signed(1, 7, to=None), signed(2)])


@case("wide_fields")
def _():
    big = Transaction(nonce=2**70, gas_price=2**80, gas_limit=2**64,
                      to=bytes(20), value=2**200, payload=b"")
    long = dataclasses.replace(big, nonce=1, payload=bytes(70000))
    return off_wire([big.signed(PRIVS[0], 5), long.signed(PRIVS[1]),
                     signed(2)])


@case("r_zero", refused=MALFORMED)
def _():
    return off_wire([signed(0), tx(1, v=27, r=0, s=5), signed(2)])


@case("s_zero", refused=MALFORMED)
def _():
    return off_wire([signed(0), tx(1, v=28, r=5, s=0)])


@case("r_too_wide", native_rows=2, refused=MALFORMED)
def _():
    # no wire can bring it (from_rlp refuses 33 bytes): a local row
    return off_wire([signed(0), signed(1)]) + [tx(2, v=27, r=1 << 256,
                                                  s=1)]


@case("recid_over_three", refused=MALFORMED)
def _():
    return off_wire([signed(0), tx(1, v=31, r=5, s=6)])


for _v in (1, 26, 29, 30, 31, 32, 33, 34):
    case(f"v_{_v}", refused=MALFORMED)(
        lambda v=_v: off_wire([signed(0, 9), tx(1, v=v, r=5, s=6)]))


@case("v_35_chain_zero")
def _():
    return off_wire([signed(0, 0), signed(1, 0), signed(2)])


@case("unsigned_and_geec_rows_inside")
def _():
    return off_wire([signed(0), tx(1), geec_txn(b"reg"), signed(3, 4),
                     dataclasses.replace(signed(4), is_geec=True)])


@case("nothing_signed")
def _():
    return off_wire([tx(0), geec_txn(b"x")])


@case("empty_body")
def _():
    return off_wire([])


@case("quirk_is_geec_two")
def _():
    # PR 30's: from_rlp reads ANY non-zero is_geec as True, and encode()
    # writes 1: the wire's Keccak is not the transaction's hash.  The row
    # carries no sender; its hash must stay today's
    odd = swap_field(signed(1).encode(), 6, b"\x02")
    return frames_off_wire([signed(0).encode(), odd, signed(2).encode()])


@case("quirk_empty_list_for_zero", native_rows=2)
def _():
    # PR 30's: decode_uint takes an EMPTY LIST for 0, and encode() writes
    # 0x80: the native pass must not rule on such a row
    zero = Transaction(nonce=0, gas_price=3, gas_limit=29000,
                       to=bytes(20), value=0, payload=b"q").signed(PRIVS[0])
    odd = swap_field(zero.encode(), 4, b"\xc0")
    assert odd != zero.encode() and Transaction.decode(odd) == zero
    return frames_off_wire([signed(1).encode(), odd, signed(2).encode()])


@case("quirk_empty_list_for_to", native_rows=1)
def _():
    born = Transaction(nonce=0, gas_price=3, gas_limit=29000, to=None,
                       payload=b"q").signed(PRIVS[2], 3)
    odd = swap_field(born.encode(), 3, b"\xc0")
    assert Transaction.decode(odd) == born
    return frames_off_wire([odd, signed(1).encode()])


@case("wire_and_local_rows_mixed", native_rows=3)
def _():
    a, b, c = off_wire([signed(0), signed(2, 5), signed(4)])
    return [a, signed(1), b, dataclasses.replace(signed(3, 8)), c]


@case("replaced_after_decode", native_rows=2)
def _():
    a, b, c = off_wire([signed(0), signed(1, 5), signed(2)])
    # a changed transaction must NOT carry the old bytes: its signature
    # now names some other sender (or none), as it did a row
    moved = dataclasses.replace(b, nonce=b.nonce + 1)
    assert "wire" in b._SENDER_CACHE and "wire" not in moved._SENDER_CACHE
    return [a, moved, c]


@case("signed_anew_after_decode", native_rows=1)
def _():
    a, b = off_wire([signed(0), signed(1, 5)])
    again = dataclasses.replace(b, value=5).signed(PRIVS[2], 5)
    assert "wire" not in again._SENDER_CACHE
    return [a, again]


@case("off_the_curve", refused=UNRECOVERABLE)
def _():
    return off_wire([signed(0), _off_curve(), signed(2)])


@case("decoded_one_by_one")
def _():
    # Transaction.decode of a frame of its own (a gossip message's way)
    return [Transaction.decode(signed(i, 3).encode()) for i in range(4)]


# bodies whose row i was signed by key i % 3 at nonce i
BY_NONCE = ("homestead", "eip155", "creation", "v_35_chain_zero",
            "decoded_one_by_one", "wire_and_local_rows_mixed")


@pytest.fixture(scope="module")
def feed():
    return gen_zipf.ZipfFeed(SEED, DEPLOY)


def _zipf(feed, block: int, repaired: bool = False) -> list:
    return [Transaction.from_rlp(t)
            for t in rlp.decode(feed.body(block, repaired))]


ZIPF = {"zipf_block": (1, False, None), "zipf_bad_block": (2, False, "any"),
        "zipf_bad_block_repaired": (2, True, None)}


def body_of(name: str, feed):
    """(transactions, native rows expected or None, refusal or None)."""
    if name in ZIPF:
        block, repaired, refused = ZIPF[name]
        return _zipf(feed, block, repaired), None, refused
    fn, native_rows, refused = CASES[name]
    return fn(), native_rows, refused


# -- the oracle: a row at a time --------------------------------------------

def by_the_row(txns) -> dict:
    """What ``recover_senders`` did before it worked in steps a block,
    over copies with an empty memo: (arrays, senders) or the refusal."""
    plain = [dataclasses.replace(t) for t in txns]
    rows = [i for i, t in enumerate(plain)
            if not (t.is_geec or (t.v == 0 and t.r == 0 and t.s == 0))]
    parts = [plain[i].signature_parts() for i in rows]
    hashes = [keccak256(rlp.encode(t.to_rlp())) for t in plain]
    if any(p is None for p in parts):
        return {"refused": MALFORMED, "senders": None, "calls": [],
                "hashes": hashes, "rows": 0}
    senders = [None] * len(plain)
    refused = None
    for i, (sig, h) in zip(rows, parts):
        try:
            senders[i] = secp.recover_address(h, sig)
        except ValueError:
            refused = UNRECOVERABLE
    return {"refused": refused, "senders": None if refused else senders,
            "calls": [(b"".join(p[0] for p in parts),
                       b"".join(p[1] for p in parts))] if rows else [],
            "hashes": hashes, "rows": len(rows)}


# -- the program, on a rung ---------------------------------------------------

class Recording:
    """What ``recover_senders`` handed over, call by call."""

    def __init__(self, inner):
        self.inner, self.calls = inner, []

    def recover_addresses(self, sigs, hashes):
        assert sigs.dtype == np.uint8 and sigs.shape[1:] == (65,)
        assert hashes.dtype == np.uint8 and hashes.shape[1:] == (32,)
        self.calls.append((sigs.tobytes(), hashes.tobytes()))
        return self.inner.recover_addresses(sigs, hashes)


NAMES = ("sender_rows", "sender_native_rows", "sender_cached_rows",
         "sender_coalesced_rows", "blocks_refused")


def _counters() -> dict:
    return {n: metrics.counter("chain." + n).value for n in NAMES}


def through(txns, verifier) -> dict:
    """One call of ``recover_senders``: the answer or the refusal, the
    arrays that reached ``verifier``, the counters' growth, the span."""
    rec = Recording(verifier) if verifier is not None else None
    before = _counters()
    tracing.DEFAULT.clear()
    try:
        senders, refused = recover_senders(txns, rec), None
    except StateError as e:
        senders, refused = None, str(e)
    span = [s for s in tracing.DEFAULT.finished()
            if s["name"] == "chain.recover_senders"][-1]
    return {"senders": senders, "refused": refused,
            "calls": rec.calls if rec else None, "attrs": span["attrs"],
            "chain": {k: v - before[k] for k, v in _counters().items()}}


@pytest.fixture(scope="module")
def jax_verifier():
    return _verifier("jax")


RUNGS = ("host", "plain", "sched_native", "sched_jax", "no_decoder")


@pytest.fixture
def rung(request, monkeypatch):
    """(name, verifier or None), the scheduler closed afterwards."""
    name = request.param
    sched = None
    if name == "host":
        v = None
    elif name in ("plain", "no_decoder"):
        v = NativeBatchVerifier()
        if name == "no_decoder":
            monkeypatch.setattr(native, "has_decode_window", lambda: False)
    else:
        raw = NativeBatchVerifier() if name == "sched_native" \
            else request.getfixturevalue("jax_verifier")
        v = sched = VerifierScheduler(raw, max_batch=MAX_BATCH)
    yield name, v
    if sched is not None:
        sched.close()


@pytest.mark.parametrize("rung", RUNGS, indirect=True)
@pytest.mark.parametrize("name", sorted(CASES) + sorted(ZIPF))
def test_a_body_in_one_native_pass_is_the_body_a_row_at_a_time(name, rung,
                                                               feed):
    rung_name, verifier = rung
    txns, native_rows, refusal = body_of(name, feed)
    want = by_the_row(txns)
    if refusal == "any":
        assert want["refused"] in (MALFORMED, UNRECOVERABLE)
    else:
        assert want["refused"] == refusal
    got = through(txns, verifier)

    # the same answer or the same words, and at the same point: a
    # malformed row is found before any row reaches the verifier
    assert got["refused"] == want["refused"]
    assert got["senders"] == want["senders"]
    if verifier is not None:
        assert got["calls"] == want["calls"]
    if name in BY_NONCE:  # and they are who signed
        assert got["senders"] == [ADDRS[t.nonce % 3] for t in txns]

    # the counters and the span: the same rows, and how many of them the
    # native pass filled in
    rows = want["rows"]
    if native_rows is None:
        native_rows = rows
    if rung_name == "no_decoder" or want["refused"] == MALFORMED:
        native_rows = 0 if rung_name == "no_decoder" else None
    assert got["chain"]["sender_rows"] == rows
    assert got["chain"]["blocks_refused"] == (want["refused"] is not None)
    assert got["attrs"]["refused"] == (1 if want["refused"] else 0)
    assert got["attrs"]["rows"] == rows
    if native_rows is not None:
        assert got["chain"]["sender_native_rows"] == native_rows
        assert got["attrs"]["native"] == native_rows

    # every memoised hash is the hash: the pass left it behind on the
    # rows it decoded, and on no row whose wire is not its encoding
    for t, h in zip(txns, want["hashes"]):
        assert t._SENDER_CACHE.get("hash", h) == h
        assert t.hash == h
    if rung_name != "no_decoder" and want["refused"] is None \
            and native_rows:
        left = sum("hash" in t._SENDER_CACHE for t in txns)
        assert left >= native_rows

    # a second call: on a scheduler the cache answers what the first call
    # computed, the counts say so, and the answer is the same
    if want["refused"] is None and rows:
        again = through(txns, verifier)
        assert again["senders"] == want["senders"]
        assert again["chain"]["sender_rows"] == rows
        if rung_name.startswith("sched"):
            assert again["chain"]["sender_cached_rows"] == rows
            assert again["attrs"]["cached"] == rows
            assert got["chain"]["sender_cached_rows"] == 0
        else:
            assert again["chain"]["sender_cached_rows"] == 0
        assert again["chain"]["sender_coalesced_rows"] == 0


def test_the_wire_travels_in_the_memo_and_a_change_drops_it():
    sent = signed(7, 1337)
    frame = sent.encode()
    got = Transaction.decode(frame)
    assert got == sent and got._SENDER_CACHE["wire"] == frame
    in_block, = off_wire([sent])
    assert in_block._SENDER_CACHE["wire"] == frame
    # built here, changed or signed anew: no bytes to be stale
    assert "wire" not in sent._SENDER_CACHE
    assert "wire" not in dataclasses.replace(got)._SENDER_CACHE
    assert "wire" not in got.signed(PRIVS[1], 1337)._SENDER_CACHE
    # a list nobody decoded names no bytes
    assert rlp.encoding_of(sent.to_rlp()) is None
    assert rlp.encoding_of(frame) is None
    assert "wire" not in Transaction.from_rlp(
        list(rlp.decode(frame)))._SENDER_CACHE


def test_a_decoded_list_is_a_list_that_knows_its_span():
    inner = [b"ab", [b"", b"\x01"], b"c" * 60]
    data = rlp.encode([b"x", inner, []])
    out = rlp.decode(data)
    assert out == [b"x", inner, []] and isinstance(out, list)
    assert isinstance(out[1], list) and out[1][1] == [b"", b"\x01"]
    head, mid, tail = out
    assert rlp.encoding_of(out) == data
    assert rlp.encoding_of(mid) == rlp.encode(inner)
    assert rlp.encoding_of(mid[1]) == rlp.encode(inner[1])
    assert rlp.encoding_of(tail) == b"\xc0"
    assert rlp.encoding_of(head) is None
    assert rlp.encode(out) == data and out[:2] == [b"x", inner]
    assert type(out[:2]) is list and type(out + []) is list
