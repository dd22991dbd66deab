"""A proposer's quorum: the signatures of its replies through the
verifier, the ACK tally, and the check of a confirm's certificate.

``consensus/node.py`` runs all of this; it sits in a unit of its own so
that the path of an ACK, from a datagram's bytes to a certified quorum,
can be driven with a bare :class:`Membership` and a verifier (a
``VerifierScheduler`` on the chip) and without a chain, a transport or
255 more nodes in the process: :func:`handle_direct` is the node's
``on_direct``, :meth:`QuorumTally.ack` the body of its
``_handle_validate_reply``, :meth:`QuorumTally.cert_ok` the
certificate half of its ``_confirm_ok``.

How a quorum is tallied (signed-vote mode).  Replies are COLLECTED, up
to two distinct ones per claimed author, until the number of authors
reaches the threshold.  The reply that brings the count there starts an
ATTEMPT: every collected signature goes through the verifier as one
call (behind a scheduler: one consensus-class window, of which the
recovery cache answers the rows an earlier attempt already recovered),
authors without a valid signature are pruned, and if the count fell
under the threshold the tally keeps collecting: each later reply that
brings the count back to the threshold starts the next attempt, over
everything collected so far.  A forged reply among the first
``threshold`` therefore costs the quorum a second attempt.

Counters (one ``inc(n)`` a call): ``consensus.quorum_attempts``,
``consensus.quorum_rows`` (signatures handed to the verifier),
``consensus.quorum_pruned`` (authors an attempt dropped),
``consensus.quorums`` (quorums certified); histogram
``consensus.quorum_seconds``: from the reply that first brought the
count to the threshold to the quorum certified.
"""

from __future__ import annotations

import time

from eges_tpu.consensus import messages as M
from eges_tpu.utils import ledger
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics


def handle_direct(data: bytes, dispatch, *, lock, book, max_bytes: int,
                   fits=None, log=None) -> None:
    """One datagram of the direct plane, from its bytes to its handler:
    the trace context off the wire, then under ``lock`` and inside one
    ``consensus.handle`` span the decode budget (``max_bytes``, checked
    before any byte is parsed; ``fits`` may refuse more), the envelope
    and the message (``M.unpack_direct``), the span's ``kind``, and
    ``dispatch(code, msg, author)``.  A datagram that does not decode,
    or whose handler raises, is dropped and never fatal.  ``book`` is
    the ingress ledger every cost of the datagram is billed to."""
    ctx, data = tracing.extract(data)
    src = ledger.current_peer()
    with lock, tracing.DEFAULT.activate(ctx), \
            ledger.bind(book, f"peer:{src}" if src else "net"), \
            tracing.DEFAULT.span("consensus.handle") as sp:
        if len(data) > max_bytes:
            # same decode budget as the gossip plane
            metrics.counter("consensus.ingress_oversized").inc()
            ledger.charge(drops=1)
            if log:
                log("oversized direct dropped", nbytes=len(data))
            return
        if fits is not None and not fits(data):
            return
        try:
            code, author, msg = M.unpack_direct(data)
        except Exception as exc:
            # malformed/unauthenticated datagram: drop, but leave a trace
            if log:
                log("malformed direct", nbytes=len(data), err=repr(exc))
            return
        sp.set_attr("kind", "vote" if code == M.UDP_ELECT
                    and msg.code == M.MSG_VOTE
                    else M.DIRECT_KINDS.get(code, "other"))
        try:
            dispatch(code, msg, author)
        except Exception as exc:
            # same contract as the gossip plane: corrupted-but-unpackable
            # payloads get rejected by the handler, not fatal
            if log:
                log("direct handler rejected", code=code, err=repr(exc))


class QuorumTally:
    """The quorum arithmetic of one node: which replies may count, when
    a quorum stands, and whether a certificate proves one.  Per-height
    state lives in the caller's :class:`WorkingBlock`; this holds none
    but the membership, the verifier and the clock it was given."""

    def __init__(self, membership, verifier=None, *, signing: bool = True,
                 now=time.monotonic):
        self.membership = membership
        self.verifier = verifier
        self.signing = signing
        self._now = now

    # -- signatures -------------------------------------------------------

    def recover_entries(self, entries) -> list:
        """Recover the signer of each ``(author, sighash, sig)`` entry
        in one verifier call: behind a scheduler ONE window entry, in
        which the recovery cache answers the rows it has seen and only
        the rest reach the device (so a call is one device window, part
        of one, or none); per-entry result is the claimed author when
        the signature checks out, else None.  With signing off every
        entry passes.  Election acks and QC checks block consensus
        progress, so the rows enter the scheduler's consensus priority
        class: they flush ahead of bulk tx-ingest rows and their windows
        preempt bulk windows at lane placement."""
        if not self.signing:
            return [a for a, _, _ in entries]
        from eges_tpu.crypto.verify_host import recover_signers
        rec = recover_signers([(h, s) for _, h, s in entries], self.verifier,
                              priority="consensus")
        return [a if r == a else None
                for (a, _, _), r in zip(entries, rec)]

    def verify_quorum(self, entries, *, attempt: int = 1,
                      need: int = 0) -> dict[bytes, bytes]:
        """One attempt at a quorum over possibly-multiple entries per
        author: returns ``{author: verified_sig}`` for every author with
        at least one valid entry (sig is ``b""`` when signing is off).
        ``attempt`` counts the attempts at this quorum from 1, ``need``
        is its threshold; both go on the span."""
        out: dict[bytes, bytes] = {}
        with tracing.DEFAULT.span("consensus.verify_quorum",
                                  rows=len(entries), attempt=attempt,
                                  need=need):
            for (a, _, s), r in zip(entries,
                                    self.recover_entries(entries)):
                if r is not None and a not in out:
                    out[a] = s if self.signing else b""
        metrics.counter("consensus.quorum_attempts").inc()
        metrics.counter("consensus.quorum_rows").inc(len(entries))
        metrics.counter("consensus.quorum_pruned").inc(
            len({a for a, _, _ in entries}) - len(out))
        return out

    def attempt(self, wb, kind: str, entries, need: int) -> dict:
        """:meth:`verify_quorum` as the next attempt at ``wb``'s quorum
        of ``kind`` (``election``, ``ack`` or ``query``); the first one
        notes when the count first stood at the threshold."""
        tries = wb.quorum_tries.get(kind)
        if tries is None:
            tries = wb.quorum_tries[kind] = [0, self._now()]
        tries[0] += 1
        return self.verify_quorum(entries, attempt=tries[0], need=need)

    def certified(self, wb, kind: str) -> None:
        """``wb``'s quorum of ``kind`` stands."""
        metrics.counter("consensus.quorums").inc()
        tries = wb.quorum_tries.pop(kind, None)
        if tries is not None:
            metrics.histogram("consensus.quorum_seconds").observe(
                self._now() - tries[1])

    # -- the ACK tally ----------------------------------------------------

    def ack(self, wb, reply: M.ValidateReply, *, seed, block_hash,
            collecting: bool, offer_fills=None) -> bool:
        """Tally one ACK (ref: handleVerifyReplies
        geec_state.go:1184-1227); True when THIS reply certified the
        quorum (``wb.validate_replies`` then holds the supporters,
        ``wb.validate_cert`` their verified signatures).

        Only replies from the seeded acceptor window for this height may
        count toward the quorum (the reference gates acceptor identity via
        IsValidator on the reply path, geec_state.go:439-521) — otherwise
        a single peer could fabricate a validate quorum.  ``seed`` is
        that height's, ``block_hash`` the proposal's (None: no proposal,
        nothing to bind to), ``collecting`` whether the proposer still
        waits for this quorum, ``offer_fills`` takes a reply's backfilled
        empty blocks.

        Signed-vote mode tallies as the module's head says: the reply
        that brings the count of authors to ``wb.validate_threshold``
        starts an attempt (every collected ACK signature through the
        verifier as one call, one scheduler window), forgeries are
        pruned, and a count that fell under the threshold keeps
        collecting, a further attempt each time a reply brings it back.
        The verified signatures become the confirm's quorum
        certificate."""
        if reply.block_num != wb.blk_num:
            return False
        if seed is None or not self.membership.is_acceptor(reply.author,
                                                           seed):
            return False
        if offer_fills is not None and reply.fill_blocks:
            offer_fills(reply.fill_blocks)
        if not reply.accepted:
            return False  # an explicit NACK never counts toward the quorum
        if block_hash is not None and reply.block_hash != block_hash:
            return False  # an ACK binds a specific block; not ours -> not ours
        # up to 2 distinct stored replies per author (spoof-squat defense)
        lst = wb.validate_replies.setdefault(reply.author, [])
        if len(lst) < 2 and all(r.sig != reply.sig for r in lst):
            lst.append(reply)
        if (len(wb.validate_replies) < wb.validate_threshold
                or wb.validate_succeeded or not collecting):
            return False
        if self.signing:
            items = [(r.author, r.signing_hash(), r.sig)
                     for rl in wb.validate_replies.values() for r in rl]
            cert = self.attempt(wb, "ack", items, wb.validate_threshold)
            for a in list(wb.validate_replies):
                if a not in cert:
                    del wb.validate_replies[a]
            if len(wb.validate_replies) < wb.validate_threshold:
                return False  # keep collecting; retry loop re-solicits
            wb.validate_cert = cert
        wb.validate_succeeded = True
        self.certified(wb, "ack")
        return True

    # -- a confirm's certificate ------------------------------------------

    def cert_entries(self, confirm):
        """Reconstruct the per-supporter signing hashes of a confirm's
        quorum certificate, or None if structurally invalid.

        ``version == 0``: supporters signed ACKs (ValidateReply sighash,
        which binds height + acceptor + the exact block hash).
        ``version > 0``: supporters signed query replies for the
        timeout-recovery outcome.  Receivers can therefore re-verify the
        quorum with NO trust in the proposer — the upgrade over the
        reference's trustedHW assumption (and over a single-member
        signature, which one malicious member could mint alone)."""
        sups, sigs = confirm.supporters, confirm.supporter_sigs
        if (len(sups) != len(sigs) or len(set(sups)) != len(sups)
                or len(sups) < self.membership.validate_threshold()):
            return None
        entries = []
        for a, s in zip(sups, sigs):
            if confirm.version == 0:
                h = M.ValidateReply(block_num=confirm.block_number, author=a,
                                    accepted=True,
                                    block_hash=confirm.hash).signing_hash()
            else:
                h = M.QueryReply(
                    block_num=confirm.block_number, author=a,
                    version=confirm.version, empty=confirm.empty_block,
                    block_hash=bytes(32) if confirm.empty_block
                    else confirm.hash).signing_hash()
            entries.append((a, h, s))
        return entries

    def cert_ok(self, confirm, seed) -> bool:
        """Whether a confirm carries a valid quorum certificate: at
        least ``validate_threshold`` distinct supporters whose
        signatures verify, that many of them acceptors of the height
        where its ``seed`` is known (None: the window is not checked).

        The threshold is evaluated against membership as currently known.
        A syncing node's membership starts at the genesis bootstrap list
        and grows in step with the blocks it applies, so historical certs
        meet the as-of-then threshold; the one rough edge is a live
        confirm racing a threshold-raising membership change, which the
        timeout/re-election ladder recovers from."""
        entries = self.cert_entries(confirm)
        if entries is None:
            return False
        with tracing.DEFAULT.span("consensus.cert_ok", rows=len(entries)):
            valid = [a for a in self.recover_entries(entries)
                     if a is not None]
        need = self.membership.validate_threshold()
        if len(valid) < need:
            return False
        if seed is not None and sum(
                1 for a in valid
                if self.membership.is_acceptor(a, seed)) < need:
            return False
        return True
