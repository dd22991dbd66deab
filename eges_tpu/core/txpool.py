"""Transaction pool with a verification window, one device batch each.

Role parity with the reference's ``core/tx_pool.go`` for the Geec
capability set: remote txns are validated (signature -> sender) before
entering the pending set the proposer drains (ref: validateTx's
``types.Sender`` call, core/tx_pool.go:571-573 — the "second TPU
batch-verify target", SURVEY §2.2).

TPU-first redesign (SURVEY §7 step 5): instead of one ecrecover per
``add``, incoming txns accumulate in a verify queue that is flushed as
ONE device batch when either ``max_batch`` rows are waiting or the
``window_ms`` timer fires — the classic latency/occupancy batching
window.  Senders come back from the same batch (recover_addresses), so
admission costs one device call per window regardless of txn rate.
"""

from __future__ import annotations

import threading

import numpy as np

from eges_tpu.core.txcolumns import (WINDOW_MAX_ROWS, TxColumns,
                                     columns_from_txns)
from eges_tpu.core.types import Transaction
from eges_tpu.utils import ledger, metrics, tracing


class _WindowChunk:
    """A window's fresh rows queued for the verify flush: what the
    ``_queue`` holds, in arrival order.  ``rows`` indexes the still-live
    rows of the window's ``TxColumns`` and shrinks in place when a flush
    slice splits the chunk at a ``max_batch`` boundary."""

    __slots__ = ("cols", "rows")

    def __init__(self, cols, rows):
        self.cols = cols
        self.rows = rows  # list of row indices into cols, arrival order


class TxPool:
    def __init__(self, clock, verifier=None, *, window_ms: float = 5.0,
                 max_batch: int = 1024, max_pending: int = 100_000,
                 on_admitted=None, journal_path: str | None = None):
        self.clock = clock
        self.verifier = verifier
        self.window_ms = window_ms
        self.max_batch = max_batch
        self.max_pending = max_pending
        self.on_admitted = on_admitted
        # One re-entrant monitor guards every mutable structure below:
        # add_locals arrives on the RPC thread while the window flush
        # fires on the clock thread.  A GeecNode that adopts this pool
        # REPLACES this lock with its own (GeecNode.txpool setter) so
        # node + pool form a single lock domain — the on_admitted hook
        # re-enters the node from inside a flush, and two separate locks
        # would be acquired in opposite orders on that path.
        self._lock = threading.RLock()
        # local-txn journal (ref: core/tx_pool.go journal — locally
        # submitted txns survive a restart): append-only RLP records,
        # rotated to the still-pending set when it grows stale
        self.journal_path = journal_path
        self._journal = None
        self._journal_count = 0
        # sender -> {nonce -> txn}; admission order preserved separately
        # as (sender, txn) so selection never rescans the whole pool
        self.pending: dict[bytes, dict[int, Transaction]] = {}  # guarded-by: _lock
        self._order: list[tuple[bytes, Transaction]] = []  # guarded-by: _lock
        # hash -> (sender, nonce)
        self._by_hash: dict[bytes, tuple[bytes, int]] = {}  # guarded-by: _lock
        self._dead: set[bytes] = set()  # guarded-by: _lock
        self._known: set[bytes] = set()  # guarded-by: _lock
        # verify queue: _WindowChunk entries in arrival order;
        # _queue_rows is the ROW count (a chunk is many rows), the unit
        # max_batch and the flush trigger are denominated in
        self._queue: list[_WindowChunk] = []  # guarded-by: _lock
        self._queue_rows = 0  # guarded-by: _lock
        self._timer = None
        self.stats = {"admitted": 0, "rejected": 0, "duplicate": 0,  # guarded-by: _lock
                      "batches": 0, "replaced": 0}
        # distributed-tracing linkage: per-txn SpanContext captured at
        # ingest.  The flush runs on a clock callback where contextvars
        # don't survive, so the context is carried here explicitly and
        # re-parented at admit / commit time.
        self.owner = ""  # identifies this pool's node in span attrs
        self._ingest_ctx: dict[bytes, tracing.SpanContext] = {}  # guarded-by: _lock
        self._INGEST_CTX_CAP = 8192
        self._KNOWN_CAP = 1 << 16  # dedup-history bound (maxKnownTxs role)
        # commit-anatomy linkage: per-txn ingest/admit timestamps on the
        # node clock (virtual under the simulator), emitted as one
        # ``commit_anatomy`` stage="pool" event when a block includes
        # the txns — the ingest->admission leg of the per-block
        # critical path (harness/anatomy.py).  Same cap discipline as
        # ``_ingest_ctx``: entries die at eviction.
        self._ingest_t: dict[bytes, float] = {}  # guarded-by: _lock
        self._admit_t: dict[bytes, float] = {}  # guarded-by: _lock
        # ingress-provenance linkage: per-txn (ledger, origin) captured
        # at ingest (utils/ledger.py ambient context) — the window flush
        # runs on a clock callback where the ambient binding is gone, so
        # admit/reject outcomes charge the captured pair.  Same cap
        # discipline as ``_ingest_ctx``; entries pop at their outcome.
        self._ingest_origin: dict[bytes, tuple] = {}
        # consensus event journal (utils/journal.py), attached by the
        # owning GeecNode; distinct from the RLP txn journal above
        self.event_journal = None
        self._depth_gauge()  # register txpool.pending at 0

    def _depth_gauge(self) -> None:
        metrics.DEFAULT.gauge("txpool.pending").set(len(self._by_hash))

    # -- ingest -----------------------------------------------------------

    def add_remotes(self, txns) -> None:  # thread-entry (RPC via add_locals); ingress-entry:bounded
        """Queue remote txns for admission
        (ref: TxPool.AddRemotes core/tx_pool.go:551): the window of
        their columns, one a chunk of at most ``WINDOW_MAX_ROWS``."""
        txns = list(txns)
        for w in range(0, len(txns), WINDOW_MAX_ROWS):
            self.add_remotes_window(
                columns_from_txns(txns[w:w + WINDOW_MAX_ROWS]))

    def add_remotes_window(self, cols: TxColumns) -> None:  # thread-entry (gossip relay); ingress-entry:bounded
        """The one way into the pool: ONE lock hold and ONE tracing span
        for the whole window, dedup against ``_known`` via set ops, and
        per-window (not per-tx) bookkeeping."""
        with self._lock, \
                tracing.DEFAULT.span("txpool.ingest", root=True,
                                     owner=self.owner) as sp:
            ctx = sp.context()
            hashes = cols.hashes
            n_undec = cols.n - int(cols.decoded.sum())
            if n_undec:
                # no identity survives a failed decode: billed to the
                # deliverer as pure waste, dropped pre-queue
                ledger.charge(drops=n_undec)
                metrics.DEFAULT.counter("txpool.window_undecoded").inc(
                    n_undec)
            hs = hashes if not n_undec else \
                [h for h in hashes if h is not None]
            known = self._known
            dup = 0
            if len(known) + len(hs) < self._KNOWN_CAP:
                # fast path: the cap cannot trip mid-window, so dedup is
                # two C-level set ops instead of a per-row probe loop
                uniq = set(hs)
                if len(uniq) == len(hs):
                    dups = uniq & known
                    if dups:
                        dup = len(dups)
                        fresh_rows = [i for i, h in enumerate(hashes)
                                      if h is not None and h not in dups]
                    elif not n_undec:
                        fresh_rows = list(range(cols.n))  # bounded-by: cols.n == len of ONE delivered gossip window (pre-decode INGRESS_MAX_BYTES datagram cap upstream)
                    else:
                        fresh_rows = [i for i, h in enumerate(hashes)
                                      if h is not None]
                    known.update(uniq)
                else:
                    fresh_rows = self._dedup_rows_slow(hashes)
                    dup = len(hs) - len(fresh_rows)
            else:
                # cap boundary: the coarse clear falls where it falls
                # in the window (a clear mid-window re-admits earlier
                # duplicates)
                fresh_rows = self._dedup_rows_slow(hashes)
                dup = len(hs) - len(fresh_rows)
            if dup:
                self.stats["duplicate"] += dup
                # ambient charge, aggregated: a re-delivered txn is pure
                # waste billed to whoever delivered THIS copy, and N
                # same-origin unit drops at one timestamp equal one
                # summed drop charge
                ledger.charge(drops=dup)
            if fresh_rows:
                now = self.clock.now()
                rec = ledger.current()
                # one capacity probe covers all three bookkeeping maps:
                # they fill together here and the thread-hygiene counter
                # reconciliation assumes a uniform cap across them
                room = self._INGEST_CTX_CAP - len(self._ingest_ctx)
                book = fresh_rows[:room] if room < len(fresh_rows) \
                    else fresh_rows
                if book:
                    self._ingest_ctx.update((hashes[i], ctx) for i in book)
                    self._ingest_t.update((hashes[i], now) for i in book)
                    if rec is not None:
                        self._ingest_origin.update(
                            (hashes[i], rec) for i in book)
                self._queue.append(_WindowChunk(cols, fresh_rows))
                self._queue_rows += len(fresh_rows)
            sp.set_attr("fresh", len(fresh_rows) if fresh_rows else 0)
            if self._queue_rows >= self.max_batch:
                self._flush()
            elif self._queue and self._timer is None:
                self._timer = self.clock.call_later(self.window_ms / 1e3,
                                                    self._on_window)

    def _dedup_rows_slow(self, hashes) -> list[int]:
        """Dedup a row at a time — the path taken when the window
        carries intra-window duplicates or could trip the ``_KNOWN_CAP``
        coarse clear mid-window (geth's maxKnownTxs idiom: briefly
        losing dedup history is cheaper than letting a hash flood grow
        the set forever)."""
        fresh_rows = []
        known = self._known
        for i, h in enumerate(hashes):
            if h is None or h in known:
                continue
            if len(known) >= self._KNOWN_CAP:
                known.clear()
                metrics.DEFAULT.counter("txpool.known_clears").inc()
            known.add(h)
            fresh_rows.append(i)
        return fresh_rows

    def _on_window(self) -> None:
        with self._lock:
            self._timer = None
            self._flush()

    def _flush(self) -> None:
        """Hand everything queued to the verifier and admit what comes
        back: one ``txpool.flush`` span, whose self time is the
        gathering of rows (the wait is ``sched.await`` inside it, the
        admission ``txpool.admit_window``)."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if not self._queue:
            return
        with tracing.DEFAULT.span("txpool.flush", owner=self.owner,
                                  rows=self._queue_rows):
            while self._queue:
                self._flush_slice()

    def _flush_slice(self) -> None:
        """The queue's first ``max_batch`` rows: ONE
        ``recover_signers_window`` call over arrays gathered straight
        out of the chunks' columns — no per-row ``signature_parts``, no
        per-row entry tuples — and ``Transaction`` objects materialize
        only for rows that admit, a chunk's in one pass over its
        columns.  Outcomes fall in arrival order."""
        take: list[_WindowChunk] = []
        rows_n = 0
        qi = 0
        while qi < len(self._queue) and rows_n < self.max_batch:
            item = self._queue[qi]
            need = self.max_batch - rows_n
            if len(item.rows) <= need:
                take.append(item)
                rows_n += len(item.rows)
                qi += 1
            else:  # split: head flushes now, tail stays queued
                take.append(_WindowChunk(item.cols, item.rows[:need]))
                item.rows = item.rows[need:]
                rows_n += need
        self._queue = self._queue[qi:]
        self._queue_rows -= rows_n
        self.stats["batches"] += 1
        # each taken chunk's first output row, arrival order; gather the
        # valid rows' arrays
        bases: list = []
        n_out = 0
        vh, vs, vpos = [], [], []
        for item in take:
            bases.append(n_out)
            c, rs = item.cols.signed(), item.rows
            rs_arr = np.asarray(rs, dtype=np.int64)
            mask = c.valid[rs_arr]
            sel = rs_arr[mask]
            if sel.size:
                vh.append(c.sighash[sel])
                vs.append(c.sig[sel])
                vpos.extend((n_out + np.nonzero(mask)[0]).tolist())
            n_out += len(rs)
        senders: list = [None] * n_out
        if vpos:
            # one recovery path for all three verifier shapes: a
            # VerifierScheduler (window coalescing across callers + the
            # sender cache, so a re-gossiped txn costs a lookup), a plain
            # batch verifier (one device batch), or None (the host
            # fallback, signature_nocgo.go role)
            from eges_tpu.crypto.verify_host import recover_signers_window
            rec = recover_signers_window(
                vh[0] if len(vh) == 1 else np.concatenate(vh),
                vs[0] if len(vs) == 1 else np.concatenate(vs),
                self.verifier)
            for pos, sender in zip(vpos, rec):
                senders[pos] = sender
        # invalid signatures: the cheap-reject path an ingress flood
        # rides — billed to the captured ingest origins, once a slice
        rej: list = []
        # ONE admit span for the whole slice (spans are ring-buffer
        # telemetry, never journaled), in the ingest trace of its first
        # row that admits: the flush that got us here ran on a clock
        # callback, outside any ambient span context
        wcm = None
        amb = ledger.current()  # stable for the whole slice
        now = self.clock.now()  # one flush, one instant
        try:
            for item, base in zip(take, bases):
                c, rs = item.cols, item.rows
                snd = senders[base:base + len(rs)]
                if None in snd:
                    hashes = c.hashes
                    rej.extend(hashes[i] for i, s in zip(rs, snd)
                               if s is None)
                    rs = [i for i, s in zip(rs, snd) if s is not None]
                    self.stats["rejected"] += len(snd) - len(rs)
                    snd = [s for s in snd if s is not None]
                    if not rs:
                        continue
                if wcm is None:
                    ctx = self._ingest_ctx.get(c.hashes[rs[0]]) \
                        or tracing.DEFAULT.current_context()
                    wcm = tracing.DEFAULT.span(
                        "txpool.admit_window", parent=ctx,
                        owner=self.owner, rows=n_out)
                    wcm.__enter__()
                # the chunk's admitted rows' Transactions in one pass
                # over the columns, then the rows' admission
                self._admit_rows(c.txns(rs), snd, amb, now)
        finally:
            if wcm is not None:
                wcm.__exit__(None, None, None)
                # once a slice, not a row: the depth gauge and the
                # compaction of ``_order``
                self._maybe_compact()
                self._depth_gauge()
        if rej:
            self._ledger_charge_many(rej, rejects=1)

    def _ledger_charge_many(self, hashes, **counts) -> None:
        """Aggregated flush billing: ONE ``charge()`` per (ledger,
        origin) group — N same-origin unit outcomes at one virtual
        timestamp sum to the same ledger state as N unit charges (the
        decay is lazy, applied per charge timestamp)."""
        amb = ledger.current()
        groups: dict = {}
        order: list = []
        for h in hashes:
            rec = self._ingest_origin.pop(h, None) or amb
            if rec is None:
                continue
            key = (id(rec[0]), rec[1])
            slot = groups.get(key)
            if slot is None:
                groups[key] = [rec, 1]
                order.append(key)
            else:
                slot[1] += 1
        for key in order:
            (led, origin), n = groups[key]
            led.charge(origin, **{k: v * n for k, v in counts.items()})

    def _ledger_charge(self, h: bytes, amb, **counts) -> None:
        """Charge a flush outcome to the origin captured at ingest (the
        flush runs on a clock callback with no ambient binding); falls
        back to ``amb``, the ambient pair as the caller resolved it
        (:func:`ledger.current` once per call or slice — the binding
        cannot change mid-flush: one clock callback, one thread);
        no-op when neither exists."""
        rec = self._ingest_origin.pop(h, None) or amb
        if rec is not None:
            led, origin = rec
            led.charge(origin, **counts)

    # a replacement for a (sender, nonce) slot must bid >= 10% more gas
    # price (ref: core/tx_pool.go PriceBump default 10)
    PRICE_BUMP_PCT = 10

    def _admit_rows(self, txns, senders, amb, now: float) -> None:
        """Admission of a flushed chunk's rows, in arrival order, under
        the slice's ``txpool.admit_window``.  What is the same for every
        row is read once: ``now`` (one flush, one instant), the ambient
        ledger pair ``amb``, and whether anybody is billed at all (no
        origin captured and no ambient pair: nothing to pop, nothing to
        charge, for any row).  The outcomes live in ``stats`` and the
        ledger."""
        pending = self.pending
        by_hash = self._by_hash
        admit_t = self._admit_t
        stats = self.stats
        max_pending = self.max_pending
        bump = 100 + self.PRICE_BUMP_PCT
        billed = amb is not None or bool(self._ingest_origin)
        charge = self._ledger_charge
        hook = self.on_admitted
        for t, sender in zip(txns, senders):
            h, nonce = t.hash, t.nonce
            by_nonce = pending.setdefault(sender, {})
            old = by_nonce.get(nonce)
            if old is None and len(by_hash) >= max_pending:
                # capacity only limits NEW slots: a price-bump
                # replacement keeps the pool size constant and must stay
                # possible even when full (ref: core/tx_pool.go admits
                # replacements)
                stats["rejected"] += 1
                if billed:
                    charge(h, amb, rejects=1, sender=sender)
                if not by_nonce:
                    del pending[sender]
                continue
            if old is not None:
                # price-bump replacement (ref: core/tx_pool.go:571+).
                # The SAME transaction delivered again (a copy that
                # gossip brings after the dedup history's coarse clear)
                # is a duplicate whatever its price: as a replacement
                # it would tombstone the hash its own new entry bears,
                # and the next compaction of ``_order`` would take the
                # sender out of a proposer's sight
                if old.hash == h or \
                        t.gas_price * 100 < old.gas_price * bump:
                    stats["duplicate"] += 1
                    if billed:
                        charge(h, amb, drops=1, sender=sender)
                    continue
                by_hash.pop(old.hash, None)
                self._dead.add(old.hash)
                stats["replaced"] += 1
            by_nonce[nonce] = t
            # _order is read through self: _maybe_compact rebinds it
            self._order.append((sender, t))
            by_hash[h] = (sender, nonce)
            if len(admit_t) < self._INGEST_CTX_CAP:
                admit_t[h] = now
            stats["admitted"] += 1
            if billed:
                charge(h, amb, admits=1, sender=sender)
            if hook is not None:
                # still inside the admit span: a broadcast hook fired
                # here injects this trace into the outbound gossip
                # envelope
                hook(t, sender)

    def _maybe_compact(self) -> None:
        """Compact ``_order`` when mostly tombstones — reachable from
        both eviction AND replacement-heavy ingest (a replacement storm
        with no block inclusions must not grow memory unboundedly)."""
        if len(self._dead) * 2 > max(len(self._order), 64):
            self._order = [(s, t) for s, t in self._order
                           if t.hash not in self._dead]
            self._dead.clear()

    # -- drain ------------------------------------------------------------

    def pending_txns(self, limit: int | None = None,
                     state=None) -> list[Transaction]:
        """Executable-ordered pending txns for block building: senders in
        first-admission order, each sender's txns nonce-ascending
        (ref: TxPool.Pending + types.TxsByPriceAndNonce,
        miner/worker.go:463).

        With ``state`` (a StateDB), only the currently *executable*
        contiguous run per sender is returned — starting at the sender's
        state nonce and staying within its balance — and already-mined
        nonces are evicted.  This is the promote/demote split of the
        reference pool (pending vs queued, core/tx_pool.go): a sender
        with a nonce gap or empty purse no longer starves other senders
        out of the per-block limit."""
        with self._lock, tracing.DEFAULT.span(
                "txpool.pending", limit=limit or 0, picked=0,
                senders=0) as sp:
            seen: set[bytes] = set()
            out: list[Transaction] = []
            for s, _ in list(self._order):
                if s in seen:
                    continue
                seen.add(s)
                by_nonce = self.pending.get(s)
                if not by_nonce:
                    continue
                run = sorted(by_nonce.items())
                if state is not None:
                    start = state.nonce(s)
                    stale = [t for n, t in run if n < start]
                    if stale:
                        self._evict([t.hash for t in stale])
                        run = [(n, t) for n, t in run if n >= start]
                    spendable = state.balance(s)
                    picked = []
                    want = start
                    for n, t in run:
                        if n != want:
                            break  # nonce gap: rest is non-executable
                        from eges_tpu.core.state import INTRINSIC_GAS
                        cost = t.value + t.gas_price * INTRINSIC_GAS
                        if cost > spendable:
                            break
                        spendable -= cost
                        picked.append(t)
                        want += 1
                    out.extend(picked)
                else:
                    out.extend(t for _, t in run)
                if limit and len(out) >= limit:
                    break
            if limit:
                out = out[:limit]
            sp.set_attr("picked", len(out))
            sp.set_attr("senders", len(seen))
            return out

    def _evict(self, hashes) -> None:
        """O(evicted) eviction by txn hash: the ``_by_hash`` index
        locates each txn's (sender, nonce) slot directly, and ``_order``
        compacts lazily via a tombstone set only when mostly dead
        (round-2 verdict weak #8: the old path rebuilt the whole order
        list per block)."""
        pending = self.pending
        by_hash = self._by_hash
        dead = self._dead
        ctx_pop = self._ingest_ctx.pop
        ingest_pop = self._ingest_t.pop
        admit_pop = self._admit_t.pop
        origin_pop = self._ingest_origin.pop
        for h in hashes:
            loc = by_hash.pop(h, None)
            if loc is None:
                continue
            sender, nonce = loc
            by_nonce = pending.get(sender)
            if by_nonce is not None:
                cur = by_nonce.get(nonce)
                if cur is not None and cur.hash == h:
                    del by_nonce[nonce]
                    if not by_nonce:
                        del pending[sender]
            dead.add(h)  # bounded-by: _maybe_compact clears when dead > live (called below)
            ctx_pop(h, None)
            ingest_pop(h, None)
            admit_pop(h, None)
            origin_pop(h, None)
        self._maybe_compact()
        self._depth_gauge()

    def remove_included(self, txns, block: int | None = None) -> None:
        """Drop txns included in a canonical block; closes their traces
        with ONE ``tx.commit`` record an ingest trace (a window's rows
        share the context of its one ``txpool.ingest`` span; a txn that
        came alone is a group of one), so ingest -> admit -> commit is
        one linked trace even across nodes."""
        with self._lock, tracing.DEFAULT.span(
                "txpool.evict", owner=self.owner, txns=len(txns)):
            hashes = [t.hash for t in txns]
            # the block's txns by ingest context, first-seen order; a
            # window's rows mostly stand together, so the group is
            # looked up where the context changes
            groups: dict = {}
            ctx_of = self._ingest_ctx.get
            last = grp = None
            for h in hashes:
                ctx = ctx_of(h)
                if ctx is None:
                    continue
                if ctx is not last:
                    last = ctx
                    grp = groups.get(ctx)
                    if grp is None:
                        grp = groups[ctx] = []
                grp.append(h)
            if groups:
                blk = {"block": block} if block is not None else {}
                for ctx, hs in groups.items():
                    # ``tx`` names the group's first txn, ``txs`` every
                    # one: 16 hex digits each, back to back
                    tracing.DEFAULT.record_span(
                        "tx.commit", 0.0, parent=ctx, owner=self.owner,
                        tx=hs[0].hex()[:16], txns=len(hs),
                        txs=b"".join([h[:8] for h in hs]).hex(), **blk)
                metrics.DEFAULT.counter("txpool.commit_rows").inc(
                    sum(map(len, groups.values())))
                metrics.DEFAULT.counter("txpool.commit_records").inc(
                    len(groups))
            # commit-anatomy pool stage: the ingest->admission leg of
            # this block's critical path, on the node clock (virtual
            # under the simulator, so deterministic in sims).  Emitted
            # BEFORE eviction drops the per-txn timestamps.
            if self.event_journal is not None and txns:
                ingest_t, admit_t = self._ingest_t, self._admit_t
                ing = [ingest_t[h] for h in hashes if h in ingest_t]
                adm = [admit_t[h] for h in hashes if h in admit_t]
                if ing and adm:
                    self.event_journal.record(
                        "commit_anatomy", blk=block, stage="pool",
                        count=len(txns),
                        t_first_ingest=round(min(ing), 6),
                        t_last_admit=round(max(adm), 6),
                        ingest_to_admit_s=round(max(adm) - min(ing), 6))
            self._evict(hashes)
            if self.event_journal is not None and txns:
                self.event_journal.record("txns_included", blk=block,
                                          count=len(txns))
            if (self.journal_path and
                    self._journal_count > max(64, 4 * len(self._by_hash))):
                self._rotate_journal()

    # -- local-txn journal (ref: core/tx_pool.go newTxJournal) ------------

    def add_locals(self, txns) -> None:  # thread-entry (RPC worker); ingress-entry:bounded
        """Admit locally-submitted txns AND journal them so they survive
        a node restart (remote gossip txns are not journaled).  Only
        FRESH txns journal — resubmitting the same txn N times must not
        grow the file — and a journal that outgrows the live pool 4x
        rotates even on a quiet chain."""
        with self._lock:
            fresh = [t for t in txns if t.hash not in self._known]
            if self.journal_path and fresh:
                import struct

                if self._journal is None:
                    self._journal = open(self.journal_path, "ab")
                for t in fresh:
                    raw = t.encode()
                    self._journal.write(struct.pack("<I", len(raw)) + raw)
                    self._journal_count += 1
                self._journal.flush()
                if self._journal_count > max(64, 4 * (len(self._by_hash)
                                                      + len(fresh))):
                    self._rotate_journal()
            self.add_remotes(txns)

    def load_journal(self) -> int:
        """Re-queue journaled local txns (stale nonces fall out at
        selection); returns how many were loaded.  A torn tail is
        repaired by rewriting the parsed prefix — otherwise every
        append after the tear would be unreadable forever."""
        import os
        import struct

        if not self.journal_path or not os.path.exists(self.journal_path):
            return 0
        with self._lock:
            with open(self.journal_path, "rb") as f:
                data = f.read()
            txns = []
            pos = 0
            good_end = 0
            while pos + 4 <= len(data):
                (n,) = struct.unpack("<I", data[pos : pos + 4])
                if pos + 4 + n > len(data):
                    break  # torn tail
                try:
                    txns.append(
                        Transaction.decode(data[pos + 4 : pos + 4 + n]))
                except Exception:
                    break  # torn/corrupt record: keep the parsed prefix
                pos += 4 + n
                good_end = pos
            if good_end != len(data):
                with open(self.journal_path, "r+b") as f:
                    f.truncate(good_end)
            self._journal_count = len(txns)
            if txns:
                self.add_remotes(txns)
                self._flush()
            return len(txns)

    def _rotate_journal(self) -> None:
        """Rewrite the journal with the still-pending set (a superset of
        the locals — geth rotates locals only; re-journaling a remote is
        harmless and keeps the rotation logic index-free)."""
        import os
        import struct

        if self._journal is not None:
            self._journal.close()
            self._journal = None
        tmp = self.journal_path + ".tmp"
        kept = 0
        with open(tmp, "wb") as f:
            for s, t in self._order:
                if t.hash in self._dead or t.hash not in self._by_hash:
                    continue
                raw = t.encode()
                f.write(struct.pack("<I", len(raw)) + raw)
                kept += 1
        os.replace(tmp, self.journal_path)
        self._journal_count = kept

    def close(self) -> None:
        with self._lock:
            if self._journal is not None:
                self._journal.close()
                self._journal = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._by_hash)
